"""benchmarks/reference/ecdsa.py, the plain reference that decides
`correct` in the `light-secp-10k` cell, against the program's own
secp256k1 oracle: three implementations that share no code (OpenSSL
behind btcec's rules, plain Python integers, `crypto/secp256k1_ref`)
give one verdict a row, on honest signatures and on every way the
rules refuse one. The same rows then go through the device kernel's
host pack and XLA kernel."""
import hashlib

import numpy as np
import pytest

from cometbft_tpu.crypto import secp256k1_ref as sc


def rows(n=12):
    """[(pub, msg, sig)] honest, from seeded secrets."""
    out = []
    for i in range(n):
        d = int.from_bytes(hashlib.sha256(b"ecdsa-ref/%d" % i).digest(),
                           "big") % (sc.N - 1) + 1
        msg = b"light-secp/%d" % i * (1 + i % 3)
        out.append((sc.pubkey_from_secret(d), msg, sc.sign(d, msg)))
    return out


def be(x: int) -> bytes:
    return x.to_bytes(32, "big")


def spoiled():
    """{case: (pub, msg, sig)} that every implementation must refuse,
    each made from an honest row by one change."""
    pub, msg, sig = rows(1)[0]
    r, s = sig[:32], int.from_bytes(sig[32:], "big")
    x_over_p = next(x for x in range(sc.P, 2**256)  # x >= P, and x - P
                    if sc.decompress(b"\x02" + be(x - sc.P)))  # on curve
    off_curve = next(x for x in range(1, 99)
                     if not sc.decompress(b"\x02" + be(x)))
    return {
        "flipped-bit-in-r": (pub, msg, bytes([sig[0] ^ 1]) + sig[1:]),
        "flipped-bit-in-s": (pub, msg, sig[:63] + bytes([sig[63] ^ 1])),
        "other-message": (pub, msg + b"!", sig),
        "high-s": (pub, msg, r + be(sc.N - s)),
        "r-zero": (pub, msg, be(0) + sig[32:]),
        "s-zero": (pub, msg, r + be(0)),
        "r-is-n": (pub, msg, be(sc.N) + sig[32:]),
        "s-is-n": (pub, msg, r + be(sc.N)),
        "prefix-4": (b"\x04" + pub[1:], msg, sig),
        "prefix-0": (b"\x00" + pub[1:], msg, sig),
        "other-parity": (bytes([pub[0] ^ 1]) + pub[1:], msg, sig),
        "uncompressed-key": (b"\x04" + pub[1:] + be(1), msg, sig),
        "x-at-least-p": (b"\x02" + be(x_over_p), msg, sig),
        "x-off-the-curve": (b"\x02" + be(off_curve), msg, sig),
        "short-signature": (pub, msg, sig[:63]),
        "long-signature": (pub, msg, sig + b"\x00"),
    }


def voices(ecdsa):
    return {"openssl": ecdsa.verify_sig, "integers": ecdsa.verify_sig_ints,
            "program": sc.verify, "program-integers": sc.verify_py}


def test_honest_rows_pass_every_implementation(plain_reference):
    for name, verify in voices(plain_reference.ecdsa).items():
        assert all(verify(*row) for row in rows()), name


@pytest.mark.parametrize("case", sorted(spoiled()))
def test_a_spoiled_row_is_refused_by_every_implementation(
        plain_reference, case):
    row = spoiled()[case]
    got = {name: verify(*row)
           for name, verify in voices(plain_reference.ecdsa).items()}
    assert got == dict.fromkeys(got, False), got


def test_the_high_s_twin_is_a_valid_ecdsa_signature_the_rules_refuse(
        plain_reference):
    """(r, N - s) verifies under plain ECDSA: it is btcec's low-S rule
    alone that refuses it, so a reference without the rule would pass
    it where upstream does not."""
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        encode_dss_signature,
    )

    pub, msg, sig = spoiled()["high-s"]
    key = ec.EllipticCurvePublicKey.from_encoded_point(ec.SECP256K1(), pub)
    key.verify(encode_dss_signature(
        int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")),
        msg, ec.ECDSA(hashes.SHA256()))  # raises if invalid
    assert plain_reference.ecdsa.parse(pub, sig) is None


def test_the_address_is_the_programs(plain_reference):
    for pub, _, _ in rows(4):
        assert plain_reference.ecdsa.address(pub) == sc.address(pub)


def test_the_device_pack_and_kernel_agree_with_the_reference(
        plain_reference):
    """The same rows through `ecdsa_kernel` (the host pack's precheck
    and the XLA kernel, one 64-row pass): verdict for verdict."""
    from cometbft_tpu.ops import ecdsa_kernel as ek

    batch = rows() + list(spoiled().values())
    want = [plain_reference.ecdsa.verify_sig(*row) for row in batch]
    got = ek.verify_batch(*(list(col) for col in zip(*batch)))
    np.testing.assert_array_equal(got, want)
    assert sum(want) == len(rows())


def test_light_rows_stop_at_the_quorum_point(plain_reference):
    ecdsa = plain_reference.ecdsa
    powers = [9, 7, 5, 3, 1]  # 25: more than 16 needed
    rows_, tallied, needed = ecdsa.light_rows(powers, [b""] * 5)
    assert (rows_, tallied, needed) == ([0, 1, 2], 21, 16)
    # an absent row is passed over, and 16 of 25 is not MORE than 2/3
    rows_, tallied, _ = ecdsa.light_rows(powers, [b"", None, b"", b"", b""])
    assert (rows_, tallied) == ([0, 2, 3], 17)
    assert ecdsa.verify_commit_light(
        [b""] * 5, [9, 7, 0, 0, 9], [b""] * 5,
        [b"", b"", None, None, None]) == ("not_enough_power", 16)


def test_trusting_rows_go_by_address_and_refuse_a_double_vote(
        plain_reference):
    ecdsa = plain_reference.ecdsa
    old = {b"a": (b"ka", 10), b"b": (b"kb", 10), b"c": (b"kc", 10)}
    # x is unknown to the old set: passed over; 10 is not MORE than 10
    rows_, tallied, needed, twice = ecdsa.trusting_rows(
        old, [b"x", b"a", b"y", b"b", b"c"], [b""] * 5)
    assert (rows_, tallied, needed, twice) == ([1, 3], 20, 10, None)
    assert ecdsa.trusting_rows(old, [b"a", b"x", b"a"], [b""] * 3)[3] == b"a"
    assert ecdsa.verify_commit_light_trusting(
        old, [b"a", b"a"], [b""] * 2, [b""] * 2) == ("double_vote", "61")
    assert ecdsa.verify_commit_light_trusting(
        old, [b"x", b"a"], [b""] * 2, [b""] * 2) == ("not_enough_power", 10)
