"""secp256k1: host oracle vs OpenSSL, device curve vs oracle, batched
ECDSA kernel edge cases.

Differential strategy mirrors tests/test_ed25519_kernel.py: the pure-
Python oracle (crypto/secp256k1_ref.py) is validated against OpenSSL,
then the device kernel is validated against the oracle — including the
malleability (high-S) and malformed-encoding paths the reference enforces
in crypto/secp256k1/secp256k1.go:192-220.
"""
import hashlib
import random

import numpy as np
import pytest

from cometbft_tpu.crypto import secp256k1_ref as ref
from cometbft_tpu.ops import ecdsa_kernel as ek
from cometbft_tpu.ops import secp256k1 as curve
from cometbft_tpu.ops.field import FSECP, limbs_to_int

F = FSECP
rng = random.Random(7)


def rand_point():
    return ref.pt_mul(rng.randrange(1, ref.N), (ref.GX, ref.GY))


def to_affine(p):
    X, Y, Z = [np.asarray(F.canonical(c)) for c in curve.unstack(p)]
    xs = np.atleast_1d(limbs_to_int(X))
    ys = np.atleast_1d(limbs_to_int(Y))
    zs = np.atleast_1d(limbs_to_int(Z))
    out = []
    for x, y, z in zip(xs, ys, zs):
        if int(z) == 0:
            out.append(None)
            continue
        zi = pow(int(z), ref.P - 2, ref.P)
        out.append((int(x) * zi % ref.P, int(y) * zi % ref.P))
    return out


def test_oracle_vs_openssl():
    """Oracle verify accepts OpenSSL signatures; oracle pubkeys match.
    Needs the `cryptography` wheel, which this container does not ship
    (ROADMAP container limits; the pure-Python fallbacks are the
    load-bearing path here) — skip rather than fail where the
    differential oracle simply cannot run."""
    ec = pytest.importorskip(
        "cryptography.hazmat.primitives.asymmetric.ec",
        reason="cryptography wheel unavailable (container constraint); "
               "OpenSSL differential needs it",
    )

    for i in range(4):
        d = rng.randrange(1, ref.N)
        sk = ec.derive_private_key(d, ec.SECP256K1())
        pn = sk.public_key().public_numbers()
        assert ref.pubkey_from_secret(d) == ref.compress(pn.x, pn.y)
        msg = b"oracle-%d" % i
        sig = ref.sign(d, msg)
        assert ref.verify(ref.pubkey_from_secret(d), msg, sig)
        assert not ref.verify(ref.pubkey_from_secret(d), msg + b"x", sig)


def test_decompress_roundtrip():
    for _ in range(4):
        x, y = rand_point()
        assert ref.decompress(ref.compress(x, y)) == (x, y)
    assert ref.decompress(b"\x04" + b"\x00" * 32) is None  # bad prefix
    assert ref.decompress(b"\x02" + ref.P.to_bytes(32, "big")) is None
    # x with no curve point (x=5 -> 132 is a QNR mod p)
    assert pow(132, (ref.P - 1) // 2, ref.P) != 1
    assert ref.decompress(b"\x02" + (5).to_bytes(32, "big")) is None


def test_device_add_double_vs_oracle():
    pts = [rand_point() for _ in range(6)]
    dev = np.stack([curve.from_affine_int(x, y) for x, y in pts])
    got = to_affine(curve.add(dev[:3], dev[3:]))
    want = [ref.pt_add(pts[i], pts[i + 3]) for i in range(3)]
    assert got == want
    got = to_affine(curve.double(dev))
    want = [ref.pt_add(p, p) for p in pts]
    assert got == want


def test_complete_formula_edge_cases():
    """Complete formulas: P + P, P + (-P) -> inf, inf + P, inf + inf."""
    x, y = rand_point()
    p = curve.from_affine_int(x, y)[None]
    minus = curve.from_affine_int(x, ref.P - y)[None]
    ident = np.asarray(curve.identity((1,)))
    assert to_affine(curve.add(p, p)) == [ref.pt_add((x, y), (x, y))]
    assert to_affine(curve.add(p, minus)) == [None]
    assert to_affine(curve.add(ident, p)) == [(x, y)]
    assert to_affine(curve.add(ident, ident)) == [None]
    assert to_affine(curve.double(ident)) == [None]


def test_scalar_mul_matches_oracle():
    ks = [1, 2, 0xDEADBEEF, ref.N - 1, (1 << 255) % ref.N]
    digs = np.stack([
        ek.nibbles(np.frombuffer(k.to_bytes(32, "little"), np.uint8))
        for k in ks
    ])
    g = np.broadcast_to(
        curve.from_affine_int(ref.GX, ref.GY), (len(ks), 3, 20)
    )
    got = to_affine(curve.scalar_mul_windowed(digs, np.ascontiguousarray(g)))
    want = [ref.pt_mul(k, (ref.GX, ref.GY)) for k in ks]
    assert got == want
    got = to_affine(curve.base_scalar_mul(digs))
    assert got == want


def test_ecdsa_batch_valid_and_blame():
    n = 8
    secrets = [rng.randrange(1, ref.N) for _ in range(n)]
    pubs = [ref.pubkey_from_secret(d) for d in secrets]
    msgs = [b"tx-%d" % i for i in range(n)]
    sigs = [ref.sign(d, m) for d, m in zip(secrets, msgs)]
    assert ek.verify_batch(pubs, msgs, sigs).all()

    # tampered sig, wrong key, wrong msg — each invalid, others unaffected
    bad_sig = bytearray(sigs[1]); bad_sig[40] ^= 0x10
    sigs2 = list(sigs); sigs2[1] = bytes(bad_sig)
    pubs2 = list(pubs); pubs2[3] = pubs[4]
    msgs2 = list(msgs); msgs2[5] = b"evil"
    valid = ek.verify_batch(pubs2, msgs2, sigs2)
    assert list(valid) == [True, False, True, False, True, False, True, True]


def test_ecdsa_malleability_and_malformed():
    d = rng.randrange(1, ref.N)
    pub = ref.pubkey_from_secret(d)
    msg = b"malleate"
    sig = ref.sign(d, msg)
    r = sig[:32]
    s = int.from_bytes(sig[32:], "big")
    high_s = r + (ref.N - s).to_bytes(32, "big")
    zero_s = r + b"\x00" * 32
    big_r = ref.N.to_bytes(32, "big") + sig[32:]
    bad_len = sig[:63]
    bad_prefix = b"\x05" + pub[1:]
    cases_pub = [pub, pub, pub, pub, bad_prefix]
    cases_sig = [high_s, zero_s, big_r, bad_len, sig]
    valid = ek.verify_batch(cases_pub, [msg] * 5, cases_sig)
    assert not valid.any()
    # oracle agrees on every case
    assert not any(
        ref.verify(p, msg, s_) for p, s_ in zip(cases_pub, cases_sig)
    )


def test_address():
    """RIPEMD160(SHA256(pub)) (secp256k1.go:131)."""
    pub = ref.pubkey_from_secret(42)
    addr = ref.address(pub)
    assert len(addr) == 20
    assert addr == hashlib.new(
        "ripemd160", hashlib.sha256(pub).digest()
    ).digest()


@pytest.mark.slow  # ~75 s: compiles two kernels for one commit;
# ecdsa_batch_valid_and_blame keeps the quick-gate batch coverage
def test_mixed_key_commit_verification():
    """A commit signed by a mix of ed25519 and secp256k1 validators
    verifies in one batch call — capability the reference lacks entirely
    (crypto/batch/batch.go:12-21 has no secp256k1 arm; mixed commits fall
    back to serial verifyCommitSingle there)."""
    from cometbft_tpu.crypto.keys import PrivKey, Secp256k1PrivKey
    from cometbft_tpu.types import canonical, validation
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    chain_id, height, round_ = "secp-chain", 5, 0
    privs = [
        PrivKey.generate(bytes([i + 1]) * 32) if i % 2 == 0
        else Secp256k1PrivKey.generate(bytes([i + 1]) * 32)
        for i in range(6)
    ]
    vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    sigs = []
    for idx, v in enumerate(vs.validators):
        ts = Timestamp(1700000000 + idx, 0)
        sb = canonical.canonical_vote_bytes(
            chain_id, canonical.PRECOMMIT_TYPE, height, round_, bid, ts
        )
        sigs.append(CommitSig(
            BLOCK_ID_FLAG_COMMIT, v.address, ts, by_addr[v.address].sign(sb)
        ))
    commit = Commit(height, round_, bid, sigs)
    for mk in (validation.oracle_batch_fn,
               lambda: validation.device_batch_fn(use_pallas=False)):
        validation.verify_commit(chain_id, vs, bid, height, commit, mk())

    # corrupt one secp sig: blame lands on the right index
    secp_idx = next(
        i for i, v in enumerate(vs.validators)
        if v.pub_key.key_type == "secp256k1"
    )
    bad = bytearray(sigs[secp_idx].signature)
    bad[8] ^= 1
    sigs2 = list(sigs)
    sigs2[secp_idx] = CommitSig(
        BLOCK_ID_FLAG_COMMIT, vs.validators[secp_idx].address,
        sigs[secp_idx].timestamp, bytes(bad),
    )
    commit2 = Commit(height, round_, bid, sigs2)
    with pytest.raises(validation.InvalidSignatureError) as ei:
        validation.verify_commit(
            chain_id, vs, bid, height, commit2,
            validation.device_batch_fn(use_pallas=False),
        )
    assert ei.value.idx == secp_idx


@pytest.mark.slow  # >8 min interpret-mode ECDSA Pallas on CPU —
# the single biggest tier-1 budget sink before it was marked
def test_ecdsa_pallas_matches_oracle():
    """Pallas ECDSA kernel vs the pure-Python oracle (interpret mode on
    CPU; Mosaic on TPU) — one tile incl. malformed/corrupt rows."""
    import numpy as np

    from cometbft_tpu.crypto import secp256k1_ref as sref
    from cometbft_tpu.crypto.keys import Secp256k1PrivKey
    from cometbft_tpu.ops import ecdsa_pallas as cp

    ks = [Secp256k1PrivKey.generate(bytes([i + 1]) * 32) for i in range(8)]
    n = 24
    msgs = [b"pallas-ecdsa-%d" % i for i in range(n)]
    pubs = [ks[i % 8].pub_key().data for i in range(n)]
    sigs = [ks[i % 8].sign(m) for i, m in enumerate(msgs)]
    sigs[2] = sigs[2][:9] + bytes([sigs[2][9] ^ 1]) + sigs[2][10:]
    sigs[5] = b"\x00" * 64                        # r = 0
    pubs[7] = b"\x07" + pubs[7][1:]               # bad prefix
    # high-S malleated twin of row 8 must be rejected (low-S rule)
    r8 = sigs[8][:32]
    s8 = int.from_bytes(sigs[8][32:], "big")
    sigs[8] = r8 + (sref.N - s8).to_bytes(32, "big")
    got = cp.verify_batch(pubs, msgs, sigs)
    exp = np.asarray(
        [sref.verify_py(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    )
    np.testing.assert_array_equal(got, exp)
    assert not exp[2] and not exp[5] and not exp[7] and not exp[8]
    assert exp[0]


def _openssl_on_the_packed_rows(qx, qparity, u1dig, u2dig, xr1, xr2,
                                precheck):
    """Stands in for ek.verify_kernel (2.5 s a 64-row pass on a CPU; the
    real kernel over these chunks is chip_smoke's `ecdsa_chunked` leg):
    what the packed rows SAY, judged by OpenSSL. A row's r is xr1, its
    s = r / u2 and its digest z = u1 s mod n, its key the compressed
    point (2 + parity, qx): verdict = precheck and ECDSA over the
    prehashed z."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        Prehashed,
        encode_dss_signature,
    )
    import jax.numpy as jnp

    def digits(row):
        return sum(int(d) << (4 * k) for k, d in enumerate(row))

    out = np.zeros(len(precheck), bool)
    for i in np.flatnonzero(precheck):
        r, u1, u2 = limbs_to_int(xr1[i]), digits(u1dig[i]), digits(u2dig[i])
        assert limbs_to_int(xr2[i]) in (r, r + ref.N) and u2
        s = r * pow(u2, ref.N - 2, ref.N) % ref.N
        key = ec.EllipticCurvePublicKey.from_encoded_point(
            ec.SECP256K1(), bytes([2 + int(qparity[i])])
            + limbs_to_int(qx[i]).to_bytes(32, "big"))
        try:
            key.verify(encode_dss_signature(r, s),
                       (u1 * s % ref.N).to_bytes(32, "big"),
                       ec.ECDSA(Prehashed(hashes.SHA256())))
            out[i] = True
        except InvalidSignature:
            pass
    return jnp.asarray(out)  # a jax.Array: the chunk loop asks is_ready()


@pytest.mark.parametrize("library", ["native", "python-loop"])
def test_served_ecdsa_chunks_give_the_oracles_verdicts(monkeypatch, library):
    """device_batch_fn() over a 1,120-row secp256k1 group with one bad
    row (two chunks of COMMIT_CHUNK_ROWS): the verdicts are
    oracle_batch_fn()'s with the chunk pack in C and with the library
    forced off, and the `secp256k1.pack` stage says which it was."""
    from cometbft_tpu import native
    from cometbft_tpu.crypto.keys import Secp256k1PrivKey
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types import validation

    pytest.importorskip("cryptography")
    if library == "python-loop":
        monkeypatch.setattr(native, "_load", lambda: None)
    elif not native.available():
        pytest.skip("no native library here")
    monkeypatch.setattr(ek, "verify_kernel", _openssl_on_the_packed_rows)
    n, bad = 1120, 1077
    ks = [Secp256k1PrivKey.generate(bytes([i + 1]) * 32) for i in range(8)]
    msgs = [b"served-ecdsa-%d" % i * (1 + i % 9) for i in range(n)]
    sigs = [ks[i % 8].sign(m) for i, m in enumerate(msgs)]
    sigs[bad] = sigs[bad][:40] + bytes([sigs[bad][40] ^ 4]) + sigs[bad][41:]
    pubs = [ks[i % 8].pub_key() for i in range(n)]
    tracing.set_clock(None)  # an empty stage ring
    got = validation.device_batch_fn(use_pallas=False)(pubs, msgs, sigs)
    want = validation.oracle_batch_fn()(pubs, msgs, sigs)
    np.testing.assert_array_equal(got, want)
    assert np.flatnonzero(~got).tolist() == [bad]
    packs = [r[4] for r in tracing.stage_records()
             if r[0] == "secp256k1.pack"]
    assert [(p["rows"], p["padded"]) for p in packs] == [
        (1024, 1024), (96, 1024)]
    assert [p["native"] for p in packs] == [int(library == "native")] * 2
    assert [p["templated"] for p in packs] == [0, 0]  # a list of bytes
