"""benchmarks/reference/schnorrkel.py, the plain reference that decides
`correct` in the `mixed-10k.commit` cell: pinned to public vectors, and
compared with the program's own host implementation (sr25519_ref, an
independent writing of the same protocol) on seeded rows, honest and
damaged in each way the protocol rejects."""
import hashlib

import pytest

from cometbft_tpu.crypto import ed25519_ref as ed
from cometbft_tpu.crypto import sr25519_ref as sr

# RFC 9496 appendix A.1: the encodings of 0 B .. 5 B
SMALL_MULTIPLES = [
    "0000000000000000000000000000000000000000000000000000000000000000",
    "e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76",
    "6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919",
    "94741f5d5d52755ece4f23f044ee27d5d1ea1e2bd196b462166b16152a9d0259",
    "da80862773358b466ffadfe0b3293ab3d9fd53c5ea6c955358f568322daf6a57",
    "e882b131016b52c1d3337080187cf768423efccbb517bb495ab812c4160ff44e",
]


@pytest.fixture(scope="module")
def sk(plain_reference):
    return plain_reference.schnorrkel


def test_merlin_transcript_vector(sk):
    """merlin/src/transcript.rs, test_transcript_equivalence_simple."""
    t = sk.Transcript(b"test protocol")
    t.append_message(b"some label", b"some data")
    assert t.challenge_bytes(b"challenge", 32).hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615")


def test_keccak_permutation_is_sha3s(sk):
    """One block of SHA3-256 built on the reference's permutation."""
    import struct

    for msg in (b"", b"abc", b"x" * 135):
        block = bytearray(msg + b"\x06" + b"\x00" * (135 - len(msg)))
        block[135] |= 0x80
        lanes = list(struct.unpack("<17Q", block)) + [0] * 8
        out = struct.pack("<25Q", *sk.keccak_f1600(lanes))[:32]
        assert out == hashlib.sha3_256(msg).digest()


def test_small_multiples_of_the_generator(sk):
    pt = sk.IDENTITY
    for k, want in enumerate(SMALL_MULTIPLES):
        assert sk.encode(pt).hex() == want
        assert sk.encode(sk.base_mul(k)).hex() == want
        assert sk.encode(sk.mul(k, sk.BASE)).hex() == want
        back = sk.decode(bytes.fromhex(want))
        assert back is not None and sk.equal(back, pt)
        pt = sk._add(pt, sk.BASE)


def _rows(n):
    out = []
    for i in range(n):
        seed = hashlib.sha256(b"schnorrkel-ref/%d" % i).digest()
        msg = hashlib.sha256(seed).digest() * (1 + i % 6)  # 32..192 B
        out.append((sr.pubkey_from_seed(seed), msg,
                    sr.sign(seed, msg, rng=seed[::-1])))
    return out


def _set(sig, at, byte):
    return sig[:at] + bytes([byte]) + sig[at + 1:]


def _s_plus_l(sig):
    """s + L is the same scalar mod L: refused for its encoding alone."""
    s = (int.from_bytes(sig[32:], "little") & (2 ** 255 - 1)) + ed.L
    return sig[:32] + (s | 1 << 255).to_bytes(32, "little")


DAMAGE = {
    "honest": lambda p, m, s: (p, m, s),
    "flipped-R": lambda p, m, s: (p, m, _set(s, 5, s[5] ^ 1)),
    "flipped-s": lambda p, m, s: (p, m, _set(s, 40, s[40] ^ 4)),
    "other-message": lambda p, m, s: (p, m + b"!", s),
    "other-key": lambda p, m, s: (sr.pubkey_from_seed(b"\x07" * 32), m, s),
    "no-marker": lambda p, m, s: (p, m, _set(s, 63, s[63] & 0x7F)),
    "s-not-below-L": lambda p, m, s: (p, m, _s_plus_l(s)),
    "R-not-below-p": lambda p, m, s: (
        p, m, (2 ** 255 - 19 + 2).to_bytes(32, "little") + s[32:]),
    "R-odd": lambda p, m, s: (p, m, _set(s, 0, s[0] | 1)),
    "key-not-below-p": lambda p, m, s: (
        (2 ** 255 - 19 + 4).to_bytes(32, "little"), m, s),
    "key-odd": lambda p, m, s: (_set(p, 0, p[0] | 1), m, s),
    "key-off-the-group": lambda p, m, s: ((2).to_bytes(32, "little"), m, s),
    "short-signature": lambda p, m, s: (p, m, s[:63]),
    "short-key": lambda p, m, s: (p[:31], m, s),
}
HONEST = {"honest"}


@pytest.mark.parametrize("damage", sorted(DAMAGE))
def test_agrees_with_the_programs_host_verifier(sk, damage):
    for row in _rows(6):
        p, m, s = DAMAGE[damage](*row)
        want = sr.verify(p, m, s)
        assert sk.verify(p, m, s) is want
        assert want is (damage in HONEST)


def test_small_even_encodings_decode_alike(sk):
    """Half of the small even strings are on the group, half are not:
    the two decoders split them alike, and what decodes encodes back."""
    from cometbft_tpu.crypto import ristretto_ref as rist

    on = 0
    for s in range(0, 80, 2):
        enc = s.to_bytes(32, "little")
        pt = sk.decode(enc)
        assert (pt is None) == (rist.decode(enc) is None)
        if pt is not None:
            on += 1
            assert sk.encode(pt) == enc
    assert 10 <= on <= 30


def test_mixed_light_verification_is_plains_with_two_verifiers(
        plain_reference, monkeypatch):
    sk, plain = plain_reference.schnorrkel, plain_reference.plain
    seen = []
    monkeypatch.setattr(plain, "verify_sig",
                        lambda p, m, s: seen.append(("ed", p)) or s == b"ok")
    monkeypatch.setattr(sk, "verify",
                        lambda p, m, s: seen.append(("sr", p)) or s == b"ok")
    types = ["ed25519", "sr25519"] * 3
    pubs = [b"%d" % i for i in range(6)]

    def run(sigs, powers=(10,) * 6):
        del seen[:]
        return sk.verify_commit_light(pubs, types, list(powers),
                                      [b"m"] * 6, sigs)

    ok = [b"ok"] * 6
    assert run(ok) == ("ok",)
    # more than 40 of 60: five rows examined, each by its own verifier
    assert seen == [("ed", b"0"), ("sr", b"1"), ("ed", b"2"), ("sr", b"3"),
                    ("ed", b"4")]
    assert run([b"ok", b"ok", b"ok", b"no", b"no", b"ok"]) == (
        "invalid_signature", 3)
    assert run(ok[:5] + [b"no"]) == ("ok",)  # past the quorum point
    assert run([b"ok", None, b"ok", None, b"ok", b"ok"]) == (
        "not_enough_power", 40)
    assert run([None, b"ok", b"no", b"ok", b"ok", b"ok"],
               (1, 30, 1, 30, 1, 1)) == ("invalid_signature", 2)
    assert run(ok) == plain.verify_commit_light(pubs, [10] * 6, [b"m"] * 6,
                                                ok)
