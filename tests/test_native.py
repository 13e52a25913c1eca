"""Native hostaccel: differential tests against hashlib.

The C++ SHA-512 (cometbft_tpu/native/hostaccel.cpp) must agree with
OpenSSL byte-for-byte on every length class (empty, sub-block,
block-boundary, multi-block) — padding bugs live at the boundaries.
"""
import hashlib
import os
import random

import numpy as np
import pytest

from cometbft_tpu import native


@pytest.fixture(scope="module")
def have_native():
    if not native.available():
        pytest.skip("no g++ / native module unavailable "
                    "(fallback path is exercised elsewhere)")
    return True


def test_batch_sha512_differential(have_native):
    rng = random.Random(3)
    # boundary lengths around the 128-byte block and the 112-byte
    # padding threshold, plus random sizes
    lengths = [0, 1, 63, 64, 111, 112, 113, 127, 128, 129, 255, 256,
               1000] + [rng.randrange(0, 5000) for _ in range(40)]
    rows = [os.urandom(n) for n in lengths]
    out = native.batch_sha512(rows)
    for i, r in enumerate(rows):
        assert out[i].tobytes() == hashlib.sha512(r).digest(), \
            f"mismatch at len {len(r)}"


def test_ed25519_batch_digest_differential(have_native):
    rng = random.Random(9)
    n = 64
    r_raw = np.frombuffer(os.urandom(32 * n), np.uint8).reshape(n, 32)
    a_raw = np.frombuffer(os.urandom(32 * n), np.uint8).reshape(n, 32)
    msgs = [os.urandom(rng.randrange(0, 300)) for _ in range(n)]
    out = native.ed25519_batch_digest(r_raw, a_raw, msgs)
    for i in range(n):
        want = hashlib.sha512(
            r_raw[i].tobytes() + a_raw[i].tobytes() + msgs[i]
        ).digest()
        assert out[i].tobytes() == want


def test_pack_batch_uses_native_and_agrees(have_native):
    """pack_batch output must be identical native vs fallback."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_kernel as ek

    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(16)]
    msgs = [b"msg-%d" % i for i in range(16)]
    pubs = [p.pub_key().data for p in privs]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    pb1 = ek.pack_batch(pubs, msgs, sigs)

    real_load = native._load
    try:
        native._load = lambda: None  # force fallback
        pb2 = ek.pack_batch(pubs, msgs, sigs)
    finally:
        native._load = real_load
    for f in ("ay", "asign", "ry", "rsign", "sdig", "hdig", "precheck"):
        np.testing.assert_array_equal(getattr(pb1, f), getattr(pb2, f),
                                      err_msg=f)


def test_empty_rows(have_native):
    out = native.batch_sha512([b"", b""])
    assert out[0].tobytes() == hashlib.sha512(b"").digest()


L = 2**252 + 27742317777372353535851937790883648493


def test_reduce_mod_l_differential(have_native):
    """The 512->253-bit reduction vs Python bigints, incl. adversarial
    extremes (all-0xff, values just above/below multiples of L)."""
    rng = random.Random(17)
    cases = [b"\x00" * 64, b"\xff" * 64,
             (L - 1).to_bytes(64, "little"),
             L.to_bytes(64, "little"),
             (L + 1).to_bytes(64, "little"),
             (L * (2**259 // L)).to_bytes(64, "little")]
    cases += [rng.getrandbits(512).to_bytes(64, "little")
              for _ in range(200)]
    digs = np.frombuffer(b"".join(cases), np.uint8).reshape(-1, 64)
    out = native.batch_reduce_mod_l(digs)
    assert out is not None
    for i, c in enumerate(cases):
        want = int.from_bytes(c, "little") % L
        got = int.from_bytes(out[i].tobytes(), "little")
        assert got == want, f"case {i}: got {got}, want {want}"


def test_batch_challenge_matches_fallback(have_native):
    rng = random.Random(23)
    n = 32
    r_raw = np.frombuffer(os.urandom(32 * n), np.uint8).reshape(n, 32)
    a_raw = np.frombuffer(os.urandom(32 * n), np.uint8).reshape(n, 32)
    msgs = [os.urandom(rng.randrange(0, 200)) for _ in range(n)]
    out = native.ed25519_batch_challenge(r_raw, a_raw, msgs)
    assert out is not None
    for i in range(n):
        d = hashlib.sha512(r_raw[i].tobytes() + a_raw[i].tobytes()
                           + msgs[i]).digest()
        want = int.from_bytes(d, "little") % L
        assert int.from_bytes(out[i].tobytes(), "little") == want


def test_pack_commits_matches_pack_batch(have_native):
    """The fused template+timestamp native pack must equal the
    msgs-list pipeline byte-for-byte (sign-bytes templating included)."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp

    rng = random.Random(41)
    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(8)]
    templates, row_tmpl, row_secs, row_nanos = [], [], [], []
    pubs, sigs, msgs = [], [], []
    for c in range(3):  # three "commits" with distinct templates
        bid = BlockID(bytes([c]) * 32, PartSetHeader(1, bytes([c]) * 32))
        enc = canonical.CanonicalVoteEncoder(
            "pc-chain", canonical.PRECOMMIT_TYPE, 100 + c, c, bid)
        templates.append(enc.template)
        for r in range(20):
            # adversarial timestamps: zeros, negatives, huge values
            secs = rng.choice([0, 1, -1, 2**40, -(2**40),
                               rng.randrange(2**33)])
            nanos = rng.choice([0, 1, 999999999, rng.randrange(10**9)])
            ts = Timestamp(secs, nanos)
            sb = enc.bytes_for(ts)
            k = privs[r % 8]
            pubs.append(k.pub_key().data)
            sigs.append(k.sign(sb))
            msgs.append(sb)
            row_tmpl.append(c)
            row_secs.append(secs)
            row_nanos.append(nanos)
    pad = 64
    packed = native.ed25519_pack_commits(
        b"".join(pubs), b"".join(sigs), templates,
        np.asarray(row_tmpl, np.int32), np.asarray(row_secs, np.int64),
        np.asarray(row_nanos, np.int64), pad,
    )
    assert packed is not None
    want = ek.pack_batch(pubs, msgs, sigs, pad_to=pad)
    names = ("ay", "asign", "ry", "rsign", "sdig", "hdig", "precheck")
    for name, got in zip(names, packed):
        np.testing.assert_array_equal(got, getattr(want, name),
                                      err_msg=name)


def test_batch_keccak_f1600_differential(have_native):
    from cometbft_tpu.crypto.keccak import keccak_f1600_np

    rng = np.random.default_rng(7)
    states = rng.integers(0, 2**64, size=(33, 25), dtype=np.uint64)
    out = native.batch_keccak_f1600(states)
    assert out is not None
    np.testing.assert_array_equal(out, keccak_f1600_np(states.copy()))
    # and the all-zero state (SHA-3 theta/iota sanity)
    z = np.zeros((1, 25), np.uint64)
    np.testing.assert_array_equal(
        native.batch_keccak_f1600(z), keccak_f1600_np(z.copy())
    )


def test_native_sr25519_challenges_match_batchstrobe():
    """The C transcript walker is byte-identical to the numpy
    BatchStrobe route AND the scalar reference transcripts, across
    message lengths (incl. rate-crossing >166-byte messages)."""
    import numpy as np

    from cometbft_tpu import native
    from cometbft_tpu.crypto import merlin
    from cometbft_tpu.crypto import sr25519_ref as sr

    if not native.available():
        import pytest

        pytest.skip("no native toolchain")
    rng = np.random.default_rng(3)
    for ln in (1, 32, 110, 166, 167, 400):
        n = 17
        msgs = rng.integers(0, 256, (n, ln), dtype=np.uint8)
        pks = rng.integers(0, 256, (n, 32), dtype=np.uint8)
        rs = rng.integers(0, 256, (n, 32), dtype=np.uint8)
        prefix = sr._signing_prefix()
        s = prefix.strobe
        got = native.sr25519_batch_challenges(
            bytes(s.st), s.pos, s.pos_begin, s.cur_flags, msgs, pks, rs)
        # numpy batch route
        bt = merlin.BatchTranscript(n, prefix)
        bt.append_message_batch(b"sign-bytes", msgs)
        bt.append_message_shared(b"proto-name", b"Schnorr-sig")
        bt.append_message_batch(b"sign:pk", pks)
        bt.append_message_batch(b"sign:R", rs)
        exp = bt.challenge_bytes_batch(b"sign:c", 64)
        np.testing.assert_array_equal(got, exp)
        # scalar reference for row 0
        t = prefix.clone()
        t.append_message(b"sign-bytes", msgs[0].tobytes())
        t.append_message(b"proto-name", b"Schnorr-sig")
        t.append_message(b"sign:pk", pks[0].tobytes())
        t.append_message(b"sign:R", rs[0].tobytes())
        assert t.challenge_bytes(b"sign:c", 64) == got[0].tobytes()


# --------------------------------------------------------------------------
# The ECDSA chunk pack in C: SHA-256, arithmetic mod the secp256k1 group
# order, and native.secp256k1_pack against ops/ecdsa_kernel.pack_batch's
# Python loop, array for array
# --------------------------------------------------------------------------

# SHA-256's block (64) and padding (56) edges, one and two blocks up
SHA256_LENGTHS = [0, 1, 55, 56, 63, 64, 119, 120, 200]


@pytest.mark.parametrize("length", SHA256_LENGTHS + ["random"])
def test_batch_sha256_differential(have_native, length):
    rng = random.Random(11)
    lengths = ([rng.randrange(0, 1500) for _ in range(40)]
               if length == "random" else [length] * 3)
    rows = [rng.randbytes(n) for n in lengths]
    out = np.empty((len(rows), 32), np.uint8)
    native._load().batch_sha256(*native._msg_arrays(rows), len(rows), out)
    for got, row in zip(out, rows):
        assert got.tobytes() == hashlib.sha256(row).digest(), len(row)


def _be32(values):
    return np.frombuffer(b"".join(v.to_bytes(32, "big") for v in values),
                         np.uint8).reshape(-1, 32)


def _ints_le(out):
    return [int.from_bytes(row.tobytes(), "little") for row in out]


@pytest.mark.parametrize("operands", ["edges", "random"])
def test_secp256k1_scalar_arithmetic_differential(have_native, operands):
    """a * b mod n over every pair of the edge operands (not reduced
    before the product: a digest can be anything under 2^256), and the
    inverse, against Python integers."""
    from cometbft_tpu.crypto.secp256k1_ref import N

    rng = random.Random(19)
    vals = ([0, 1, 2, N - 1, N, N + 1, 2**256 - 1, 2**255, 2**128,
             2**256 - N, (N - 1) // 2]
            if operands == "edges"
            else [rng.getrandbits(256) for _ in range(24)])
    a = [x for x in vals for _ in vals]
    b = [y for _ in vals for y in vals]
    out = np.empty((len(a), 32), np.uint8)
    native._load().secp256k1_batch_mulmod_n(_be32(a), _be32(b), len(a), out)
    assert _ints_le(out) == [x * y % N for x, y in zip(a, b)]
    out = np.empty((len(vals), 32), np.uint8)
    native._load().secp256k1_batch_invmod_n(_be32(vals), len(vals), out)
    # 0 and n have no inverse: Fermat's power gives 0, as pow() does
    assert _ints_le(out) == [pow(v, N - 2, N) for v in vals]
    assert all(v * w % N == 1 for v, w in zip(vals, _ints_le(out))
               if v % N)


ECDSA_ARRAYS = ("qx", "qparity", "u1dig", "u2dig", "xr1", "xr2", "precheck")


def _ecdsa_both_packs(monkeypatch, pubs, msgs, sigs, pad, native_on=True):
    """pack_batch with the C call and with the wrapper answering None
    (the Python loop, the referee): all seven arrays equal element for
    element, in dtype and shape; returns the native pack."""
    from cometbft_tpu.ops import ecdsa_kernel as eck

    got = eck.pack_batch(pubs, msgs, sigs, pad_to=pad)
    with monkeypatch.context() as m:
        m.setattr(native, "secp256k1_pack", lambda *a: None)
        want = eck.pack_batch(pubs, msgs, sigs, pad_to=pad)
    assert (got.native, want.native) == (native_on, False)
    assert (got.n, got.padded) == (want.n, want.padded) == (len(pubs), pad)
    for name in ECDSA_ARRAYS:
        g, w = getattr(got, name), getattr(want, name)
        assert (g.dtype, g.shape) == (w.dtype, w.shape), name
        np.testing.assert_array_equal(g, w, err_msg=name)
        assert not g[got.n:].any(), name  # padded rows all zero
    return got


def _ecdsa_signed(n, rng, lengths=None):
    from cometbft_tpu.crypto import secp256k1_ref as sc

    secrets = [rng.randrange(1, sc.N) for _ in range(5)]
    keys = [sc.pubkey_from_secret(d) for d in secrets]
    msgs = [rng.randbytes(lengths[i % len(lengths)] if lengths
                          else rng.randrange(0, 300)) for i in range(n)]
    return ([keys[i % 5] for i in range(n)], msgs,
            [sc.sign(secrets[i % 5], m) for i, m in enumerate(msgs)])


def _crafted(xs=None, rs=None, ss=None, prefixes=None):
    """Rows of chosen integers (nothing here is a valid signature: the
    pack screens and converts, it does not verify), every list as long
    as the longest, the others at harmless values."""
    n = max(len(v) for v in (xs, rs, ss, prefixes) if v is not None)
    xs, rs, ss = xs or [7 + i for i in range(n)], rs or [11] * n, ss or [13] * n
    prefixes = prefixes or [2 + i % 2 for i in range(n)]
    pubs = [bytes([p]) + x.to_bytes(32, "big") for p, x in zip(prefixes, xs)]
    sigs = [r.to_bytes(32, "big") + s.to_bytes(32, "big")
            for r, s in zip(rs, ss)]
    return pubs, [b"crafted-%d" % i for i in range(n)], sigs


def _ecdsa_cases():
    from cometbft_tpu.crypto.secp256k1_ref import HALF_N, N, P

    top = 2**256 - 1
    return {
        # r + n < p, so the second x is r + n: never at random (p - n
        # has 129 bits), and r just at and above the line
        "r-below-p-minus-n": (_crafted(rs=[1, 2**128, P - N - 1, P - N,
                                           P - N + 1, 2**129]),
                              [1, 1, 1, 1, 1, 1]),
        "s-edges": (_crafted(ss=[0, 1, HALF_N, HALF_N + 1, N - 1, N, top]),
                    [0, 1, 1, 0, 0, 0, 0]),
        "r-edges": (_crafted(rs=[0, 1, N - 1, N, top]), [0, 1, 1, 0, 0]),
        "x-edges": (_crafted(xs=[0, P - 1, P, top]), [1, 1, 0, 0]),
        "prefixes": (_crafted(prefixes=[0, 1, 2, 3, 4, 5, 255]),
                     [0, 0, 1, 1, 0, 0, 0]),
        "none-screened": (_crafted(ss=[0, N, 0]), [0, 0, 0]),
        "one-screened": (_crafted(ss=[0, 0, 5, 0]), [0, 0, 1, 0]),
        "first-and-last-refused": (_crafted(ss=[0, 3, 5, 7, N]),
                                   [0, 1, 1, 1, 0]),
    }


@pytest.mark.parametrize("case", sorted(_ecdsa_cases()))
def test_secp256k1_pack_screens_as_the_python_loop(have_native, monkeypatch,
                                                   case):
    (pubs, msgs, sigs), screened = _ecdsa_cases()[case]
    got = _ecdsa_both_packs(monkeypatch, pubs, msgs, sigs, pad=16)
    assert got.precheck[:got.n].astype(int).tolist() == screened
    for name in ECDSA_ARRAYS:  # a refused row keeps an all-zero payload
        assert not getattr(got, name)[:got.n][~got.precheck[:got.n]].any()


def test_secp256k1_pack_second_x_is_r_plus_n_below_p_minus_n(have_native):
    """The crafted rows really take the branch: xr2 = r + n for r under
    p - n, r itself from there up."""
    from cometbft_tpu.crypto.secp256k1_ref import N, P
    from cometbft_tpu.ops import ecdsa_kernel as eck
    from cometbft_tpu.ops.field import limbs_to_int

    rs = [1, P - N - 1, P - N, P - N + 1]
    got = eck.pack_batch(*_crafted(rs=rs), pad_to=8)
    assert got.native
    assert [limbs_to_int(v) for v in got.xr1[:4]] == rs
    assert [limbs_to_int(v) for v in got.xr2[:4]] == [
        1 + N, P - 1, P - N, P - N + 1]


@pytest.mark.parametrize("lengths", [None, SHA256_LENGTHS],
                         ids=["rfc6979-random", "sha256-edges"])
def test_secp256k1_pack_signed_rows(have_native, monkeypatch, lengths):
    """RFC 6979 signatures over random messages and over messages of
    SHA-256's edge lengths: every row screened (the chunk's `all`), the
    arrays the Python loop's, and what they say is what was signed:
    u1 = z / s and u2 = r / s mod n."""
    from cometbft_tpu.crypto.secp256k1_ref import N

    pubs, msgs, sigs = _ecdsa_signed(45, random.Random(29), lengths)
    got = _ecdsa_both_packs(monkeypatch, pubs, msgs, sigs, pad=64)
    assert got.precheck[:45].all()
    for i in (0, 17, 44):
        z = int.from_bytes(hashlib.sha256(msgs[i]).digest(), "big")
        r = int.from_bytes(sigs[i][:32], "big")
        s = int.from_bytes(sigs[i][32:], "big")
        u1 = sum(int(d) << (4 * k) for k, d in enumerate(got.u1dig[i]))
        u2 = sum(int(d) << (4 * k) for k, d in enumerate(got.u2dig[i]))
        assert (u1 * s % N, u2 * s % N) == (z % N, r)


@pytest.mark.parametrize("form", ["sign-rows-unequal-lens",
                                  "sign-rows-run", "template-rows",
                                  "list-of-bytes", "zero-width-matrix"])
def test_secp256k1_pack_message_forms(have_native, monkeypatch, form):
    """The messages as a SignRows matrix with rows of unequal length
    (each hashed where it lies, the zero padding never read), a run of
    one (a chunk's slice), a commit's lazy TemplateRows, and a list of
    bytes: the same arrays, and the same as for the list."""
    from cometbft_tpu.crypto import secp256k1_ref as sc
    from cometbft_tpu.ops import ecdsa_kernel as eck
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader

    rng = random.Random(31)
    n = 21
    if form == "template-rows":
        bid = BlockID(b"\x0a" * 32, PartSetHeader(1, b"\x0b" * 32))
        rows = canonical.TemplateRows(
            [canonical.VoteRowTemplate("secp-rows", canonical.PRECOMMIT_TYPE,
                                       9, 1, b) for b in (bid, None)],
            np.asarray([i % 4 == 3 for i in range(n)], np.int32),
            np.asarray([1_700_000_000 + i % 3 for i in range(n)], np.int64),
            np.asarray([0, 1, 999_999_999] * 7, np.int64))
        as_list = list(rows)
    else:
        lengths = {"zero-width-matrix": [0]}.get(form, [0, 1, 64, 119, 5])
        as_list = [rng.randbytes(lengths[i % len(lengths)])
                   for i in range(n)]
        width = max(map(len, as_list)) + (3 if lengths != [0] else 0)
        mat = np.full((n, width), 0xEE, np.uint8)  # never read past lens
        for i, m in enumerate(as_list):
            mat[i, :len(m)] = np.frombuffer(m, np.uint8)
        rows = canonical.SignRows(mat, [len(m) for m in as_list])
    secrets = [rng.randrange(1, sc.N) for _ in range(n)]
    pubs = [sc.pubkey_from_secret(d) for d in secrets]
    sigs = [sc.sign(d, m) for d, m in zip(secrets, as_list)]
    lo, hi = (5, 17) if form == "sign-rows-run" else (0, n)
    msgs = as_list if form == "list-of-bytes" else rows[lo:hi]
    got = _ecdsa_both_packs(monkeypatch, pubs[lo:hi], msgs, sigs[lo:hi],
                            pad=32)
    assert got.precheck[:hi - lo].all()
    want = eck.pack_batch(pubs[lo:hi], as_list[lo:hi], sigs[lo:hi], pad_to=32)
    for name in ECDSA_ARRAYS:
        np.testing.assert_array_equal(getattr(got, name),
                                      getattr(want, name), err_msg=name)


@pytest.mark.parametrize("odd", ["32-byte-key", "63-byte-signature",
                                 "34-byte-key", "empty-chunk"])
def test_secp256k1_pack_takes_the_python_loop_for_odd_lengths(
        have_native, monkeypatch, odd):
    """One key or signature of another length in the chunk (or no row
    at all): the Python loop packs it, `native` False, that row refused
    and the others as ever."""
    pubs, msgs, sigs = _ecdsa_signed(9, random.Random(37))
    if odd == "32-byte-key":
        pubs[4] = pubs[4][1:]
    elif odd == "34-byte-key":
        pubs[4] = pubs[4] + b"\x00"
    elif odd == "63-byte-signature":
        sigs[4] = sigs[4][:63]
    else:
        pubs, msgs, sigs = [], [], []
    got = _ecdsa_both_packs(monkeypatch, pubs, msgs, sigs, pad=16,
                            native_on=False)
    assert got.precheck[:got.n].tolist() == [i != 4 for i in range(got.n)]


def test_secp256k1_pack_wrapper_checks_its_sizes(have_native):
    pubs, msgs, sigs = _ecdsa_signed(3, random.Random(41))
    with pytest.raises(ValueError):
        native.secp256k1_pack(b"".join(pubs)[:-1], b"".join(sigs), msgs, 4)
    with pytest.raises(ValueError):
        native.secp256k1_pack(b"".join(pubs), b"".join(sigs), msgs, 2)


def test_secp256k1_pack_without_the_library(monkeypatch):
    """No library: the wrapper answers None, as every wrapper here, and
    pack_batch is the Python loop."""
    from cometbft_tpu.ops import ecdsa_kernel as eck

    pubs, msgs, sigs = _ecdsa_signed(3, random.Random(43))
    monkeypatch.setattr(native, "_load", lambda: None)
    assert native.secp256k1_pack(b"".join(pubs), b"".join(sigs), msgs,
                                 4) is None
    pb = eck.pack_batch(pubs, msgs, sigs, pad_to=4)
    assert not pb.native and pb.precheck[:3].all()


# --------------------------------------------------------------------------
# a validator set's merkle root in one native call (native.valset_root)
# --------------------------------------------------------------------------


def _valset(n, key_type, rnd, klen=None):
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.types.validator import (MAX_TOTAL_VOTING_POWER,
                                              Validator, ValidatorSet)

    klen = klen or (32 if key_type == "ed25519" else 33)
    # powers across the varint's byte boundaries, the largest at which
    # the set's total stays under MAX_TOTAL_VOTING_POWER among them
    top = MAX_TOTAL_VOTING_POWER // n
    powers = [1, 127, 128, 16383, 16384, 2**35, top]
    return ValidatorSet([
        Validator(PubKey(rnd.randbytes(klen), key_type),
                  min(top, rnd.choice(powers + [rnd.randint(1, top)])))
        for _ in range(n)])


def _root_and_stage(vs):
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types.validator import HASH_STAGE

    tracing.set_clock(None)  # an empty stage ring
    root = vs.hash()
    rec, = [r for r in tracing.stage_records() if r[0] == HASH_STAGE]
    return root, rec[4]


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 7, 8, 9, 100, 1000, 10000])
@pytest.mark.parametrize("key_type", ["ed25519", "secp256k1"])
def test_valset_root_is_the_merkle_root_of_the_leaves(have_native, n,
                                                      key_type):
    """ValidatorSet.hash() through the one C call equals the Python
    tree over Validator.bytes(), at every shape of the tree's split."""
    from cometbft_tpu.crypto import merkle

    vs = _valset(n, key_type, random.Random(f"root/{n}/{key_type}"))
    root, args = _root_and_stage(vs)
    assert args == {"n": n, "native": 1}
    assert root == merkle.hash_from_byte_slices(
        [v.bytes() for v in vs.validators])


@pytest.mark.parametrize("why", ["no-library", "a-33-byte-key-among-32",
                                 "mixed-key-types"])
def test_valset_root_falls_back_to_the_python_tree(have_native, monkeypatch,
                                                   why):
    """No library, or keys of unequal length: the leaves are built in
    Python, `native` 0, the same root; keys of one length and two types
    stay in the C call, each under its own field number."""
    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.types.validator import Validator

    rnd = random.Random(f"fallback/{why}")
    vs = _valset(9, "ed25519", rnd)
    if why == "no-library":
        monkeypatch.setattr(native, "_load", lambda: None)
    elif why == "a-33-byte-key-among-32":
        vs.validators = list(vs.validators)
        vs.validators[4] = Validator(PubKey(rnd.randbytes(33), "secp256k1"),
                                     7)
    else:
        vs.validators = list(vs.validators)
        vs.validators[4] = Validator(PubKey(rnd.randbytes(32), "sr25519"), 7)
    root, args = _root_and_stage(vs)
    assert args == {"n": 9, "native": int(why == "mixed-key-types")}
    assert root == merkle.hash_from_byte_slices(
        [v.bytes() for v in vs.validators])


def test_valset_root_wrapper_checks_its_sizes(have_native):
    keys, fields = b"\x01" * 64, np.ones(2, np.uint8)
    powers = np.ones(2, np.int64)
    assert len(native.valset_root(keys, 32, fields, powers)) == 32
    for bad in ((keys[:-1], 32, fields, powers), (keys, 32, fields[:1],
                                                   powers),
                (b"", 32, fields[:0], powers[:0]),
                (b"\x01" * 258, 129, fields, powers)):
        with pytest.raises(ValueError):
            native.valset_root(*bad)
