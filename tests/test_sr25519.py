"""sr25519 (schnorrkel): keccak/STROBE/merlin conformance, ristretto255
round trips, host sign/verify, and the batched device kernel.

Reference: crypto/sr25519/{batch.go,pubkey.go,privkey.go} — the protocol
itself lives in curve25519-voi; our ground truths are (a) hashlib for the
keccak permutation, (b) the published merlin conformance vector, (c) the
pure-host schnorrkel implementation as a differential oracle.
"""
import hashlib
import os

import numpy as np
import pytest

from cometbft_tpu.crypto import keccak, merlin
from cometbft_tpu.crypto import ristretto_ref as rist
from cometbft_tpu.crypto import sr25519_ref as sr
from cometbft_tpu.crypto import ed25519_ref as ed
from cometbft_tpu.crypto.keys import SR25519_KEY_TYPE, Sr25519PrivKey


def test_keccak_permutation_vs_hashlib():
    """Full SHA3-256 sponge built on our keccak-f must match hashlib —
    validates the derived round constants and rotation offsets."""
    for n in (0, 1, 135, 136, 137, 1000):
        d = os.urandom(n)
        assert keccak.sha3_256(d) == hashlib.sha3_256(d).digest()


def test_keccak_batched_matches_scalar():
    rng = np.random.default_rng(1)
    sts = rng.integers(0, 1 << 63, (5, 25), np.int64).astype(np.uint64)
    out = keccak.keccak_f1600_np(sts.copy())
    for i in range(5):
        assert [int(x) for x in out[i]] == keccak.keccak_f1600(
            [int(x) for x in sts[i]]
        )


def test_merlin_conformance_vector():
    """The published merlin transcript test vector
    (merlin/src/transcript.rs, test_transcript_equivalence_simple)."""
    t = merlin.Transcript(b"test protocol")
    t.append_message(b"some label", b"some data")
    c = t.challenge_bytes(b"challenge", 32)
    assert c.hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615"
    )


def test_merlin_batch_matches_scalar():
    prefix = merlin.Transcript(b"proto")
    prefix.append_message(b"ctx", b"shared")
    msgs = np.frombuffer(
        b"".join(bytes([i]) * 40 for i in range(4)), np.uint8
    ).reshape(4, 40)
    bt = merlin.BatchTranscript(4, prefix)
    bt.append_message_batch(b"m", msgs)
    out = bt.challenge_bytes_batch(b"c", 64)
    for i in range(4):
        ts = prefix.clone()
        ts.append_message(b"m", bytes(msgs[i]))
        assert bytes(out[i]) == ts.challenge_bytes(b"c", 64)


def test_ristretto_roundtrip():
    for k in (1, 2, 7, 123456, ed.L - 1):
        pt = ed.pt_mul(k, ed.BASE_EXT)
        b = rist.encode(pt)
        pt2 = rist.decode(b)
        assert pt2 is not None and rist.equals(pt, pt2)
        assert rist.encode(pt2) == b


def test_ristretto_rejects_noncanonical():
    assert rist.decode((rist.P + 2).to_bytes(32, "little")) is None  # >= p
    assert rist.decode((1).to_bytes(32, "little")) is None  # negative (odd)
    # sqrt-ratio failures must reject, and everything that DOES decode
    # must round-trip to the identical canonical bytes (decode is a
    # bijection onto its image — RFC 9496 §4.3.1); small even s values
    # split roughly half and half between the two cases
    rejected = 0
    for s in range(0, 60, 2):
        b = s.to_bytes(32, "little")
        pt = rist.decode(b)
        if pt is None:
            rejected += 1
        else:
            assert rist.encode(pt) == b
    assert rejected >= 10


def test_sign_verify_roundtrip():
    k = Sr25519PrivKey.generate(b"\x11" * 32)
    pk = k.pub_key()
    assert pk.key_type == SR25519_KEY_TYPE
    sig = k.sign(b"hello")
    assert sig[63] & 0x80
    assert pk.verify_signature(b"hello", sig)
    assert not pk.verify_signature(b"hellp", sig)
    bad = bytearray(sig)
    bad[0] ^= 1
    assert not pk.verify_signature(b"hello", bytes(bad))
    # marker bit is mandatory (schnorrkel signature format)
    nomark = bytearray(sig)
    nomark[63] &= 0x7F
    assert not pk.verify_signature(b"hello", bytes(nomark))


def _fixture(n, bad=()):
    ks = [Sr25519PrivKey.generate(bytes([i + 1]) * 32) for i in range(8)]
    msgs = [b"sr-%04d" % i for i in range(n)]
    pubs = [ks[i % 8].pub_key().data for i in range(n)]
    sigs = [ks[i % 8].sign(m) for i, m in enumerate(msgs)]
    for i in bad:
        sigs[i] = sigs[i][:5] + bytes([sigs[i][5] ^ 1]) + sigs[i][6:]
    return pubs, msgs, sigs


@pytest.mark.slow  # ~6 min sr25519 kernel compile+run on CPU;
# kernel_rejects_bad_encodings keeps a quick-gate kernel probe
def test_kernel_matches_oracle():
    from cometbft_tpu.ops import sr25519_kernel as srk

    pubs, msgs, sigs = _fixture(32, bad=(3, 17))
    got = srk.verify_batch(pubs, msgs, sigs)
    exp = np.asarray(
        [sr.verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    )
    assert (got == exp).all()
    assert not exp[3] and not exp[17] and exp[0]


@pytest.mark.slow  # ~71 s on the 1-core host under suite load;
# ristretto_rejects_noncanonical + mixed_batch_dispatch stay quick
def test_kernel_rejects_bad_encodings():
    from cometbft_tpu.ops import sr25519_kernel as srk

    pubs, msgs, sigs = _fixture(8)
    sigs[1] = sigs[1][:63] + bytes([sigs[1][63] & 0x7F])  # no marker
    sigs[2] = b"\x01" + sigs[2][1:]  # R likely invalid/odd encoding
    pubs[4] = (rist.P + 2).to_bytes(32, "little")  # non-canonical pk
    got = srk.verify_batch(pubs, msgs, sigs)
    exp = np.asarray(
        [sr.verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    )
    assert (got == exp).all()
    assert not exp[1] and not exp[2] and not exp[4]


def _pack_batch_sr_row_by_row(pubkeys, msgs, sigs, pad_to=None):
    """pack_batch_sr as it stood before the served commit check fed it
    chunks (ISSUE 33), kept as the reference of the vectorised intake:
    a Python loop over the well-formed rows."""
    from cometbft_tpu import native
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.ops import ed25519_pallas as kp
    from cometbft_tpu.ops import sr25519_kernel as srk
    from cometbft_tpu.ops.field import NLIMBS, F25519

    n = len(pubkeys)
    pad = pad_to or kp.pad_to_tile(n)
    a_l = np.zeros((pad, NLIMBS), np.int32)
    r_l = np.zeros((pad, NLIMBS), np.int32)
    sdig = np.zeros((pad, 64), np.int32)
    hdig = np.zeros((pad, 64), np.int32)
    precheck = np.zeros((pad,), np.int32)
    r_encs = [bytes(s[:32]) if len(s) == 64 else b"\x00" * 32 for s in sigs]
    chal = srk.batch_challenges(
        [bytes(m) for m in msgs], [bytes(p) for p in pubkeys], r_encs)
    lenok = np.array(
        [len(pubkeys[i]) == 32 and len(sigs[i]) == 64
         and bool(sigs[i][63] & 0x80) for i in range(n)], np.bool_)
    pk_arr = np.zeros((n, 32), np.uint8)
    r_arr = np.zeros((n, 32), np.uint8)
    s_arr = np.zeros((n, 32), np.uint8)
    for i in np.flatnonzero(lenok):
        pk_arr[i] = np.frombuffer(bytes(pubkeys[i]), np.uint8)
        sig = np.frombuffer(bytes(sigs[i]), np.uint8)
        r_arr[i] = sig[:32]
        s_arr[i] = sig[32:]
    s_arr[:, 31] &= 0x7F
    ok = (lenok & srk._below_p(pk_arr) & srk._below_p(r_arr)
          & ((pk_arr[:, 0] & 1) == 0) & ((r_arr[:, 0] & 1) == 0)
          & ek.s_below_l(s_arr))
    k_red = native.batch_reduce_mod_l(chal[:n])
    if k_red is None:
        k_red = np.zeros((n, 32), np.uint8)
        for i in range(n):
            k_red[i] = np.frombuffer(
                (int.from_bytes(bytes(chal[i]), "little")
                 % ed.L).to_bytes(32, "little"), np.uint8)
    bad = ~ok
    for arr in (pk_arr, r_arr, s_arr, k_red):
        arr[bad] = 0
    a_l[:n] = F25519.from_bytes_le(pk_arr)
    r_l[:n] = F25519.from_bytes_le(r_arr)
    sdig[:n] = ek.nibbles(s_arr)
    hdig[:n] = ek.nibbles(k_red)
    precheck[:n] = ok.astype(np.int32)
    pb = kp._PB(a_l, np.zeros((pad,), np.int32), r_l,
                np.zeros((pad,), np.int32), sdig, hdig, precheck)
    pb.n = n
    return kp.pack_rows(pb)


def _damaged(n):
    """Honest rows, and among them every kind the host prechecks
    reject: a flipped bit (passes them), no marker, s >= L, encodings
    not below p or odd, a short and an empty signature, messages of
    two lengths."""
    pubs, msgs, sigs = _fixture(n, bad=(2,))
    msgs[3] += b"-longer"
    sigs[3] = Sr25519PrivKey.generate(b"\x04" * 32).sign(msgs[3])
    sigs[5] = sigs[5][:63] + bytes([sigs[5][63] & 0x7F])
    sigs[6] = sigs[6][:32] + (ed.L + 5).to_bytes(32, "little")[:31] + \
        bytes([0x80 | (ed.L + 5).to_bytes(32, "little")[31]])
    sigs[7] = (rist.P + 4).to_bytes(32, "little") + sigs[7][32:]
    sigs[8] = b"\x03" + sigs[8][1:]
    pubs[9] = (rist.P + 2).to_bytes(32, "little")
    pubs[10] = b"\x05" + pubs[10][1:]
    sigs[11] = sigs[11][:40]
    sigs[12] = b""
    return pubs, msgs, sigs


_SHAPES = [(0, None), (1, None), (40, None), (40, 128), (130, 256),
           (128, 128)]


@pytest.mark.parametrize("rows,n,pad_to", [
    (rows, n, pad_to) for rows in ("honest", "damaged")
    for n, pad_to in _SHAPES if rows == "honest" or n >= 13])
def test_pack_batch_sr_packs_what_it_always_packed(rows, n, pad_to):
    """Byte for byte, whatever the padding: the vectorised intake of
    well-formed rows against the row-by-row loop it replaced."""
    from cometbft_tpu.ops import ed25519_pallas as kp
    from cometbft_tpu.ops import sr25519_kernel as srk

    pubs, msgs, sigs = _damaged(n) if rows == "damaged" else _fixture(n)
    got = srk.pack_batch_sr(pubs, msgs, sigs, pad_to=pad_to)
    want = _pack_batch_sr_row_by_row(pubs, msgs, sigs, pad_to=pad_to)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.shape[1] == (pad_to or kp.pad_to_tile(n))
    np.testing.assert_array_equal(got, want)
    live = (got[kp.C_FLAGS, :n] >> 2) & 1
    if rows == "damaged":  # only the prechecks' rejections are flagged
        assert sorted(np.flatnonzero(live == 0)) == [5, 6, 7, 8, 9, 10,
                                                     11, 12]
    else:
        assert live.all()


def _mixed_fixture():
    from cometbft_tpu.crypto.keys import PrivKey

    eks = [PrivKey.generate(bytes([40 + i]) * 32) for i in range(4)]
    sks = [Sr25519PrivKey.generate(bytes([80 + i]) * 32) for i in range(4)]
    pubs, msgs, sigs = [], [], []
    for i in range(8):
        m = b"mixed-%d" % i
        if i % 2 == 0:
            k = eks[i // 2]
        else:
            k = sks[i // 2]
        pubs.append(k.pub_key())
        msgs.append(m)
        sigs.append(k.sign(m))
    sigs[5] = sigs[5][:8] + bytes([sigs[5][8] ^ 1]) + sigs[5][9:]
    exp = np.ones(8, bool)
    exp[5] = False
    return pubs, msgs, sigs, exp


@pytest.mark.slow  # ~143 s: the sr25519 group pays the kernel
# compile on CPU ([tier1-duration] flagged it past the 60 s line);
# test_mixed_batch_dispatch_grouping keeps the dispatch seam quick
def test_mixed_batch_dispatch():
    """ed25519 + sr25519 rows in one crypto/batch call (the BASELINE
    config #3 seam; goes beyond crypto/batch/batch.go:12 which can't mix
    key types in one verifier)."""
    from cometbft_tpu.crypto import batch as cbatch

    pubs, msgs, sigs, exp = _mixed_fixture()
    valid = cbatch.verify_batch(pubs, msgs, sigs)
    assert (valid == exp).all()


def test_mixed_batch_dispatch_grouping(monkeypatch):
    """The quick-gate sibling of test_mixed_batch_dispatch: same mixed
    fixture, same grouping/reassembly/blame logic in
    crypto/batch.verify_batch, but the per-key-type kernels are
    monkeypatched to the host oracles at the `_kernel_for` seam — so
    the DISPATCH layer (group by key type, one call per group, verdicts
    scattered back to input order) is proven without paying the
    sr25519 kernel compile the slow variant covers."""
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto.keys import ED25519_KEY_TYPE

    routed = []

    def host_kernel_for(key_type):
        routed.append(key_type)
        if key_type == ED25519_KEY_TYPE:
            return lambda pubs, msgs, sigs: np.asarray(
                [ed.verify(p, m, s)
                 for p, m, s in zip(pubs, msgs, sigs)])
        if key_type == SR25519_KEY_TYPE:
            return lambda pubs, msgs, sigs: np.asarray(
                [sr.verify(p, m, s)
                 for p, m, s in zip(pubs, msgs, sigs)])
        raise ValueError(key_type)

    monkeypatch.setattr(cbatch, "_kernel_for", host_kernel_for)
    pubs, msgs, sigs, exp = _mixed_fixture()
    # a pinned fresh breaker keeps the test independent of global
    # breaker state (and of any mounted plane — pinning goes direct)
    valid = cbatch.verify_batch(pubs, msgs, sigs,
                                breaker=cbatch.CircuitBreaker())
    assert (valid == exp).all()
    # one kernel lookup per key-type group, both groups routed
    assert sorted(routed) == sorted([ED25519_KEY_TYPE,
                                     SR25519_KEY_TYPE])


@pytest.mark.slow  # the interpreted sr25519 kernel compiles for ~85 s
# at its one 128-row tile; tests/test_validation.py keeps the chunked
# seam quick behind a host stand-in
def test_served_chunks_through_the_true_kernel(monkeypatch):
    """A mixed batch through validation.device_batch_fn with the true
    sr25519 kernel: more sr25519 rows than one chunk (patched to the
    kernel's tile, 128, so two chunks of ONE compiled shape), a bad row
    in each chunk and one among the ed25519 rows."""
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types import validation

    monkeypatch.setattr(validation, "COMMIT_CHUNK_ROWS", 128)
    pubs, msgs, sigs, exp = _mixed_fixture()
    sr_pubs, sr_msgs, sr_sigs = _fixture(16, bad=(3,))
    from cometbft_tpu.crypto.keys import PubKey

    pubs += [PubKey(p, SR25519_KEY_TYPE) for p in sr_pubs] * 9
    msgs += sr_msgs * 9
    sigs += sr_sigs * 9
    exp = np.concatenate([exp, np.tile(np.arange(16) != 3, 9)])
    tracing.set_clock(None)  # an empty stage ring
    got = validation.device_batch_fn(use_pallas=False)(pubs, msgs, sigs)
    assert (got == exp).all()
    packs = [r[4] for r in tracing.stage_records()
             if r[0] == "sr25519.pack"]
    assert [(p["rows"], p["padded"]) for p in packs] == [(128, 128),
                                                         (20, 128)]


# --------------------------------------------------------------------------
# A commit's lazy sign-bytes (canonical.TemplateRows): merlin needs the
# bytes, so the pack hashes them from the expanded matrix by length group
# --------------------------------------------------------------------------


def _lazy_rows(n):
    """n sr25519 rows whose messages are a commit's sign-bytes under
    two templates and timestamps of several varint widths (so several
    message lengths), as TemplateRows; signed by the bytes."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader

    bid = BlockID(b"\x51" * 32, PartSetHeader(2, b"\x52" * 32))
    tmpls = [canonical.VoteRowTemplate("sr-rows", canonical.PRECOMMIT_TYPE,
                                       12, 1, b) for b in (bid, None)]
    secs = np.asarray([(0, 1, 300, 1_700_000_000, -5)[i % 5]
                       for i in range(n)], np.int64)
    nanos = np.asarray([(0, 7, 999_999_999)[i % 3] for i in range(n)],
                       np.int64)
    rows = canonical.TemplateRows(
        tmpls, (np.arange(n) % 7 == 2).astype(np.int32), secs, nanos)
    ks = [Sr25519PrivKey.generate(bytes([i + 1]) * 32) for i in range(4)]
    msgs = list(rows)
    pubs = [ks[i % 4].pub_key().data for i in range(n)]
    sigs = [ks[i % 4].sign(m) for i, m in enumerate(msgs)]
    return pubs, rows, msgs, sigs


@pytest.fixture(params=["native", "no-native"])
def native_lib(request, monkeypatch):
    from cometbft_tpu import native

    if request.param == "no-native":
        monkeypatch.setattr(native, "_load", lambda: None)
    elif not native.available():
        pytest.skip("no native library here")


@pytest.mark.parametrize("n,pad_to,damage", [
    (1, None, None), (23, None, None), (23, 128, None),
    (23, 128, "short-signature"), (23, 128, "short-key"),
    (0, 128, None)])
def test_pack_batch_sr_over_lazy_rows_is_the_pack_over_their_bytes(
        native_lib, n, pad_to, damage):
    """pack_batch_sr handed TemplateRows packs what it packs from the
    list of bytes they stand for, with the native transcripts and with
    the numpy BatchStrobe; and the challenges over the expanded matrix
    are those over the list, whatever the rows' lengths."""
    from cometbft_tpu.ops import sr25519_kernel as srk

    pubs, rows, msgs, sigs = _lazy_rows(n)
    if damage == "short-signature":
        sigs[5] = sigs[5][:40]
    if damage == "short-key":
        pubs[6] = pubs[6][:31]
    assert len({len(m) for m in msgs}) >= min(n, 3)
    got = srk.pack_batch_sr(pubs, rows, sigs, pad_to=pad_to)
    want = srk.pack_batch_sr(pubs, msgs, sigs, pad_to=pad_to)
    np.testing.assert_array_equal(got, want)
    if n and damage is None:
        r_encs = [s[:32] for s in sigs]
        np.testing.assert_array_equal(
            srk.batch_challenges(rows.expand(), pubs, r_encs),
            srk.batch_challenges(msgs, pubs, r_encs))
        # a slice is a chunk's rows: of the lazy rows, or of the matrix
        # the served call expands a group's rows into before its first
        for chunk in (rows[3:9], rows.expand()[3:9]):
            np.testing.assert_array_equal(
                srk.pack_batch_sr(pubs[3:9], chunk, sigs[3:9], pad_to=128),
                srk.pack_batch_sr(pubs[3:9], msgs[3:9], sigs[3:9],
                                  pad_to=128))
