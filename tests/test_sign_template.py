"""Template row packing (the zero-copy verify hot path): property-style
byte-equality of the vectorized patch paths against the legacy per-vote
encoders, across fuzzed heights/rounds/timestamps/BlockIDs/chain ids.

Host-only numpy — no kernels, no compiles (tier-1 friendly)."""
import random

import numpy as np
import pytest

from cometbft_tpu.crypto.keys import PrivKey
from cometbft_tpu.types import canonical
from cometbft_tpu.types import validation as tv
from cometbft_tpu.types.block_id import BlockID, PartSetHeader
from cometbft_tpu.types.commit import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    Commit,
    CommitSig,
)
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import sign_bytes_template

# timestamps chosen to cross every varint width boundary, including the
# zero-skipping cases and the 10-byte two's-complement negatives
FUZZ_SECS = [0, 1, 127, 128, 16383, 16384, 1_700_000_000, 2**31 - 1,
             2**31, 2**40, 2**62, -1, -2**33]
FUZZ_NANOS = [0, 1, 127, 128, 999_999_999, 5, 42, -7]


def _bids():
    return [
        None,
        BlockID(),
        BlockID(b"\xab" * 32, PartSetHeader(2, b"\xcd" * 32)),
        BlockID(b"\x00" * 32, PartSetHeader(1, b"\x11" * 32)),
    ]


def test_patch_rows_matches_canonical_vote_bytes_fuzzed():
    """The acceptance property: template-packed rows are byte-identical
    to per-vote canonical_vote_bytes for every fuzzed combination —
    including chain ids sized to push the outer length prefix across
    the 127/128 one-vs-two-byte varint boundary."""
    rng = random.Random(1234)
    chains = ["a", "zero-copy-chain", "c" * 49, "q" * 107, "w" * 120]
    checked = 0
    for chain in chains:
        for bid in _bids():
            for vote_type in (canonical.PREVOTE_TYPE,
                              canonical.PRECOMMIT_TYPE):
                h = rng.choice([0, 1, 4096, 2**31, 2**62 - 1])
                r = rng.choice([0, 1, 255])
                tmpl = sign_bytes_template(chain, vote_type, h, r, bid)
                secs = [rng.choice(FUZZ_SECS) for _ in range(24)]
                nanos = [rng.choice(FUZZ_NANOS) for _ in range(24)]
                rows = tmpl.patch_rows(secs, nanos)
                lst = rows.tolist()
                for i, (s, nn) in enumerate(zip(secs, nanos)):
                    exp = canonical.canonical_vote_bytes(
                        chain, vote_type, h, r, bid, Timestamp(s, nn)
                    )
                    assert rows.row(i) == exp, (chain, bid, h, r, s, nn)
                    assert lst[i] == exp
                    checked += 1
    assert checked >= 500


def test_delta_rows_roundtrip_matches_patch_rows_fuzzed():
    """ISSUE 19: the per-row delta payload (what a stamped flush ships
    to the device — 80 B/row instead of full packed rows) must expand
    back to EXACTLY the patch_rows bytes, for every varint width
    boundary, both vote types, and nil/real BlockIDs. Also pins the
    wire layout: ts_words() is (secs_lo u32-view, secs_hi, nanos) as
    int32 — the device stamping prologue decodes exactly this."""
    rng = random.Random(919)
    checked = 0
    for chain in ("d", "delta-chain", "y" * 96):
        for bid in _bids():
            for vote_type in (canonical.PREVOTE_TYPE,
                              canonical.PRECOMMIT_TYPE):
                h = rng.choice([1, 4096, 2**62 - 1])
                tmpl = sign_bytes_template(chain, vote_type, h, 1, bid)
                secs = FUZZ_SECS + [rng.choice(FUZZ_SECS)
                                    for _ in range(8)]
                nanos = (FUZZ_NANOS * 3)[:len(secs)]
                dr = tmpl.delta_rows(secs, nanos)
                assert dr.stampable()
                got, ref = dr.expand(), tmpl.patch_rows(secs, nanos)
                for i in range(len(secs)):
                    assert got.row(i) == ref.row(i), (chain, bid, i)
                    checked += 1
                w = np.asarray(dr.ts_words())
                assert w.shape == (len(secs), 3) and w.dtype == np.int32
                sa = np.asarray(secs, np.int64)
                np.testing.assert_array_equal(
                    w[:, 0],
                    (sa & 0xFFFFFFFF).astype(np.uint32).view(np.int32))
                np.testing.assert_array_equal(
                    w[:, 1], (sa >> 32).astype(np.int32))
                np.testing.assert_array_equal(
                    w[:, 2], np.asarray(nanos, np.int32))
                # the shipped payload really is delta-sized: ts words +
                # nothing per-row from the template body
                assert dr.nbytes < len(ref.row(0)) * len(secs)
    assert checked >= 500


def _stamp_fixture(n=16, seed=7777):
    """n signed precommit rows over one template, every FUZZ edge
    timestamp represented, plus the host-packed reference rows and the
    staged delta buffers (dsig/dts/dflags with zeroed dead lanes)."""
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek

    rng = random.Random(seed)
    privs = [PrivKey.generate(bytes([160 + i]) * 32) for i in range(n)]
    pubs = [p.pub_key().data for p in privs]
    bid = BlockID(b"\x23" * 32, PartSetHeader(5, b"\x34" * 32))
    chain, h, r = "stamp-chain", 77, 1
    tmpl = sign_bytes_template(chain, canonical.PRECOMMIT_TYPE, h, r,
                               bid)
    secs = list(FUZZ_SECS) + [rng.choice(FUZZ_SECS)
                              for _ in range(n - len(FUZZ_SECS))]
    nanos = (FUZZ_NANOS * ((n + 7) // 8))[:n]
    msgs = [canonical.canonical_vote_bytes(
        chain, canonical.PRECOMMIT_TYPE, h, r, bid, Timestamp(s, nn))
        for s, nn in zip(secs, nanos)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]

    B = ec.pad_rows(n)
    thresh = ek.threshold_limbs(101)
    counted = np.zeros(B, np.bool_)
    counted[:n] = True
    cids = np.zeros(B, np.int32)
    pb = ek.pack_batch(pubs, msgs, sigs, pad_to=B)
    ref = np.asarray(ec.pack_rows_cached(pb, counted, cids, thresh))

    ent = ec.template_entry([tmpl.stamp_site()])
    sec_a = np.asarray(secs, np.int64)
    dsig = np.zeros((B, 64), np.uint8)
    dsig[:n] = np.frombuffer(b"".join(sigs), np.uint8).reshape(-1, 64)
    dts = np.zeros((B, 3), np.int32)
    dts[:n, 0] = (sec_a & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    dts[:n, 1] = (sec_a >> 32).astype(np.int32)
    dts[:n, 2] = np.asarray(nanos, np.int32)
    dfl = np.zeros((B,), np.int32)
    dfl[:n] = 3  # live | counted, template 0, commit 0
    return pubs, B, thresh, ref, ent, dsig, dts, dfl


def test_stamp_rows_device_matches_host_pack():
    """ISSUE 19 acceptance: stamp_rows_cached — the device prologue
    that assembles sign-bytes rows from (template, per-row deltas) —
    is BIT-IDENTICAL to the host pack_rows_cached output for the same
    flush, across fuzzed varint-boundary timestamps, including the
    zero dead lanes a rotated staging buffer ships. CPU XLA (tier-1):
    the prologue only consumes the table's pub_raw matrix, so a stub
    table keeps this under the tier-1 clock — the slow sibling runs
    the REAL table + fused verify end to end."""
    pytest.importorskip("jax")
    from types import SimpleNamespace

    from cometbft_tpu.ops import ed25519_cached as ec

    pubs, B, thresh, ref, ent, dsig, dts, dfl = _stamp_fixture()
    table = SimpleNamespace(pub_raw=ec._pub_raw(pubs, B))
    got = np.asarray(ec.stamp_rows_cached(
        dsig, dts, dfl, ent, table, 1, thresh))
    np.testing.assert_array_equal(got, ref)


def test_delta_donation_still_noop():
    """ISSUE 19 satellite: donate_argnums RE-EVALUATED on the staged
    delta buffers. Structural verdict: no output aval of the stamping
    prologue matches any delta input aval — the rows output is
    (R, B) int32 while dsig is (B, 64) uint8, dts (B, 3) int32 and
    dflags (B,) int32 — so XLA cannot alias a donated delta buffer
    into the output and donation stays a NO-OP; staging turnover
    remains the host-side pool rotation (README "Zero-copy hot
    path"). The empirical half jits the same prologue WITH donation
    and proves XLA merely warns the donated buffers were unusable
    while the output stays bit-identical."""
    pytest.importorskip("jax")
    import warnings
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from cometbft_tpu.ops import ed25519_cached as ec

    pubs, B, thresh, ref, ent, dsig, dts, dfl = _stamp_fixture()
    table = SimpleNamespace(pub_raw=ec._pub_raw(pubs, B))
    for a in (dsig, dts, dfl):  # the structural reason, kept honest
        assert not (a.shape == ref.shape and a.dtype == np.int32)

    donated = jax.jit(ec._stamp_rows_core,
                      static_argnames=("msg_max", "t_rows"),
                      donate_argnums=(0, 1, 2))
    t_rows = ec.packed_rows_shape(B, 1)[0] - ec.V_THRESH
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = np.asarray(donated(
            jnp.asarray(dsig), jnp.asarray(dts), jnp.asarray(dfl),
            ent.pre_mat, ent.pre_len, ent.suf_mat, ent.suf_len,
            ent.ts_tag, table.pub_raw,
            jnp.asarray(np.asarray(thresh, np.int32)),
            msg_max=ent.msg_max, t_rows=t_rows))
    np.testing.assert_array_equal(got, ref)
    assert any("donat" in str(w.message).lower() for w in caught), \
        [str(w.message) for w in caught]


@pytest.mark.slow
def test_stamp_verify_delta_matches_host_pack_real_table():
    """Slow sibling of the stamp byte-equality test: the REAL valset
    table (pub_raw present by default) and the fused delta verify —
    verdicts and tallies bit-equal to the host-packed kernel, rows
    never leaving the device between stamp and verify."""
    pytest.importorskip("jax")
    import jax

    from cometbft_tpu.ops import ed25519_cached as ec

    n = 16
    pubs, B, thresh, ref, ent, dsig, dts, dfl = _stamp_fixture(n)
    table = ec.table_for_pubs(pubs)
    assert table.pub_raw is not None  # stamping-aware by default
    got = np.asarray(ec.stamp_rows_cached(
        dsig, dts, dfl, ent, table, 1, thresh))
    np.testing.assert_array_equal(got, ref)
    v_ref = ec.verify_tally_rows_cached(jax.device_put(ref), table, 1)
    v_got = ec.verify_tally_delta_cached(
        dsig, dts, dfl, ent, table, 1, thresh)
    np.testing.assert_array_equal(np.asarray(v_got[0]),
                                  np.asarray(v_ref[0]))
    assert np.asarray(v_got[0])[:n].all()
    np.testing.assert_array_equal(np.asarray(v_got[1]),
                                  np.asarray(v_ref[1]))


@pytest.mark.slow
def test_stamp_rows_device_matches_host_pack_wide():
    """Slow sibling: every FUZZ_SECS x FUZZ_NANOS cross product, two
    templates in one flush (tmpl_id bits live), nil BlockID — the
    multi-site stamp path a 10k-row flush takes."""
    pytest.importorskip("jax")
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek

    combos = [(s, nn) for s in FUZZ_SECS for nn in FUZZ_NANOS]
    n = len(combos)  # 104
    privs = [PrivKey.generate((900 + i).to_bytes(2, "big") * 16)
             for i in range(n)]
    pubs = [p.pub_key().data for p in privs]
    chain, r = "stamp-wide", 0
    bids = [None, BlockID(b"\x55" * 32, PartSetHeader(9, b"\x66" * 32))]
    tmpls = [sign_bytes_template(chain, canonical.PRECOMMIT_TYPE,
                                 1000 + t, r, bids[t])
             for t in range(2)]
    msgs, sigs, tids = [], [], []
    for i, (s, nn) in enumerate(combos):
        t = i % 2
        tids.append(t)
        msgs.append(canonical.canonical_vote_bytes(
            chain, canonical.PRECOMMIT_TYPE, 1000 + t, r, bids[t],
            Timestamp(s, nn)))
        sigs.append(privs[i].sign(msgs[-1]))

    B = ec.pad_rows(n)
    thresh = ek.threshold_limbs(3)
    counted = np.zeros(B, np.bool_)
    counted[:n] = True
    cids = np.zeros(B, np.int32)
    pb = ek.pack_batch(pubs, msgs, sigs, pad_to=B)
    ref = np.asarray(ec.pack_rows_cached(pb, counted, cids, thresh))

    table = ec.table_for_pubs(pubs)
    ent = ec.template_entry([t.stamp_site() for t in tmpls])
    sec_a = np.asarray([s for s, _ in combos], np.int64)
    dsig = np.zeros((B, 64), np.uint8)
    dsig[:n] = np.frombuffer(b"".join(sigs), np.uint8).reshape(-1, 64)
    dts = np.zeros((B, 3), np.int32)
    dts[:n, 0] = (sec_a & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    dts[:n, 1] = (sec_a >> 32).astype(np.int32)
    dts[:n, 2] = np.asarray([nn for _, nn in combos], np.int32)
    dfl = np.zeros((B,), np.int32)
    dfl[:n] = 3 | (np.asarray(tids, np.int32) << 2)
    got = np.asarray(ec.stamp_rows_cached(
        dsig, dts, dfl, ent, table, 1, thresh))
    np.testing.assert_array_equal(got, ref)


def test_patch_rows_empty_and_singleton():
    tmpl = sign_bytes_template("c", canonical.PRECOMMIT_TYPE, 3, 0, None)
    assert tmpl.patch_rows([], []).tolist() == []
    one = tmpl.patch_rows([7], [0])
    assert one.row(0) == canonical.canonical_vote_bytes(
        "c", canonical.PRECOMMIT_TYPE, 3, 0, None, Timestamp(7, 0)
    )


def _fixture_commit(n=12, height=9, round_=2, seed=50):
    privs = [PrivKey.generate(bytes([seed + i]) * 32) for i in range(n)]
    vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\x77" * 32, PartSetHeader(3, b"\x88" * 32))
    sigs = []
    for idx, v in enumerate(vs.validators):
        if idx == 4:
            sigs.append(CommitSig(BLOCK_ID_FLAG_ABSENT))
            continue
        nil = idx == 7
        ts = Timestamp(1_700_000_000 + idx * 129, idx * 1000)
        sb = canonical.canonical_vote_bytes(
            "tmpl-chain", canonical.PRECOMMIT_TYPE, height, round_,
            None if nil else bid, ts,
        )
        sigs.append(CommitSig(
            BLOCK_ID_FLAG_NIL if nil else BLOCK_ID_FLAG_COMMIT,
            v.address, ts, by[v.address].sign(sb),
        ))
    return vs, Commit(height, round_, bid, sigs), bid


def test_commit_sign_bytes_rows_matches_per_vote():
    """Commit.sign_bytes_rows (mixed for-block / nil / absent rows) is
    byte-equal to the legacy vote_sign_bytes loop, over any index
    subset and in subset order."""
    _, commit, _ = _fixture_commit()
    n = len(commit.signatures)
    all_idx = list(range(n))
    assert commit.sign_bytes_rows("tmpl-chain", all_idx) == [
        commit.vote_sign_bytes("tmpl-chain", i) for i in all_idx
    ]
    sub = [7, 1, 11, 3]
    assert commit.sign_bytes_rows("tmpl-chain", sub) == [
        commit.vote_sign_bytes("tmpl-chain", i) for i in sub
    ]
    # a different chain id invalidates the cached templates
    assert commit.sign_bytes_rows("other", [1]) == [
        commit.vote_sign_bytes("other", 1)
    ]


def test_verify_commit_template_toggle_equivalence():
    """verify_commit passes with the oracle batch_fn under BOTH packing
    paths, and a wrong-signature commit is blamed identically — the
    toggle must never change behavior (simnet determinism guard's
    local half)."""
    vs, commit, bid = _fixture_commit()
    for on in (True, False):
        prev = tv.set_template_packing(on)
        try:
            tv.verify_commit("tmpl-chain", vs, bid, 9, commit,
                             batch_fn=tv.oracle_batch_fn())
            bad = Commit(commit.height, commit.round, commit.block_id,
                         list(commit.signatures))
            cs = bad.signatures[2]
            bad.signatures[2] = CommitSig(cs.flag, cs.validator_address,
                                          cs.timestamp, b"\x5a" * 64)
            with pytest.raises(tv.InvalidSignatureError) as ei:
                tv.verify_commit("tmpl-chain", vs, bid, 9, bad,
                                 batch_fn=tv.oracle_batch_fn())
            assert ei.value.idx == 2
        finally:
            tv.set_template_packing(prev)


def test_commit_packed_batch_matches_pack_batch():
    """The zero-copy staging path (native template pack when available,
    numpy template fallback otherwise) produces the exact arrays of the
    legacy msgs+pack_batch pipeline."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    vs, commit, bid = _fixture_commit()
    keys = [v.pub_key.data for v in vs.validators]
    pb, idxs = tv.commit_packed_batch("tmpl-chain", commit, keys)
    assert idxs == [i for i, cs in enumerate(commit.signatures)
                    if cs.for_block()]
    msgs = [commit.vote_sign_bytes("tmpl-chain", i) for i in idxs]
    ref = ek.pack_batch([keys[i] for i in idxs], msgs,
                        [commit.signatures[i].signature for i in idxs],
                        pad_to=pb.padded)
    for name in ("ay", "asign", "ry", "rsign", "sdig", "hdig",
                 "precheck"):
        np.testing.assert_array_equal(
            np.asarray(getattr(pb, name)), np.asarray(getattr(ref, name)),
            err_msg=name,
        )


def test_pack_rows_cached_out_buffer_parity():
    """pack_rows_cached into a rotated (zeroed) staging buffer is
    bit-identical to the allocating path, including threshold rows and
    dead padding — the double-buffer must never leak a previous
    flush's rows."""
    from cometbft_tpu.libs.staging import StagingPool
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek

    vs, commit, bid = _fixture_commit()
    keys = [v.pub_key.data for v in vs.validators]
    pb, idxs = tv.commit_packed_batch("tmpl-chain", commit, keys,
                                      pad_to=128)
    counted = np.zeros(128, np.bool_)
    counted[: len(idxs)] = True
    cids = np.zeros(128, np.int32)
    thresh = ek.threshold_limbs(77)
    ref = ec.pack_rows_cached(pb, counted, cids, thresh)
    pool = StagingPool(slots=2)
    a = pool.get("rows", ref.shape, np.int32)
    a[:] = -1  # dirty slot A, rotate past it so the pool re-zeroes
    pool.get("rows", ref.shape, np.int32)
    out = pool.get("rows", ref.shape, np.int32)
    assert out is a
    got = ec.pack_rows_cached(pb, counted, cids, thresh, out=out)
    assert got is out
    np.testing.assert_array_equal(got, ref)
    # a mismatched out buffer is ignored, not corrupted
    wrong = np.full((ref.shape[0] + 1, ref.shape[1]), 3, np.int32)
    got2 = ec.pack_rows_cached(pb, counted, cids, thresh, out=wrong)
    assert got2 is not wrong
    np.testing.assert_array_equal(got2, ref)


def test_table_for_valset_identity_memo(monkeypatch):
    """ed25519_cached.table_for_valset: memoized by ValidatorSet
    identity, invalidated when update_with_change_set replaces the
    validators list (the only mutation that can change keys/powers).
    The underlying build is stubbed — no device table on CPU."""
    from cometbft_tpu.ops import ed25519_cached as ec

    calls = []

    def fake_table_for_pubs(pubs, powers=None):
        calls.append((pubs, powers))
        return "TBL%d" % len(calls)

    monkeypatch.setattr(ec, "table_for_pubs", fake_table_for_pubs)
    vs, _, _ = _fixture_commit()
    ec._VALSET_MEMO.clear()
    try:
        t1 = ec.table_for_valset(vs)
        t2 = ec.table_for_valset(vs)
        assert t1 is t2 and len(calls) == 1
        st = ec.table_cache_stats()
        assert st["valset_hits"] >= 1
        # a wholesale validators-list replacement (what
        # update_with_change_set does) must invalidate the memo
        vs.validators = list(vs.validators)
        ec.table_for_valset(vs)
        assert len(calls) == 2
    finally:
        ec._VALSET_MEMO.clear()


def test_packed_rows_shape_matches_pack_rows_cached():
    """The staging-buffer sizing helper agrees with what
    pack_rows_cached actually builds, across thresh widths."""
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek

    vs, commit, _ = _fixture_commit()
    keys = [v.pub_key.data for v in vs.validators]
    pb, idxs = tv.commit_packed_batch("tmpl-chain", commit, keys,
                                      pad_to=128)
    for n_commits in (1, 3, 64):
        thresh = np.zeros((n_commits, ek.TALLY_LIMBS), np.int32)
        rows = ec.pack_rows_cached(pb, None, None, thresh)
        assert rows.shape == ec.packed_rows_shape(128, n_commits)


def test_staging_pool_rotation_and_reuse():
    """libs/staging: two slots per shape rotate; a third request
    returns the first buffer again, zeroed."""
    from cometbft_tpu.libs.staging import StagingPool

    p = StagingPool(slots=2)
    a = p.get("rows", (3, 4), np.int32)
    a[:] = 9
    b = p.get("rows", (3, 4), np.int32)
    assert b is not a
    c = p.get("rows", (3, 4), np.int32)
    assert c is a and (c == 0).all()
    # distinct shapes/names never alias
    d = p.get("rows", (3, 5), np.int32)
    e = p.get("other", (3, 4), np.int32)
    assert d is not a and e is not a
    st = p.stats()
    assert st["hits"] == 1 and st["misses"] == 4


def test_staging_pool_concurrent_flushes():
    """ISSUE 6 satellite: the pool under concurrent flush traffic.

    The rotation contract is one writer per KEY (each dispatcher/
    pipeline owns its buffer names), but nothing serializes DIFFERENT
    keys — the verify-plane dispatcher, blocksync's private pool
    pattern, and crypto/batch all hammer one process-global pool from
    their own threads. Each thread here rotates its own key under load and
    checks its buffer still holds its own pattern after every get
    (cross-key aliasing would corrupt it); the lock-protected counters
    must come out EXACT, not approximately."""
    import threading

    from cometbft_tpu.libs.staging import StagingPool

    slots, iters, n_threads = 2, 200, 6
    p = StagingPool(slots=slots)
    errs = []
    start = threading.Barrier(n_threads)

    def flusher(tid):
        try:
            start.wait(5)
            for i in range(iters):
                buf = p.get(f"flush.t{tid}", (16, 8), np.int32)
                if buf.any():  # zeroed on every handout
                    raise AssertionError(f"t{tid} got a dirty buffer")
                buf[:] = tid * 1000 + i
                # the buffer must still be OURS after other threads run
                # their own gets (no cross-key slot sharing)
                if not (buf == tid * 1000 + i).all():
                    raise AssertionError(f"t{tid} buffer overwritten")
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    ts = [threading.Thread(target=flusher, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not errs, errs
    st = p.stats()
    # exhaustion accounting: per key exactly `slots` allocation misses,
    # every other get recycled a slot (a rotation hit)
    assert st["misses"] == n_threads * slots
    assert st["hits"] == n_threads * (iters - slots)
    assert st["shapes"] == n_threads
    assert st["resident_bytes"] == n_threads * slots * 16 * 8 * 4


def test_staging_pool_depth_tracks_flight_count():
    """ISSUE 11 satellite: under the flight deck, up to `flights`
    flushes keep their packed buffers pinned while the NEXT flush
    packs — the plane must size its private pool flights+1 deep (the
    old hardcoded 2 aliased the third concurrent pack: pack(k+2) wrote
    into the buffer flight k was still uploading from). Exact
    accounting: with depth flights+1, flights+1 outstanding buffers
    per key never alias and every rotation hit/miss is counted."""
    import threading

    from cometbft_tpu.libs.staging import StagingPool
    from cometbft_tpu.verifyplane import VerifyPlane

    # the plane wires the knob straight into its pool depth
    for flights in (1, 2, 3):
        plane = VerifyPlane(pipeline_flights=flights)
        assert plane._staging.slots == flights + 1

    flights, iters, n_threads = 2, 120, 4
    depth = flights + 1
    p = StagingPool(slots=depth)
    errs = []
    start = threading.Barrier(n_threads)

    def deck_packer(tid):
        """Hold `depth` buffers outstanding (flights airborne + the
        pack in progress) and verify none alias within the window."""
        try:
            start.wait(5)
            window = []
            for i in range(iters):
                buf = p.get(f"deck.t{tid}", (8, 4), np.int32)
                if buf.any():
                    raise AssertionError(f"t{tid} got a dirty buffer")
                buf[:] = tid * 10_000 + i
                window.append((buf, tid * 10_000 + i))
                if len(window) > depth:
                    window.pop(0)
                # every buffer still pinned under an airborne flight
                # must hold ITS flush's rows — an alias would show the
                # newest pack's pattern in an older flight's buffer
                for b, pat in window:
                    if not (b == pat).all():
                        raise AssertionError(
                            f"t{tid} airborne buffer overwritten")
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    ts = [threading.Thread(target=deck_packer, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not errs, errs
    st = p.stats()
    # exact accounting: per key exactly `depth` allocation misses,
    # every other get a rotation hit, footprint capped at depth x shape
    assert st["misses"] == n_threads * depth
    assert st["hits"] == n_threads * (iters - depth)
    assert st["resident_bytes"] == n_threads * depth * 8 * 4 * 4


def test_staging_pool_exhaustion_aliases_oldest():
    """More outstanding buffers than slots is the documented hazard:
    request slots+1 of one key while all are 'in flight' and the pool
    recycles the OLDEST — callers must be done writing before asking
    for `slots` more. The stats make the exhaustion visible (hits move
    while misses stay at the slot count)."""
    from cometbft_tpu.libs.staging import StagingPool

    p = StagingPool(slots=3)
    outstanding = [p.get("x", (4,), np.int64) for _ in range(3)]
    assert p.stats()["misses"] == 3 and p.stats()["hits"] == 0
    again = p.get("x", (4,), np.int64)  # exhausted: recycles slot 0
    assert again is outstanding[0]
    assert p.stats()["hits"] == 1 and p.stats()["misses"] == 3
    # resident footprint never grows past slots x shape
    assert p.stats()["resident_bytes"] == 3 * 4 * 8
    assert p.nbytes() == 3 * 4 * 8


# --------------------------------------------------------------------------
# The lazy row sequence (canonical.TemplateRows: what the served commit
# check hands its batch_fn) and the pack that hashes it in C
# --------------------------------------------------------------------------

ROWS_CHAIN = "rows-chain"


def _fuzz_commit(seed, n=40):
    """A commit whose rows mix for-block, nil and absent flags under
    timestamps of every varint width, zero and negative seconds and
    nanos among them. Keys and signatures are random bytes of the right
    length: nothing here verifies one."""
    rng = random.Random(seed)
    bid = BlockID(b"\x77" * 32, PartSetHeader(3, b"\x88" * 32))
    sigs = []
    for idx in range(n):
        flag = rng.choice([BLOCK_ID_FLAG_COMMIT] * 4
                          + [BLOCK_ID_FLAG_NIL] * 2
                          + [BLOCK_ID_FLAG_ABSENT])
        if flag == BLOCK_ID_FLAG_ABSENT:
            sigs.append(CommitSig(BLOCK_ID_FLAG_ABSENT))
            continue
        ts = Timestamp(rng.choice(FUZZ_SECS), rng.choice(FUZZ_NANOS))
        sigs.append(CommitSig(flag, rng.randbytes(20), ts,
                              rng.randbytes(64)))
    return Commit(11, 1, bid, sigs)


# a view of the rows at `idxs`, and the indices it leaves
ROW_VIEWS = {
    "all": lambda rows, idxs: (rows, idxs),
    "slice": lambda rows, idxs: (rows[3:17], idxs[3:17]),
    "chunk-past-the-end": lambda rows, idxs: (rows[32:96], idxs[32:96]),
    "stepped-slice": lambda rows, idxs: (rows[1::3], idxs[1::3]),
    "empty-slice": lambda rows, idxs: (rows[5:5], []),
    "take": lambda rows, idxs: (rows.take([7, 1, 11, 3, 3]),
                                [idxs[k] for k in (7, 1, 11, 3, 3)]),
    "take-of-a-slice": lambda rows, idxs: (
        rows[5:25].take([0, 19, 4]), [idxs[5], idxs[24], idxs[9]]),
    "take-none": lambda rows, idxs: (rows.take([]), []),
}


@pytest.mark.parametrize("view", sorted(ROW_VIEWS))
@pytest.mark.parametrize("select", ["every-row", "sub-selection"])
@pytest.mark.parametrize("seed", [1, 2])
def test_template_rows_are_the_per_vote_bytes(seed, select, view):
    """Commit.sign_rows, and every slice and `take` of it, IS the list
    [commit.vote_sign_bytes(chain, i) for i in idxs]: by index, by
    iteration, as a list, and as the expanded matrix."""
    from collections import abc

    commit = _fuzz_commit(seed)
    idxs = list(range(len(commit.signatures)))
    if select == "sub-selection":
        idxs = random.Random(seed).sample(idxs, 30)
    rows, idxs = ROW_VIEWS[view](commit.sign_rows(ROWS_CHAIN, idxs), idxs)
    want = [commit.vote_sign_bytes(ROWS_CHAIN, i) for i in idxs]
    assert isinstance(rows, canonical.TemplateRows)
    assert isinstance(rows, abc.Sequence)
    assert len(rows) == len(want)
    assert [rows[k] for k in range(len(rows))] == want
    assert list(rows) == want and rows.tolist() == want
    assert [m for m in rows] == want
    grown = [b"first"]
    grown += rows  # blocksync/pipeline._template_msgs extends a list
    assert grown == [b"first"] + want
    mat = rows.expand()
    assert isinstance(mat, canonical.SignRows) and len(mat) == len(want)
    assert [mat.row(k) for k in range(len(mat))] == want
    assert [int(ln) for ln in mat.lens] == [len(m) for m in want]
    for k, m in enumerate(want):  # right-padded with zeros
        assert not mat.mat[k, len(m):].any()
    # a run of the matrix is a chunk's rows (the sr25519 pack's input)
    run = mat[2:9]
    assert isinstance(run, canonical.SignRows)
    assert [run[k] for k in range(len(run))] == want[2:9] == list(run)
    with pytest.raises(IndexError):
        rows[len(want)]
    if want:
        assert rows[-1] == want[-1]
        assert want[0] in rows and rows.index(want[-1]) == want.index(
            want[-1])
    # the rows hold arrays and templates: nothing of a row on the commit
    assert set(vars(commit)) <= {"height", "round", "block_id",
                                 "signatures", "_sb_tmpl", "_sb_enc"}


@pytest.mark.parametrize("on", [True, False])
def test_commit_msgs_is_lazy_rows_or_the_legacy_list(on):
    """validation._commit_msgs: the lazy rows with template packing on,
    the per-vote loop's plain list with it off; the same bytes."""
    commit = _fuzz_commit(5)
    idxs = [i for i, cs in enumerate(commit.signatures)
            if not cs.is_absent()]
    prev = tv.set_template_packing(on)
    try:
        msgs = tv._commit_msgs(ROWS_CHAIN, commit, idxs)
    finally:
        tv.set_template_packing(prev)
    assert type(msgs) is (canonical.TemplateRows if on else list)
    assert list(msgs) == [commit.vote_sign_bytes(ROWS_CHAIN, i)
                          for i in idxs]


@pytest.fixture(params=["native", "no-native"])
def native_lib(request, monkeypatch):
    """Runs a test with the native library and with it monkeypatched
    away; True where it is there."""
    from cometbft_tpu import native

    if request.param == "no-native":
        monkeypatch.setattr(native, "_load", lambda: None)
    elif not native.available():
        pytest.skip("no native library here")
    return request.param == "native"


@pytest.mark.parametrize("case", ["well-formed", "one-row", "short-key",
                                  "long-signature", "no-rows"])
def test_pack_templated_is_pack_batch_over_the_bytes(native_lib, case):
    """ops/ed25519_kernel.pack_templated over the lazy rows, padded to
    a chunk shape, gives the arrays of pack_batch over the bytes they
    stand for. Templated (no bytes made) only with the native library
    and every key 32, every signature 64 bytes long; a malformed length
    sends the chunk down pack_batch's list route, screens and all."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    commit = _fuzz_commit(9, n=70)
    idxs = [i for i, cs in enumerate(commit.signatures)
            if not cs.is_absent()]
    idxs = {"one-row": idxs[:1], "no-rows": []}.get(case, idxs)
    rng = random.Random(3)
    pubs = [rng.randbytes(32) for _ in idxs]
    sigs = [commit.signatures[i].signature[:63] + b"\x00" for i in idxs]
    if case == "short-key":
        pubs[4] = pubs[4][:31]
    if case == "long-signature":
        sigs[-1] = sigs[-1] + b"\x00"
    rows = commit.sign_rows(ROWS_CHAIN, idxs)
    want = ek.pack_batch(pubs, [commit.vote_sign_bytes(ROWS_CHAIN, i)
                                for i in idxs], sigs, pad_to=64)
    got, templated = ek.pack_templated(pubs, rows, sigs, pad_to=64)
    assert templated is (native_lib
                         and case in ("well-formed", "one-row"))
    assert (got.n, got.padded) == (want.n, want.padded) == (len(idxs), 64)
    for name in ek.PackedBatch._fields[2:]:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=name)
    if case in ("short-key", "long-signature"):
        bad = 4 if case == "short-key" else len(idxs) - 1
        assert not got.precheck[bad] and got.precheck[:len(idxs)].sum() > 0
    # a plain list is pack_batch's, never templated; no pad_to is the
    # bucket ladder's rung, as pack_batch's
    lst, templated = ek.pack_templated(pubs, list(rows), sigs)
    assert templated is False
    assert lst.padded == ek.pack_batch(pubs, list(rows), sigs).padded
    np.testing.assert_array_equal(lst.hdig[:len(idxs)],
                                  want.hdig[:len(idxs)])
