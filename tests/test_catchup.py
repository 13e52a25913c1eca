"""Catch-up firehose engine tests (ISSUE 18 tentpole).

Pins the archival replay contracts directly against a real-signed
in-memory history: fused segments never pack across a valset
boundary, warm-ahead hands the NEXT epoch's valset to the warmer
BEFORE the replay cursor reaches the boundary, and — the
crash-resume heart of the thing — a kill at EVERY read-ahead
position (the catchup.read_ahead failpoint, test_wal_recovery.py's
kill-at-every-failpoint style) resumes from the persisted cursor
re-verifying ZERO already-verified blocks. Plus the cursor's
corrupt/torn-file conservatism, the bounded always-on ledger and its
/dump_catchup document, and the catchup_stall incident on a frozen
ledger.
"""
import json

import numpy as np
import pytest

from cometbft_tpu.blocksync import catchup as cu
from cometbft_tpu.blocksync.catchup import (
    CatchupCursor, CatchupEngine, CatchupError, CatchupLedger,
    HostCommitVerifier, StoreHistorySource)
from cometbft_tpu.crypto.keys import PrivKey
from cometbft_tpu.libs import failpoints as fp
from cometbft_tpu.libs import incidents, tracing
from cometbft_tpu.types import canonical
from cometbft_tpu.types.block import Block, Data, Header
from cometbft_tpu.types.commit import (
    BLOCK_ID_FLAG_COMMIT, Commit, CommitSig)
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet

# every engine run of this module checks each remembered validator-set
# root against a fresh one (conftest.checked_valset_roots)
pytestmark = pytest.mark.usefixtures("checked_valset_roots")

CHAIN = "catchup-chain"
N_BLOCKS = 10
EPOCH_LEN = 4


def make_history(n_blocks=N_BLOCKS, n_vals=3, epoch_len=EPOCH_LEN,
                 chain_id=CHAIN):
    """Real ed25519-signed history with per-epoch valset rotation;
    returns (items={h: (block, commit)}, vals_at)."""
    n_epochs = n_blocks // epoch_len + 2
    epochs = []
    for e in range(n_epochs):
        privs = [PrivKey.generate(bytes([60 + e, i + 1]) + b"\x19" * 30)
                 for i in range(n_vals)]
        vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
        epochs.append((vs, {p.pub_key().address(): p for p in privs}))

    def vals_at(h):
        return epochs[min((h - 1) // epoch_len, n_epochs - 1)][0]

    items = {}
    last_bid = None
    for h in range(1, n_blocks + 1):
        vs, by_addr = epochs[min((h - 1) // epoch_len, n_epochs - 1)]
        hdr = Header(chain_id=chain_id, height=h,
                     time=Timestamp(1700000000 + h, 0),
                     validators_hash=vs.hash(),
                     next_validators_hash=vals_at(h + 1).hash(),
                     proposer_address=vs.validators[0].address)
        if last_bid is not None:
            hdr.last_block_id = last_bid
        blk = Block(hdr, Data())
        blk.fill_header()
        bid = blk.block_id()
        sigs = []
        for v in vs.validators:
            ts = Timestamp(1700000000 + h, 1)
            sb = canonical.canonical_vote_bytes(
                chain_id, canonical.PRECOMMIT_TYPE, h, 0, bid, ts)
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                  by_addr[v.address].sign(sb)))
        items[h] = (blk, Commit(h, 0, bid, sigs))
        last_bid = bid
    return items, vals_at


@pytest.fixture(scope="module")
def history():
    return make_history()


class _Source:
    def __init__(self, items):
        self.items = items

    def base(self):
        return min(self.items)

    def tip(self):
        return max(self.items)

    def load(self, h):
        if h not in self.items:
            raise CatchupError(f"history missing block {h}")
        return self.items[h]


class _State:
    __slots__ = ("chain_id", "last_block_height", "validators",
                 "next_validators")

    def __init__(self, chain_id, h, validators, next_validators):
        self.chain_id = chain_id
        self.last_block_height = h
        self.validators = validators
        self.next_validators = next_validators


class _Warmer:
    def __init__(self):
        self.requests = []  # (valset_hash, chain_id)

    def request_valset(self, vals, chain_id=None):
        self.requests.append((vals.hash(), chain_id))


class _CountingVerifier(HostCommitVerifier):
    def __init__(self):
        self.heights = []

    def verify(self, jobs):
        self.heights.extend(j.height for j in jobs)
        return super().verify(jobs)


def _engine(items, vals_at, *, start=0, cursor_path=None,
            read_ahead=3, max_run=3, verifier=None, warmer=None,
            warm_ahead=True, on_apply=None):
    state = _State(CHAIN, start, vals_at(start + 1), vals_at(start + 2))

    def apply_fn(st, blk, commit):
        h = blk.header.height
        if on_apply is not None:
            on_apply(h)
        return _State(st.chain_id, h, vals_at(h + 1), vals_at(h + 2))

    return CatchupEngine(
        _Source(items), state, apply_fn=apply_fn,
        verifier=verifier or HostCommitVerifier(),
        cursor_path=cursor_path, read_ahead=read_ahead,
        max_run=max_run, warm_ahead=warm_ahead,
        warmer=warmer or _Warmer())


def test_replays_history_to_tip(history):
    items, vals_at = history
    eng = _engine(items, vals_at)
    final = eng.run()
    assert final.last_block_height == N_BLOCKS
    c = eng.ledger.counters
    assert c["blocks_applied"] == N_BLOCKS
    assert c["blocks_verified"] == N_BLOCKS
    assert c["blocks_skipped"] == 0
    assert c["sigs_verified"] == N_BLOCKS * 3  # every val signed
    assert eng.cursor.verified == eng.cursor.applied == N_BLOCKS


def test_segments_never_cross_valset_boundaries(history):
    """The pre-scan bounds every fused flush at the first
    validators_hash change: record (first, last) always lies inside
    one epoch, and the flush that hit the wall carries boundary=True."""
    items, vals_at = history
    eng = _engine(items, vals_at, read_ahead=8, max_run=8)
    eng.run()
    recs = eng.ledger.records()
    for r in recs:
        assert (r["first"] - 1) // EPOCH_LEN == \
            (r["last"] - 1) // EPOCH_LEN, r
    walls = [r for r in recs if r["boundary"]]
    # epochs end inside the history at 4 and 8
    assert sorted(r["last"] for r in walls) == [4, 8]
    assert eng.ledger.counters["boundaries"] == 2


def test_warm_ahead_fires_before_the_boundary(history):
    """The next epoch's valset reaches the warmer while the replay
    cursor is still BELOW the boundary — the table builds ahead."""
    items, vals_at = history
    cursor_h = [0]
    warmer = _Warmer()
    # record the replay height at which each warm request landed
    orig = warmer.request_valset

    def stamped(vals, chain_id=None):
        warmer.requests.append((vals.hash(), cursor_h[0]))
    warmer.request_valset = stamped
    eng = _engine(items, vals_at, warmer=warmer,
                  on_apply=lambda h: cursor_h.__setitem__(0, h))
    eng.run()
    del orig
    by_hash = {h: at for h, at in warmer.requests}
    # boundary into epoch 1 is at height 5; its valset warmed earlier
    assert by_hash[vals_at(5).hash()] < 5
    assert by_hash[vals_at(9).hash()] < 9
    assert eng.ledger.counters["warm_requests"] >= 2


@pytest.mark.parametrize("n_blocks,max_run", [(10, 3), (22, 2), (22, 8)])
def test_one_merkle_root_per_distinct_valset(n_blocks, max_run,
                                             checked_valset_roots):
    """However many blocks are applied, the replay builds at most one
    merkle root per distinct validator set (the `valset.hash` stages of
    the ring: the pre-scan and the per-block warm-ahead check answer
    from the set's memo), and the warmer is asked about each next set
    at the height it was asked before the memo: the last block but one
    of the epoch before it."""
    items, vals_at = make_history(n_blocks=n_blocks)
    distinct = {id(vals_at(h)) for h in range(1, n_blocks + 3)}
    cursor_h = [0]
    warmer = _Warmer()
    warmer.request_valset = lambda vals, chain_id=None: \
        warmer.requests.append((vals.hash(), cursor_h[0]))
    eng = _engine(items, vals_at, warmer=warmer, read_ahead=max_run,
                  max_run=max_run,
                  on_apply=lambda h: cursor_h.__setitem__(0, h))
    tracing.set_clock(None)  # an empty stage ring
    del checked_valset_roots[:]
    eng.run()
    computes = [r for r in tracing.stages() if r[0] == "valset.hash"]
    assert len(computes) <= len(distinct)
    # asked far more often than that: one pre-scan a step, two checks a
    # block, one per warm request
    assert len(checked_valset_roots) \
        >= 2 * n_blocks + len(eng.ledger.records())
    # epoch e (blocks 4e+1..4e+4) becomes state.next_validators when
    # block 4e-1 is applied: the request lands there, once per epoch
    epochs_reached = range(1, (n_blocks + 1) // EPOCH_LEN + 1)
    assert warmer.requests == [
        (vals_at(EPOCH_LEN * e + 1).hash(), EPOCH_LEN * e - 1)
        for e in epochs_reached]
    assert eng.ledger.counters["warm_requests"] == len(warmer.requests)
    assert eng.state.last_block_height == n_blocks


def test_warm_ahead_off_means_no_requests(history):
    items, vals_at = history
    warmer = _Warmer()
    eng = _engine(items, vals_at, warmer=warmer, warm_ahead=False)
    eng.run()
    assert warmer.requests == []
    assert eng.ledger.counters["warm_requests"] == 0


def test_kill_at_every_read_resumes_reverifying_zero(history, tmp_path):
    """The matrix: crash at read-ahead position K for EVERY K, resume
    from the persisted cursor, and prove the second run re-verifies
    not one block at or below the crash-time verified mark."""
    items, vals_at = history
    for k in range(1, N_BLOCKS + 1):
        cpath = str(tmp_path / f"cursor-{k}.json")
        eng1 = _engine(items, vals_at, cursor_path=cpath)
        fp.arm("catchup.read_ahead", "flake", k, count=1)
        try:
            with pytest.raises(fp.FailpointError):
                eng1.run()
        finally:
            fp.disarm("catchup.read_ahead")
        verified1, applied1 = eng1.cursor.verified, eng1.cursor.applied
        assert applied1 <= verified1 < N_BLOCKS

        v2 = _CountingVerifier()
        eng2 = _engine(items, vals_at, start=applied1,
                       cursor_path=cpath, verifier=v2)
        assert eng2.cursor.resumed, f"k={k}: cursor did not resume"
        assert eng2.ledger.counters["resumes"] == 1
        final = eng2.run()
        assert final.last_block_height == N_BLOCKS
        reverified = [h for h in v2.heights if h <= verified1]
        assert reverified == [], \
            f"k={k}: resume re-verified {reverified}"
        # heights in (applied, verified] replay WITHOUT verification
        assert eng2.ledger.counters["blocks_skipped"] == \
            verified1 - applied1, f"k={k}"
        assert eng2.ledger.counters["blocks_applied"] == \
            N_BLOCKS - applied1, f"k={k}"


def test_bad_signature_raises_with_height():
    items, vals_at = make_history(n_blocks=6, epoch_len=100)
    sig = items[4][1].signatures[0]
    sig.signature = sig.signature[:10] + \
        bytes([sig.signature[10] ^ 1]) + sig.signature[11:]
    eng = _engine(items, vals_at)
    with pytest.raises(CatchupError, match="height 4"):
        eng.run()
    # verified mark never advanced past the poisoned flush
    assert eng.cursor.verified < 4


def test_wrong_resume_state_is_corrupt_history(history):
    """A resume state whose valset does not match the next block's
    validators_hash must fail loudly, not verify against the wrong
    keys."""
    items, vals_at = history
    state = _State(CHAIN, 2, vals_at(99), vals_at(99))
    eng = CatchupEngine(_Source(items), state,
                        apply_fn=lambda s, b, c: s,
                        verifier=HostCommitVerifier(),
                        warmer=_Warmer())
    with pytest.raises(CatchupError, match="corrupt history"):
        eng.run()


def test_history_gap_raises(history):
    items, vals_at = history
    gappy = dict(items)
    del gappy[7]
    eng = _engine(gappy, vals_at)
    with pytest.raises(CatchupError, match="missing block 7"):
        eng.run()


def test_store_history_source_contract():
    class _EmptyStore:
        def base(self):
            return 1

        def height(self):
            return 3

        def load_block(self, h):
            return None

        def load_block_commit(self, h):
            return None

    src = StoreHistorySource(_EmptyStore())
    assert src.tip() == 3
    with pytest.raises(CatchupError, match="missing block 1"):
        src.load(1)


STEP_STAGES = ["catchup.refill", "catchup.scan", "catchup.jobs",
               "catchup.verify", "catchup.apply", "catchup.cursor",
               "catchup.step"]  # in the order they close


def test_step_stages_cover_the_step_and_feed_the_ledger(history,
                                                        tmp_path):
    """Every step closes the seven step-level stages once, in order,
    with one catchup.warm_ahead per applied block inside
    catchup.apply; the children fit inside the step; and the ledger's
    *_ms columns are those stages' own readings, not second timers."""
    items, vals_at = history
    tracing.set_clock(None)  # an empty stage ring
    eng = _engine(items, vals_at,
                  cursor_path=str(tmp_path / "cursor.json"))
    eng.run()
    recs = [r for r in tracing.stages() if r[0].startswith("catchup.")]
    ledger = eng.ledger.records()
    steps, cur = [], []
    for r in recs:
        cur.append(r)
        if r[0] == "catchup.step":
            steps.append(cur)
            cur = []
    assert not cur and len(steps) == len(ledger) >= 4
    for step, led in zip(steps, ledger):
        by_name = {r[0]: r for r in step}
        assert [r[0] for r in step
                if r[0] != "catchup.warm_ahead"] == STEP_STAGES
        warm = [r for r in step if r[0] == "catchup.warm_ahead"]
        assert len(warm) == led["blocks"]
        _, a0, adur, _ = by_name["catchup.apply"]
        assert all(a0 <= w0 and w0 + wdur <= a0 + adur
                   for _, w0, wdur, _ in warm)
        _, s0, sdur, _ = by_name["catchup.step"]
        children = [by_name[n] for n in STEP_STAGES[:-1]]
        assert all(s0 <= c0 and c0 + cdur <= s0 + sdur
                   for _, c0, cdur, _ in children)
        assert sum(c[2] for c in children) <= sdur
        for col, name in (("read_ms", "catchup.refill"),
                          ("verify_ms", "catchup.verify"),
                          ("apply_ms", "catchup.apply")):
            assert led[col] == round(by_name[name][2] / 1e6, 3)
        assert led["warm_ms"] == round(sum(w[2] for w in warm) / 1e6, 3)
        assert led["warm_ms"] <= led["apply_ms"]
    assert eng.ledger.summary()["warm_ms_total"] == round(
        sum(r["warm_ms"] for r in ledger), 3)


def test_stream_verifier_stages_once_per_chunk():
    """StreamVerifier.verify closes stream.prechecks once, and
    stream.dispatch inside stream.pack, then stream.collect, once per
    chunk. The device call is stood in for (the verdicts it would
    return): what is under test is where the stages sit."""
    import numpy as np

    from cometbft_tpu.blocksync.pipeline import CommitJob, StreamVerifier

    items, vals_at = make_history(n_blocks=4, n_vals=3, epoch_len=8)
    jobs = [CommitJob(vals_at(h), blk.block_id(), h, commit, CHAIN)
            for h, (blk, commit) in sorted(items.items())]
    # 3 signatures a commit, 6 a chunk: two chunks of two commits
    sv = StreamVerifier(max_sigs=6, use_pallas=False, min_device_sigs=1)

    def device(pb, power5, counted, commit_ids, thresh, n_commits):
        return (np.ones(pb.padded, np.bool_), None,
                np.ones(n_commits, np.bool_))

    sv._dispatch = device
    tracing.set_clock(None)
    assert sv.verify(jobs) == [None] * 4
    assert sv.chunks == {"stamped": 0, "host_packed": 0, "dense": 2}
    recs = [r for r in tracing.stages() if r[0].startswith("stream.")]
    assert [r[0] for r in recs] == [
        "stream.prechecks",
        "stream.dispatch", "stream.pack",
        "stream.dispatch", "stream.pack",
        "stream.collect", "stream.collect"]
    for (_, d0, ddur, _), (_, p0, pdur, _) in (recs[1:3], recs[3:5]):
        assert p0 <= d0 and d0 + ddur <= p0 + pdur


class _LateRead:
    """What a device call hands back before it ran. The verdicts are
    computed from the STAGED host buffers only when they are fetched:
    the device may read a numpy argument at any moment up to the
    collect of its result (libs/staging.py), so a buffer rewritten
    under a chunk in flight changes what this returns."""

    def __init__(self, run, pick):
        self.run, self.pick = run, pick

    def __array__(self, dtype=None, copy=None):
        return self.run()[self.pick]


class _StandInDevice:
    """Stands in for the cached-table path's device (the Pallas kernel
    does not run on the CPU): a table of `width` slots for any set, and
    in place of `verify_tally_delta_cached` a host check of every live
    staged row against its commit (`check=False`: all rows valid). It
    keeps each call's static shapes, and which chunk every staging
    buffer was last handed out for."""

    def __init__(self, monkeypatch, sv, width, check=True):
        from cometbft_tpu.ops import ed25519_cached as ec

        self.width, self.check = width, check
        self.calls = []        # (B, template rows, n_commits) per chunk
        self.collected = set()
        self.owner = {}        # id(staging buffer) -> chunk it was for
        self._packing = None
        table = type("Table", (), {"n_vals": width, "pub_raw": True})()
        monkeypatch.setattr(ec, "table_for_valset", lambda vs: table)
        monkeypatch.setattr(ec, "verify_tally_delta_cached", self.delta)
        pack, collect, get = (sv._pack_chunk_cached, sv._collect,
                              sv._staging.get)

        def packing(jobs, tbl):
            self._packing = jobs
            return pack(jobs, tbl)

        def collecting(chunk, results):
            collect(chunk, results)
            self.collected.add(chunk.pending[0].chunk)

        def getting(*a, **kw):
            buf = get(*a, **kw)
            # the rotation contract: never the buffer of a chunk whose
            # result was not fetched yet
            assert self.owner.get(id(buf), -1) in self.collected | {-1}, a
            self.owner[id(buf)] = len(self.calls)
            return buf

        sv._pack_chunk_cached, sv._collect = packing, collecting
        sv._staging.get = getting

    def delta(self, sig, ts, flags, ent, table, n_commits, thresh=None):
        jobs, k, M = self._packing, len(self.calls), self.width
        self.calls.append((sig.shape[0], int(ent.pre_mat.shape[0]),
                           n_commits))
        done = []

        def run():
            if done:
                return done[0]
            valid = np.ones(sig.shape[0], np.bool_)
            quorum = np.ones(n_commits, np.bool_)
            live = np.flatnonzero(flags & 1) if self.check else ()
            for b in live:
                j, i = divmod(int(b), M)
                assert (int(flags[b]) >> 2) & 0xFF == j
                job = jobs[j][1]
                cs = job.commit.signatures[i]
                msg = canonical.canonical_vote_bytes(
                    job.chain_id, canonical.PRECOMMIT_TYPE,
                    job.commit.height, job.commit.round,
                    job.commit.block_id, cs.timestamp)
                valid[b] = job.vals.validators[i].pub_key \
                    .verify_signature(msg, bytes(sig[b]))
            for j in range(len(jobs) if self.check else 0):
                quorum[j] = 3 * valid[j * M:j * M + 3].sum() > 2 * 3
            done.append((valid, quorum))
            return done[0]

        out = (_LateRead(run, 0), None, _LateRead(run, 1))
        out[0].chunk = k
        return out


def _stream_jobs(items, vals_at):
    from cometbft_tpu.blocksync.pipeline import CommitJob

    return [CommitJob(vals_at(h), blk.block_id(), h, commit, CHAIN)
            for h, (blk, commit) in sorted(items.items())]


def _flip(items, h, idx):
    sig = items[h][1].signatures[idx]
    sig.signature = sig.signature[:10] + \
        bytes([sig.signature[10] ^ 1]) + sig.signature[11:]


def _outcomes(errs):
    return [(type(e).__name__, getattr(e, "idx", None)) for e in errs]


@pytest.mark.parametrize("n_blocks,order", [
    (8, "pack pack pack collect pack collect collect collect"),
    (9, "pack pack pack collect pack collect pack collect collect collect"),
], ids=["4-chunks", "5-chunks"])
def test_stream_verifier_overlaps_the_chunks_of_one_call(
        monkeypatch, n_blocks, order):
    """A verify call of several cached-table chunks: two fly while the
    next packs, a chunk's staging buffers come round only after it was
    collected, and every verdict equals the host's, with a bad
    signature in the first, a middle and the last chunk blamed at its
    commit-signature index."""
    from cometbft_tpu.blocksync.pipeline import StreamVerifier

    n_chunks = order.split().count("pack")
    items, vals_at = make_history(n_blocks=n_blocks, epoch_len=100)
    # 128 slots a commit, 256 rows a chunk: two commits a chunk
    bad = {2: 0, 5: 2, n_blocks: 1}  # height -> flipped signature
    for h, idx in bad.items():
        _flip(items, h, idx)
    jobs = _stream_jobs(items, vals_at)
    sv = StreamVerifier(max_sigs=256, use_pallas=True, min_device_sigs=1)
    dev = _StandInDevice(monkeypatch, sv, width=128)
    tracing.set_clock(None)
    got = sv.verify(jobs)
    assert _outcomes(got) == _outcomes(HostCommitVerifier().verify(jobs))
    assert {j.height: e.idx for j, e in zip(jobs, got)
            if e is not None} == bad
    assert sv.chunks == {"stamped": n_chunks, "host_packed": 0, "dense": 0}
    assert dev.calls == [(256, 8, 2)] * n_chunks
    assert dev.collected == set(range(n_chunks))
    recs = [(r[0][len("stream."):], r[4]) for r in tracing.stage_records()
            if r[0] in ("stream.pack", "stream.collect")]
    # three packs before anything is fetched, then a fetch before
    # each further pack, then the rest
    assert [name for name, _ in recs] == order.split()
    assert [a["flying"] for name, a in recs if name == "pack"] == \
        [0, 1] + [2] * (n_chunks - 2)
    assert [a["jobs"] for name, a in recs if name == "pack"] == \
        [2] * (n_blocks // 2) + [1] * (n_blocks % 2)


@pytest.mark.parametrize("width,cap", [(256, 32), (1024, 8), (4096, 2),
                                       (16384, 1)])
def test_stream_default_chunk_is_one_shape_per_table_width(
        monkeypatch, width, cap):
    """With the default capacity a chunk holds CHUNK_ROWS device rows
    (one commit where the table is wider than that): 32 / 8 / 2 / 1
    commits by the table's width, and the batch, the template matrix
    and the tally compile to the same shapes whatever a run's length."""
    from cometbft_tpu.blocksync import pipeline

    items, vals_at = make_history(n_blocks=64, epoch_len=100)
    jobs = _stream_jobs(items, vals_at)
    sv = pipeline.make_stream_verifier(use_pallas=True)
    assert sv.max_sigs == pipeline.CHUNK_ROWS == 8192
    sv.min_device_sigs = 1
    dev = _StandInDevice(monkeypatch, sv, width=width, check=False)
    for n in (1, 16, 17, 64):
        del dev.calls[:]
        assert sv.verify(jobs[:n]) == [None] * n
        assert len(dev.calls) == -(-n // cap)
        assert set(dev.calls) == {(max(8192, width), max(8, cap), cap)}
    assert sv.chunks["host_packed"] == sv.chunks["dense"] == 0


def test_tampered_block_in_a_later_chunk_fails_the_whole_run(monkeypatch):
    """A run's verdicts are all in hand before its first block is
    applied: a bad commit in the third of four chunks raises at its
    height, the cursor stays behind the run and nothing is applied."""
    from cometbft_tpu.blocksync.pipeline import StreamVerifier

    items, vals_at = make_history(n_blocks=8, epoch_len=100)
    _flip(items, 6, 1)
    sv = StreamVerifier(max_sigs=256, use_pallas=True, min_device_sigs=1)
    dev = _StandInDevice(monkeypatch, sv, width=128)
    applied = []
    eng = _engine(items, vals_at, read_ahead=8, max_run=8, verifier=sv,
                  on_apply=applied.append)
    with pytest.raises(CatchupError, match=r"height 6: .*\(#1\)"):
        eng.run()
    assert len(dev.calls) == 4 and dev.collected == {0, 1, 2, 3}
    assert (eng.cursor.verified, eng.cursor.applied) == (0, 0)
    assert applied == [] and eng.state.last_block_height == 0


def test_cursor_roundtrip_and_corrupt_file(tmp_path):
    path = str(tmp_path / "cursor.json")
    c = CatchupCursor(path)
    assert (c.verified, c.applied, c.resumed) == (0, 0, False)
    c.verified, c.applied = 42, 40
    c.save()
    c2 = CatchupCursor(path)
    assert (c2.verified, c2.applied, c2.resumed) == (42, 40, True)
    # torn/corrupt file: resume conservatively from zero, never crash
    with open(path, "w") as f:
        f.write("{not json")
    c3 = CatchupCursor(path)
    assert (c3.verified, c3.applied, c3.resumed) == (0, 0, False)
    # pathless cursor is inert
    CatchupCursor(None).save()


def test_ledger_ring_bounded_and_summary():
    led = CatchupLedger(capacity=8)
    for i in range(20):
        led.record(first=i, last=i, blocks=1, sigs=3, skipped=0,
                   read_ms=1.0, verify_ms=2.0, apply_ms=0.5,
                   boundary=(i % 5 == 0), warmed=False)
    assert len(led) == 8  # ring bounded; counters cumulative
    assert led.counters["flushes"] == 20
    assert led.counters["blocks_applied"] == 20
    assert led.counters["boundaries"] == 4
    s = led.summary()
    assert s["window_flushes"] == 8
    assert s["verify_ms_total"] == pytest.approx(16.0)
    assert [r["seq"] for r in led.tail(3)] == [17, 18, 19]
    m = led.mark()
    assert not led.advanced(m)
    led.record(first=99, last=99, blocks=1, sigs=0, skipped=0,
               read_ms=0, verify_ms=0, apply_ms=0,
               boundary=False, warmed=False)
    assert led.advanced(m)


def test_dump_catchup_document(history):
    items, vals_at = history
    old_g, old_l = cu._GLOBAL, cu._LAST
    try:
        cu.set_global_ledger(None)
        cu._LAST = None
        assert cu.dump_catchup() == {"records": [], "summary": {},
                                     "counters": {}}
        eng = _engine(items, vals_at)
        eng.run()  # run() installs its ledger as the process-global
        doc = cu.dump_catchup()
        assert doc["counters"]["blocks_applied"] == N_BLOCKS
        assert doc["records"] and doc["summary"]["flushes"] >= 1
        json.dumps(doc)  # the /dump_catchup body must serialize
        assert cu.ledger_tail(2) == doc["records"][-2:]
    finally:
        cu._GLOBAL, cu._LAST = old_g, old_l


def test_catchup_stall_incident_fires_on_frozen_ledger():
    """Catch-up ACTIVE + no ledger advance past catchup_stall_s fires
    catchup_stall (with the ledger tail in the snapshot); progress
    notes and deactivation both re-arm the window. Driven entirely on
    a virtual clock — the satellite-1 contract that stall detection
    works under simnet."""
    now = [10 ** 12]
    tracing.set_clock(lambda: now[0])
    old_g, old_l = cu._GLOBAL, cu._LAST
    try:
        led = CatchupLedger()
        led.record(first=1, last=2, blocks=2, sigs=6, skipped=0,
                   read_ms=0, verify_ms=0, apply_ms=0,
                   boundary=False, warmed=False)
        cu.set_global_ledger(led)
        rec = incidents.IncidentRecorder(catchup_stall_s=5.0)
        rec.poke()  # clock-domain change: re-arms every window
        rec.note_catchup(True)
        now[0] += int(4e9)
        rec.poke()
        assert rec.fired.get("catchup_stall") is None  # within limit
        now[0] += int(2e9)  # 6s since the last note: stalled
        rec.poke()
        assert rec.fired.get("catchup_stall") == 1
        snap = rec.incidents()[-1]
        assert snap["trigger"] == "catchup_stall"
        assert snap["detail"]["stalled_s"] == pytest.approx(6.0)
        assert snap["catchup_tail"], "ledger tail missing from snapshot"
        # progress re-arms; inactive never fires however stale
        rec.note_catchup(True)
        now[0] += int(3e9)
        rec.poke()
        assert rec.fired.get("catchup_stall") == 1
        rec.note_catchup(False)
        now[0] += int(60e9)
        rec.poke()
        assert rec.fired.get("catchup_stall") == 1
    finally:
        tracing.set_clock(None)
        cu._GLOBAL, cu._LAST = old_g, old_l
