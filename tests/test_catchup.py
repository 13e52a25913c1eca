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
