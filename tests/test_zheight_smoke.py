"""Consensus flight recorder tier-1 wiring (ISSUE 13): height-ledger
record shape over a REAL committing LocalNetwork, the /dump_heights +
/dump_incidents RPC surfaces (including the stopping-node concurrency
hammer — the _LAST pattern), incident trigger + snapshot freeze via
the registered failpoint, the height_report --diff regression
detector, and the <10 us step-transition bookkeeping budget.

Late in the alphabet on purpose (tier-1 ordering note in ROADMAP): by
the time this runs the cheap unit tests have localized real breakage.
Host-only: the whole file must run with NO jax import (asserted).
"""
import copy
import json
import sys
import threading
import time
import urllib.request

import pytest

from cometbft_tpu.libs import failpoints as fp
from cometbft_tpu.libs import incidents

_JAX_LOADED_BEFORE = "jax" in sys.modules


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.reset()
    yield
    fp.reset()


def _mini_net(n_nodes=3):
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import LocalNetwork, Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    fast = TimeoutParams(propose=0.4, propose_delta=0.1, prevote=0.2,
                         prevote_delta=0.1, precommit=0.2,
                         precommit_delta=0.1, commit=0.05)
    privs = [PrivKey.generate(bytes([90 + i]) * 32)
             for i in range(n_nodes)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("zheight-chain", vals)
    net = LocalNetwork()
    nodes = []
    for i, priv in enumerate(privs):
        node = Node(KVStoreApplication(), state.copy(),
                    privval=FilePV(priv), broadcast=net.broadcaster(i),
                    timeouts=fast)
        net.add(node)
        nodes.append(node)
    return nodes


@pytest.fixture(scope="module")
def committed_net():
    """ONE LocalNetwork run to height 4, shared read-only across the
    module (the suite sits near the tier-1 ceiling); yields the
    stopped nodes + node 0's height dump."""
    nodes = _mini_net()
    for n in nodes:
        n.start()
    assert nodes[0].consensus.wait_for_height(4, timeout=30.0)
    for n in nodes:
        n.stop()
    yield nodes, nodes[0].consensus.height_ledger.dump()


def test_height_ledger_record_shape(committed_net):
    """Every record carries the full FIELDS surface with a monotone
    cumulative stage timeline, the proposer, and the via path; the
    summary decomposes commit latency per stage."""
    from cometbft_tpu.consensus.heightledger import HeightLedger

    _, dump = committed_net
    recs = dump["heights"]
    assert len(recs) >= 4
    heights = [r["height"] for r in recs]
    assert heights == sorted(heights)
    for r in recs:
        assert set(r) == set(HeightLedger.FIELDS)
        assert r["via"] == "consensus"
        assert len(r["proposer"]) == 12
        # cumulative timeline: each stage at or after the previous
        stages = [r["proposal_ms"], r["prevote_quorum_ms"],
                  r["precommit_quorum_ms"], r["commit_ms"],
                  r["apply_ms"]]
        assert all(s > 0 for s in stages), r
        assert stages == sorted(stages), r
        assert r["rounds"] >= 0 and r["txs"] == 0
        assert isinstance(r["late"], list)
    s = dump["summary"]
    assert s["heights"] == len(recs)
    assert s["commit_latency_ms"]["p50"] > 0
    assert set(s["stage_ms"]) == {"proposal", "prevote_quorum",
                                  "precommit_quorum", "commit", "apply"}


def test_dump_routes_serve_after_stop(committed_net):
    """The _LAST pattern: /dump_heights (node-attached AND module
    fallback), /dump_flushes, /dump_incidents all serve history from a
    STOPPED node, and /metrics carries the new height/incident
    families."""
    from cometbft_tpu.consensus import heightledger
    from cometbft_tpu.rpc.server import Routes

    nodes, dump = committed_net
    routes = Routes(nodes[0])
    served = routes.dump_heights()
    assert served["summary"]["heights"] == dump["summary"]["heights"]
    # the module-global fallback serves the LAST registered ledger
    assert heightledger.dump_heights()["summary"]["heights"] >= 1
    inc = routes.dump_incidents()
    assert set(inc) == {"incidents", "fired", "thresholds"}
    assert routes.dump_flushes()["summary"] is not None
    text = nodes[0].metrics.expose_text()
    for fam in ("cometbft_consensus_height_stage_ms",
                "cometbft_consensus_height_ledger_records",
                "cometbft_consensus_late_signer_heights_total",
                "cometbft_incidents_fired_total",
                "cometbft_incidents_ring_records"):
        assert fam in text, fam
    # the stage percentiles really sampled from the ledger
    line = next(ln for ln in text.splitlines()
                if ln.startswith("cometbft_consensus_height_ledger_"))
    assert float(line.split()[-1]) >= 4


def test_dump_routes_concurrent_with_stop():
    """ISSUE 13 satellite: hammer /dump_flushes, /dump_heights and
    /dump_incidents from threads WHILE the plane and node stop — no
    crash, every response well-formed, and post-stop history still
    served."""
    from cometbft_tpu.rpc.server import Routes
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    nodes = _mini_net(2)
    plane = VerifyPlane(window_ms=0.5, use_device=False)
    plane.start()
    set_global_plane(plane)
    nodes[0].verify_plane = plane  # the node-attached dump path
    stop_ev = threading.Event()
    try:
        for n in nodes:
            n.start()
        assert nodes[0].consensus.wait_for_height(2, timeout=30.0)
        routes = Routes(nodes[0])
        errors = []
        responses = [0]

        def hammer():
            while not stop_ev.is_set():
                try:
                    for fn in (routes.dump_heights, routes.dump_flushes,
                               routes.dump_incidents):
                        doc = fn()
                        json.dumps(doc)  # well-formed, serializable
                        responses[0] += 1
                except Exception as e:  # noqa: BLE001 - the assertion
                    errors.append(repr(e))
                    return

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        # stop everything WHILE the hammer runs
        for n in nodes:
            n.stop()
        set_global_plane(None)
        plane.stop()
        stop_ev.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors[:3]
        assert responses[0] > 0
    finally:
        stop_ev.set()
        set_global_plane(None)
        if plane.is_running():
            plane.stop()
        for n in nodes:
            if n.is_running():
                n.stop()
    # post-stop: history still served through every layer
    post = routes.dump_heights()
    assert post["summary"]["heights"] >= 2
    assert routes.dump_flushes()["summary"]["flushes"] >= 0
    assert routes.dump_incidents()["thresholds"]


def test_dump_heights_over_real_rpc():
    """GET /dump_heights and /dump_incidents over a live JSON-RPC
    server (the curl path operators actually use)."""
    nodes = _mini_net(2)
    try:
        for n in nodes:
            n.start()
        url = nodes[0].rpc_listen("127.0.0.1", 0)
        assert nodes[0].consensus.wait_for_height(2, timeout=30.0)
        with urllib.request.urlopen(url + "/dump_heights",
                                    timeout=10) as r:
            doc = json.loads(r.read().decode())
        assert doc["summary"]["heights"] >= 1
        assert doc["heights"][0]["apply_ms"] > 0
        with urllib.request.urlopen(url + "/dump_incidents",
                                    timeout=10) as r:
            inc = json.loads(r.read().decode())
        assert "thresholds" in inc
        # the JSON-RPC form of the same route
        body = json.dumps({"jsonrpc": "2.0", "id": 1,
                           "method": "dump_heights",
                           "params": {}}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            rpc = json.loads(r.read().decode())
        assert rpc["result"]["summary"]["heights"] >= 1
    finally:
        for n in nodes:
            n.stop()


def test_incident_failpoint_trigger_freezes_snapshot(committed_net):
    """Arming ``incidents.force=raise*1`` forces ONE snapshot at the
    next watchdog poke: the frozen bundle carries the height-ledger
    tail, the counter sample and the fingerprint, and /dump_incidents
    serves it; the cooldown keeps a re-poke from double-firing."""
    rec = incidents.IncidentRecorder(cooldown_s=60.0)
    rec.set_fingerprint({"chain_id": "zheight-chain", "drill": True})
    old = incidents.install(rec)
    try:
        fp.registry().arm_from_spec("incidents.force=raise*1")
        incidents.poke(height=7, round_=1)
        incidents.poke(height=7, round_=1)  # armed *1: no re-fire
        dump = incidents.dump_incidents()
    finally:
        incidents.install(old)
    assert dump["fired"] == {"forced": 1}
    snap = dump["incidents"][0]
    assert snap["trigger"] == "forced"
    assert snap["height"] == 7 and snap["round"] == 1
    # the committed_net fixture registered a height ledger: its tail
    # was frozen into the black box at trigger time
    assert snap["height_tail"], snap
    assert snap["fingerprint"]["drill"] is True
    assert "heights_recorded" in snap["counters"]


def test_incident_commit_stall_and_round_escalation_triggers():
    """The watchdog's threshold arms, driven directly: a commit gap
    past commit_stall_s fires commit_stall; a poke at round >= the
    limit fires round_escalation; cooldown suppresses same-kind
    refires."""
    from cometbft_tpu.libs import tracing

    now = [1_000_000_000_000]
    tracing.set_clock(lambda: now[0])
    try:
        rec = incidents.IncidentRecorder(
            commit_stall_s=5.0, round_limit=3, cooldown_s=100.0)
        rec.note_commit(10)
        now[0] += int(2e9)
        rec.poke(11, 0)
        assert not rec.fired  # 2s < 5s: quiet
        now[0] += int(4e9)
        rec.poke(11, 0)
        assert rec.fired == {"commit_stall": 1}
        now[0] += int(1e9)
        rec.poke(11, 0)  # cooldown holds
        assert rec.fired == {"commit_stall": 1}
        rec.poke(11, 3)  # round escalation is its own kind
        assert rec.fired == {"commit_stall": 1, "round_escalation": 1}
        snaps = rec.incidents()
        assert [s["trigger"] for s in snaps] == ["commit_stall",
                                                 "round_escalation"]
        assert snaps[0]["detail"]["stalled_s"] >= 5.0
    finally:
        tracing.set_clock(None)


def test_shed_storm_window_semantics():
    """Review regression: sheds that accumulated over LONGER than
    window_s (a wedged poker waking up after a quorumless partition)
    are a drip, not a storm — the expired window resets BEFORE the
    threshold check. A genuine in-window burst still fires."""
    from cometbft_tpu.libs import tracing

    now = [10 ** 15]
    tracing.set_clock(lambda: now[0])
    try:
        rec = incidents.IncidentRecorder(shed_storm=10, window_s=2.0,
                                         commit_stall_s=0.0)
        rec.note_commit(1)
        rec.note_shed(5)
        rec.poke(1, 0)          # anchors the storm window
        now[0] += int(60e9)     # a minute wedged, sheds dripping
        rec.note_shed(20)
        rec.poke(1, 0)          # expired window: 25 sheds, no storm
        assert "shed_storm" not in rec.fired, rec.fired
        rec.note_shed(15)       # burst INSIDE the fresh window
        now[0] += int(1e9)
        rec.poke(1, 0)
        assert rec.fired.get("shed_storm") == 1, rec.fired
        snap = rec.incidents()[-1]
        assert snap["detail"]["sheds"] == 15
    finally:
        tracing.set_clock(None)


def test_watchdog_ticker_detects_total_wedge():
    """The production half of stall detection: with NO pokes arriving
    at all (a quorumless partition produces zero step transitions),
    the refcounted real-clock ticker thread still fires commit_stall —
    and stop_watchdog tears the thread down when the last node
    releases it."""
    import time

    rec = incidents.IncidentRecorder(commit_stall_s=0.4,
                                     cooldown_s=60.0)
    rec.note_commit(3)
    rec.start_watchdog()
    rec.start_watchdog()  # second node's reference
    try:
        deadline = time.time() + 5.0
        while time.time() < deadline and not rec.fired:
            time.sleep(0.05)
        assert rec.fired.get("commit_stall") == 1, rec.fired
    finally:
        rec.stop_watchdog()
        assert rec._watch_thread is not None  # one ref still held
        rec.stop_watchdog()
    assert rec._watch_thread is None


def test_height_report_diff_detects_synthetic_regression(
        committed_net, tmp_path, capsys):
    """The --diff CLI path flags an injected +500 ms prevote-quorum
    regression (exit 1 under --fail-on-regression) and stays quiet on
    identical dumps (exit 0)."""
    from tools import height_report

    _, dump = committed_net
    a_path = tmp_path / "a.json"
    a_path.write_text(json.dumps(dump))
    doctored = copy.deepcopy(dump)
    for r in doctored["heights"]:
        for k in ("prevote_quorum_ms", "precommit_quorum_ms",
                  "commit_ms", "apply_ms"):
            r[k] += 500.0
    b_path = tmp_path / "b.json"
    b_path.write_text(json.dumps(doctored))

    rc = height_report.main([str(a_path), str(a_path), "--diff",
                             "--fail-on-regression"])
    assert rc == 0
    capsys.readouterr()
    rc = height_report.main([str(a_path), str(b_path), "--diff",
                             "--fail-on-regression"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "prevote_quorum" in out
    # the miswired-CI-gate guard mirrors trace_report's
    with pytest.raises(SystemExit):
        height_report.main([str(a_path), "--fail-on-regression"])
    # and the single-dump report renders the late-signer-aware table
    capsys.readouterr()
    assert height_report.main([str(a_path)]) == 0
    out = capsys.readouterr().out
    assert "commit latency p50/p99" in out


def test_late_signer_attribution_math():
    """Driven on a fake clock: offsets are measured against the
    precommit-quorum instant (only AFTER-quorum arrivals are late),
    each late row splits into net_ms (in-flight, from the vote's own
    signing stamp) vs sign_ms (signed late), the gossip-observatory
    join names the delivering hop, absent precommits land in the
    bitmap + count, and repeat offenders accumulate net/sign sums in
    the chronically-late table /dump_heights ranks."""
    from cometbft_tpu.consensus.heightledger import HeightLedger
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.p2p.peerledger import PeerLedger

    class _Sig:
        def __init__(self, absent):
            self._a = absent

        def is_absent(self):
            return self._a

    now = [5_000_000_000_000]
    tracing.set_clock(lambda: now[0])
    try:
        led = HeightLedger()
        pled = PeerLedger()
        led.peer_ledger = pled
        for h in (1, 2):
            led.on_step(h, 0, 2)          # new_round opens the height
            now[0] += 10_000_000
            led.on_step(h, 0, 4)          # prevote entry
            led.note_vote(0, 0)           # val 0: before quorum
            now[0] += 5_000_000
            led.on_step(h, 0, 6)          # precommit entry
            led.note_vote(0, 1)           # val 1: AT quorum crossing
            now[0] += 2_000_000
            led.on_step(h, 0, 8)          # commit: quorum instant
            now[0] += 7_500_000
            # val 2: 7.5 ms LATE, of which 3 ms was flight time; the
            # peer ledger saw the vote arrive from n1 (+1 duplicate)
            pled.note_vote_seen((h, 0, 2, 2), "n1")
            pled.note_vote_seen((h, 0, 2, 2), "n0")
            led.note_vote(0, 2, net_ns=3_000_000)
            now[0] += 1_000_000
            led.on_commit(h)
            now[0] += 3_000_000
            led.record_height(
                h, 0, "aabbccddeeff", n_txs=2, block_bytes=64,
                commit_sigs=[_Sig(False), _Sig(False), _Sig(False),
                             _Sig(True)])
        # pruning lags ONE height so straggler joins still resolve:
        # finalizing h=2 pruned h=1's routes, h=2's survive
        assert pled.vote_route(1, 0, 2, 2) is None
        assert pled.vote_route(2, 0, 2, 2) is not None
        # post-commit straggler: a verified precommit for the JUST-
        # finalized height arrives 4 ms later (2 ms of it in flight)
        # and folds into the finalized record with the same split
        assert led.wants_straggler(2, 0, 1)
        assert not led.wants_straggler(2, 0, 2)  # already late
        assert not led.wants_straggler(1, 0, 1)  # older height
        now[0] += 4_000_000
        pled.note_vote_seen((2, 0, 2, 1), "n3")
        led.note_straggler(2, 0, 1, net_ns=2_000_000)
        led.note_straggler(2, 0, 1, net_ns=2_000_000)  # dedup
        recs = led.records()
    finally:
        tracing.set_clock(None)
    # the straggler row landed in height 2's FINALIZED record: offset
    # measured against its quorum instant (4 ms since finalize + the
    # 1+3 ms between quorum and finalize = 8 ms), net/sign split, hop
    straggler_rows = [row for row in recs[1]["late"] if row[0] == 1]
    assert straggler_rows == [[1, 15.5, 2.0, 13.5, "n3"]], \
        recs[1]["late"]
    r = recs[0]
    # vals 0/1 arrived at or before the quorum instant (not late);
    # val 2's stamp is 7.5 ms past it: 3 ms network, 4.5 ms sign-late,
    # delivered via n1 with one duplicate receipt
    assert r["late"] == [[2, 7.5, 3.0, 4.5, "n1+1dup"]], r["late"]
    assert r["absent"] == 1
    # bitmap: index 3 absent -> bit 3 of byte 0 -> 0x08
    assert r["absent_bitmap"] == "08"
    assert r["txs"] == 2 and r["block_bytes"] == 64
    # two heights of the same offenders -> chronic table ranks them,
    # accumulating the net-vs-sign decomposition
    top = led.top_late_signers()
    by_val = {t["val"]: t for t in top}
    assert by_val[2]["late_heights"] == 2
    assert by_val[2]["net_ms"] == 6.0
    assert by_val[2]["sign_ms"] == 9.0
    assert by_val[3]["absent_heights"] == 2
    # the straggler folded into val 1's chronic row too
    assert by_val[1]["late_heights"] == 1
    assert by_val[1]["net_ms"] == 2.0 and by_val[1]["sign_ms"] == 13.5
    assert top[0]["total"] == 2
    dump = led.dump()
    assert dump["late_signers"] == top
    assert dump["summary"]["late_votes"] == 3  # incl. the straggler
    assert dump["summary"]["late_net_ms"] == 8.0
    assert dump["summary"]["late_sign_ms"] == 22.5
    assert dump["summary"]["absent_votes"] == 2


def test_late_signer_split_on_live_network():
    """ISSUE 14 acceptance: a REAL committing multi-node network with
    one chronically slow signer produces late-signer rows carrying the
    net_ms vs sign_ms split — through the post-commit straggler path
    (finalize is atomic with quorum here, so the slow validator's
    precommit always loses the height race; the reference folds those
    into LastCommit, this ledger attributes them post-hoc)."""
    import time

    import cometbft_tpu.types.canonical as canonical
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import LocalNetwork, Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    fast = TimeoutParams(propose=0.4, propose_delta=0.1, prevote=0.2,
                         prevote_delta=0.1, precommit=0.2,
                         precommit_delta=0.1, commit=0.05)

    class SlowPV(FilePV):
        def sign_vote(self, chain_id, vote, **kw):
            if vote.vote_type == canonical.PRECOMMIT_TYPE:
                time.sleep(0.08)
            return super().sign_vote(chain_id, vote, **kw)

    privs = [PrivKey.generate(bytes([110 + i]) * 32) for i in range(4)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("zlate-chain", vals)
    net = LocalNetwork()
    nodes = []
    for i, priv in enumerate(privs):
        pv = SlowPV(priv) if i == 3 else FilePV(priv)
        node = Node(KVStoreApplication(), state.copy(), privval=pv,
                    broadcast=net.broadcaster(i), timeouts=fast)
        net.add(node)
        nodes.append(node)
    try:
        for n in nodes:
            n.start()
        assert nodes[0].consensus.wait_for_height(6, timeout=60.0)
    finally:
        for n in nodes:
            n.stop()
    dumps = [n.consensus.height_ledger.dump() for n in nodes]
    rows = [row for d in dumps for r in d["heights"]
            for row in r["late"]]
    # on a 1-core host WHICH validator loses the height race varies
    # (GIL contention competes with the injected sleep), but the
    # straggler path must attribute SOMEBODY with the full split
    assert rows, "no late-signer rows on a live multi-node run"
    for row in rows:
        assert len(row) == 5 and row[1] > 0
        assert abs(row[1] - (row[2] + row[3])) < 0.011, row
    # real in-flight time measured (signing stamp -> arrival)
    assert any(row[2] > 0 for row in rows), rows
    split_dumps = [d for d in dumps
                   if d["summary"]["late_net_ms"] > 0]
    assert split_dumps, "summary never carried the net split"
    tops = [t for d in split_dumps for t in d["late_signers"]
            if t["late_heights"]]
    assert tops and all("net_ms" in t and "sign_ms" in t for t in tops)


def _height_ledger_bookkeeping_us(k):
    """One replay of the exact per-transition sequence _set_step drives
    with tracing off (on_step: clock read + step-slot lookup + in-place
    stores, plus the once-per-height fsync anchor check), over a full
    open -> steps -> next-height cycle so the ring append amortizes in
    like production. Returns (us per transition, allocated blocks per
    steady-state transition within one height)."""
    from cometbft_tpu.consensus.heightledger import HeightLedger
    from cometbft_tpu.libs import tracing

    assert not tracing.enabled(), "measure the DISABLED path"
    led = HeightLedger()
    steps = (2, 3, 4, 6, 8)  # new_round/propose/prevote/precommit/commit
    t0 = time.perf_counter()
    h = 0
    for i in range(k):
        if i % len(steps) == 0:
            h += 1
        led.on_step(h, 0, steps[i % len(steps)])
        led.note_wal_fsync_base(1234)
    step_us = (time.perf_counter() - t0) * 1e6 / k
    # no height open, no ring append: the scratch list absorbs every
    # stamp in place (the clock's ints churn through the freelist)
    led.on_step(h + 1, 0, 2)  # open once, off the measured window
    blocks0 = sys.getallocatedblocks()
    for i in range(1024):
        led.on_step(h + 1, 0, steps[i % len(steps)])
    return step_us, (sys.getallocatedblocks() - blocks0) / 1024


def test_height_ledger_step_bookkeeping_budget():
    """ISSUE 13 acceptance: < 10 us per step transition with tracing
    OFF (best of 3 to dodge 1-core scheduler spikes; the typical
    number is < 1 us)."""
    rows = [_height_ledger_bookkeeping_us(5_000) for _ in range(3)]
    best = min(us for us, _ in rows)
    assert best < 10.0, f"step bookkeeping {best} us >= 10 us budget"
    # allocation-free in the FlushLedger sense: steady-state step
    # transitions hold the process block count flat (< 1 block/2 steps
    # tolerates freelist jitter; the real number is ~0.004)
    assert min(alloc for _, alloc in rows) < 0.5


def test_no_jax_import():
    """Host-only contract: nothing in this file (LocalNetwork
    consensus, ledgers, incidents, RPC, height_report, the budget
    replay) may pull jax into the process."""
    if not _JAX_LOADED_BEFORE:
        assert "jax" not in sys.modules
