"""The trace plane (libs/tracing.py): tracer semantics, Chrome-trace
export, the /dump_traces surface, trace_report's stage table, and the
simnet trace-determinism acceptance (same seed+schedule => identical
span names/order/timestamps under the virtual clock).
"""
import json
import threading

import pytest

from cometbft_tpu.libs import tracing


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.disable()
    tracing.set_clock(None)
    yield
    tracing.disable()
    tracing.set_clock(None)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_disabled_is_noop():
    assert not tracing.enabled()
    with tracing.span("never", cat="x", k=1) as s:
        assert s is None
    tracing.instant("never")
    tracing.flight_begin("never", 1)
    tracing.flight_end("never", 1)
    assert tracing.export_chrome()["traceEvents"] == []
    assert tracing.tail() == []


def test_span_instant_flight_export():
    tracing.enable(capacity=128)
    with tracing.span("outer", cat="t", height=3):
        tracing.instant("mark", cat="t", n=1)
        with tracing.span("inner", cat="t"):
            pass
    tracing.flight_begin("fly", 7, cat="t", rows=4)
    tracing.flight_end("fly", 7, cat="t")
    evs = tracing.export_chrome()["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["ph"] == "X"
    assert by_name["outer"]["args"] == {"height": 3}
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"] >= 0
    assert by_name["mark"]["ph"] == "i" and by_name["mark"]["s"] == "t"
    # async pair correlated by id, required for perfetto overlap tracks
    b = [e for e in evs if e["ph"] == "b"][0]
    e = [e for e in evs if e["ph"] == "e"][0]
    assert b["id"] == e["id"] == "7"
    assert b["cat"] == e["cat"] == "t"
    # inner closed before outer: ring order is completion order
    names = [ev["name"] for ev in evs]
    assert names.index("inner") < names.index("outer")
    # the whole document is valid JSON with the chrome keys
    doc = json.loads(json.dumps(tracing.export_chrome()))
    assert doc["displayTimeUnit"] == "ms"


def test_ring_buffer_bounds_and_drop_count():
    t = tracing.enable(capacity=16)
    for i in range(40):
        tracing.instant(f"e{i}")
    evs = t.events()
    assert len(evs) == 16
    assert evs[0]["name"] == "e24" and evs[-1]["name"] == "e39"
    assert t.dropped == 24


def test_deterministic_mode_and_custom_clock():
    ticks = iter(range(0, 10_000, 1000))
    tracing.enable(capacity=32, clock=lambda: next(ticks),
                   deterministic=True)
    with tracing.span("a"):
        tracing.instant("b")
    evs = tracing.export_chrome()["traceEvents"]
    assert all(e["tid"] == 0 and e["pid"] == 1 for e in evs)
    assert [e["ts"] for e in evs] == [1.0, 0.0]  # ns -> us
    assert evs[1]["dur"] == 2.0  # span a: t0=0, closed at t=2000ns


def test_write_and_tail(tmp_path):
    tracing.enable(capacity=32)
    tracing.instant("alpha")
    with tracing.span("beta"):
        pass
    path = str(tmp_path / "trace.json")
    tracing.write(path)
    with open(path) as f:
        doc = json.load(f)
    assert [e["name"] for e in doc["traceEvents"]] == ["alpha", "beta"]
    assert tracing.tail(1) == ["beta(X)"]


# ---------------------------------------------------------------------------
# stages: always on, bounded, on the profiler's clock
# ---------------------------------------------------------------------------


def test_stage_records_with_tracer_off_and_exposes_ms():
    assert not tracing.enabled()
    with tracing.stage("unit.outer", rows=3) as outer:
        with tracing.stage("unit.inner") as inner:
            pass
    recs = tracing.stages()
    # a nested stage lands before the stage around it
    assert [r[0] for r in recs] == ["unit.inner", "unit.outer"]
    (_, i0, idur, itid), (_, o0, odur, otid) = recs
    assert itid == otid == threading.get_ident()
    assert o0 <= i0 and i0 + idur <= o0 + odur
    # .ms is the very reading the record holds
    assert outer.ms == odur / 1e6 and inner.ms == idur / 1e6
    assert tracing.stages_dropped() == 0
    assert tracing.export_chrome()["traceEvents"] == []  # no tracer


def test_stage_records_keeps_the_args_beside_the_four_fields():
    with tracing.stage("unit.args", n=7):
        pass
    with tracing.stage("unit.bare"):
        pass
    recs = tracing.stage_records()
    assert [r[:4] for r in recs] == tracing.stages()
    assert [(r[0], r[4]) for r in recs] == [("unit.args", {"n": 7}),
                                            ("unit.bare", {})]


def test_stage_records_when_its_body_raises():
    with pytest.raises(KeyError):
        with tracing.stage("unit.raises") as st:
            raise KeyError("x")
    assert [r[0] for r in tracing.stages()] == ["unit.raises"]
    assert st.ms == tracing.stages()[0][2] / 1e6


def test_stage_ring_is_bounded_and_counts_drops():
    over = 5
    for i in range(tracing.STAGE_CAPACITY + over):
        with tracing.stage("unit.fill" if i else "unit.first"):
            pass
    recs = tracing.stages()
    assert len(recs) == tracing.STAGE_CAPACITY
    assert tracing.stages_dropped() == over
    assert recs[0][0] == "unit.fill"  # the oldest went first
    tracing.set_clock(None)
    assert tracing.stages() == [] and tracing.stages_dropped() == 0


def test_stage_exports_the_event_a_span_would():
    ticks = iter(range(1000, 100000, 1000))
    tracing.enable(capacity=16, clock=lambda: next(ticks),
                   deterministic=True)
    with tracing.span("unit.same", rows=4, path="dense"):
        pass
    with tracing.stage("unit.same", rows=4, path="dense"):
        pass
    with tracing.stage("unit.bare"):
        pass
    a, b, c = tracing.export_chrome()["traceEvents"]
    assert a["ph"] == "X" and a["args"] == {"rows": 4, "path": "dense"}
    # same event but for when it happened
    assert {**a, "ts": b["ts"]} == b
    assert c["name"] == "unit.bare" and "args" not in c
    # and the stage is in its own ring as well, on the tracer's clock
    assert [(r[0], r[1], r[2]) for r in tracing.stages()] == [
        ("unit.same", 3000, 1000), ("unit.bare", 5000, 1000)]


def test_stage_untraced_is_in_the_ring_and_not_in_the_trace():
    """A stage whose stamps a virtual clock cannot make repeat (the
    verify plane's idle wait) keeps its ring record and its .ms, and
    pushes no event of the exported trace."""
    ticks = iter(range(1000, 100000, 1000))
    tracing.enable(capacity=16, clock=lambda: next(ticks),
                   deterministic=True)
    with tracing.stage_untraced("unit.wait", deck=1) as st:
        pass
    with tracing.stage("unit.traced", deck=1):
        pass
    assert [e["name"] for e in tracing.export_chrome()["traceEvents"]] \
        == ["unit.traced"]
    assert [(r[0], r[2], r[4]) for r in tracing.stage_records()] == [
        ("unit.wait", 1000, {"deck": 1}), ("unit.traced", 1000, {"deck": 1})]
    assert st.ms == 0.001


def test_set_clock_clears_stages_and_a_virtual_clock_repeats():
    def run():
        ticks = iter(range(0, 10**6, 250))
        tracing.set_clock(lambda: next(ticks))
        with tracing.stage("unit.a"):
            with tracing.stage("unit.b", k=1):
                pass
        with tracing.stage("unit.c"):
            pass
        return tracing.stages()

    with tracing.stage("unit.wall_clock"):
        pass
    first = run()  # set_clock dropped the wall-clock record
    assert [r[0] for r in first] == ["unit.b", "unit.a", "unit.c"]
    assert [(r[1], r[2]) for r in first] == [(250, 250), (0, 750),
                                             (1000, 250)]
    assert run() == first


def test_stage_is_an_event_of_a_profiler_capture(tmp_path):
    """Under a real jax.profiler capture (CPU) a stage's name and args
    are in the profile's host plane, on the profile's own clock."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracing.stage("unit.profiled", rows=7, path="dense") as st:
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = [(plane.name, e)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "unit.profiled"]
    assert len(found) == 1
    plane_name, ev = found[0]
    assert plane_name.startswith("/host:")
    assert {k: v for k, v in ev.stats} == {"rows": 7, "path": "dense"}
    # the same region on two clocks: the annotation is entered first
    # and left last, so it is no shorter and barely longer
    assert st.ms * 1e6 <= ev.duration_ns <= st.ms * 1e6 + 50e6
    # the session clock, not perf_counter's
    assert ev.start_ns < tracing.stages()[-1][1]


def test_stage_is_cheap():
    """A stage costs microseconds (about 2 here, with jax imported);
    the limit is loose enough to hold on a busy host."""
    import time

    import jax  # noqa: F401 - the dearer case: the annotation is made

    costs = []
    for _ in range(10_000):
        t = time.perf_counter_ns()
        with tracing.stage("unit.cost", rows=1):
            pass
        costs.append(time.perf_counter_ns() - t)
    assert sorted(costs)[len(costs) // 2] < 20_000


def test_stages_need_no_jax():
    """libs/tracing stays importable, and a stage usable, in a process
    that never imports jax (host-only and simnet runs)."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from cometbft_tpu.libs import tracing\n"
            "with tracing.stage('unit.nojax', rows=1) as st:\n"
            "    pass\n"
            "assert [r[0] for r in tracing.stages()] == ['unit.nojax']\n"
            "assert st.ms > 0 and 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_dump_traces_route():
    from cometbft_tpu.rpc.server import Routes

    tracing.enable(capacity=32)
    tracing.instant("rpc-visible")
    doc = Routes(None).dump_traces()
    assert doc["traceEvents"][0]["name"] == "rpc-visible"


def test_tracing_config_applies():
    from cometbft_tpu.config.config import Config, ConfigError

    cfg = Config()
    assert cfg.tracing.enable is False
    cfg.tracing.enable = True
    cfg.tracing.buffer = 64
    cfg.validate_basic()
    cfg.tracing.apply()
    assert tracing.enabled() and tracing.tracer().capacity == 64
    cfg.tracing.buffer = 1
    with pytest.raises(ConfigError, match="tracing"):
        cfg.validate_basic()


# ---------------------------------------------------------------------------
# instrumented seams produce spans
# ---------------------------------------------------------------------------


def test_wal_spans_and_fsync_stats(tmp_path):
    from cometbft_tpu.consensus import wal as walmod

    tracing.enable(capacity=64)
    before = walmod.fsync_stats()
    w = walmod.WAL(str(tmp_path / "t.wal"))
    w.write_sync(walmod.MSG_INFO, b"payload")
    w.close()
    after = walmod.fsync_stats()
    assert after["count"] >= before["count"] + 1
    assert after["seconds"] >= before["seconds"]
    names = [e["name"] for e in tracing.export_chrome()["traceEvents"]]
    assert "wal.fsync" in names and "wal.write_sync" in names


def test_plane_flush_lifecycle_spans():
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import VerifyPlane

    tracing.enable(capacity=256)
    plane = VerifyPlane(window_ms=0.5, use_device=False)
    plane.start()
    try:
        priv = PrivKey.generate(b"\x61" * 32)
        msg = b"traced-vote"
        fut = plane.submit(priv.pub_key(), msg, priv.sign(msg))
        assert fut.result(10.0) == (True,)
    finally:
        plane.stop()
    evs = tracing.export_chrome()["traceEvents"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert "plane.submit" in by_name
    packs = by_name["plane.pack"]
    settles = by_name["plane.settle"]
    assert packs and settles
    # pack and settle of one flush correlate by flush id (ids are
    # process-global so concurrent planes can never cross-pair flights)
    assert packs[0]["args"]["flush"] == settles[0]["args"]["flush"]
    assert packs[0]["args"]["rows"] == 1
    assert packs[0]["args"]["queued_ms"] >= 0


def test_queued_ms_ignores_cross_clock_stamps():
    """A submission stamped before a clock install (a simnet
    enter/exit lands between submit and flush) must not difference two
    clock domains: the stale stamp is skipped and queued_ms falls back
    to 0 instead of an absurd virtual-minus-perf_counter delta."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import plane as vp

    priv = PrivKey.generate(b"\x62" * 32)
    msg = b"cross-clock"
    rows = [(priv.pub_key(), msg, priv.sign(msg))]
    p = vp.VerifyPlane(window_ms=0.5, use_device=False)
    sub = vp._Submission(rows, None, 0, False)  # perf_counter domain
    # a simnet-style virtual clock (ns since epoch) lands mid-queue
    tracing.set_clock(lambda: 1_700_000_000_000_000_000)
    try:
        flight = p._stage([sub])
        verdicts, _ = flight.finish()
        led = flight.led
    finally:
        tracing.set_clock(None)
    assert list(verdicts) == [True]
    assert led[vp.FlushLedger.FIELDS.index("queued_ms")] == 0.0


def test_consensus_step_metrics_and_instants(tmp_path):
    """A live single-validator node emits consensus.step instants and
    per-step duration observations while committing blocks."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    tracing.enable(capacity=4096)
    fast = TimeoutParams(propose=0.4, propose_delta=0.1, prevote=0.2,
                         prevote_delta=0.1, precommit=0.2,
                         precommit_delta=0.1, commit=0.01)
    priv = PrivKey.generate(bytes([29]) * 32)
    vals = ValidatorSet([Validator(priv.pub_key(), 10)])
    state = State.make_genesis("trace-chain", vals)
    node = Node(KVStoreApplication(), state, privval=FilePV(priv),
                home=str(tmp_path / "n0"), timeouts=fast)
    node.start()
    try:
        assert node.consensus.wait_for_height(2, timeout=30)
        text = node.metrics.expose_text()
    finally:
        node.stop()
    steps = [e for e in tracing.export_chrome()["traceEvents"]
             if e["name"] == "consensus.step"]
    seen = {e["args"]["step"] for e in steps}
    assert {"propose", "prevote", "precommit", "commit"} <= seen
    # per-step durations landed in the labeled histogram
    assert 'cometbft_consensus_step_duration_seconds_count' \
        '{step="propose"}' in text


# ---------------------------------------------------------------------------
# trace_report
# ---------------------------------------------------------------------------


def test_trace_report_stage_table(tmp_path):
    from tools import trace_report

    clock = iter(range(0, 10_000_000, 500_000))  # 0.5 ms ticks
    tracing.enable(capacity=256, clock=lambda: next(clock),
                   deterministic=True)
    # flush 0 flies while flush 1 packs: pack(1) must show overlap
    tracing.flight_begin("plane.flight", 0, cat="verifyplane")
    with tracing.span("plane.pack", cat="verifyplane", flush=1):
        pass
    tracing.flight_end("plane.flight", 0, cat="verifyplane")
    with tracing.span("plane.collect", cat="verifyplane", flush=0):
        pass
    tracing.instant("simnet.op", cat="simnet", op="heal")
    path = str(tmp_path / "t.json")
    tracing.write(path)

    rep = trace_report.stage_report(trace_report.load(path))
    stages = {r["stage"]: r for r in rep["stages"]}
    assert stages["plane.pack"]["count"] == 1
    assert stages["plane.pack"]["total_ms"] == pytest.approx(0.5)
    assert stages["plane.collect"]["count"] == 1
    # plane pipeline order leads the table
    assert rep["stages"][0]["stage"] == "plane.pack"
    assert rep["plane"]["flights"] == 1
    # flight: begin tick 0 -> end tick 3 = 1.5 ms on the 0.5 ms clock
    assert rep["plane"]["flight_total_ms"] == pytest.approx(1.5)
    # the whole pack happened while flight 0 was airborne
    assert rep["plane"]["pack_overlap_frac"] == pytest.approx(1.0)
    assert rep["instants"] == {"simnet.op": 1}
    txt = trace_report.format_report(rep)
    assert "plane.pack" in txt and "verify-plane flights: 1" in txt


def test_trace_report_deck_occupancy_and_overlap_union():
    """ISSUE 11 satellite: the overlap/critical-path math must handle
    MORE than one airborne flight. Two concurrent flights overlapping
    one pack span used to double-count it (fractions over 1.0); the
    fix computes pack overlap against the UNION of flight intervals,
    and the new deck block sweeps concurrency: fraction of wall time
    with >=1 and >=2 flights airborne."""
    from tools import trace_report

    # synthetic trace, us timestamps: flight A [0, 100], flight B
    # [40, 140] (60 us of two-deep deck), one pack span [50, 90]
    # entirely inside BOTH flights
    events = [
        {"ph": "b", "name": "plane.flight", "id": "a", "ts": 0},
        {"ph": "b", "name": "plane.flight", "id": "b", "ts": 40},
        {"ph": "X", "name": "plane.pack", "ts": 50, "dur": 40},
        {"ph": "e", "name": "plane.flight", "id": "a", "ts": 100},
        {"ph": "e", "name": "plane.flight", "id": "b", "ts": 140},
    ]
    rep = trace_report.stage_report(events)
    p = rep["plane"]
    assert p["flights"] == 2
    # union, not per-flight sums: the 40 us pack overlaps ONCE
    assert p["pack_overlapped_ms"] == pytest.approx(0.04)
    assert p["pack_overlap_frac"] == pytest.approx(1.0)
    deck = p["deck"]
    assert deck["max_airborne"] == 2
    # >=1 flight over [0, 140] = the whole 140 us wall; >=2 over
    # [40, 100] = 60 us
    assert deck["airborne_ge1_ms"] == pytest.approx(0.14)
    assert deck["airborne_ge2_ms"] == pytest.approx(0.06)
    assert deck["occupancy_ge1"] == pytest.approx(1.0)
    assert deck["occupancy_ge2"] == pytest.approx(60 / 140, abs=1e-3)
    txt = trace_report.format_report(rep)
    assert "deck occupancy" in txt and "max airborne 2" in txt
    # the diff's overlap block carries the occupancy deltas
    diff = trace_report.diff_report(rep, rep)
    assert diff["overlap"]["occupancy_ge2_a"] == \
        diff["overlap"]["occupancy_ge2_b"]
    assert diff["overlap"]["max_airborne_b"] == 2
    assert not diff["regressions"]


def test_trace_report_cli(tmp_path, capsys):
    from tools import trace_report

    tracing.enable(capacity=16)
    with tracing.span("stage.a"):
        pass
    path = str(tmp_path / "t.json")
    tracing.write(path)
    assert trace_report.main([path]) == 0
    assert "stage.a" in capsys.readouterr().out
    assert trace_report.main([path, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["stages"][0]["stage"] == "stage.a"


def _write_trace(tmp_path, name, events):
    path = str(tmp_path / name)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path


def _span_ev(name, ts, dur, **args):
    ev = {"ph": "X", "name": name, "cat": "t", "ts": ts, "dur": dur,
          "pid": 1, "tid": 0}
    if args:
        ev["args"] = args
    return ev


def test_trace_report_diff_flags_regressions(tmp_path, capsys):
    """ISSUE 6 tentpole: --diff aligns two stage tables and flags the
    stage whose mean grew past the thresholds, the stage that appeared,
    and an overlap collapse (flights vanished = the plane degraded to
    synchronous flushes)."""
    from tools import trace_report

    a = [_span_ev("plane.pack", i * 1000, 400) for i in range(8)]
    a += [{"ph": "b", "name": "plane.flight", "id": str(i),
           "ts": i * 1000 + 100, "pid": 1, "tid": 0} for i in range(8)]
    a += [{"ph": "e", "name": "plane.flight", "id": str(i),
           "ts": i * 1000 + 600, "pid": 1, "tid": 0} for i in range(8)]
    b = [_span_ev("plane.pack", i * 1000, 900) for i in range(8)]
    b += [_span_ev("plane.verify", i * 1000 + 900, 300)
          for i in range(8)]
    pa = _write_trace(tmp_path, "a.json", a)
    pb = _write_trace(tmp_path, "b.json", b)

    diff = trace_report.diff_report(
        trace_report.stage_report(trace_report.load(pa)),
        trace_report.stage_report(trace_report.load(pb)),
    )
    rows = {r["stage"]: r for r in diff["stages"]}
    assert rows["plane.pack"]["flag"] == "REGRESSED"
    assert rows["plane.pack"]["delta_mean_ms"] == pytest.approx(0.5)
    assert rows["plane.verify"]["flag"] == "appeared"
    assert diff["overlap"]["flag"] == "REGRESSED"  # flights 8 -> 0
    assert "plane.pack" in diff["regressions"]
    assert "pack_overlap_frac" in diff["regressions"]

    # CLI: table mode exits 0, --fail-on-regression exits 1
    assert trace_report.main(["--diff", pa, pb]) == 0
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "plane.pack" in out
    assert trace_report.main(
        ["--diff", pa, pb, "--fail-on-regression"]) == 1
    capsys.readouterr()
    # the reverse direction (B -> A) is pure improvement: pack shrank,
    # the flights (and their overlap) came back — nothing flags, so
    # --fail-on-regression exits 0
    assert trace_report.main(
        ["--diff", pb, pa, "--fail-on-regression", "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["regressions"] == []
    assert rep["overlap"]["flag"] == "improved"


def test_trace_report_consensus_fallback(tmp_path, capsys):
    """ISSUE 6 satellite: a trace with zero plane spans (consensus-only
    run) must not crash or print an empty table — it falls back to the
    per-step dwell table derived from consensus.step instants and says
    so."""
    from tools import trace_report

    evs = []
    steps = ["propose", "prevote", "precommit", "commit", "propose"]
    for i, st in enumerate(steps):
        evs.append({"ph": "i", "name": "consensus.step",
                    "cat": "consensus", "ts": i * 500, "s": "t",
                    "pid": 1, "tid": 0,
                    "args": {"step": st, "height": 1, "round": 0}})
    path = _write_trace(tmp_path, "c.json", evs)
    rep = trace_report.stage_report(trace_report.load(path))
    assert rep["fallback"]
    names = [r["stage"] for r in rep["stages"]]
    assert "step.propose" in names and "step.commit" in names
    # each step dwelled one 500 us tick before the next instant
    assert all(r["mean_ms"] == pytest.approx(0.5)
               for r in rep["stages"])
    assert trace_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "NOTE:" in out and "step.propose" in out


# ---------------------------------------------------------------------------
# simnet determinism (the acceptance criterion)
# ---------------------------------------------------------------------------

TRACE_SCHEDULE = [
    {"at": 0.1, "op": "link", "drop": 0.05, "delay": 0.02},
    {"at": 0.8, "op": "heal"},
]


@pytest.mark.simnet
def test_simnet_trace_byte_identical(tmp_path):
    """Same (seed, schedule) twice => the exported trace is
    BYTE-identical: every span/instant name, order, argument, and
    virtual-clock timestamp matches. This is what makes a trace of a
    wedged schedule replayable evidence. (Budgeted small for tier-1:
    3 nodes, 2 heights — the trace shape, not the fault coverage,
    is under test; test_simnet owns the scenario matrix.)"""
    from cometbft_tpu.simnet import Simnet

    def run_once(tag):
        tracing.enable(capacity=1 << 15, deterministic=True)
        try:
            with Simnet(3, seed=42, basedir=str(tmp_path / tag)) as sim:
                assert sim.run(TRACE_SCHEDULE, until_height=2,
                               max_time=60.0)
                sim.assert_safety()
            return json.dumps(tracing.export_chrome(), sort_keys=True)
        finally:
            tracing.disable()

    a = run_once("a")
    b = run_once("b")
    assert a == b
    evs = json.loads(a)["traceEvents"]
    names = {e["name"] for e in evs}
    # the run actually traced the layers that matter
    assert "consensus.step" in names
    assert "wal.fsync" in names
    assert "simnet.op" in names
    # timestamps ride the VIRTUAL clock: they live inside the sim's
    # epoch (seconds around SIM_EPOCH_SECONDS, expressed in us)
    from cometbft_tpu.simnet.core import SIM_EPOCH_SECONDS

    ts0 = min(e["ts"] for e in evs)
    assert ts0 >= SIM_EPOCH_SECONDS * 1e6
