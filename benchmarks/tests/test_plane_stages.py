"""The five readers of the verify plane's dispatcher stages (PR 37):
each entry is found BY NAME in BENCHMARK.json with its reader file, its
layer, what it moves and both vote cells; the stage readers over a
synthetic ring; the pure reduction of `harness/plane_profile.py` over
hand-made event tuples; and every reader gives nothing on an `obs`
without a trace, a window or stages."""
import json
import os

import pytest

from cometbft_tpu.libs import tracing
from harness import catalog, plane_profile, stages

CELLS = ["valset-1k.votes", "qa200.bursts"]
SOURCES = {
    "plane_pack_ms": "program_span",
    "plane_dispatch_ms": "program_span",
    "plane_land_ms": "program_span",
    "plane_collect_ms": "program_span",
    "plane_land_lag_ms": "device_trace",
}
MS = 1_000_000
T0, T1 = 100.0, 120.0  # the window, in seconds
OBS = {"t0": T0, "t1": T1}


def at(ms: float) -> int:
    """ns of a moment `ms` into the window."""
    return int(T0 * 1e9) + int(ms * MS)


def flush(ms: float, fid: int, land_ms=5.2, polls=1, packed=0):
    """The seven records of one fused flush cut `ms` into the window,
    in the order they end."""
    land_args = {"polls": polls, "ready": 1 - packed, "packed": packed}
    if not packed:
        land_args["flush"] = fid
    return [
        ("plane.wait", at(ms - 2.0), 2 * MS, 7, {"deck": 0}),
        ("plane.dispatch", at(ms + 0.2), int(1.75 * MS), 7, {"flush": fid}),
        ("plane.pack", at(ms), 2 * MS, 7,
         {"flush": fid, "rows": 1, "subs": 1, "queued_ms": 2.0}),
        ("plane.wait", at(ms + 2.05), MS // 100, 7, {"deck": 1}),
        ("plane.land", at(ms + 2.1), int(land_ms * MS), 7, land_args),
        ("plane.collect", at(ms + 2.1 + land_ms), MS, 7, {"flush": fid}),
        ("plane.settle", at(ms + 3.1 + land_ms), MS // 10, 7,
         {"flush": fid}),
    ]


BEFORE = flush(-900, 0, land_ms=9.0)  # a warm-up flush: not the window's


@pytest.fixture(params=CELLS)
def readers(request):
    """{name: (entry, reader module)} of one vote cell."""
    return {e["name"]: (e, r) for e, r in
            catalog.Cell(request.param).metrics("per_layer")}


@pytest.fixture
def ring(monkeypatch):
    """Puts synthetic records where the readers look."""
    monkeypatch.setattr(stages, "_SAME_CLOCK", True)

    def put(records, dropped=0):
        monkeypatch.setattr(tracing, "stage_records", lambda: list(records))
        monkeypatch.setattr(tracing, "stages",
                            lambda: [r[:4] for r in records])
        monkeypatch.setattr(tracing, "stages_dropped", lambda: dropped)

    return put


@pytest.mark.parametrize("name", sorted(SOURCES))
def test_each_entry_is_found_by_name_with_its_file(readers, name):
    entry, mod = readers[name]
    with open(os.path.join(catalog.REPO_ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert [e for e in spec["per_layer"] if e["name"] == name] == [entry]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == CELLS
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"]) == ("ms", "lower", SOURCES[name], "verify plane",
                            "vote_p50_ms")
    # the layer is one BENCHMARK.json named before, letter for letter
    assert entry["layer"] in {e["layer"] for e in spec["per_layer"]
                              if e["name"] not in SOURCES}
    # every cell that reports it reports the metric it moves
    moved = next(e for e in spec["end_to_end"]
                 if e["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    for w in spec["workloads"]:
        names = {e["name"] for e, _ in
                 catalog.Cell(w["name"]).metrics("per_layer")}
        assert (name in names) == (w["name"] in CELLS)


def test_stage_medians_of_the_windows_flushes(readers, ring):
    ring(BEFORE + flush(0, 1, land_ms=5.0) + flush(70, 2, land_ms=5.2)
         + flush(140, 3, land_ms=5.6))
    assert readers["plane_pack_ms"][1].read(OBS) == pytest.approx(2.0)
    assert readers["plane_dispatch_ms"][1].read(OBS) == pytest.approx(1.75)
    assert readers["plane_collect_ms"][1].read(OBS) == pytest.approx(1.0)
    assert readers["plane_land_ms"][1].read(OBS) == pytest.approx(5.2)


def test_a_land_that_new_work_cut_short_is_left_out(readers, ring):
    ring(BEFORE + flush(0, 1, land_ms=5.0)
         + flush(70, 2, land_ms=0.3, packed=1)
         + flush(140, 3, land_ms=5.4) + flush(210, 4, land_ms=5.2))
    assert readers["plane_land_ms"][1].read(OBS) == pytest.approx(5.2)
    ring(BEFORE + flush(0, 1, land_ms=0.3, packed=1))  # none chose a flight
    assert readers["plane_land_ms"][1].read(OBS) is None


@pytest.mark.parametrize("name", [n for n, s in sorted(SOURCES.items())
                                  if s == "program_span"])
def test_stage_readers_give_nothing_where_there_is_nothing(
        readers, ring, monkeypatch, name):
    read = readers[name][1].read
    ring(BEFORE + flush(0, 1))
    assert read(OBS) is not None
    assert read({}) is None  # no window to read in
    assert read({"samples": {}, "counters": {}}) is None
    ring(BEFORE)  # no flush started in the window
    assert read(OBS) is None
    # the parent: a host-path record only, no plane.* stage at all
    ring([("votes.settle", at(0), 10 * MS, 1, {})])
    assert read(OBS) is None
    # a land without its args (a program that keeps none)
    ring([r[:4] + ({},) if r[0] == "plane.land" else r
          for r in flush(0, 1)])
    assert (read(OBS) is None) == (name == "plane_land_ms")
    ring(flush(0, 1), dropped=3)  # its oldest record is of the window
    assert read(OBS) is None
    ring(BEFORE + flush(0, 1))
    monkeypatch.setattr(stages, "_SAME_CLOCK", False)  # two clocks
    assert read(OBS) is None
    monkeypatch.setattr(stages, "_SAME_CLOCK", True)
    monkeypatch.delattr(tracing, "stage_records")  # the parent of PR 27
    if name == "plane_land_ms":
        assert read(OBS) is None
    monkeypatch.delattr(tracing, "stages")  # the parent of PR 25
    assert read(OBS) is None


# -- the profile's reduction, on hand-made tuples ---------------------------

WINDOW = (1_000 * MS, 3_000 * MS)


def ev(a_ms: float, b_ms: float, name: str):
    return (int(a_ms * MS), int(b_ms * MS), name)


def cycle(ms: float, ops):
    """A dispatch at `ms`, a land from 2 ms to 7.5 ms after it, and the
    device operations `ops` as (start, end) ms after the dispatch."""
    host = [ev(ms - 0.2, ms + 1.8, "plane.pack"),
            ev(ms, ms + 1.75, "plane.dispatch"),
            ev(ms + 2.0, ms + 7.5, "plane.land"),
            ev(ms + 7.5, ms + 8.5, "plane.collect")]
    return host, [ev(ms + a, ms + b, "%fusion.3") for a, b in ops]


def test_lag_is_the_lands_end_minus_the_last_operations_end():
    # operations from 0.5 to 3.5 ms after the dispatch: the land ends
    # at 7.5, 4 ms after the last of them
    host, dev = cycle(1_100, [(0.5, 1.0), (1.0, 2.9), (2.0, 3.5)])
    assert plane_profile.lags_ns(host, dev, WINDOW) == [4 * MS]


def test_an_operation_still_running_at_the_lands_end_reads_zero():
    host, dev = cycle(1_100, [(0.5, 1.0), (1.0, 9.0)])
    assert plane_profile.lags_ns(host, dev, WINDOW) == [0]


def test_a_land_with_no_operation_since_its_dispatch_is_left_out():
    # the only operations are of the flush before: they started before
    # this land's dispatch did
    host, dev = cycle(1_100, [(-6.0, -4.0), (-3.0, -0.1)])
    assert plane_profile.lags_ns(host, dev, WINDOW) == []
    # an operation that starts after the land's end is not its flight's
    host, dev = cycle(1_100, [(8.0, 9.0)])
    assert plane_profile.lags_ns(host, dev, WINDOW) == []
    # and a land with no dispatch before it has no flight to read
    host = [ev(1_102, 1_107.5, "plane.land")]
    assert plane_profile.lags_ns(host, [ev(1_100, 1_101, "%op")],
                                 WINDOW) == []


def test_events_outside_the_window_are_ignored():
    h0, d0 = cycle(990, [(0.5, 3.5)])      # starts before the window
    h1, d1 = cycle(1_100, [(0.5, 3.5)])    # inside: 4 ms
    h2, d2 = cycle(1_200, [(0.5, 5.5)])    # inside: 2 ms
    h3, d3 = cycle(2_995, [(0.5, 3.5)])    # its land ends after it
    lags = plane_profile.lags_ns(h0 + h1 + h2 + h3, d0 + d1 + d2 + d3,
                                 WINDOW)
    assert lags == [4 * MS, 2 * MS]
    # each land reads the operations since ITS dispatch, not an older one
    h4, d4 = cycle(1_300, [])
    assert plane_profile.lags_ns(h1 + h4, d1 + d4, WINDOW) == [4 * MS]
    # events of other names on the host plane change nothing
    other = [ev(1_101, 1_109, "bench.add_vote"),
             ev(1_099, 1_100, "plane.wait")]
    assert plane_profile.lags_ns(h1 + other, d1, WINDOW) == [4 * MS]


def test_a_device_plane_that_runs_ahead_is_moved_back_by_causality():
    """The device plane's stamps lead the host plane's by 1.5 ms: every
    operation appears 1.5 ms early, the first before its program was
    launched. The lead is bounded by the tightest flight (launch minus
    first operation), and every lag shrinks by it."""
    skew = 1.5
    host, dev = [], []
    # launches 1.2 ms into each dispatch; the device starts 0.3 / 0.2 /
    # 0.4 ms after its launch and works 2 ms
    for ms, latency in ((1_100, 0.3), (1_200, 0.2), (1_300, 0.4)):
        h, _ = cycle(ms, [])
        host += h + [ev(ms + 1.2, ms + 1.5, "PjitFunction(_stamp_rows_core)"),
                     ev(ms + 1.5, ms + 1.9, "PjitFunction(_verify)")]
        a = ms + 1.2 + latency
        dev += [ev(a - skew, a + 1.0 - skew, "%fusion.3"),
                ev(a + 1.0 - skew, a + 2.0 - skew, "%verify")]
    # bounded by the tightest flight: 1.5 less its launch latency of 0.2
    assert plane_profile.lead_ns(host, dev) == int(1.3 * MS)
    # true lags are 7.5 - (1.2 + latency + 2.0); each reads 0.2 more
    want = [int(round((7.5 - 3.2 - lat + 0.2) * MS)) for lat in (0.3, 0.2, 0.4)]
    got = plane_profile.lags_ns(host, dev, WINDOW)
    assert [round(x / MS, 6) for x in got] == [round(x / MS, 6) for x in want]
    # without jax's launch events the dispatch's start is the bound:
    # looser by the 1.2 ms a launch lies into its dispatch
    bare = [e for e in host if not e[2].startswith("PjitFunction(")]
    assert plane_profile.lead_ns(bare, dev) == int(round(0.1 * MS))
    # a launch of another flight's dispatch bounds nothing here
    assert plane_profile.lead_ns(bare + [ev(1_050, 1_051, "PjitFunction(x)")],
                                 dev) == int(round(0.1 * MS))


def test_planes_on_one_clock_are_left_as_they_are():
    host, dev = cycle(1_100, [(1.4, 2.0), (2.0, 3.5)])
    host += [ev(1_101.2, 1_101.5, "PjitFunction(_stamp_rows_core)")]
    assert plane_profile.lead_ns(host, dev) == 0
    assert plane_profile.lags_ns(host, dev, WINDOW) == [4 * MS]
    assert plane_profile.lead_ns([], []) == 0


def _obs_traced():
    return {**OBS, "trace": {"window_s": 1.0, "busy_s": 0.2},
            "trace_window": (T0 + 2.0, T0 + 3.0)}


def test_land_lag_reader_gives_nothing_without_a_trace_or_a_profile(
        readers, monkeypatch, tmp_path):
    read = readers["plane_land_lag_ms"][1].read
    h1, d1 = cycle(1_100, [(0.5, 3.5)])
    h2, d2 = cycle(1_200, [(0.5, 5.5)])
    h3, d3 = cycle(1_300, [(0.5, 6.5)])
    monkeypatch.setattr(plane_profile, "find_profile", lambda: "p.xplane.pb")
    monkeypatch.setattr(plane_profile, "read_events",
                        lambda path: (h1 + h2 + h3, d1 + d2 + d3, WINDOW))
    assert read(_obs_traced()) == pytest.approx(2.0)
    assert read({}) is None and read(OBS) is None  # not a traced run
    assert read({**_obs_traced(), "trace": None}) is None
    assert read({**_obs_traced(), "trace_window": None}) is None
    # the parent: a profile with device operations and no plane.* event
    monkeypatch.setattr(plane_profile, "read_events",
                        lambda path: ([], d1 + d2, WINDOW))
    assert read(_obs_traced()) is None
    # a profile without the benchmark's window span
    monkeypatch.setattr(plane_profile, "read_events",
                        lambda path: (h1, d1, None))
    assert read(_obs_traced()) is None
    monkeypatch.setattr(plane_profile, "find_profile", lambda: None)
    assert read(_obs_traced()) is None


def test_the_finder_takes_the_newest_profile_of_a_harness_directory(
        monkeypatch, tmp_path):
    monkeypatch.setattr(plane_profile.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    assert plane_profile.find_profile() is None
    (tmp_path / "tpu-bft-bench-empty" / "trace").mkdir(parents=True)
    assert plane_profile.find_profile() is None  # traced nothing yet
    paths = []
    for k, run in enumerate(("tpu-bft-bench-old", "tpu-bft-bench-new")):
        d = tmp_path / run / "trace" / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        paths.append(d / "host.xplane.pb")
        paths[-1].write_bytes(b"")
        os.utime(paths[-1], (1_000 + k, 1_000 + k))
    other = tmp_path / "someone-else" / "trace" / "plugins" / "profile" / "x"
    other.mkdir(parents=True)
    (other / "host.xplane.pb").write_bytes(b"")
    assert plane_profile.find_profile() == str(paths[1])


def test_the_profile_reader_on_a_real_capture(tmp_path):
    """A CPU capture with the plane's stage names in it: the host
    events come back with their names on the profile's clock; a CPU
    profile has no device plane, so there is nothing to lag behind."""
    import time

    import jax

    from harness import trace

    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.options())
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with tracing.stage("plane.dispatch", flush=1):
                time.sleep(0.001)
            with tracing.stage("plane.land") as land:
                time.sleep(0.002)
                land.args.update(polls=1, ready=1, packed=0, flush=1)
            with tracing.stage("commit.verify"):
                pass
    finally:
        jax.profiler.stop_trace()
    host, device, window = plane_profile.read_events(
        trace.find_xplane(str(tmp_path)))
    assert sorted(name for _, _, name in host
                  if name.startswith("plane.")) == ["plane.dispatch",
                                                    "plane.land"]
    assert device == [] and window is not None
    assert all(window[0] <= a <= b <= window[1] for a, b, _ in host)
    assert plane_profile.lags_ns(host, device, window) == []
