"""The reader of `plane_land_woken_share` over a synthetic stage ring:
of the window's lands that chose a flight and had to wait for it, the
share whose sleep the lander ended (`woke` = 1); nothing from records
without the arg (a parent of PR 38), nothing when the ring dropped
records of the window; BENCHMARK.json's entry, looked up by NAME (no
list is pinned), finds this reader in both vote cells, and the
program's lands record the arg."""
import json
import os
import threading
import time
from types import SimpleNamespace

import pytest

from cometbft_tpu.libs import tracing
from harness import catalog, stages

NAME = "plane_land_woken_share"
CELLS = ["valset-1k.votes", "qa200.bursts"]
MS = 1_000_000
T0, T1 = 100.0, 120.0  # the window, in seconds
OBS = {"t0": T0, "t1": T1}


def at(ms: float) -> int:
    """ns of a moment `ms` into the window."""
    return int(T0 * 1e9) + int(ms * MS)


def land(ms: float, woke=None, polls=2, packed=0, fid=1, name="plane.land"):
    args = {"polls": polls, "ready": 1 - packed, "packed": packed}
    if woke is not None:
        args["woke"] = woke
    if not packed:
        args["flush"] = fid
    return (name, at(ms), int((1.2 if woke else 5.4) * MS), 7, args)


BEFORE = [land(-900, 0)]  # a warm-up flush: not the window's
LATER = [land(20_001, 0)]  # starts after the window


@pytest.fixture(params=CELLS)
def reader(request):
    found = {e["name"]: (e, r) for e, r in
             catalog.Cell(request.param).metrics("per_layer")}
    return found[NAME]


@pytest.fixture
def ring(monkeypatch):
    """Puts synthetic records where the reader looks."""
    monkeypatch.setattr(stages, "_SAME_CLOCK", True)

    def put(records, dropped=0):
        monkeypatch.setattr(tracing, "stage_records", lambda: list(records))
        monkeypatch.setattr(tracing, "stages",
                            lambda: [r[:4] for r in records])
        monkeypatch.setattr(tracing, "stages_dropped", lambda: dropped)

    return put


@pytest.mark.parametrize("records,want", [
    (BEFORE + [land(0, 1), land(14, 1), land(28, 1)] + LATER, 100.0),
    (BEFORE + [land(0, 0), land(14, 0)], 0.0),  # every sleep ran out
    (BEFORE + [land(0, 1), land(14, 0), land(28, 1), land(42, 1)], 75.0),
    # a flight ready at the entry probe never slept: not a wait
    (BEFORE + [land(0, 1), land(14, 0, polls=1)], 100.0),
    # a land new work cut short chose no flight, whatever woke it
    (BEFORE + [land(0, 1), land(14, 1, packed=1), land(28, 0, packed=1)],
     100.0),
    # a slice and then the mark: still woken
    (BEFORE + [land(0, 1, polls=3), land(14, 0, polls=3)], 50.0),
    # a record without the arg is left out
    (BEFORE + [land(0, 1), land(14, None)], 100.0),
    # another stage's records are not this metric's, whatever they carry
    (BEFORE + [land(0, 1), land(14, 0, name="plane.collect")], 100.0),
], ids=["all-woken", "none", "one-slept-out", "ready-at-entry-apart",
        "packed-apart", "a-slice-then-the-mark", "one-without-the-arg",
        "other-stages-apart"])
def test_share_of_waiting_lands_the_lander_woke(reader, ring, records, want):
    ring(records)
    assert reader[1].read(OBS) == pytest.approx(want)
    assert reader[1].read({}) is None  # no window to read in


def test_none_not_zero_where_no_land_carries_woke(reader, ring, monkeypatch):
    ring(BEFORE + [land(0, None), land(14, None)])  # the parent's lands
    assert reader[1].read(OBS) is None
    ring(BEFORE + LATER)  # no land started in the window
    assert reader[1].read(OBS) is None
    ring(BEFORE + [land(0, 0, polls=1), land(14, 1, packed=1)])  # no wait
    assert reader[1].read(OBS) is None
    ring([("votes.settle", at(0), 10 * MS, 1, {})])  # a host-path plane
    assert reader[1].read(OBS) is None
    assert reader[1].read({"samples": {}, "counters": {}}) is None
    ring([land(0, 1)])
    monkeypatch.delattr(tracing, "stage_records")  # the parent of PR 27
    assert reader[1].read(OBS) is None
    monkeypatch.delattr(tracing, "stages")  # the parent of PR 25
    assert reader[1].read(OBS) is None


def test_none_when_the_ring_dropped_records_of_the_window(reader, ring,
                                                          monkeypatch):
    ring(BEFORE + [land(0, 1)], dropped=7)  # still holds one from before t0
    assert reader[1].read(OBS) == pytest.approx(100.0)
    ring([land(0, 1)], dropped=7)  # its oldest record is of the window
    assert reader[1].read(OBS) is None
    ring([], dropped=1)
    assert reader[1].read(OBS) is None
    ring(BEFORE + [land(0, 1)])
    monkeypatch.setattr(stages, "_SAME_CLOCK", False)  # two clocks
    assert reader[1].read(OBS) is None


def test_the_entry_is_found_by_name(reader):
    entry, mod = reader
    with open(os.path.join(catalog.REPO_ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert [e for e in spec["per_layer"] if e["name"] == NAME] == [entry]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert set(CELLS) <= set(entry["workloads"])
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"]) == ("%", "higher", "program_span", "verify plane",
                            "vote_p50_ms")
    # the layer is one BENCHMARK.json already names, letter for letter
    assert entry["layer"] in {e["layer"] for e in spec["per_layer"]
                              if e["name"] != NAME}
    # every cell that lists it reports the metric it moves, and the
    # cell's land stage has its other readers beside it
    for w in entry["workloads"]:
        cell = catalog.Cell(w)
        assert entry["moves"] in {e["name"] for e, _ in
                                  cell.metrics("end_to_end")}
        assert {"plane_land_ms", "plane_land_lag_ms"} <= {
            e["name"] for e, _ in cell.metrics("per_layer")}


def test_the_programs_lands_record_the_arg(reader, monkeypatch):
    """The real ring: a plane whose flushes fly (verifyplane.fused stood
    in for; the fake device is done 1 ms after a land's first probe) leaves
    `plane.land` records that carry `woke`, and the reader reads the
    share off them; a host-path plane leaves no land and reads nothing."""
    from cometbft_tpu.crypto import ed25519_ref as ed
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import VerifyPlane
    from cometbft_tpu.verifyplane import fused as fz

    mod = reader[1]
    flights = []

    def plan_fused(batch, **_):
        return SimpleNamespace(
            rows=[r for sub in batch for r in sub.rows], drain_first=False,
            stamped=True, delta_bytes=0, util=0.25, mesh=None, n_dev=1,
            devs=(0,), warm=True, done=threading.Event(), probed=False)

    def plan_ready(plan):
        if not plan.probed:
            plan.probed = True
            threading.Timer(0.001, plan.done.set).start()
        return plan.done.is_set()

    def collect_fused(plan):
        plan.done.set()
        return [ed.verify(p.data, m, s) for p, m, s in plan.rows], {}

    monkeypatch.setattr(fz, "plan_fused", plan_fused)
    monkeypatch.setattr(fz, "dispatch_fused", flights.append)
    monkeypatch.setattr(fz, "collect_fused", collect_fused)
    monkeypatch.setattr(fz, "plan_ready", plan_ready)
    monkeypatch.setattr(fz, "plan_wait", lambda plan: plan.done.wait(30.0))
    monkeypatch.setattr(fz, "plan_h2d_bytes", lambda plan: 80)
    monkeypatch.setattr(stages, "_SAME_CLOCK", True)
    priv = PrivKey.generate(b"\x26" * 32)
    tracing.disable()  # an empty ring on the monotonic clock
    try:
        for use_device in (True, False):
            t0 = time.monotonic()
            p = VerifyPlane(window_ms=0.5, use_device=use_device)
            p.start()
            try:
                for k in range(8):
                    msg = b"land-%d" % k
                    assert p.submit(priv.pub_key(), msg,
                                    priv.sign(msg)).result(30.0) == (True,)
            finally:
                p.stop()
            obs = {"t0": t0, "t1": time.monotonic()}
            lands = [r for r in tracing.stage_records()
                     if r[0] == mod.STAGE and r[1] >= int(t0 * 1e9)]
            if use_device:
                assert len(lands) == len(flights) == 8
                assert all(mod.ARG in r[4] and r[4]["polls"] >= 2
                           for r in lands)
                want = 100.0 * sum(r[4]["woke"] for r in lands) / 8
                assert mod.read(obs) == pytest.approx(want)
                # a 1 ms flight under a 5 ms slice: most lands are woken
                assert want >= 50.0
            else:
                assert lands == [] and mod.read(obs) is None
    finally:
        tracing.disable()
