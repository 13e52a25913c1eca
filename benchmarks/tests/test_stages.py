"""The readers of the program's own stages (harness/stages.py and the
ten metrics of PR 25) over a synthetic ring: what each computes, that
a ring which lost the head of the window gives nothing, and that every
entry of BENCHMARK.json finds its reader."""
import json
import os
import time

import pytest

from cometbft_tpu.libs import tracing
from harness import catalog, stages

MS = 1_000_000
T0, T1 = 100.0, 120.0  # the window, in seconds


def at(ms: float) -> int:
    """ns of a moment `ms` into the window."""
    return int(T0 * 1e9) + int(ms * MS)


def step(t: float, refill, scan, jobs, verify, apply, warm, cursor, rest):
    """One catch-up step's records, in the order they close."""
    out, at_ms = [], t
    for name, ms in (("catchup.refill", refill), ("catchup.scan", scan),
                     ("catchup.jobs", jobs), ("catchup.verify", verify)):
        out.append((name, at(at_ms), int(ms * MS), 1))
        at_ms += ms
    out += [("catchup.warm_ahead", at(at_ms + k * warm), int(warm * MS), 1)
            for k in range(2)]
    out.append(("catchup.apply", at(at_ms), int(apply * MS), 1))
    at_ms += apply
    out.append(("catchup.cursor", at(at_ms), int(cursor * MS), 1))
    total = at_ms + cursor + rest - t
    out.append(("catchup.step", at(t), int(total * MS), 1))
    return out


REPLAY = (
    [("catchup.step", at(-900), 800 * MS, 1)]     # before the window
    + step(0, 10, 20, 30, 250, 600, 200, 40, 50)        # 1000 ms
    + [("stream.pack", at(61), 90 * MS, 1),
       ("stream.collect", at(160), 100 * MS, 1)]
    + step(1000, 10, 20, 30, 250, 600, 200, 40, 50)
    + [("stream.pack", at(1061), 70 * MS, 1),
       ("stream.pack", at(1140), 80 * MS, 1),
       ("stream.collect", at(1230), 60 * MS, 1)]
    + [("catchup.step", at(20_500), 700 * MS, 1)])      # after it

COMMIT = [rec for k, (c, s, p, d, f) in enumerate(
    [(9, 7, 24, 1, 26), (11, 8, 25, 2, 27), (10, 9, 23, 3, 25)])
    for rec in (("commit.collect", at(100 * k), c * MS, 1),
                ("commit.sign_bytes", at(100 * k + 12), s * MS, 1),
                ("ed25519.pack", at(100 * k + 22), p * MS, 1),
                ("ed25519.dispatch", at(100 * k + 48), d * MS, 1),
                ("ed25519.fetch", at(100 * k + 52), f * MS, 1),
                ("commit.batch_fn", at(100 * k + 21), 60 * MS, 1),
                ("commit.verify", at(100 * k), 82 * MS, 1))]

EXPECTED = {
    "valset-1k.replay": (REPLAY, {
        "catchup_warm_ahead_share": 40.0,        # 2 * 2 * 200 / 2000
        "catchup_bookkeeping_share": 10.0,       # 2 * 100 / 2000
        "catchup_unattributed_share": 5.0,       # 2 * 50 / 2000
        "stream_pack_ms": 80.0, "stream_collect_ms": 60.0}),
    "valset-10k.commit": (COMMIT, {
        "commit_collect_ms": 10.0, "commit_sign_bytes_ms": 8.0,
        "commit_pack_ms": 24.0, "commit_dispatch_ms": 2.0,
        "commit_fetch_wait_ms": 26.0}),
}
NEW = sorted(n for _, want in EXPECTED.values() for n in want)


@pytest.fixture
def ring(monkeypatch):
    """Puts synthetic records where the readers look."""
    monkeypatch.setattr(stages, "_SAME_CLOCK", True)

    def put(records, dropped=0):
        monkeypatch.setattr(tracing, "stages", lambda: list(records))
        monkeypatch.setattr(tracing, "stages_dropped", lambda: dropped)

    return put


@pytest.mark.parametrize("cell_name", sorted(EXPECTED))
def test_each_reader_over_a_synthetic_ring(cell_name, ring):
    records, want = EXPECTED[cell_name]
    ring(records)
    readers = {e["name"]: r for e, r in
               catalog.Cell(cell_name).metrics("per_layer")}
    obs = {"t0": T0, "t1": T1}
    got = {name: readers[name].read(obs) for name in want}
    assert got == pytest.approx(want)
    # nothing to read: no window, or no such stage in it
    assert all(readers[name].read({}) is None for name in want)
    assert all(readers[name].read({"t0": T1 + 5, "t1": T1 + 9}) is None
               for name in want)


def test_nothing_is_read_from_a_ring_that_lost_the_head_of_the_window(ring):
    obs = {"t0": T0, "t1": T1}
    ring(REPLAY, dropped=3)  # still holds a record from before t0
    assert stages.share_pct(obs, ("catchup.warm_ahead",),
                            "catchup.step") == pytest.approx(40.0)
    ring(REPLAY[1:], dropped=3)  # its oldest record is of the window
    assert stages.in_window(obs) is None
    assert stages.totals_ms(obs) is None
    assert stages.median_ms(obs, "stream.pack") is None
    assert stages.share_pct(obs, ("catchup.warm_ahead",),
                            "catchup.step") is None
    ring([], dropped=1)
    assert stages.in_window(obs) is None


def test_nothing_is_read_across_two_clocks_or_from_an_older_program(
        monkeypatch, ring):
    obs = {"t0": T0, "t1": T1}
    ring(COMMIT)
    monkeypatch.setattr(stages, "_SAME_CLOCK", False)
    assert stages.median_ms(obs, "commit.collect") is None
    monkeypatch.setattr(stages, "_SAME_CLOCK", True)
    assert stages.median_ms(obs, "commit.collect") == 10.0
    monkeypatch.delattr(tracing, "stages")  # the parent of PR 25
    assert stages.median_ms(obs, "commit.collect") is None


def test_the_drivers_clock_is_the_programs_clock_here(monkeypatch):
    monkeypatch.setattr(stages, "_SAME_CLOCK", None)
    tracing.set_clock(None)
    t0 = time.monotonic()
    with tracing.stage("unit.real"):
        pass
    obs = {"t0": t0, "t1": time.monotonic()}
    assert [r[0] for r in stages.in_window(obs)] == ["unit.real"]
    # a virtual clock (the simnet's) is another clock: nothing is read
    monkeypatch.setattr(stages, "_SAME_CLOCK", None)
    tracing.set_clock(lambda: 5)
    try:
        assert stages.in_window(obs) is None
    finally:
        tracing.set_clock(None)


def test_every_new_entry_finds_its_file():
    with open(os.path.join(catalog.REPO_ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    assert [m["name"] for m in spec["per_layer"][-len(NEW):]] == [
        "catchup_warm_ahead_share", "catchup_bookkeeping_share",
        "catchup_unattributed_share", "stream_pack_ms",
        "stream_collect_ms", "commit_collect_ms", "commit_sign_bytes_ms",
        "commit_pack_ms", "commit_dispatch_ms", "commit_fetch_wait_ms"]
    for cell_name, (_, want) in EXPECTED.items():
        found = {e["name"]: r for e, r in
                 catalog.Cell(cell_name).metrics("per_layer")}
        for name in want:
            entry, reader = entries[name], found[name]
            assert entry["workloads"] == [cell_name]
            assert (entry["better"], entry["source"]) == (
                "lower", "program_span")
            assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                    reader.MOVES) == (entry["unit"], entry["better"],
                                      entry["source"], entry["layer"],
                                      entry["moves"])
    # and the third cell reads none of them
    votes = {e["name"] for e, _ in
             catalog.Cell("valset-1k.votes").metrics("per_layer")}
    assert not votes & set(NEW)
