"""The readers of `valset_build_ms` and `valset_columnar_share` over a
stage ring written through the program's own `tracing.stage`, as
`test_light_ed_10k.py` reads its five: the median build and the share of
members in sets built column-wise, nothing where no record carries
`columnar` (a parent's ring), nothing where the ring dropped records of
the window; both entries are found by NAME in `light-ed-10k.bisect`
alone, and the program's constructor records the stage and its arg."""
import time

import pytest

from harness import catalog

CELL = "light-ed-10k.bisect"
NEW = ("valset_build_ms", "valset_columnar_share")


@pytest.fixture(scope="module")
def readers():
    return {e["name"]: (e, r)
            for e, r in catalog.Cell(CELL).metrics("per_layer")}


@pytest.fixture
def stage_ring():
    """The program's stage ring, emptied, on the drivers' clock."""
    from cometbft_tpu.libs import tracing

    tracing.set_clock(None)
    return tracing


def _fetches(ring):
    """Four fetches of a bisection, a set built in each (two of them by
    the loops, one of 300 members), beside a root and a step, which
    these readers do not read; then a build of another size."""
    start = time.monotonic()
    for height, pause, columnar, n in ((8, 0.002, 1, 100),
                                       (4, 0.003, 1, 100),
                                       (2, 0.004, 0, 300),
                                       (6, 0.005, 1, 100)):
        with ring.stage("light.fetch", height=height, pivot=0):
            with ring.stage("valset.build", n=n) as st:
                time.sleep(pause)
                st.args["columnar"] = columnar
            with ring.stage("valset.hash", n=n) as st:
                st.args["native"] = 1
        with ring.stage("light.step", adjacent=0, height=height):
            pass
    with ring.stage("valset.build", n=100) as st:
        time.sleep(0.006)
        st.args["columnar"] = 0
    return start, time.monotonic()


def test_the_readers_read_a_stage_ring(readers, stage_ring):
    t0, t1 = _fetches(stage_ring)
    obs = {"t0": t0, "t1": t1, "op_spans": [(t0, t1)]}
    build = readers["valset_build_ms"][1].read(obs)
    durs = sorted(r[2] / 1e6 for r in stage_ring.stages()
                  if r[0] == "valset.build")
    assert len(durs) == 5 and build == durs[2] >= 4.0  # the middle of five
    share = readers["valset_columnar_share"][1].read(obs)
    assert share == pytest.approx(100.0 * 300 / 700)


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_without_their_records(readers,
                                                        stage_ring,
                                                        monkeypatch, name):
    reader = readers[name][1]
    assert reader.read({}) is None
    assert reader.read({"samples": {}, "t0": 1.0, "t1": 0.0,
                        "op_spans": [(0.5, 0.6)]}) is None
    # a ring with roots and steps but no build
    t0 = time.monotonic()
    with stage_ring.stage("valset.hash", n=100) as st:
        st.args["native"] = 1
    obs = {"t0": t0, "t1": time.monotonic(), "op_spans": []}
    assert reader.read(obs) is None
    # a ring that dropped records of the window reads nothing at all
    _, t1 = _fetches(stage_ring)
    obs = {"t0": t0, "t1": t1, "op_spans": [(t0, t1)]}
    monkeypatch.setattr(stage_ring, "stages_dropped", lambda: 3)
    assert reader.read(obs) is None


def test_a_parents_ring_reads_no_share(readers, stage_ring, monkeypatch):
    """A program without the `columnar` arg: no share, not 0. A
    program without the stage at all (the parent): no build either."""
    from cometbft_tpu.types import validator

    t0 = time.monotonic()
    with stage_ring.stage("valset.build", n=100):
        time.sleep(0.001)
    obs = {"t0": t0, "t1": time.monotonic(), "op_spans": []}
    assert readers["valset_columnar_share"][1].read(obs) is None
    assert readers["valset_build_ms"][1].read(obs) >= 1.0
    monkeypatch.delattr(validator, "BUILD_STAGE")
    assert readers["valset_build_ms"][1].read(obs) is None


@pytest.mark.parametrize("name", NEW)
def test_the_entry_is_found_by_name(readers, name):
    entry, reader = readers[name]
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
            reader.MOVES) == (entry["unit"], entry["better"],
                              entry["source"], entry["layer"],
                              entry["moves"])
    assert entry["layer"] == "validator set"
    assert entry["moves"] == "commit_p50_ms"
    assert entry["workloads"] == [CELL]


def test_the_program_records_the_stage_and_its_arg(stage_ring):
    """A set built by the constructor writes one `valset.build` record
    with `n` and `columnar`; a priority past the int64 limit takes the
    loops and records 0."""
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.types.validator import (BUILD_STAGE, Validator,
                                              ValidatorSet)

    def build(prio):
        ValidatorSet([Validator(PubKey(bytes([i]) * 32), 10, b"",
                                prio if i == 0 else 0) for i in range(5)])
        rec, = [r for r in stage_ring.stage_records()
                if r[0] == BUILD_STAGE]
        stage_ring.set_clock(None)
        return rec[4]

    assert BUILD_STAGE == "valset.build"
    assert build(0) == {"n": 5, "columnar": 1}
    assert build(2**63) == {"n": 5, "columnar": 0}
