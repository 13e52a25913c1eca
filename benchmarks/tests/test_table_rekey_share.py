"""The reader of `table_rekey_share` over a stage ring written through
the program's own `tracing.stage_untraced`: the share of the window's
`table.make` records whose `path` is "rekeyed", nothing where no record
carries `path` (a parent's ring, or a window that made no table),
nothing where the ring dropped records of the window; the entry is
found by NAME in `pos175-churn.replay` alone."""
import time

import pytest

from harness import catalog

CELL = "pos175-churn.replay"
NAME = "table_rekey_share"


@pytest.fixture(scope="module")
def entry_and_reader():
    found = {e["name"]: (e, r)
             for e, r in catalog.Cell(CELL).metrics("per_layer")}
    return found[NAME]


@pytest.fixture
def stage_ring():
    """The program's stage ring, emptied, on the drivers' clock."""
    from cometbft_tpu.libs import tracing

    tracing.set_clock(None)
    return tracing


def _tables(ring, paths):
    start = time.monotonic()
    for path in paths:
        with ring.stage_untraced("table.make", n=256) as st:
            st.args["path"] = path
        with ring.stage("catchup.verify"):
            pass
    return start, time.monotonic()


def test_the_reader_reads_a_stage_ring(entry_and_reader, stage_ring):
    t0, t1 = _tables(stage_ring, ["built"] + ["rekeyed"] * 6
                     + ["patched"])
    share = entry_and_reader[1].read({"t0": t0, "t1": t1})
    assert share == pytest.approx(75.0)


def test_the_reader_reads_nothing_without_its_records(entry_and_reader,
                                                      stage_ring,
                                                      monkeypatch):
    reader = entry_and_reader[1]
    assert reader.read({}) is None
    t0 = time.monotonic()
    with stage_ring.stage("table.make", n=256):  # no `path`: a parent
        pass
    with stage_ring.stage("catchup.verify"):
        pass
    assert reader.read({"t0": t0, "t1": time.monotonic()}) is None
    # a ring that dropped records of the window reads nothing at all
    _, t1 = _tables(stage_ring, ["rekeyed"])
    monkeypatch.setattr(stage_ring, "stages_dropped", lambda: 3)
    assert reader.read({"t0": t0, "t1": t1}) is None


def test_the_entry_is_found_by_name(entry_and_reader):
    entry, reader = entry_and_reader
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
            reader.MOVES) == (entry["unit"], entry["better"],
                              entry["source"], entry["layer"],
                              entry["moves"])
    assert entry["layer"] == "table build"
    assert entry["workloads"] == [CELL]
