"""The `pos175-churn.replay` cell's four new readers over a stage ring
written through the program's own `tracing.stage`: the median segment
and the share closed by powers (`catchup.scan`'s `blocks` and
`closed_by`), the apply path's validate and updateState medians
(`exec.apply`'s args); nothing where no record carries the arg (a
parent's ring) or the ring dropped records of the window. The new
entries are found by NAME in this cell alone, and the cell is IN every
shared replay list it was appended to (no list is pinned)."""
import time

import pytest

from harness import catalog

CELL = "pos175-churn.replay"
NEW = ("catchup_segment_blocks", "catchup_power_boundary_share",
       "exec_validate_ms", "exec_update_state_ms")
SHARED = ("catchup_verify_share", "stream_stamped_share",
          "replay_device_us_per_sig", "verify_roofline",
          "replay_device_idle_share", "table_builds",
          "catchup_warm_ahead_share", "catchup_bookkeeping_share",
          "catchup_unattributed_share", "stream_pack_ms",
          "stream_collect_ms", "valset_hash_computes",
          "stream_overlap_share")


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


def _steps(ring, args=True):
    """Five one-block steps and one of two blocks, each with its scan
    and one apply record a block."""
    start = time.monotonic()
    for k, (blocks, closed) in enumerate(((2, "keys"), (1, "powers"),
                                          (1, "powers"), (1, "keys"),
                                          (1, "powers"), (1, "buffer"))):
        with ring.stage("catchup.scan") as st:
            if args:
                st.args.update(blocks=blocks, closed_by=closed)
        for b in range(blocks):
            with ring.stage("exec.apply", height=k + b) as st:
                if args:
                    st.args.update(validate_ms=6.0 + k, validations=2,
                                   finalize_ms=0.1, update_state_ms=1.0 + k,
                                   save_ms=2.0)
    return {"t0": start, "t1": time.monotonic()}


def test_the_readers_read_a_stage_ring(readers, stage_ring):
    obs = _steps(stage_ring)
    read = {name: readers[name][1].read(obs) for name in NEW}
    assert read["catchup_segment_blocks"] == 1
    assert read["catchup_power_boundary_share"] == pytest.approx(50.0)
    # seven apply records: validate 6, 6, 7, 8, 9, 10, 11 ms
    assert read["exec_validate_ms"] == 8.0
    assert read["exec_update_state_ms"] == 3.0


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_without_their_args(readers, stage_ring,
                                                     monkeypatch, name):
    reader = readers[name][1]
    assert reader.read({}) is None
    # a parent's ring: the stages without the args
    assert reader.read(_steps(stage_ring, args=False)) is None
    # a ring that dropped records of the window reads nothing at all
    stage_ring.set_clock(None)  # nothing left that ended before it
    obs = _steps(stage_ring)
    monkeypatch.setattr(stage_ring, "stages_dropped", lambda: 3)
    assert reader.read(obs) is None


@pytest.mark.parametrize("name", NEW)
def test_the_entry_is_found_by_name(readers, name):
    entry, reader = readers[name]
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
            reader.MOVES) == (entry["unit"], entry["better"],
                              entry["source"], entry["layer"],
                              entry["moves"])
    assert entry["moves"] == "replay_rate"
    assert entry["workloads"] == [CELL]


def test_the_cell_is_in_every_shared_replay_list(readers):
    spec = catalog.Cell(CELL).spec
    assert all(name in readers for name in SHARED)
    assert all(CELL in readers[name][0]["workloads"] for name in SHARED)
    e2e = {e["name"]: e for e, _ in catalog.Cell(CELL).metrics("end_to_end")}
    assert set(e2e) == {"replay_rate", "setup_s"}
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell[0]["chips"] == 1 and cell[0]["config"] == "pos175-churn"
