"""The reader of `valset_hash_computes` over a synthetic stage ring:
nothing without the stage, the count of `valset.hash` records that
start in the window with it, nothing when the ring dropped records of
the window; and BENCHMARK.json's entry finds this reader."""
import pytest

from cometbft_tpu.libs import tracing
from cometbft_tpu.types import validator
from harness import catalog, stages

MS = 1_000_000
T0, T1 = 100.0, 120.0  # the window, in seconds
OBS = {"t0": T0, "t1": T1}
CELL = "valset-1k.replay"


def at(ms: float) -> int:
    """ns of a moment `ms` into the window."""
    return int(T0 * 1e9) + int(ms * MS)


def hashed(ms: float):
    return ("valset.hash", at(ms), 5 * MS, 1)


STEPS = [("catchup.step", at(k * 250), 245 * MS, 1) for k in range(4)]
RING = (
    [hashed(-30_000)]                                # set-up's one root
    + [("catchup.step", at(-900), 800 * MS, 1)]
    + STEPS[:2] + [hashed(510), hashed(515)] + STEPS[2:]
    + [hashed(20_001)])                              # after the window


@pytest.fixture
def reader():
    found = {e["name"]: (e, r) for e, r in
             catalog.Cell(CELL).metrics("per_layer")}
    return found["valset_hash_computes"]


@pytest.fixture
def ring(monkeypatch):
    """Puts synthetic records where the reader looks."""
    monkeypatch.setattr(stages, "_SAME_CLOCK", True)

    def put(records, dropped=0):
        monkeypatch.setattr(tracing, "stages", lambda: list(records))
        monkeypatch.setattr(tracing, "stages_dropped", lambda: dropped)

    return put


@pytest.mark.parametrize("records,want", [
    (RING, 2),                         # the two that start in the window
    (RING[:2] + STEPS, 0),             # one set, hashed in set-up: 0
    ([], 0),                           # no stage closed at all yet
], ids=["two-in-window", "none-in-window", "empty-ring"])
def test_counts_the_records_that_start_in_the_window(reader, ring,
                                                     records, want):
    ring(records)
    got = reader[1].read(OBS)
    assert got == want and got is not None
    assert reader[1].read({}) is None  # no window to read in


def test_none_not_zero_where_the_program_has_no_such_stage(
        reader, ring, monkeypatch):
    ring(STEPS)  # the program's other stages are there
    monkeypatch.delattr(validator, "HASH_STAGE")  # the parent of PR 26
    assert reader[1].read(OBS) is None
    monkeypatch.setattr(validator, "HASH_STAGE", "valset.root",
                        raising=False)  # another stage is not this one
    assert reader[1].read(OBS) is None
    monkeypatch.setattr(validator, "HASH_STAGE", "valset.hash")
    assert reader[1].read(OBS) == 0
    monkeypatch.delattr(tracing, "stages")  # the parent of PR 25
    assert reader[1].read(OBS) is None


def test_none_when_the_ring_dropped_records_of_the_window(reader, ring,
                                                          monkeypatch):
    ring(RING, dropped=7)  # still holds records from before t0
    assert reader[1].read(OBS) == 2
    ring(RING[2:], dropped=7)  # its oldest record is of the window
    assert reader[1].read(OBS) is None
    ring([], dropped=1)
    assert reader[1].read(OBS) is None
    ring(RING)
    monkeypatch.setattr(stages, "_SAME_CLOCK", False)  # two clocks
    assert reader[1].read(OBS) is None


def test_the_entry_finds_this_reader_and_the_program_fires_the_stage(
        reader):
    entry, mod = reader
    assert entry["workloads"] == [CELL]
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"]) == ("count", "lower", "program_span",
                            "stream pipeline", "replay_rate")
    assert mod.STAGE == validator.HASH_STAGE
    others = {e["name"] for cell in ("valset-10k.commit", "valset-1k.votes")
              for e, _ in catalog.Cell(cell).metrics("per_layer")}
    assert "valset_hash_computes" not in others
    # the real ring: a miss records the stage, a hit records nothing
    from cometbft_tpu.crypto.keys import PrivKey

    vs = validator.ValidatorSet([
        validator.Validator(PrivKey.generate(bytes([k]) * 32).pub_key(), 1)
        for k in (1, 2, 3)])
    tracing.set_clock(None)  # an empty stage ring
    assert vs.hash() == vs.hash() == vs.copy().hash()
    assert [r[0] for r in tracing.stages()] == ["valset.hash"]
