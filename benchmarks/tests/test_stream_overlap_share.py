"""The reader of `stream_overlap_share` over a synthetic stage ring:
the share of the window's pack time entered with a chunk flying,
nothing from records without `flying`, nothing when the ring dropped
records of the window; and BENCHMARK.json's entry finds this reader."""
import pytest

from cometbft_tpu.libs import tracing
from harness import catalog, stages

MS = 1_000_000
T0, T1 = 100.0, 120.0  # the window, in seconds
OBS = {"t0": T0, "t1": T1}
CELL = "valset-1k.replay"


def at(ms: float) -> int:
    """ns of a moment `ms` into the window."""
    return int(T0 * 1e9) + int(ms * MS)


def pack(ms: float, dur_ms: float, flying=None):
    args = {"jobs": 16, "rows": 16000}
    if flying is not None:
        args["flying"] = flying
    return ("stream.pack", at(ms), int(dur_ms * MS), 1, args)


def call(ms: float, flying=(0, 1, 2, 2)):
    """One verify call of four chunks: packs of 30, 20, 20, 20 ms."""
    durs = (30, 20, 20, 20)
    recs, t = [], ms
    for dur, f in zip(durs, flying):
        recs.append(pack(t, dur, f))
        t += dur + 1
    recs.append(("stream.collect", at(t), 40 * MS, 1, {"jobs": 16}))
    return recs


BEFORE = [pack(-900, 800, 0)]  # set-up's one-chunk call: not the window's
RING = BEFORE + call(0) + call(200) + [pack(20_001, 20, 1)]


@pytest.fixture
def reader():
    found = {e["name"]: (e, r) for e, r in
             catalog.Cell(CELL).metrics("per_layer")}
    return found["stream_overlap_share"]


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
    (RING, 100.0 * 60 / 90),                  # 3 of 4 packs overlap
    (BEFORE + call(0, (0, 0, 0, 0)), 0.0),    # chunks in turn: 0, not None
    (BEFORE + call(0, (0, 1, 2, 2))[:1], 0.0),  # one-chunk calls
    (BEFORE + [pack(5, 30, 2)], 100.0),
], ids=["four-chunk-calls", "in-turn", "one-chunk", "all-overlapped"])
def test_share_of_pack_time_entered_with_a_chunk_flying(reader, ring,
                                                        records, want):
    ring(records)
    assert reader[1].read(OBS) == pytest.approx(want)
    assert reader[1].read({}) is None  # no window to read in


def test_none_not_zero_where_no_pack_carries_flying(reader, ring,
                                                    monkeypatch):
    ring(BEFORE + call(0, (None,) * 4))  # the parent: packs without it
    assert reader[1].read(OBS) is None
    ring(BEFORE)  # no pack started in the window
    assert reader[1].read(OBS) is None
    ring([("catchup.step", at(0), 245 * MS, 1, {})])  # other stages only
    assert reader[1].read(OBS) is None
    ring(RING)
    monkeypatch.delattr(tracing, "stage_records")  # the parent of PR 27
    assert reader[1].read(OBS) is None
    monkeypatch.delattr(tracing, "stages")  # the parent of PR 25
    assert reader[1].read(OBS) is None


def test_none_when_the_ring_dropped_records_of_the_window(reader, ring,
                                                          monkeypatch):
    ring(RING, dropped=7)  # still holds a record from before t0
    assert reader[1].read(OBS) == pytest.approx(100.0 * 60 / 90)
    ring(RING[1:], dropped=7)  # its oldest record is of the window
    assert reader[1].read(OBS) is None
    ring([], dropped=1)
    assert reader[1].read(OBS) is None
    ring(RING)
    monkeypatch.setattr(stages, "_SAME_CLOCK", False)  # two clocks
    assert reader[1].read(OBS) is None


def test_the_entry_finds_this_reader_and_the_program_records_the_arg(
        reader):
    entry, mod = reader
    assert entry["workloads"] == [CELL]
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"]) == ("%", "higher", "program_span",
                            "stream pipeline", "replay_rate")
    others = {e["name"] for cell in ("valset-10k.commit", "valset-1k.votes",
                                     "qa200.bursts")
              for e, _ in catalog.Cell(cell).metrics("per_layer")}
    assert "stream_overlap_share" not in others
    # the real ring: StreamVerifier.verify enters stream.pack with the
    # arg (three chunks of one commit; the device call stood in for)
    import numpy as np

    from cometbft_tpu.blocksync.pipeline import CommitJob, StreamVerifier
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.types.block import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT, Commit, CommitSig)
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    vs = ValidatorSet([
        Validator(PrivKey.generate(bytes([k]) * 32).pub_key(), 1)
        for k in (1, 2, 3)])
    jobs = []
    for h in (1, 2, 3):
        bid = BlockID(bytes([h]) * 32, PartSetHeader(1, b"\x0f" * 32))
        sigs = [CommitSig(BLOCK_ID_FLAG_COMMIT, v.address,
                          Timestamp(1_700_000_000 + h, 0), b"\x01" * 64)
                for v in vs.validators]
        jobs.append(CommitJob(vs, bid, h, Commit(h, 0, bid, sigs), "c"))
    sv = StreamVerifier(max_sigs=3, use_pallas=False, min_device_sigs=1)
    sv._dispatch = lambda pb, p5, counted, cids, thresh, n: (
        np.ones(pb.padded, np.bool_), None, np.ones(n, np.bool_))
    tracing.set_clock(None)  # an empty stage ring
    assert sv.verify(jobs) == [None] * 3
    assert [r[4][mod.ARG] for r in tracing.stage_records()
            if r[0] == mod.STAGE] == [0, 1, 2]
