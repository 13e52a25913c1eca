"""The reader of `commit_overlap_share` over a synthetic stage ring:
the share of the window's `ed25519.pack` time entered with a chunk
flying, nothing from records without `flying`, nothing when the ring
dropped records of the window; and BENCHMARK.json's entry finds this
reader."""
import pytest

from cometbft_tpu.libs import tracing
from harness import catalog, stages

MS = 1_000_000
T0, T1 = 100.0, 120.0  # the window, in seconds
OBS = {"t0": T0, "t1": T1}
CELL = "valset-10k.commit"


def at(ms: float) -> int:
    """ns of a moment `ms` into the window."""
    return int(T0 * 1e9) + int(ms * MS)


def pack(ms: float, dur_ms: float, chunk: int, flying=None):
    args = {"rows": 2048, "padded": 2048}
    if flying is not None:
        args.update(chunk=chunk, chunks=4, flying=flying)
    return ("ed25519.pack", at(ms), int(dur_ms * MS), 1, args)


def call(ms: float, flying=(0, 1, 1, 2)):
    """One batch_fn call of four chunks: packs of 6, 4, 4, 4 ms, a
    dispatch after each, one fetch."""
    recs, t = [], ms
    for k, (dur, f) in enumerate(zip((6, 4, 4, 4), flying)):
        recs.append(pack(t, dur, k, f))
        recs.append(("ed25519.dispatch", at(t + dur), MS // 2, 1, {}))
        t += dur + 1
    recs.append(("ed25519.fetch", at(t), 5 * MS, 1, {}))
    return recs


BEFORE = [pack(-900, 15, 0, 0)]  # a warm-up call: not the window's
RING = BEFORE + call(0) + call(70) + [pack(20_001, 4, 1, 1)]


@pytest.fixture
def reader():
    found = {e["name"]: (e, r) for e, r in
             catalog.Cell(CELL).metrics("per_layer")}
    return found["commit_overlap_share"]


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
    (RING, 100.0 * 12 / 18),                  # 3 of 4 packs overlap
    (BEFORE + call(0, (0, 0, 0, 0)), 0.0),    # the device ran dry: 0
    (BEFORE + call(0)[:1], 0.0),              # one-chunk calls
    (BEFORE + call(0, (0, 1, 0, 1)), 100.0 * 8 / 18),
    (BEFORE + [pack(5, 4, 1, 2)], 100.0),
], ids=["four-chunk-calls", "in-turn", "one-chunk", "mixed",
        "all-overlapped"])
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
    ring([("commit.collect", at(0), 5 * MS, 1, {})])  # other stages only
    assert reader[1].read(OBS) is None
    ring(RING)
    monkeypatch.delattr(tracing, "stage_records")  # the parent of PR 27
    assert reader[1].read(OBS) is None
    monkeypatch.delattr(tracing, "stages")  # the parent of PR 25
    assert reader[1].read(OBS) is None


def test_none_when_the_ring_dropped_records_of_the_window(reader, ring,
                                                          monkeypatch):
    ring(RING, dropped=7)  # still holds a record from before t0
    assert reader[1].read(OBS) == pytest.approx(100.0 * 12 / 18)
    ring(RING[1:], dropped=7)  # its oldest record is of the window
    assert reader[1].read(OBS) is None
    ring([], dropped=1)
    assert reader[1].read(OBS) is None
    ring(RING)
    monkeypatch.setattr(stages, "_SAME_CLOCK", False)  # two clocks
    assert reader[1].read(OBS) is None


def test_the_entry_finds_this_reader_and_the_program_records_the_arg(
        reader, monkeypatch):
    entry, mod = reader
    assert entry["workloads"] == [CELL]
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"]) == ("%", "higher", "program_span",
                            "crypto batch + host pack", "commit_p50_ms")
    others = {e["name"] for cell in ("valset-1k.replay", "valset-1k.votes",
                                     "qa200.bursts")
              for e, _ in catalog.Cell(cell).metrics("per_layer")}
    assert "commit_overlap_share" not in others
    # the real ring: device_batch_fn enters ed25519.pack with the arg
    # (three chunks of four rows; the kernel stood in for)
    import jax.numpy as jnp

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.types import validation

    privs = [PrivKey.generate(bytes([k + 1]) * 32) for k in range(10)]
    msgs = [b"row-%d" % k for k in range(10)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    monkeypatch.setattr(validation, "COMMIT_CHUNK_ROWS", 4)
    monkeypatch.setattr(
        ek, "verify_kernel",
        lambda ay, asign, ry, rsign, sdig, hdig, ok: jnp.asarray(ok))
    tracing.set_clock(None)  # an empty stage ring
    fn = validation.device_batch_fn(use_pallas=False)
    assert fn([p.pub_key() for p in privs], msgs, sigs).all()
    assert [(r[4]["chunk"], r[4]["chunks"], r[4][mod.ARG] >= 0)
            for r in tracing.stage_records() if r[0] == mod.STAGE] == [
        (0, 3, True), (1, 3, True), (2, 3, True)]
