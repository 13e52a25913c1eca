"""The reader of `secp256k1_native_share` over a synthetic stage ring:
the share of the window's `secp256k1.pack` rows packed by the one C call
(`native` = 1), nothing from records without the arg, nothing when the
ring dropped records of the window; BENCHMARK.json's entry, looked up by
NAME (no list is pinned), finds this reader in the light cell, and the
program's packs record the arg."""
import json
import os

import pytest

from cometbft_tpu.libs import tracing
from harness import catalog, stages

NAME = "secp256k1_native_share"
CELL = "light-secp-10k.skip"
MS = 1_000_000
T0, T1 = 100.0, 120.0  # the window, in seconds
OBS = {"t0": T0, "t1": T1}


def at(ms: float) -> int:
    """ns of a moment `ms` into the window."""
    return int(T0 * 1e9) + int(ms * MS)


def pack(ms: float, rows: int, native=None, name="secp256k1.pack"):
    args = {"rows": rows, "padded": 1024, "chunk": 0, "chunks": 3,
            "flying": 1, "templated": 1}
    if native is not None:
        args["native"] = native
    return (name, at(ms), 2 * MS, 1, args)


def call(ms: float, native=(1,) * 3):
    """One batch_fn call of a light step's trusting check: 2,440 rows,
    two chunks of 1,024 and a tail of 392, a dispatch after each, one
    fetch."""
    recs = []
    for k, v in enumerate(native):
        recs.append(pack(ms + 4 * k, 1024 if k < 2 else 392, v))
        recs.append(("secp256k1.dispatch", at(ms + 4 * k + 2), MS // 2, 1,
                     {}))
    recs.append(("secp256k1.fetch", at(ms + 13), 4 * MS, 1, {}))
    return recs


BEFORE = [pack(-900, 1024, 0)]  # a warm-up call: not the window's
LATER = [pack(20_001, 1024, 0)]  # starts after the window


@pytest.fixture(scope="module")
def reader():
    found = {e["name"]: (e, r) for e, r in
             catalog.Cell(CELL).metrics("per_layer")}
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
    (BEFORE + call(0) + call(40) + LATER, 100.0),
    (BEFORE + call(0, (0,) * 3), 0.0),  # every chunk by the Python loop
    (BEFORE + call(0, (1, 1, 0)), 100.0 * 2048 / 2440),
    (BEFORE + call(0, (0, 1, 1)), 100.0 * 1416 / 2440),
    # another key type's packs are not this metric's, whatever they carry
    (BEFORE + call(0) + [pack(30, 1024, 0, "ed25519.pack")], 100.0),
    # a record without the arg is left out of both sums
    (BEFORE + call(0, (1, None, 0)), 100.0 * 1024 / 1416),
], ids=["all-native", "none", "tail-by-the-loop", "first-by-the-loop",
        "ed25519-packs-apart", "one-without-the-arg"])
def test_share_of_packed_rows_the_c_call_packed(reader, ring, records, want):
    ring(records)
    assert reader[1].read(OBS) == pytest.approx(want)
    assert reader[1].read({}) is None  # no window to read in


def test_none_not_zero_where_no_pack_carries_native(reader, ring,
                                                    monkeypatch):
    ring(BEFORE + call(0, (None,) * 3))  # the parent: packs without it
    assert reader[1].read(OBS) is None
    ring(BEFORE + LATER)  # no pack started in the window
    assert reader[1].read(OBS) is None
    ring([("commit.sign_bytes", at(0), 2 * MS, 1, {"rows": 2440})])
    assert reader[1].read(OBS) is None
    ring(call(0))
    monkeypatch.delattr(tracing, "stage_records")  # the parent of PR 27
    assert reader[1].read(OBS) is None
    monkeypatch.delattr(tracing, "stages")  # the parent of PR 25
    assert reader[1].read(OBS) is None


def test_none_when_the_ring_dropped_records_of_the_window(reader, ring,
                                                          monkeypatch):
    ring(BEFORE + call(0), dropped=7)  # still holds one from before t0
    assert reader[1].read(OBS) == pytest.approx(100.0)
    ring(call(0), dropped=7)  # its oldest record is of the window
    assert reader[1].read(OBS) is None
    ring([], dropped=1)
    assert reader[1].read(OBS) is None
    ring(BEFORE + call(0))
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
    assert CELL in entry["workloads"]
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"]) == ("%", "higher", "program_span",
                            "crypto batch + host pack", "commit_p50_ms")
    # the layer is one BENCHMARK.json already names, letter for letter
    assert entry["layer"] in {e["layer"] for e in spec["per_layer"]
                              if e["name"] != NAME}
    # every cell that lists it reports the metric it moves, and the
    # cell's pack stage has its other readers beside it
    for w in entry["workloads"]:
        cell = catalog.Cell(w)
        assert entry["moves"] in {e["name"] for e, _ in
                                  cell.metrics("end_to_end")}
        assert "secp256k1_pack_ms" in {e["name"] for e, _ in
                                       cell.metrics("per_layer")}


@pytest.mark.parametrize("library", ["native", "python-loop"])
def test_the_programs_packs_record_the_arg(reader, monkeypatch, library):
    """The real ring: the served call's secp256k1 chunks leave
    `secp256k1.pack` with `native` 1 where the library loads and every
    key is 33 and every signature 64 bytes long, 0 for a chunk that
    holds a short signature and everywhere without the library (three
    chunks of four rows; the kernel stood in for)."""
    import jax.numpy as jnp

    from cometbft_tpu import native
    from cometbft_tpu.crypto.keys import Secp256k1PrivKey
    from cometbft_tpu.ops import ecdsa_kernel as eck
    from cometbft_tpu.types import validation

    mod = reader[1]
    if library == "python-loop":
        monkeypatch.setattr(native, "_load", lambda: None)
    elif not native.available():
        pytest.skip("no native library here")
    on = int(library == "native")
    monkeypatch.setattr(validation, "COMMIT_CHUNK_ROWS", 4)
    monkeypatch.setattr(
        eck, "verify_kernel",
        lambda qx, qparity, u1dig, u2dig, xr1, xr2, ok: jnp.asarray(ok))
    ks = [Secp256k1PrivKey.generate(bytes([k + 1]) * 32) for k in range(11)]
    msgs = [b"row-%d" % k for k in range(11)]
    sigs = [k.sign(m) for k, m in zip(ks, msgs)]
    fn = validation.device_batch_fn(use_pallas=False)
    tracing.set_clock(None)  # an empty stage ring
    assert fn([k.pub_key() for k in ks], msgs, sigs).all()
    packs = [r[4] for r in tracing.stage_records() if r[0] == mod.STAGE]
    assert [(p["chunk"], p["chunks"], p["rows"]) for p in packs] == [
        (0, 3, 4), (1, 3, 4), (2, 3, 3)]
    assert [p[mod.ARG] for p in packs] == [on] * 3
    sigs[5] = sigs[5][:63]  # the second chunk takes the Python loop
    tracing.set_clock(None)
    got = fn([k.pub_key() for k in ks], msgs, sigs)
    assert got.tolist() == [k != 5 for k in range(11)]
    assert [r[4][mod.ARG] for r in tracing.stage_records()
            if r[0] == mod.STAGE] == [on, 0, on]
