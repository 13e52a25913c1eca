"""The reader of `commit_templated_share` over a synthetic stage ring:
the share of the window's `ed25519.pack` rows packed in chunks whose
sign-bytes never were Python objects (`templated` = 1), nothing from
records without the arg, nothing when the ring dropped records of the
window; BENCHMARK.json's entry, looked up by NAME, finds this reader in
both commit cells, and the program's packs record the arg."""
import json
import os

import pytest

from cometbft_tpu.libs import tracing
from harness import catalog, stages

NAME = "commit_templated_share"
MS = 1_000_000
T0, T1 = 100.0, 120.0  # the window, in seconds
OBS = {"t0": T0, "t1": T1}
CELLS = ["valset-10k.commit", "mixed-10k.commit"]


def at(ms: float) -> int:
    """ns of a moment `ms` into the window."""
    return int(T0 * 1e9) + int(ms * MS)


def pack(ms: float, rows: int, templated=None, name="ed25519.pack"):
    args = {"rows": rows, "padded": 1024, "chunk": 0, "chunks": 7,
            "flying": 1}
    if templated is not None:
        args["templated"] = templated
    return (name, at(ms), 2 * MS, 1, args)


def call(ms: float, templated=(1,) * 7):
    """One batch_fn call of 6,667 rows: six chunks of 1,024 and a tail
    of 523, a dispatch after each, one fetch."""
    recs = []
    for k, t in enumerate(templated):
        recs.append(pack(ms + 3 * k, 1024 if k < 6 else 523, t))
        recs.append(("ed25519.dispatch", at(ms + 3 * k + 2), MS // 2, 1, {}))
    recs.append(("ed25519.fetch", at(ms + 22), 5 * MS, 1, {}))
    return recs


BEFORE = [pack(-900, 1024, 0)]  # a warm-up call: not the window's
LATER = [pack(20_001, 1024, 0)]  # starts after the window


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
    (BEFORE + call(0) + call(70) + LATER, 100.0),
    (BEFORE + call(0, (0,) * 7), 0.0),        # every chunk from a list
    (BEFORE + call(0, (1, 1, 1, 1, 1, 1, 0)), 100.0 * 6144 / 6667),
    (BEFORE + call(0, (0, 1, 1, 1, 1, 1, 1)), 100.0 * 5643 / 6667),
    # the sr25519 packs carry the arg too and are not this metric's
    (BEFORE + call(0) + [pack(30, 1024, 0, "sr25519.pack")], 100.0),
    # a record without the arg is left out of both sums
    (BEFORE + call(0, (1, None, 0, 1, 1, 1, 1)),
     100.0 * (4 * 1024 + 523) / (5 * 1024 + 523)),
], ids=["all-templated", "none", "tail-from-a-list", "first-from-a-list",
        "sr25519-packs-apart", "one-without-the-arg"])
def test_share_of_packed_rows_whose_bytes_were_never_objects(
        reader, ring, records, want):
    ring(records)
    assert reader[1].read(OBS) == pytest.approx(want)
    assert reader[1].read({}) is None  # no window to read in


def test_none_not_zero_where_no_pack_carries_templated(reader, ring,
                                                       monkeypatch):
    ring(BEFORE + call(0, (None,) * 7))  # the parent: packs without it
    assert reader[1].read(OBS) is None
    ring(BEFORE + LATER)  # no pack started in the window
    assert reader[1].read(OBS) is None
    ring([("commit.sign_bytes", at(0), 2 * MS, 1, {"rows": 6667})])
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


def test_the_entry_is_found_by_name_in_the_commit_cells_alone(reader):
    entry, mod = reader
    with open(os.path.join(catalog.REPO_ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    entries = [e for e in spec["per_layer"] if e["name"] == NAME]
    assert entries == [entry]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == CELLS
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"]) == ("%", "higher", "program_span",
                            "crypto batch + host pack", "commit_p50_ms")
    # the layer is one BENCHMARK.json already names, letter for letter
    assert entry["layer"] in {e["layer"] for e in spec["per_layer"]
                              if e["name"] != NAME}
    for w in spec["workloads"]:
        names = {e["name"] for e, _ in
                 catalog.Cell(w["name"]).metrics("per_layer")}
        assert (NAME in names) == (w["name"] in CELLS)


def test_the_programs_packs_record_the_arg(monkeypatch):
    """The real ring: the served call's chunks enter `ed25519.pack`
    with `templated` 1 where the batch_fn is handed the commit's lazy
    rows and the native library is there, 0 for a list of bytes (three
    chunks of four rows; the kernel stood in for)."""
    import jax.numpy as jnp

    from cometbft_tpu import native
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.types import canonical, validation
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import Commit, CommitSig
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    mod = catalog.Cell(CELLS[0])  # the reader's own names
    mod = dict((e["name"], r) for e, r in mod.metrics("per_layer"))[NAME]
    privs = [PrivKey.generate(bytes([k + 1]) * 32) for k in range(15)]
    vs = ValidatorSet([Validator(p.pub_key(), 1) for p in privs])
    bid = BlockID(b"\x61" * 32, PartSetHeader(1, b"\x62" * 32))
    commit = Commit(3, 0, bid, [
        CommitSig(2, v.address, Timestamp(1_700_000_000, k), b"\x00" * 64)
        for k, v in enumerate(vs.validators)])
    monkeypatch.setattr(validation, "COMMIT_CHUNK_ROWS", 4)
    monkeypatch.setattr(
        ek, "verify_kernel",
        lambda ay, asign, ry, rsign, sdig, hdig, ok: jnp.asarray(ok))
    seen = []
    inner = validation.device_batch_fn(use_pallas=False)

    def batch_fn(pubs, msgs, sigs):  # as the cells' drivers wrap it
        seen.append(type(msgs))
        return inner(pubs, msgs, sigs)

    tracing.set_clock(None)  # an empty stage ring
    validation.verify_commit_light("templated", vs, bid, 3, commit,
                                   batch_fn)
    assert seen == [canonical.TemplateRows]
    packs = [r[4] for r in tracing.stage_records() if r[0] == mod.STAGE]
    assert [(p["chunk"], p["chunks"], p["rows"]) for p in packs] == [
        (0, 3, 4), (1, 3, 4), (2, 3, 3)]  # 11 of 15 rows are examined
    assert [p[mod.ARG] for p in packs] == [int(native.available())] * 3
    tracing.set_clock(None)
    inner([p.pub_key() for p in privs[:9]], [b"row"] * 9, [b"\x00" * 64] * 9)
    assert [r[4][mod.ARG] for r in tracing.stage_records()
            if r[0] == mod.STAGE] == [0, 0, 0]
