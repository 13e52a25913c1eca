"""`mixed-10k.commit`: the cell's files are found by name, its traffic
is ISSUE 33's, its six new readers find nothing in an empty
observation, the key types split 5,000 / 5,000 with the tampered row an
sr25519 one inside the examined prefix at every seed tried, the
sr25519 work count is the algorithm's, and the whole cell walks through
on the CPU (`--rehearse`: 48 validators, ring 3, counts only)."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from harness import catalog, fixtures_mixed, roofline, roofline_sr25519

CELL = "mixed-10k.commit"
NEW = ("sr25519_pack_ms", "sr25519_challenge_ms", "sr25519_fetch_wait_ms",
       "sr25519_overlap_share", "sr25519_device_us_per_sig",
       "sr25519_roofline")
COMMIT = ("commit_host_ms", "commit_batchfn_ms", "commit_device_us_per_sig",
          "commit_device_idle_share", "commit_collect_ms",
          "commit_sign_bytes_ms", "commit_pack_ms", "commit_dispatch_ms",
          "commit_fetch_wait_ms", "commit_overlap_share")
SEEDS = (0, 1, 2147483999, 2147484123, 2**31 + 77)


@pytest.fixture(scope="module")
def cell():
    return catalog.Cell(CELL)


def test_the_cells_files_are_found(cell):
    cfg = cell.config
    assert cfg["validators"] == 10000 and cell.chips == 1
    assert cfg["key_types"] == {"ed25519": 5000, "sr25519": 5000}
    assert cfg["reduced"] == [] and cfg["chain_id"] == "bench-mixed-10k"
    assert (cfg["voting_power"], cfg["signing_share"]) == (1000, 1.0)
    assert cfg["rehearsal"]["validators"] == 48
    assert cell.driver.__file__.endswith("drivers/commit_closed_mixed.py")
    want = {"loop": "closed", "callers": 1, "ring": 8, "tampered": 1,
            "tampered_key_type": "sr25519", "run_seconds": 20,
            "rehearsal": {"ring": 3}}
    assert {k: cell.traffic[k] for k in want} == want
    assert "verify_commit_light" in cell.traffic["entry"]
    assert "Config().crypto.batch_fn()" in cell.traffic["entry"]
    assert [e["name"] for e, _ in cell.metrics("end_to_end")] == [
        "commit_p50_ms", "setup_s"]
    layer = {e["name"]: (e, r) for e, r in cell.metrics("per_layer")}
    assert set(NEW) | set(COMMIT) | {"compiles_in_window"} == set(layer)
    for name in NEW:
        entry, reader = layer[name]
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (entry["unit"], entry["better"],
                                  entry["source"], entry["layer"],
                                  entry["moves"])
        assert entry["workloads"] == [CELL]
    for name in COMMIT:  # appended, as PR 27 appended qa200.bursts
        assert layer[name][0]["workloads"] == ["valset-10k.commit", CELL]


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_in_an_empty_observation(cell, name):
    reader = dict((e["name"], r) for e, r in cell.metrics("per_layer"))[name]
    assert reader.read({}) is None
    assert reader.read({"samples": {}, "t0": 1.0, "t1": 0.0}) is None
    # traced, but no operation of the sr25519 kernel and no count of
    # its signatures: what the parent's run gives
    assert reader.read({
        "t0": 1.0, "t1": 0.0, "trace_window": (0.0, 4.0),
        "device_kind": "TPU v5 lite", "work": [(1.0, 6667)],
        "trace": {"busy_s": 1.0, "window_s": 4.0,
                  "device_ops": [["%_verify_rows.1", 1.0]]}}) is None


def test_the_device_readers_read_the_sr25519_kernel_alone(cell):
    readers = dict((e["name"], r) for e, r in cell.metrics("per_layer"))
    obs = {"trace_window": (10.0, 14.0), "device_kind": "TPU v5 lite",
           "work": [(t, 6667) for t in (9.9, 10.5, 11.0, 14.1)],
           "work_sr25519": [(t, 3300) for t in (9.9, 10.5, 11.0, 14.1)],
           "trace": {"busy_s": 0.5, "window_s": 4.0, "device_ops": [
               ["%_verify_rows.1", 0.2], ["%_verify_rows_sr.1", 0.0165],
               ["%copy", 0.01]]}}
    # two calls completed in the window: 6,600 live sr25519 signatures
    assert readers["sr25519_device_us_per_sig"].read(obs) == \
        pytest.approx(2.5)
    need = roofline_sr25519.sr25519_verify(6600)
    least, bound = roofline.least_seconds("TPU v5 lite", need)
    assert bound == "int32_mac"
    assert readers["sr25519_roofline"].read(obs) == \
        pytest.approx(100.0 * least / 0.0165)
    assert 0 < readers["sr25519_roofline"].read(obs) < 100


def test_the_work_count_is_the_algorithms():
    one = roofline_sr25519.sr25519_verify(1)
    mul, sqr = roofline.MUL, roofline.SQR
    decode = 258 * sqr + 23 * mul
    ladder = 63 * (4 * (4 * sqr + 4 * mul) + 9 * mul)
    assert one["int32_mac"] == (2 * decode + 14 * 9 * mul + ladder
                                + 32 * 7 * mul + 13 * mul)
    assert one["bytes"] == 172
    many = roofline_sr25519.sr25519_verify(3333)
    assert many == {k: 3333 * v for k, v in one.items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_split_and_the_tampered_row(cell, seed):
    """5,000 keys of each type dealt from the seed; the one tampered
    commit carries its flipped signature on an sr25519 row inside the
    examined prefix (6,667 rows of 10,000 equal powers)."""
    rows = fixtures_mixed.key_rows(seed, cell.config["key_types"])
    types = [kt for kt, _ in rows]
    assert len(rows) == 10000 and types.count("sr25519") == 5000
    assert types.count("ed25519") == 5000
    assert fixtures_mixed.key_rows(seed, cell.config["key_types"]) == rows
    assert fixtures_mixed.key_rows(seed + 1, cell.config["key_types"]) \
        != rows
    # the set's own order is by address: any fixed permutation of the
    # dealt rows stands for it here (the real one is built in the
    # rehearsal below, with the 5,000 sr25519 public keys it costs)
    ctx = SimpleNamespace(traffic=cell.traffic, seed=seed)
    order = sorted(range(10000), key=lambda i: rows[i][1])
    in_set = [types[i] for i in order]
    plan = cell.driver._ring_plan(ctx, in_set)
    assert len(plan) == 8 and len({h for h, _ in plan}) == 8
    bad = [i for _, i in plan if i is not None]
    assert len(bad) == 1 and in_set[bad[0]] == "sr25519"
    assert 0 <= bad[0] < 6666 and cell.driver._examined(10000) == 6667
    assert cell.driver._ring_plan(ctx, in_set) == plan
    sr_live = in_set[:6667].count("sr25519")
    assert 3072 < sr_live <= 4096  # 4 chunks of 1,024 of each type
    assert 3072 < 6667 - sr_live <= 4096


def test_signed_rows_pass_the_reference_and_the_program():
    """The bulk signer's signatures (fixed-base R, native challenges)
    are schnorrkel's: the plain reference and the program's host
    verifier both accept them, and refuse them flipped."""
    from cometbft_tpu.crypto import sr25519_ref
    from harness import fixtures
    from reference import schnorrkel

    rows = [("sr25519", s) for s in fixtures.key_seeds(5, "t", 6)]
    secrets = [fixtures_mixed.sr_secret(s) for _, s in rows]
    pubs = [fixtures_mixed.pub_of(r) for r in rows]
    msgs = [b"m" * (100 + i % 3) for i in range(6)]  # three lengths
    sigs = fixtures_mixed.sign_sr25519(secrets, pubs, msgs)
    for p, m, s in zip(pubs, msgs, sigs):
        assert schnorrkel.verify(p, m, s) and sr25519_ref.verify(p, m, s)
        assert not schnorrkel.verify(p, m, fixtures.flip(s))
        assert not sr25519_ref.verify(p, m, fixtures.flip(s))


def test_the_cell_rehearses_on_the_cpu():
    """The sr25519 kernel runs interpreted here: its one 128-row tile
    compiles for a minute and a half where `.jax_cache` is cold."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, os.path.join(catalog.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147484123", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=1500, env=env,
        cwd=catalog.REPO_ROOT)
    assert run.returncode == 0, run.stderr[-2000:]
    head = json.loads(run.stdout.strip().splitlines()[-1])
    assert head["rehearsal"] and head["correct"] and head["failed"] == 0
    c = head["counters"]
    # 33 of 48 equal powers are examined; the set's order decides how
    # many of them hold which key type
    assert c["signatures_per_call"] == 33 and c["breaker_faults"] == 0
    assert 0 < c["sr25519_signatures_per_call"] < 33
    assert head["attempted"] >= 1 and head["samples"]["commit_ms"] >= 1
    stage_readers = set(NEW[:4]) | set(COMMIT) - {
        "commit_device_us_per_sig", "commit_device_idle_share"}
    assert stage_readers <= set(head["metrics_readable"])
