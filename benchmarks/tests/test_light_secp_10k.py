"""`light-secp-10k.skip`: the cell's files and entries are found by
NAME (no list of `BENCHMARK.json` is pinned, so the next cell does not
break this file), its traffic is ISSUE 35's, each of its eight new
readers reads a synthetic observation and finds nothing in an empty
one, the ECDSA work count is the algorithm's, the seat plan and the
tampered row follow from the seed, and the whole cell walks through on
the CPU (`--rehearse`: 48 validators, ring 3, counts only)."""
import json
import os
import random
import subprocess
import sys

import pytest

from harness import (catalog, fixtures_light, overlap, roofline,
                     roofline_secp256k1)
from reference import ecdsa

CELL = "light-secp-10k.skip"
NEW = ("secp256k1_pack_ms", "secp256k1_fetch_wait_ms",
       "secp256k1_overlap_share", "secp256k1_device_us_per_sig",
       "secp256k1_roofline", "light_trusting_ms", "light_new_set_ms",
       "light_step_host_ms")
SHARED = ("commit_host_ms", "commit_batchfn_ms", "commit_collect_ms",
          "commit_sign_bytes_ms", "commit_device_us_per_sig",
          "commit_device_idle_share")


@pytest.fixture(scope="module")
def cell():
    return catalog.Cell(CELL)


@pytest.fixture(scope="module")
def readers(cell):
    return {e["name"]: (e, r) for e, r in cell.metrics("per_layer")}


def test_the_cells_files_are_found(cell, readers):
    cfg = cell.config
    assert cfg["validators"] == 10000 and cell.chips == 1
    assert cfg["key_type"] == "secp256k1" and cfg["reduced"] == []
    assert (cfg["voting_power"]["low"],
            cfg["voting_power"]["high"]) == (500, 1500)
    assert (cfg["seats_changed"], cfg["light_blocks"]) == (1000, 9)
    assert cfg["trust_level"] == [1, 3] and cfg["signing_share"] == 1.0
    assert cfg["rehearsal"]["validators"] == 48
    row, = [c for c in cell.spec["configs"] if c["name"] == "light-secp-10k"]
    assert row["source"] == cfg["source"] and row["reduced"] == []
    assert cell.driver.__file__.endswith("drivers/light_skip_closed.py")
    want = {"loop": "closed", "callers": 1, "ring": 8, "tampered": 1,
            "run_seconds": 20, "rehearsal": {"ring": 3}}
    assert {k: cell.traffic[k] for k in want} == want
    assert "verify_light_block_at_height" in cell.traffic["entry"]
    assert "Config().crypto.batch_fn()" in cell.traffic["entry"]
    ends = [e["name"] for e, _ in cell.metrics("end_to_end")]
    assert "commit_p50_ms" in ends and "setup_s" in ends
    for name in NEW:
        entry, reader = readers[name]
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (entry["unit"], entry["better"],
                                  entry["source"], entry["layer"],
                                  entry["moves"])
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "commit_p50_ms"
    for name in SHARED:  # appended to, nothing else of them changed
        assert readers[name][0]["workloads"][-1] == CELL
    assert not [n for n in readers if n.startswith(("ed25519", "sr25519"))]


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_in_an_empty_observation(readers, name):
    reader = readers[name][1]
    assert reader.read({}) is None
    assert reader.read({"samples": {}, "t0": 1.0, "t1": 0.0}) is None
    # traced, but the kernel under the ed25519 kernel's name and no
    # count of its signatures: what the parent's run gives
    assert reader.read({
        "t0": 1.0, "t1": 0.0, "trace_window": (0.0, 4.0),
        "device_kind": "TPU v5 lite", "work": [(1.0, 7860)],
        "trace": {"busy_s": 1.0, "window_s": 4.0,
                  "device_ops": [["%_verify_rows.1", 1.0]]}}) is None


def test_the_device_readers_read_the_ecdsa_kernel_alone(readers):
    work = [(t, 7860) for t in (9.9, 10.5, 11.0, 14.1)]
    obs = {"trace_window": (10.0, 14.0), "device_kind": "TPU v5 lite",
           "work": work, "work_secp256k1": work,
           "trace": {"busy_s": 0.5, "window_s": 4.0, "device_ops": [
               ["%_verify_rows_secp.1", 0.0393], ["%_verify_rows.1", 0.2],
               ["%_verify_rows_sr.1", 0.1], ["%copy", 0.01]]}}
    # two steps completed in the window: 15,720 live signatures
    assert readers["secp256k1_device_us_per_sig"][1].read(obs) == \
        pytest.approx(2.5)
    need = roofline_secp256k1.ecdsa_verify(15720)
    least, bound = roofline.least_seconds("TPU v5 lite", need)
    assert bound == "int32_mac"
    share = readers["secp256k1_roofline"][1].read(obs)
    assert share == pytest.approx(100.0 * least / 0.0393) and 0 < share < 100


def test_the_work_count_is_the_algorithms():
    one = roofline_secp256k1.ecdsa_verify(1)
    mul, sqr = roofline.MUL, roofline.SQR
    decompress = 256 * sqr + 14 * mul
    ladder = 63 * (4 * (6 * mul + 2 * sqr) + 12 * mul)
    assert one["int32_mac"] == (decompress + 14 * 12 * mul + ladder
                                + 32 * 11 * mul + 14 * mul)
    assert one["int32_mac"] == 1286000 and one["bytes"] == 192
    many = roofline_secp256k1.ecdsa_verify(7860)
    assert many == {k: 7860 * v for k, v in one.items()}


@pytest.fixture
def stage_ring():
    """The program's stage ring, emptied, on the drivers' clock."""
    from cometbft_tpu.libs import tracing

    tracing.set_clock(None)
    return tracing


def test_the_stage_readers_read_a_step(readers, stage_ring):
    """Two synthetic steps written through the program's own
    `tracing.stage`: the readers take the medians, the overlap share
    the packs entered with a pass flying, and the step's host time is
    each step minus ITS two checks."""
    import time

    t0 = time.monotonic()
    for pause in (0.002, 0.004):
        with stage_ring.stage("light.step", adjacent=0):
            time.sleep(0.001)
            with stage_ring.stage("light.trusting"):
                for k in range(2):
                    with stage_ring.stage("secp256k1.pack", flying=k,
                                          rows=1024):
                        time.sleep(pause)
                with stage_ring.stage("secp256k1.fetch"):
                    time.sleep(0.001)
            with stage_ring.stage("light.new_set"):
                time.sleep(pause)
    obs = {"t0": t0, "t1": time.monotonic()}
    read = {name: readers[name][1].read(obs) for name in NEW[:3] + NEW[5:]}
    assert 2.0 <= read["secp256k1_pack_ms"] < 4.0  # nearest rank of four
    assert 1.0 <= read["secp256k1_fetch_wait_ms"] < 2.5
    assert read["secp256k1_overlap_share"] == pytest.approx(50, abs=8)
    assert overlap.share_pct(obs, "ed25519.pack") is None
    assert 5.0 <= read["light_trusting_ms"] < 9.0
    assert 2.0 <= read["light_new_set_ms"] < 4.0
    assert 1.0 <= read["light_step_host_ms"] < 2.5


@pytest.mark.parametrize("seed", (0, 1, 2147483999, 2**31 + 77))
def test_the_seats_and_the_tampered_row_follow_from_the_seed(cell, seed):
    cfg = cell.config
    power = cfg["voting_power"]
    plan = fixtures_light.seat_plan(seed, 400, 4, 40, power["low"],
                                    power["high"])
    assert plan == fixtures_light.seat_plan(seed, 400, 4, 40, power["low"],
                                            power["high"])
    assert plan != fixtures_light.seat_plan(seed + 1, 400, 4, 40,
                                            power["low"], power["high"])
    assert all(len(blk) == 400 for blk in plan)
    assert all(power["low"] <= p <= power["high"]
               for blk in plan for _, p in blk)
    for a, b in zip(plan, plan[1:]):  # 40 seats change hands a block
        assert len(set(a) - set(b)) == len(set(b) - set(a)) == 40
    # stand-in keys (an address is a hash of the key bytes): sets in
    # power order, then the row only the second check examines
    def in_order(blk):
        rows = sorted(((s[:1] + s, p) for s, p in blk),
                      key=lambda r: (-r[1], ecdsa.address(r[0])))
        return [k for k, _ in rows], [p for _, p in rows]

    (old_k, old_p), (new_k, new_p) = in_order(plan[0]), in_order(plan[1])
    at = fixtures_light.tamper_at(random.Random(seed), old_k, old_p,
                                  new_k, new_p)
    signed = [b""] * 400
    trusting = ecdsa.trusting_rows(
        {ecdsa.address(k): (k, p) for k, p in zip(old_k, old_p)},
        [ecdsa.address(k) for k in new_k], signed)[0]
    light = ecdsa.light_rows(new_p, signed)[0]
    assert trusting[-1] < at < light[-1]
    assert cell.driver._steps(8, {3}) == [
        (0, 1), (1, 2), (2, 3), (2, 4), (4, 5), (5, 6), (6, 7), (7, 8)]


def test_signed_rows_pass_the_reference_and_the_program():
    """The fixtures' deterministic low-S signatures: the same bytes
    twice, accepted by the plain reference (both voices) and by the
    program's host verifier, refused flipped."""
    from cometbft_tpu.crypto import secp256k1_ref
    from harness import fixtures

    seeds = fixtures.key_seeds(5, "t", 4)
    pubs = fixtures_light.pubs_of(seeds)
    for seed32, pub in zip(seeds, pubs):
        msg = b"m" * 120 + seed32[:2]
        sig = fixtures_light.sign(fixtures_light._key(seed32), msg)
        assert sig == fixtures_light.sign(fixtures_light._key(seed32), msg)
        assert ecdsa.verify_sig(pub, msg, sig)
        assert ecdsa.verify_sig_ints(pub, msg, sig)
        assert secp256k1_ref.verify(pub, msg, sig)
        assert not ecdsa.verify_sig(pub, msg, fixtures.flip(sig))
        assert not secp256k1_ref.verify(pub, msg, fixtures.flip(sig))


def test_the_cell_rehearses_on_the_cpu():
    """The XLA ECDSA kernel takes some 2.5 s a 64-row pass on a CPU: a
    step is two, so the window is 30 s for a lap and a half."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, os.path.join(catalog.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147484123", "--seconds", "30",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=1500, env=env,
        cwd=catalog.REPO_ROOT)
    assert run.returncode == 0, run.stderr[-2000:]
    head = json.loads(run.stdout.strip().splitlines()[-1])
    assert head["rehearsal"] and head["correct"] and head["failed"] == 0
    c = head["counters"]
    assert c["breaker_faults"] == 0 and c["compiles_in_window"] == 0
    rows = c["batch_rows_first_lap"]  # a trusting and a new-set check
    assert len(rows) == 6 and all(a < b for a, b in zip(rows[::2],
                                                        rows[1::2]))
    assert head["attempted"] >= 3 and head["samples"]["commit_ms"] >= 3
    stage_readers = (set(NEW) | set(SHARED)) - {
        "secp256k1_device_us_per_sig", "secp256k1_roofline",
        "commit_device_us_per_sig", "commit_device_idle_share"}
    assert stage_readers <= set(head["metrics_readable"])
