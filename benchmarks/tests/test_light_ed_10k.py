"""`light-ed-10k.bisect`: the cell's files and entries are found by NAME
(no list of `BENCHMARK.json` is pinned, so the next cell does not break
this file), its configuration and traffic hold the deployment's numbers,
the seat plan slides and the tampered row follow from the seed, each of
its five new readers reads a synthetic stage ring and finds nothing
where the ring dropped records or the arg is absent, and the whole cell
walks through on the CPU (`--rehearse`: 48 validators, a slide of 10,
counts only)."""
import json
import os
import random
import subprocess
import sys
import time

import pytest

from harness import catalog, fixtures_bisect
from reference import bisection, ecdsa

CELL = "light-ed-10k.bisect"
NEW = ("light_fetch_ms", "light_refused_ms", "valset_hash_ms",
       "valset_native_share", "light_attempts_per_op")
SHARED = ("commit_host_ms", "commit_batchfn_ms", "commit_device_us_per_sig",
          "commit_device_idle_share", "commit_collect_ms",
          "commit_sign_bytes_ms", "commit_pack_ms", "commit_dispatch_ms",
          "commit_fetch_wait_ms", "commit_overlap_share",
          "commit_templated_share", "light_new_set_ms", "light_step_host_ms")
DEVICE = ("commit_device_us_per_sig", "commit_device_idle_share")


@pytest.fixture(scope="module")
def cell():
    return catalog.Cell(CELL)


@pytest.fixture(scope="module")
def readers(cell):
    return {e["name"]: (e, r) for e, r in cell.metrics("per_layer")}


def test_the_cells_files_are_found(cell, readers):
    cfg = cell.config
    assert cfg["validators"] == 10000 and cell.chips == 1
    assert cfg["key_type"] == "ed25519" and cfg["reduced"] == []
    assert (cfg["voting_power"]["low"],
            cfg["voting_power"]["high"]) == (500, 1500)
    assert (cfg["seats_slid"], cfg["light_blocks"],
            cfg["height_gap"]) == (2000, 9, 1000)
    assert cfg["trust_level"] == [1, 3] and cfg["signing_share"] == 1.0
    assert cfg["rehearsal"] == {"validators": 48, "seats_slid": 10}
    row, = [c for c in cell.spec["configs"] if c["name"] == "light-ed-10k"]
    assert row["source"] == cfg["source"] and row["reduced"] == []
    assert cell.driver.__file__.endswith("drivers/light_bisect_closed.py")
    want = {"loop": "closed", "callers": 1, "ring": 4, "target_block": 8,
            "tampered": 1, "tampered_block": 6, "tampered_unknown_to": 4,
            "run_seconds": 20}
    assert {k: cell.traffic[k] for k in want} == want
    assert "verify_light_block_at_height(H8, now)" in cell.traffic["entry"]
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
        assert entry["layer"] in ("light client", "validator set")
    for name in SHARED:  # appended to, nothing else of them changed
        assert CELL in readers[name][0]["workloads"]
    # its median would mix refused and verified checks
    assert "light_trusting_ms" not in readers


@pytest.mark.parametrize("seed", (0, 1, 2147483999, 2**31 + 77))
def test_the_seats_slide_and_the_tampered_row_follow_from_the_seed(seed):
    plan = fixtures_bisect.seats(seed, 2, 400, 9, 80, 500, 1500)
    assert plan == fixtures_bisect.seats(seed, 2, 400, 9, 80, 500, 1500)
    assert plan != fixtures_bisect.seats(seed, 3, 400, 9, 80, 500, 1500)
    assert len(plan) == 400 + 8 * 80 and len(set(plan)) == len(plan)
    blocks = [fixtures_bisect.members(plan, k, 400, 80) for k in range(9)]
    for k, blk in enumerate(blocks):  # k blocks on, 400 - 80 k remain
        assert len(blk) == 400
        assert len(set(blocks[0]) & set(blk)) == max(0, 400 - 80 * k)
        assert all(500 <= p <= 1500 for _, p in blk)
    # stand-in keys, sets in power order: the row only the new-set
    # check can see, of a seat the H4 set does not hold
    order = [sorted(blk, key=lambda s: (-s[1], s[0])) for blk in blocks]
    old, new = order[4], order[6]
    at = fixtures_bisect.tamper_at(random.Random(seed), [s for s, _ in old],
                                   [s for s, _ in new],
                                   [p for _, p in new])
    assert new[at] not in set(old)
    light = ecdsa.light_rows([p for _, p in new], [b""] * 400)[0]
    assert at in light


def test_the_reference_roots_a_set_as_the_program(cell):
    """`bisection.validators_root` is written out with hashlib; the
    program's root (the one C call) is the same bytes."""
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    rnd = random.Random(7)
    for n in (1, 2, 3, 5, 8, 100):
        vs = ValidatorSet([Validator(PubKey(rnd.randbytes(32)),
                                     rnd.randint(500, 1500))
                           for _ in range(n)])
        assert bisection.validators_root(
            [v.pub_key.data for v in vs.validators],
            [v.voting_power for v in vs.validators]) == vs.hash()
    assert bisection.address(b"\x07" * 32) == PubKey(b"\x07" * 32).address()


@pytest.fixture
def stage_ring():
    """The program's stage ring, emptied, on the drivers' clock."""
    from cometbft_tpu.libs import tracing

    tracing.set_clock(None)
    return tracing


def _two_operations(ring):
    """Two synthetic operations written through the program's own
    `tracing.stage`: each two fetches (a root each), a refused trusting
    check, then a verified step (two checks) and another."""
    spans = []
    for pause in (0.002, 0.004):
        start = time.monotonic()
        for height in (8, 4):
            with ring.stage("light.fetch", height=height, pivot=0):
                with ring.stage("valset.hash", n=100) as st:
                    time.sleep(pause)
                    st.args["native"] = 1
        with ring.stage("light.step", adjacent=0, height=8):
            with ring.stage("light.trusting", height=8) as st:
                time.sleep(pause)
                st.args["refused"] = 1
        for _ in range(2):
            with ring.stage("light.step", adjacent=0, height=4):
                with ring.stage("light.trusting", height=4) as st:
                    time.sleep(0.001)
                    st.args["refused"] = 0
                with ring.stage("light.new_set", height=4):
                    time.sleep(0.001)
        spans.append((start, time.monotonic()))
    # a root of another set the C call did not build
    with ring.stage("valset.hash", n=300) as st:
        st.args["native"] = 0
    return spans


def test_the_new_readers_read_a_stage_ring(readers, stage_ring):
    t0 = time.monotonic()
    spans = _two_operations(stage_ring)
    obs = {"t0": t0, "t1": time.monotonic(), "op_spans": spans}
    read = {name: readers[name][1].read(obs) for name in NEW}
    assert 2.0 <= read["light_fetch_ms"] < 4.0  # nearest rank of four
    assert 2.0 <= read["valset_hash_ms"] < 4.0
    assert 2.0 <= read["light_refused_ms"] < 4.0  # the refused alone
    assert read["valset_native_share"] == pytest.approx(100 * 400 / 700)
    assert read["light_attempts_per_op"] == 3


def test_the_new_readers_read_nothing_without_their_records(readers,
                                                            stage_ring,
                                                            monkeypatch):
    for name in NEW:
        reader = readers[name][1]
        assert reader.read({}) is None
        assert reader.read({"samples": {}, "t0": 1.0, "t1": 0.0,
                            "op_spans": [(0.5, 0.6)]}) is None
    # no `refused` and no `native` on any record: a parent's ring
    t0 = time.monotonic()
    with stage_ring.stage("light.trusting", height=8):
        pass
    with stage_ring.stage("valset.hash", n=100):
        pass
    obs = {"t0": t0, "t1": time.monotonic(), "op_spans": []}
    assert readers["light_refused_ms"][1].read(obs) is None
    assert readers["valset_native_share"][1].read(obs) is None
    assert readers["light_attempts_per_op"][1].read(obs) is None
    # a ring that dropped records of the window reads nothing at all
    spans = _two_operations(stage_ring)
    obs = {"t0": t0, "t1": time.monotonic(), "op_spans": spans}
    monkeypatch.setattr(stage_ring, "stages_dropped", lambda: 3)
    for name in NEW:
        assert readers[name][1].read(obs) is None, name


def test_the_cell_rehearses_on_the_cpu():
    """A lap of the four chains is four bisections of 7 and 8 attempts,
    each with four first-contact roots; the XLA kernel on a CPU takes
    seconds an operation, so the window holds a few."""
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
    assert c["stages_dropped"] == 0
    assert sorted(c["attempts_first_lap"]) == [7, 8, 8, 8]
    assert c["roots_first_lap"] == [4, 4, 4, 4]
    assert head["attempted"] >= 1 and head["samples"]["commit_ms"] >= 1
    stage_readers = (set(NEW) | set(SHARED)) - set(DEVICE)
    assert stage_readers <= set(head["metrics_readable"])
