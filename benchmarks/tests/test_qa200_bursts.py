"""`qa200.bursts`: the cell's files are found by name, its traffic is
what BENCHMARK.json says (7 heights, 14 bursts, 2,450 votes in 20 s),
its three readers find nothing in an empty observation, the quorum
reference names the deciding vote, and the whole cell walks through on
the CPU (`--rehearse`: 24 validators, a 0.3 s period, counts only)."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from harness import catalog
from reference import quorum

CELL = "qa200.bursts"
NEW = ("intake_rows_per_call", "intake_settle_ms", "burst_quorum_ms")


@pytest.fixture(scope="module")
def cell():
    return catalog.Cell(CELL)


def test_the_cells_files_are_found(cell):
    assert cell.config["validators"] == 175 and cell.chips == 1
    assert cell.config["block_period_s"] == 3.0
    assert cell.config["reduced"] == [] and cell.config["chain_id"] == \
        "bench-qa200"
    assert cell.driver.__file__.endswith("drivers/votes_bursts.py")
    want = {"block_period_s": 3.0, "burst_ms": 150, "votes_per_burst": 175,
            "precommit_offset_s": 1.5, "consumers": 1, "bad_share": 0.01,
            "late_after_s": 3.0, "warm_votes": 64, "drain_s": 10.0,
            "trace_seconds": 1.5}  # not votes-serial's 1.0: see its why
    assert {k: cell.traffic[k] for k in want} == want
    assert [e["name"] for e, _ in cell.metrics("end_to_end")] == [
        "vote_p50_ms", "setup_s"]
    layer = {e["name"]: (e, r) for e, r in cell.metrics("per_layer")}
    assert set(NEW) <= set(layer)
    for name in NEW:
        entry, reader = layer[name]
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (entry["unit"], entry["better"],
                                  entry["source"], entry["layer"],
                                  entry["moves"])
        assert entry["workloads"] == [CELL]


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_in_an_empty_observation(cell, name):
    reader = dict((e["name"], r) for e, r in cell.metrics("per_layer"))[name]
    assert reader.read({}) is None
    assert reader.read({"samples": {}, "t0": 1.0, "t1": 0.0}) is None


def test_the_schedule_is_the_issues(cell):
    ctx = SimpleNamespace(traffic=cell.traffic, config=cell.config,
                          seed=2147483999, seconds=20.0)
    plan = cell.driver._schedule(ctx, 175)
    assert len(plan) == 2450 and len({r[0] for r in plan}) == 14
    assert [r[3] for r in plan] == sorted(r[3] for r in plan)
    for s in range(14):
        rows = [r for r in plan if r[0] == s]
        start = (s // 2) * 3.0 + (s % 2) * 1.5
        assert sorted(r[1] for r in rows) == list(range(175))
        # due times drawn from the whole burst, each on its own: they
        # lie inside it and are not one to each 1/175 of it
        assert all(start <= r[3] < start + 0.150 for r in rows)
        slots = {int((r[3] - start) / (0.150 / 175)) for r in rows}
        assert 90 <= len(slots) <= 130  # 175 x (1 - 1/e) = 111 expected
    assert 5 <= sum(r[2] for r in plan) <= 60  # 1 % bad, from the seed
    again = cell.driver._schedule(ctx, 175)
    assert again == plan  # the same seed gives the same traffic
    ctx.seed += 1
    assert cell.driver._schedule(ctx, 175) != plan


def test_quorum_reference_names_the_deciding_vote(monkeypatch):
    monkeypatch.setattr(quorum.plain, "verify_sig",
                        lambda pub, msg, sig: sig == b"good")
    pubs, powers = [b"p"] * 6, [10] * 6  # more than 40 of 60: 5 votes

    def fed(*rows):
        return [(i, blk, b"m", sig) for i, blk, sig in rows]

    a, b = b"A", b"B"
    good = [(i, a, b"good") for i in range(6)]
    assert quorum.first_quorum_index(pubs, powers, fed(*good)) == 4
    # a bad signature and a validator's second vote add nothing
    rows = [good[0], (1, a, b"bad"), good[0], good[2], good[3], good[4],
            (5, b, b"good"), good[1]]
    assert quorum.first_quorum_index(pubs, powers, fed(*rows)) == 7
    assert quorum.first_quorum_index(pubs, powers, fed(*good[:4])) is None


def test_the_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, os.path.join(catalog.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147484123", "--seconds", "1.2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=catalog.REPO_ROOT)
    assert run.returncode == 0, run.stderr[-2000:]
    head = json.loads(run.stdout.strip().splitlines()[-1])
    assert head["rehearsal"] and head["correct"] and head["failed"] == 0
    c = head["counters"]
    # 4 heights of 0.3 s fit in 1.2 s: 8 bursts of 24 votes
    assert (c["bursts"], c["votes_due"], c["votes_served"]) == (8, 192, 192)
    assert c["late"] == 0 and c["intake_calls"] < 192
    assert c["flush_rows_max"] > 1  # rows of a burst met in a flush
    assert head["samples"]["burst_quorum_ms"] == 8
    assert set(NEW) <= set(head["metrics_readable"])
