"""Tier-1 guard against tools/loadtime.py rot (ISSUE 7 satellite).

The full loadtime modes drive a live consensus net for tens of seconds;
`--smoke` is the tier-1-safe slice — mempool + admission + a host-path
verify plane only, no consensus, NO jax import, a couple of seconds.
This file (late in the alphabet on purpose) drives it through main()
exactly like the CI invocation would, keeping the overload-verdict path (explicit OVERLOADED codes with retry hints)
continuously exercised.
"""
import json
import sys

from tools import loadtime


def test_loadtime_smoke_cli(capsys):
    """`loadtime.py --smoke` exits 0, prints one JSON document with
    both outcomes populated (accepted AND explicitly overloaded), and
    never imports jax."""
    jax_loaded_before = "jax" in sys.modules
    rc = loadtime.main(["--smoke"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    # open-loop accounting: every offered tx got exactly one verdict
    assert rep["offered"] == rep["accepted"] + rep["overloaded"] \
        + rep["rejected_other"]
    assert rep["accepted"] > 0
    assert rep["overloaded"] > 0, "smoke never exercised overload"
    assert rep["rejected_other"] == 0, rep["codes"]
    # every overload verdict carries the backoff hint
    assert rep["overload_log_samples"]
    assert all("retry_after_ms=" in s
               for s in rep["overload_log_samples"])
    # the signed flood rode the BULK lane; consensus lane stayed empty
    # and was never shed (there IS no consensus traffic here)
    assert rep["plane"]["lane_rows"]["bulk"] > 0
    assert rep["plane"]["sheds"]["consensus"] == 0
    # admission accounting adds up
    adm = rep["admission"]
    assert adm["inflight"] == 0, "admission slots leaked"
    assert sum(adm["counts"].values()) >= rep["offered"]
    if not jax_loaded_before:
        assert "jax" not in sys.modules, "--smoke imported jax"
    assert rep["jax_imported"] is False


def test_open_loop_schedule_is_not_closed_loop():
    """The open-loop discipline itself: a submit path that stalls hard
    must not slow the offered schedule below its configured rate — the
    generator keeps injecting (queueing on workers) instead of politely
    waiting, which is the honesty property the ISSUE names."""
    import time

    run = loadtime.OpenLoopRun()
    calls = []

    def slow_submit(tx):
        calls.append(tx)
        time.sleep(0.05)  # 20/s per worker vs 200/s offered
        return 0, ""

    wall = loadtime.open_loop(200.0, 0.5, lambda k: b"x%d" % k,
                              slow_submit, run, workers=4)
    assert run.offered == 100
    # closed-loop would need 100 * 50ms / 4 workers = 1.25 s of
    # injection pacing; open-loop pacing finishes the schedule on time
    # and only then drains the queue
    assert wall < 2.5
    lat = run.report(wall)["checktx_latency"]
    # queueing delay is VISIBLE in the latencies (not hidden by pacing)
    assert lat["max_ms"] >= 50.0
