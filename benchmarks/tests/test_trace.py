"""The trace reduction: interval arithmetic on made-up intervals, then
the whole reduction on a small trace recorded on the chip
(data/v5e_probe.xplane.pb: `probe_int32.py --trace-out`, three calls of
one fused program with a 2 ms pause after each)."""
import os

import pytest

from harness import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_probe.xplane.pb")


def test_union_clip_and_gaps():
    busy = trace.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)])
    assert busy == [(1, 4), (5, 8)]
    assert trace.clip(busy, 2, 6) == [(2, 4), (5, 6)]
    assert trace.gaps(busy, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert trace.gaps([], 0, 10) == [(0, 10)]


def test_gaps_go_to_the_innermost_span():
    spans = [(0, 100, "bench.lap"), (10, 40, "bench.verify"),
             (50, 60, "bench.apply")]
    got = trace.attribute([(5, 20), (35, 55), (90, 120)], spans)
    assert got == {"bench.lap": 5 + 10 + 10, "bench.verify": 10 + 5,
                   "bench.apply": 5, "host.other": 20}


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside this test")
def test_reduction_of_the_recorded_trace():
    r = trace.reduce_xplane(RECORDED, n_chips=1)
    assert r is not None and r["n_device_events"] > 0
    assert 0 < r["busy_s"] < r["window_s"]
    # three calls, each followed by a 2 ms sleep: the device idles at
    # least 6 ms of the window, and the pauses own most of the gaps
    idle = r["window_s"] - r["busy_s"]
    assert idle >= 0.006
    by = dict(r["idle_gaps"])
    assert by["bench.pause"] >= 0.006
    assert abs(sum(by.values()) - idle) < 1e-6
    assert r["device_ops"] == [["%multiply_add_fusion", r["busy_s"]]]
    assert EXPECTED == pytest.approx(
        (r["window_s"], r["busy_s"]), rel=1e-9)


# (window_s, busy_s) of the recorded trace, checked by hand against its
# events: `bench.window` lasts 9,964,540 ns; the device plane holds three
# `multiply_add_fusion` events of 25,712, 25,697 and 25,486 ns, and the
# first of them lies 1.05 ms BEFORE the window opens although its call
# was made inside it: in this recording the device plane's clock runs
# one to two milliseconds ahead of the host plane's. The reduction takes
# the profile's clocks as they are, so two events count as busy, and
# gaps of a millisecond or two cannot be trusted to the span they fall
# under.
EXPECTED = (0.00996454, 5.1183e-05)
