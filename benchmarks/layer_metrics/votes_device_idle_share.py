"""votes_device_idle_share: 1 minus the union of the device-operation
intervals over the traced window, from the profiler's .xplane.pb
(harness/trace.py). The device's idle share in this cell."""
from harness import readings

LAYER = "device"
UNIT, BETTER, SOURCE, MOVES = "%", "lower", "device_trace", "vote_p50_ms"


def read(obs):
    return readings.idle_share_pct(obs)
