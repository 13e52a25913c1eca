"""commit_device_us_per_sig: device busy time of the traced window over
the live signatures verified in it, on the served commit path (the
general Pallas kernel). All device time of the window, until kernels
carry stable names."""
from harness import readings

LAYER = "verify kernels"
UNIT, BETTER, SOURCE, MOVES = "us", "lower", "device_trace", "commit_p50_ms"


def read(obs):
    return readings.device_us_per_sig(obs)
