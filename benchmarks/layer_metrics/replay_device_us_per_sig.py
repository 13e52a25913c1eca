"""replay_device_us_per_sig: device busy time of the traced window over
the live signatures verified in it, on the catch-up path. All device
time of the window (stamp + gather + cached verify + tally), until
kernels carry stable names."""
from harness import readings

LAYER = "verify kernels"
UNIT, BETTER, SOURCE, MOVES = "us", "lower", "device_trace", "replay_rate"


def read(obs):
    return readings.device_us_per_sig(obs)
