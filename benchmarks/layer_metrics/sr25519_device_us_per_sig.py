"""sr25519_device_us_per_sig: the traced window's device seconds of the
sr25519 kernel (the operations whose name holds `verify_rows_sr`) over
the live sr25519 signatures of the calls completed in it: ONE kernel's
time, padding included, per signature that counted."""
from harness import readings_sr25519

LAYER = "verify kernels"
UNIT, BETTER, SOURCE, MOVES = "us", "lower", "device_trace", "commit_p50_ms"


def read(obs):
    found = readings_sr25519.kernel_seconds_and_sigs(obs)
    return None if found is None else found[0] * 1e6 / found[1]
