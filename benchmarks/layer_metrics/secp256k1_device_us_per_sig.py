"""secp256k1_device_us_per_sig: the traced window's device seconds of
the ECDSA kernel (the operations whose name holds `verify_rows_secp`)
over the live secp256k1 signatures of the steps completed in it: ONE
kernel's time, padding included, per signature that counted."""
from harness import readings_secp256k1

LAYER = "verify kernels"
UNIT, BETTER, SOURCE, MOVES = "us", "lower", "device_trace", "commit_p50_ms"


def read(obs):
    found = readings_secp256k1.kernel_seconds_and_sigs(obs)
    return None if found is None else found[0] * 1e6 / found[1]
