"""secp256k1_roofline: the least time the chip could take for the
int32 multiply-adds and the bytes the traced window's live secp256k1
signatures need (harness/roofline_secp256k1.py: the algorithm's count,
per live signature) by peaks.json, over the ECDSA kernel's device
seconds in that window. The kernel is 13-bit x 20-limb int32 VPU
arithmetic, so the ceiling is the measured int32 multiply-add rate,
which is itself a lower estimate of the unit's: the share is an upper
estimate."""
from harness import readings_secp256k1, roofline, roofline_secp256k1

LAYER = "verify kernels"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "device_trace", "commit_p50_ms"


def read(obs):
    found = readings_secp256k1.kernel_seconds_and_sigs(obs)
    if found is None:
        return None
    seconds, sigs = found
    least = roofline.least_seconds(
        obs["device_kind"], roofline_secp256k1.ecdsa_verify(sigs))
    return None if least is None else 100.0 * least[0] / seconds
