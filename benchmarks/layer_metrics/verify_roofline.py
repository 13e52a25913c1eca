"""verify_roofline: the least time the chip could take for the int32
multiply-adds and the bytes the cached-table verification needs
(harness/roofline.py, functions of the shapes), over the device busy
time of the traced window. The kernels are 13-bit x 20-limb int32 VPU
arithmetic, so the ceiling is the measured int32 multiply-add rate in
peaks.json, not the published MXU peaks. Taken over ALL device time of
the window (stamp + gather + verify + tally) until kernels carry stable
names, so it understates the verify kernel's own share."""
from harness import readings, roofline

LAYER = "verify kernels"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "device_trace", "replay_rate"


def read(obs):
    tr = readings.traced(obs)
    if tr is None or tr["busy_s"] <= 0:
        return None
    sigs = readings.sigs_in_trace(obs)
    if not sigs:
        return None
    least = roofline.least_seconds(obs["device_kind"],
                                   roofline.cached_verify(sigs))
    return None if least is None else 100.0 * least[0] / tr["busy_s"]
