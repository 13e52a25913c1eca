"""What the two device readers of the sr25519 kernel share: its device
seconds in the traced window and the live sr25519 signatures of the
calls completed in it.

The kernel's operations are found by name: the jitted function around
the Pallas call is `_verify_rows_sr` (`ops/sr25519_kernel.py`), and the
profile names its Mosaic program after it (`%_verify_rows_sr.1`), as
the ed25519 kernel's reads `%_verify_rows.1`. The reduction keeps the
ten operations that took most time; in this cell the two kernels lead
it. Nothing where the run was not traced, no such operation ran (a
parent whose kernel has another name), or the driver did not count
sr25519 signatures."""
from __future__ import annotations

from typing import Optional, Tuple

from harness import readings

KERNEL = "verify_rows_sr"


def kernel_seconds_and_sigs(obs) -> Optional[Tuple[float, int]]:
    tr = readings.traced(obs)
    if tr is None or "work_sr25519" not in obs:
        return None
    seconds = sum(s for name, s in tr.get("device_ops", ())
                  if KERNEL in name)
    t_on, t_off = obs["trace_window"]
    sigs = sum(n for t, n in obs["work_sr25519"] if t_on <= t <= t_off)
    return (seconds, sigs) if seconds > 0 and sigs else None
