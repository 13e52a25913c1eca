"""What the two device readers of the ECDSA kernel share: its device
seconds in the traced window and the live secp256k1 signatures of the
operations completed in it.

The kernel's operations are found by name: the jitted function around
the Pallas call is `_verify_rows_secp` (`ops/ecdsa_pallas.py`, since
PR 35), and the profile names its Mosaic program after it
(`%_verify_rows_secp.1`), apart from the ed25519 kernel's
`%_verify_rows.1` and the sr25519 kernel's `%_verify_rows_sr.1`.
Nothing where the run was not traced, no such operation ran (a parent
whose kernel carries the ed25519 kernel's name), or the driver did not
count secp256k1 signatures."""
from __future__ import annotations

from typing import Optional, Tuple

from harness import readings

KERNEL = "verify_rows_secp"


def kernel_seconds_and_sigs(obs) -> Optional[Tuple[float, int]]:
    tr = readings.traced(obs)
    if tr is None or "work_secp256k1" not in obs:
        return None
    seconds = sum(s for name, s in tr.get("device_ops", ())
                  if KERNEL in name)
    t_on, t_off = obs["trace_window"]
    sigs = sum(n for t, n in obs["work_secp256k1"] if t_on <= t <= t_off)
    return (seconds, sigs) if seconds > 0 and sigs else None
