"""The last line of standard output: the keys the driver's contract
fixes and no other."""
from __future__ import annotations

import json

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_DEVICE_KEYS = ("busy_s", "window_s")


def last_line(*, correct: bool, attempted: int, failed: int,
              metrics: dict, device: dict, breakdown: dict = None) -> str:
    """metrics: {name: (value, unit)}; device: the keys above (and, in
    a traced run, busy_s and window_s)."""
    extra = set(device) - set(DEVICE_KEYS) - set(TRACE_DEVICE_KEYS)
    if extra or not all(k in device for k in DEVICE_KEYS):
        raise ValueError(f"device keys: {sorted(device)}")
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {n: {"value": v, "unit": u}
                    for n, (v, u) in metrics.items()},
        "device": dict(device),
    }
    if breakdown is not None:
        line["breakdown"] = {
            "device_ops": [list(x) for x in breakdown["device_ops"][:10]],
            "idle_gaps": [list(x) for x in breakdown["idle_gaps"][:10]],
        }
    return json.dumps(line)
