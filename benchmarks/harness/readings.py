"""What several metric readers share: reading the traced window.
`obs` is the dictionary a driver's window returns, with what the
harness adds (harness/main.py): samples, counters, `work` as
(time.monotonic() of completion, live signatures) per operation,
`trace` (the reduction) and `trace_window` (its bounds on the same
clock). A helper that finds nothing to read returns None."""
from __future__ import annotations

from typing import Optional


def traced(obs) -> Optional[dict]:
    return obs.get("trace") if obs.get("trace_window") else None


def idle_share_pct(obs) -> Optional[float]:
    """100 * (1 - union of device-op intervals / traced window)."""
    tr = traced(obs)
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def sigs_in_trace(obs) -> int:
    """Live signatures of the operations that completed inside the
    traced window."""
    t_on, t_off = obs["trace_window"]
    return sum(n for t, n in obs.get("work", ()) if t_on <= t <= t_off)


def device_us_per_sig(obs) -> Optional[float]:
    """Device busy time of the traced window over the live signatures
    verified in it. Until kernels carry stable names this is ALL device
    time of the window (in a replay: stamp + gather + verify + tally),
    not one kernel's."""
    tr = traced(obs)
    if tr is None:
        return None
    sigs = sigs_in_trace(obs)
    return tr["busy_s"] * 1e6 / sigs if sigs else None
