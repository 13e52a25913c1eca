"""What the readers of the program's own stages share.

The program records a few always-on stages per served operation
(`cometbft_tpu.libs.tracing.stage`: name, start and duration in ns,
thread) into one bounded ring. A reader runs in the cell's process
after the window, so it reads that ring directly: the records whose
START lies in the cell's window `[obs["t0"], obs["t1"]]`.

The drivers stamp the window on `time.monotonic()`, the program its
stages on `tracing.monotonic_ns()` (`time.perf_counter_ns` unless a
node or the simnet installed another clock). On Linux the two are one
clock; `in_window` checks that once, and reads nothing where they are
apart. It also reads nothing where the program has no stages (a parent
of the PR that added them) or where the ring dropped records that may
have been of the window: no partial answer. A helper that finds
nothing to read returns None, and the metric is left out of the line.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

from harness import stats

CLOCK_SLACK_NS = 1_000_000  # "the same clock": apart by under 1 ms
_SAME_CLOCK: Optional[bool] = None


def _same_clock(tracing) -> bool:
    """Whether a stage's stamp and the drivers' window compare."""
    global _SAME_CLOCK
    if _SAME_CLOCK is None:
        before = time.monotonic() * 1e9
        at = tracing.monotonic_ns()
        after = time.monotonic() * 1e9
        _SAME_CLOCK = (before - CLOCK_SLACK_NS <= at
                       <= after + CLOCK_SLACK_NS)
    return _SAME_CLOCK


def select(records: Sequence[tuple], dropped: int, t0: float,
           t1: float) -> Optional[List[tuple]]:
    """The (name, t0_ns, dur_ns, tid) records that start in [t0, t1]
    (seconds). The ring holds records in the order they ENDED and
    drops the oldest, so it lost nothing of the window if it dropped
    nothing at all, or still holds a record that ended before t0."""
    lo, hi = t0 * 1e9, t1 * 1e9
    if dropped and not (records and records[0][1] + records[0][2] < lo):
        return None
    return [r for r in records if lo <= r[1] <= hi]


def in_window(obs) -> Optional[List[tuple]]:
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stages") or "t0" not in obs
            or "t1" not in obs or not _same_clock(tracing)):
        return None
    return select(tracing.stages(), tracing.stages_dropped(),
                  obs["t0"], obs["t1"])


def totals_ms(obs) -> Optional[Dict[str, float]]:
    """Summed duration per stage name, in ms."""
    recs = in_window(obs)
    if recs is None:
        return None
    out: Dict[str, float] = {}
    for name, _, dur, _ in recs:
        out[name] = out.get(name, 0.0) + dur / 1e6
    return out


def share_pct(obs, parts: Sequence[str], whole: str) -> Optional[float]:
    """100 * summed `parts` over summed `whole`."""
    tot = totals_ms(obs)
    if not tot or not tot.get(whole):
        return None
    return 100.0 * sum(tot.get(p, 0.0) for p in parts) / tot[whole]


def median_ms(obs, name: str) -> Optional[float]:
    """Median duration of the stage `name`, in ms."""
    recs = in_window(obs)
    xs = [dur / 1e6 for n, _, dur, _ in recs or () if n == name]
    return stats.median(xs) if xs else None
