"""The values one arg of one program stage took in the cell's window:
what the readers of a stage's args share (`harness/stages.py` gives the
window's records without their args)."""
from __future__ import annotations

from typing import List, Optional

from harness import stages


def values(obs, stage: str, arg: str) -> Optional[List]:
    """`arg` of every `stage` record that starts in the window and
    carries it; None where the window, the clock or the ring gives
    nothing to read, where the program keeps no stage args, or where no
    record carries the arg (a parent of the PR that added it)."""
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    xs = [r[4][arg] for r in recs or () if r[0] == stage and arg in r[4]]
    return xs or None
