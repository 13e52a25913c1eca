"""What the overlap readers share: of the host time a window's pack
stages of one name took, the part spent while the device had a pass of
the same call to work on.

The chunk loops of the program (`types/validation._verify_chunked`,
`blocksync/pipeline.StreamVerifier.verify`) enter each pack stage with
`flying`: passes of this call dispatched whose verdicts were not ready
when the pack started; 0 past a call's first means the device ran dry.
The share is the summed duration of the packs entered with `flying`
>= 1 over the summed duration of all that carry the arg.

Nothing, not 0, where there is no window, clock or ring to read, where
no record of `stage` carries `flying` (a parent of the PR that added
it), or where the program keeps no stage args.

Written for `secp256k1_overlap_share` (PR 35). The three older twins
(`stream_overlap_share`, `commit_overlap_share`,
`sr25519_overlap_share`) hold this body with another stage name each:
the next `benchmark` issue should point them here (PERF.md section 7).
"""
from __future__ import annotations

from typing import Optional

from harness import stages

ARG = "flying"


def share_pct(obs, stage: str) -> Optional[float]:
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    packs = [(r[2], r[4][ARG]) for r in recs or ()
             if r[0] == stage and ARG in r[4]]
    total = sum(dur for dur, _ in packs)
    if not total:
        return None
    return 100.0 * sum(dur for dur, flying in packs if flying >= 1) / total
