"""light_refused_ms: median `light.trusting` of the checks that ended in
too little of the OLD set's power (`refused` = 1): a scan of the new
commit's rows by address that verifies no signature, after which the
skipping client bisects. Nothing, not 0, where no record carries
`refused` (a parent of the PR that added the arg), none was refused, or
the program keeps no stage args."""
from harness import stages, stats

LAYER = "light client"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"
STAGE, ARG = "light.trusting", "refused"


def read(obs):
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    xs = [r[2] / 1e6 for r in recs or ()
          if r[0] == STAGE and r[4].get(ARG) == 1]
    return stats.median(xs) if xs else None
