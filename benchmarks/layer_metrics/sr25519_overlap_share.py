"""sr25519_overlap_share: of the host time the window's `sr25519.pack`
stages took, the part spent while the device had a chunk of the same
`device_batch_fn` call to work on, of EITHER key type: the summed
duration of the packs entered with `flying` >= 1 over the summed
duration of all of them (`commit_overlap_share`, for the other stage).
Nothing, not 0, where no `sr25519.pack` record carries `flying` (a
parent of the PR that added the stage) or the program keeps no stage
args."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "commit_p50_ms"
STAGE, ARG = "sr25519.pack", "flying"


def read(obs):
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    packs = [(r[2], r[4][ARG]) for r in recs or ()
             if r[0] == STAGE and ARG in r[4]]
    total = sum(dur for dur, _ in packs)
    if not total:
        return None
    return 100.0 * sum(dur for dur, flying in packs if flying >= 1) / total
