"""commit_overlap_share: of the host time the window's `ed25519.pack`
stages took, the part spent while the device had a chunk of the same
`device_batch_fn` call to work on: the summed duration of the packs
entered with `flying` >= 1 (chunks dispatched whose verdicts are not
ready when the pack starts) over the summed duration of all of them.
0 means pack and device run in turn: every call is one chunk, or the
device ran dry before each next pack began. Nothing, not 0, where no
`ed25519.pack` record carries `flying` (a parent of the PR that added
the arg) or the program keeps no stage args."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "commit_p50_ms"
STAGE, ARG = "ed25519.pack", "flying"


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
