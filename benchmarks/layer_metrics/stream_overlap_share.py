"""stream_overlap_share: of the host time the window's `stream.pack`
stages took, the part spent while the device had a chunk of the same
`StreamVerifier.verify` call to work on: the summed duration of the
packs entered with `flying` >= 1 (chunks dispatched and not yet
collected when the pack starts) over the summed duration of all of
them. 0 means pack and device run in turn: every call is one chunk.
Nothing, not 0, where no `stream.pack` record carries `flying` (a
parent of the PR that added the arg) or the program keeps no stage
args."""
from harness import stages

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "replay_rate"
STAGE, ARG = "stream.pack", "flying"


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
