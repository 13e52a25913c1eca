"""catchup_segment_blocks: the median length, in blocks, of the fused
segments the catch-up engine verified in the window: the `blocks` arg
of the window's `catchup.scan` stages (the pre-scan cuts a segment at a
validator-set change, at `max_run` or at the end of the read-ahead
buffer). 1 means a set change at every block closes every segment.
Nothing, not 0, where no `catchup.scan` record carries the arg (a
parent of the PR that added it) or the program keeps no stage args."""
from harness import stage_args, stats

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "blocks", "higher", "program_span", "replay_rate"
STAGE, ARG = "catchup.scan", "blocks"


def read(obs):
    xs = stage_args.values(obs, STAGE, ARG)
    return stats.median(xs) if xs else None
