"""exec_update_state_ms: the apply path's updateState time per block:
the median `update_state_ms` of the window's `exec.apply` stages (one
a block `BlockExecutor.apply_block` applied: the copies of the last,
current and next validator sets, the block's change set applied to the
next one with its re-sort, the warmer's notice and the proposer round).
Nothing where no `exec.apply` record carries the arg (a parent of the
PR that added the stage) or the program keeps no stage args."""
from harness import stage_args, stats

LAYER = "apply path"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "replay_rate"
STAGE, ARG = "exec.apply", "update_state_ms"


def read(obs):
    xs = stage_args.values(obs, STAGE, ARG)
    return stats.median(xs) if xs else None
