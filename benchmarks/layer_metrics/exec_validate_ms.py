"""exec_validate_ms: the apply path's validation time per block: the
median `validate_ms` of the window's `exec.apply` stages (one a block
`BlockExecutor.apply_block` applied: the validate_block calls since the
previous block, its own included; on the catch-up engine's apply path
two a block, each verifying the LastCommit in full). Nothing where no
`exec.apply` record carries the arg (a parent of the PR that added the
stage) or the program keeps no stage args."""
from harness import stage_args, stats

LAYER = "apply path"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "replay_rate"
STAGE, ARG = "exec.apply", "validate_ms"


def read(obs):
    xs = stage_args.values(obs, STAGE, ARG)
    return stats.median(xs) if xs else None
