"""valset_hash_computes: merkle roots of a validator set actually built
in the window: the `valset.hash` stages that start in it.
`ValidatorSet.hash()` remembers its root per membership and records the
stage on a miss only, so a replay under one validator set reads 0 (the
set is hashed once, in set-up). Nothing, not 0, where the program does
not declare the stage (a parent of the PR that added the memo: there
every call computes and none is recorded)."""
from harness import stages

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "count", "lower", "program_span", "replay_rate"
STAGE = "valset.hash"


def read(obs):
    from cometbft_tpu.types import validator

    if getattr(validator, "HASH_STAGE", None) != STAGE:
        return None
    recs = stages.in_window(obs)
    if recs is None:
        return None
    return sum(1 for name, _, _, _ in recs if name == STAGE)
