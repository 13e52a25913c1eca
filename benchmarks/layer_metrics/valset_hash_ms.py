"""valset_hash_ms: median `valset.hash` of the window: the merkle root
of a validator set actually built (a miss of `ValidatorSet.hash()`'s
memo: a set met for the first time). Nothing on a program that
declares no such stage, or where no root was built."""
from harness import stages

LAYER = "validator set"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"
STAGE = "valset.hash"


def read(obs):
    from cometbft_tpu.types import validator

    if getattr(validator, "HASH_STAGE", None) != STAGE:
        return None
    return stages.median_ms(obs, STAGE)
