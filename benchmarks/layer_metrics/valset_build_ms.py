"""valset_build_ms: median `valset.build` of the window: one
`ValidatorSet(...)` constructor's sort, index, total power and first
proposer round (in `light-ed-10k.bisect`, the set the provider builds at
every fetch). Nothing on a program that declares no such stage, or where
no set was built."""
from harness import stages

LAYER = "validator set"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"
STAGE = "valset.build"


def read(obs):
    from cometbft_tpu.types import validator

    if getattr(validator, "BUILD_STAGE", None) != STAGE:
        return None
    return stages.median_ms(obs, STAGE)
