"""light_fetch_ms: median `light.fetch`: the provider's light block and
its `validate_basic`, the target's and each pivot's, which builds the
root of a validator set met for the first time. Nothing on a program
that has no such stage."""
from harness import stages

LAYER = "light client"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "light.fetch")
