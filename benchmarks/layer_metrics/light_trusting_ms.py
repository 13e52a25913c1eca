"""light_trusting_ms: median `light.trusting`: the first check of a
non-adjacent light step, `verify_commit_light_trusting` of the OLD set
(rows found by address, up to trust_level of its power). Nothing on a
program that has no such stage."""
from harness import stages

LAYER = "light client"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "light.trusting")
