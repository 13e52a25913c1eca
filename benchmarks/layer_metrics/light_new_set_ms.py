"""light_new_set_ms: median `light.new_set`: the second check of a
light step, `verify_commit_light` of the NEW set (by index, up to 2/3
of its power). Nothing on a program that has no such stage."""
from harness import stages

LAYER = "light client"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "light.new_set")
