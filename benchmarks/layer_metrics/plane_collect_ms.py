"""plane_collect_ms: median `plane.collect` per fused flush: the
blocking fetch of an airborne flight's verdicts and tallies, from the
end of the landing wait. The flush ledger's `collect_ms` is this
stage's duration. Nothing where the program has no such stage."""
from harness import stages

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "vote_p50_ms"


def read(obs):
    return stages.median_ms(obs, "plane.collect")
