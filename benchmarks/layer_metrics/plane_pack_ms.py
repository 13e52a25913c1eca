"""plane_pack_ms: median `plane.pack` per flush of the verify plane:
the dispatcher's host work from the cut batch to the flight in the air
(plan, staging, `plane.dispatch` inside it). The flush ledger's
`pack_ms` is this stage's duration. Nothing where the program has no
such stage (a parent of the PR that added the plane's stages)."""
from harness import stages

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "vote_p50_ms"


def read(obs):
    return stages.median_ms(obs, "plane.pack")
