"""plane_dispatch_ms: median `plane.dispatch` per fused flush: the
`fused.dispatch_fused` call inside `plane.pack`, host to device copies
and the enqueue of the flush's device programs, until it returns. The
flush ledger's `h2d_ms` is this stage less the compile time charged to
the flush. Nothing where the program has no such stage."""
from harness import stages

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "vote_p50_ms"


def read(obs):
    return stages.median_ms(obs, "plane.dispatch")
