"""dispatch_floor_ms: one trivial jitted call fetched back to the host,
median of 50 taken in the set-up of the traced run (copy of
`bench.measure_dispatch_floor`): what any device call pays before it
does work."""
from harness import stats

LAYER = "JAX runtime"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "host_clock", "vote_p50_ms"


def read(obs):
    xs = obs.get("dispatch_floor_ms")
    return stats.median(xs) if xs else None
