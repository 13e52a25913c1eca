"""plane_queued_p50_ms: how long a flush's oldest row waited in the
plane's queue before the flush was cut (flush ledger `queued_ms`,
host clock), median."""
from harness import stats

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "vote_p50_ms"


def read(obs):
    xs = obs.get("samples", {}).get("flush_queued_ms")
    return stats.median(xs) if xs else None
