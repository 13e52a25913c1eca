"""vote_p95_ms: 95th percentile of the readings `vote_p50_ms` takes its
median of. A per-layer metric and not an end-to-end one, because at
four fifths of the knee of a serial server one stall of a fifth of a
second delays some fifty votes behind it, about the 70 that lie beyond
the 95th percentile of a window: it spread by 12 % over runs of the same
code (my chip runs, PR 22), and no bound the contract allows holds that.
It says how often the intake queued; the median says what a vote costs."""
from harness import stats

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "host_clock", "vote_p50_ms"


def read(obs):
    xs = obs.get("samples", {}).get("vote_ms")
    return stats.percentile(xs, 0.95) if xs else None
