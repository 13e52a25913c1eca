"""generator_lag_p99_ms: how late the open-loop sender ran: for every
vote, how long after its due time a sleeping consumer woke for it (0
for a vote that was already waiting). 99th percentile. A starved
generator must not read as a fast server."""
from harness import stats

LAYER = "load generator"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "host_clock", "vote_p50_ms"


def read(obs):
    xs = obs.get("samples", {}).get("generator_lag_ms")
    return stats.percentile(xs, 0.99) if xs else None
