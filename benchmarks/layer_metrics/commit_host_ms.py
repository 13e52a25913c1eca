"""commit_host_ms: what `verify_commit_light` itself costs on the host
(row collection, sign-bytes, tally): the benchmark's span around the
call minus its span around the `batch_fn` it hands in. Median."""
from harness import stats

LAYER = "served call"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    xs = obs.get("samples", {}).get("host_ms")
    return stats.median(xs) if xs else None
