"""commit_batchfn_ms: the benchmark's span around
`Config().crypto.batch_fn()`: key-type dispatch, host pack, staging,
the device pass and the fetch of the verdicts. Median."""
from harness import stats

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    xs = obs.get("samples", {}).get("batchfn_ms")
    return stats.median(xs) if xs else None
