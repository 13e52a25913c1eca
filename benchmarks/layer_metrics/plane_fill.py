"""plane_fill: live rows over the padded rows a fused flush sent to the
device (the flush ledger's `util`, read as the fill ratio it is, not as
a utilization), median."""
from harness import stats

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_counter", "vote_p50_ms"


def read(obs):
    xs = obs.get("samples", {}).get("flush_fill")
    return 100.0 * stats.median(xs) if xs else None
