"""plane_rows_per_flush: rows a flush carried, median over the window's
flushes (flush ledger `rows`): 1 means nothing coalesced."""
from harness import stats

LAYER = "verify plane"
UNIT, BETTER, SOURCE = "rows", "higher", "program_counter"
MOVES = "vote_p50_ms"


def read(obs):
    xs = obs.get("samples", {}).get("flush_rows")
    return stats.median(xs) if xs else None
