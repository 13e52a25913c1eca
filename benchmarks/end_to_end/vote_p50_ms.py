"""vote_p50_ms: median, over every vote of the window, of the verdict's
time minus the time the vote was DUE (open loop, host clock)."""
from harness import stats

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(obs):
    xs = obs.get("samples", {}).get("vote_ms")
    return stats.median(xs) if xs else None
