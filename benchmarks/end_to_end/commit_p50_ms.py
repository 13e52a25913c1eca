"""commit_p50_ms: median wall of one served `verify_commit_light` call
with the verdicts in hand (host clock, every call of the window)."""
from harness import stats

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(obs):
    xs = obs.get("samples", {}).get("commit_ms")
    return stats.median(xs) if xs else None
