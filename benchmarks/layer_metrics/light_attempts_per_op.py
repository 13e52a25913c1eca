"""light_attempts_per_op: verification attempts of one operation of the
skipping client: the `light.step` records that start inside each timed
operation (`obs["op_spans"]`, on the drivers' clock), median over the
operations of the window. Upstream's loop makes 8 on an untampered
bisection of `light-ed-10k` (4 refused, 4 verified), 7 on the tampered
chain's. Nothing where the driver gives no spans or the ring has no
window to read."""
from harness import stages, stats

LAYER = "light client"
UNIT, BETTER, SOURCE, MOVES = "count", "lower", "program_span", \
    "commit_p50_ms"
STAGE = "light.step"


def read(obs):
    spans = obs.get("op_spans")
    recs = stages.in_window(obs) if spans else None
    if recs is None:
        return None
    starts = sorted(t0 for name, t0, _, _ in recs if name == STAGE)
    if not starts:
        return None
    return stats.median([
        sum(1 for t in starts if a * 1e9 <= t <= b * 1e9)
        for a, b in spans])
