"""intake_rows_per_call: votes one call of the vote intake was handed
(`n` of the program's `votes.intake` stage), median over the calls that
start in the window. 1 means the votes came one at a time and nothing
could be staged ahead; the rows of a call are what can meet in a flush.
Nothing where the program keeps no stage args (a parent of the PR that
added the intake)."""
from harness import stages, stats

LAYER = "vote intake"
UNIT, BETTER, SOURCE, MOVES = "rows", "higher", "program_span", "vote_p50_ms"
STAGE = "votes.intake"


def read(obs):
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    xs = [r[4]["n"] for r in recs or () if r[0] == STAGE and "n" in r[4]]
    return stats.median(xs) if xs else None
