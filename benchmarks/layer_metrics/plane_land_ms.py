"""plane_land_ms: median `plane.land` of the lands that chose a flight
(`packed` = 0): the dispatcher's readiness wait with a flight airborne,
from its first probe to the choice of the flight to collect. With the
way from the pack's end to it and from it to the collect, this is the
flush ledger's `flight_ms`. A land that new work cut short (`packed` =
1) chose none and is left out. Nothing where no `plane.land` record of
the window carries the arg: a parent of the PR that added the stage, or
a program that keeps no stage args."""
from harness import stages, stats

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "vote_p50_ms"
STAGE, ARG = "plane.land", "packed"


def read(obs):
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    xs = [r[2] / 1e6 for r in recs or ()
          if r[0] == STAGE and r[4].get(ARG) == 0]
    return stats.median(xs) if xs else None
