"""catchup_power_boundary_share: of the fused segments the catch-up
engine verified in the window, the share closed by a validator-set
change of powers alone (the `catchup.scan` stages whose `closed_by` is
"powers": the boundary's set holds the current set's keys), which a
catch-up that fused across such changes would not close. Nothing, not 0,
where no `catchup.scan` record carries the arg (a parent of the PR
that added it) or the program keeps no stage args."""
from harness import stage_args

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "%", "lower", "program_span", "replay_rate"
STAGE, ARG = "catchup.scan", "closed_by"


def read(obs):
    closed = stage_args.values(obs, STAGE, ARG)
    if not closed:
        return None
    return 100.0 * sum(1 for c in closed if c == "powers") / len(closed)
