"""plane_land_woken_share: of the lands that chose a flight (`packed` =
0) and had to wait for it (`polls` >= 2: the entry probe said not
ready), the share, in %, whose sleep was ended by the lander's mark
(`woke` = 1) and not by the 5 ms slice running out. 100 = the
dispatcher was woken when the verdicts were ready, every time; near 0
with `plane_land_ms` at 5.4 = the wake-up is lost or late. A land that
found its flight ready at entry never slept and is left out, as is one
that new work cut short. Nothing, not 0, where no such record of the
window carries the arg: a parent of the PR that added the lander, or a
program that keeps no stage args."""
from harness import stages

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "vote_p50_ms"
STAGE, ARG = "plane.land", "woke"


def read(obs):
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    xs = [r[4][ARG] for r in recs or ()
          if r[0] == STAGE and ARG in r[4] and r[4].get("packed") == 0
          and r[4].get("polls", 0) >= 2]
    return 100.0 * sum(1 for x in xs if x == 1) / len(xs) if xs else None
