"""catchup_unattributed_share: the part of the summed `catchup.step`
stages that none of the six stages directly inside a step covers. Small
when the stages are complete; a rise says the step grew a region that
nothing times."""
from harness import stages

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "%", "lower", "program_span", "replay_rate"

CHILDREN = ("catchup.refill", "catchup.scan", "catchup.jobs",
            "catchup.verify", "catchup.apply", "catchup.cursor")


def read(obs):
    covered = stages.share_pct(obs, CHILDREN, "catchup.step")
    return None if covered is None else 100.0 - covered
