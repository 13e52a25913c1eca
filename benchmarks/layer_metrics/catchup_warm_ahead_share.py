"""catchup_warm_ahead_share: the summed `catchup.warm_ahead` stages (one per
applied block: the next validator set hashed and compared, the warmer
asked when it is new) over the summed `catchup.step` stages of the
window."""
from harness import stages

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "%", "lower", "program_span", "replay_rate"


def read(obs):
    return stages.share_pct(obs, ("catchup.warm_ahead",), "catchup.step")
