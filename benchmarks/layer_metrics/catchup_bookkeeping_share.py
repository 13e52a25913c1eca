"""catchup_bookkeeping_share: what a catch-up step spends on its own
books (`catchup.refill`, `catchup.scan`, `catchup.jobs`,
`catchup.cursor`: read-ahead, the pre-scan with its valset hash, the
job list with a block id per block, the cursor file) over the summed
`catchup.step` stages of the window."""
from harness import stages

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "%", "lower", "program_span", "replay_rate"

PARTS = ("catchup.refill", "catchup.scan", "catchup.jobs", "catchup.cursor")


def read(obs):
    return stages.share_pct(obs, PARTS, "catchup.step")
