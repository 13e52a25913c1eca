"""commit_collect_ms: median `commit.collect`: the row loop of
`validation._verify_batch` (keys, signatures and the optimistic tally
up to the quorum point)."""
from harness import stages

LAYER = "served call"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "commit.collect")
