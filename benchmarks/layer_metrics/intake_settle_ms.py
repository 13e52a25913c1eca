"""intake_settle_ms: median `votes.settle` per call of the vote intake:
the wait for the staged verdicts and the admissions in arrival order
(WAL write, `add_vote`, quorum checks on a node). With the rows of a
call in one flush this is about one flush's flight, whatever the call
holds."""
from harness import stages

LAYER = "vote intake"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "vote_p50_ms"


def read(obs):
    return stages.median_ms(obs, "votes.settle")
