"""burst_quorum_ms: how long after the deciding vote was due its vote
set reported `two_thirds_majority()`: per set, the moment the driver
first saw the majority (looked for after every vote it handled) minus
the due time of the vote the plain reference names
(`reference/quorum.first_quorum_index`); median over the window's
sets. The number a validator feels: it moves to the next step then."""
from harness import stats

LAYER = "vote intake"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "host_clock", "vote_p50_ms"


def read(obs):
    xs = obs.get("samples", {}).get("burst_quorum_ms")
    return stats.median(xs) if xs else None
