"""stream_collect_ms: median `stream.collect` per chunk: the wait for
the device, the fetch of the verdicts and the blame loop, in
`StreamVerifier.verify`."""
from harness import stages

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "replay_rate"


def read(obs):
    return stages.median_ms(obs, "stream.collect")
