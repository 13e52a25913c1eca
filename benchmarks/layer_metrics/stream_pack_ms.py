"""stream_pack_ms: median `stream.pack` per chunk: host pack or delta
staging, the template entry and (nested, `stream.dispatch`) the device
call, in `StreamVerifier.verify`."""
from harness import stages

LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "replay_rate"


def read(obs):
    return stages.median_ms(obs, "stream.pack")
