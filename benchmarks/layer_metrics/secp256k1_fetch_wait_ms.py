"""secp256k1_fetch_wait_ms: median `secp256k1.fetch`, once a `batch_fn`
call (two a light step): what the host still waits for the secp256k1
group's verdicts, and their copy back, after the call's last
dispatch."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "secp256k1.fetch")
