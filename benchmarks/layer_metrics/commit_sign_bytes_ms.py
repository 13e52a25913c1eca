"""commit_sign_bytes_ms: median `commit.sign_bytes`: the sign-bytes
of the collected rows (`validation._commit_msgs`)."""
from harness import stages

LAYER = "served call"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "commit.sign_bytes")
