"""sr25519_fetch_wait_ms: median `sr25519.fetch`, once a call: what the
host still waits for the sr25519 group's verdicts, and their copy back,
after the call's last dispatch of either key type."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "sr25519.fetch")
