"""sr25519_challenge_ms: median `sr25519.challenge`: the merlin /
STROBE / keccak transcripts of one chunk's rows (`batch_challenges`,
one native call), inside `sr25519.pack`."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "sr25519.challenge")
