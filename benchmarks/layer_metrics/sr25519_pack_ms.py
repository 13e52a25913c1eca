"""sr25519_pack_ms: median `sr25519.pack`: `pack_batch_sr` of one chunk
of a commit's sr25519 rows in `device_batch_fn` (the merlin challenges,
the canonicality prechecks, limbs and digits), before its kernel is
called."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "sr25519.pack")
