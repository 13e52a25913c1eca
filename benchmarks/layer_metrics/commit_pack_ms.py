"""commit_pack_ms: median `ed25519.pack`: `pack_batch` and `pack_rows`
of one commit in `device_batch_fn`, before the kernel is called."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "ed25519.pack")
