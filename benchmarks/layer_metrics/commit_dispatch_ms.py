"""commit_dispatch_ms: median `ed25519.dispatch`: the kernel call of
`device_batch_fn` until it returns (the transfer in is enqueued; the
device may still be running)."""
from harness import stages

LAYER = "JAX runtime"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "ed25519.dispatch")
