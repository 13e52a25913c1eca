"""commit_fetch_wait_ms: median `ed25519.fetch`: `np.asarray` of the
verdicts in `device_batch_fn`: device time plus the transfer back, as
the host waits it out."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "ed25519.fetch")
