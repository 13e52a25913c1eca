"""secp256k1_overlap_share: of the host time the window's
`secp256k1.pack` stages took, the part spent while the device had a
chunk of the same `device_batch_fn` call to work on (`flying` >= 1):
harness/overlap.py. The first pack of each of a light step's two calls
finds nothing flying."""
from harness import overlap

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "commit_p50_ms"


def read(obs):
    return overlap.share_pct(obs, "secp256k1.pack")
