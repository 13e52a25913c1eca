"""secp256k1_pack_ms: median `secp256k1.pack`: `ecdsa_kernel.pack_batch`
of one chunk of a commit's secp256k1 rows in `device_batch_fn` (SHA-256
of the sign-bytes, the batched inverse of s, u1 and u2, limbs and
digits) and `ecdsa_pallas.pack_rows`, before its kernel is called.
Nothing on a program that has no such stage."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"


def read(obs):
    return stages.median_ms(obs, "secp256k1.pack")
