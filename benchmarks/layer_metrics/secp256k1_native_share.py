"""secp256k1_native_share: of the rows the window's `secp256k1.pack`
stages packed, the share in chunks whose pack was the ONE C call
(`native.secp256k1_pack`: SHA-256, the batched inverse of s, u1, u2,
limbs and digits of the chunk): the summed `rows` of the packs that
carry `native` = 1 over the summed `rows` of all that carry the arg. 0
means every chunk went through `ecdsa_kernel.pack_batch`'s Python loop
(the library did not build, or a key or signature had a wrong length).
Nothing, not 0, where no `secp256k1.pack` record carries `native` (a
parent of the PR that added the arg) or the program keeps no stage
args."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "commit_p50_ms"
STAGE, ARG = "secp256k1.pack", "native"


def read(obs):
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    packs = [(r[4].get("rows", 0), r[4][ARG]) for r in recs or ()
             if r[0] == STAGE and ARG in r[4]]
    total = sum(rows for rows, _ in packs)
    if not total:
        return None
    return 100.0 * sum(rows for rows, native in packs
                       if native == 1) / total
