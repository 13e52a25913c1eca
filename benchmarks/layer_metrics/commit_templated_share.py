"""commit_templated_share: of the rows the window's `ed25519.pack`
stages packed, the share in chunks whose sign-bytes never existed as
Python objects: the summed `rows` of the packs that carry `templated`
= 1 (built in C from the commit's templates and each row's timestamp,
where they are hashed) over the summed `rows` of all that carry the
arg. 0 means every chunk was handed, or had to make, a list of bytes.
Nothing, not 0, where no `ed25519.pack` record carries `templated` (a
parent of the PR that added the arg) or the program keeps no stage
args."""
from harness import stages

LAYER = "crypto batch + host pack"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "commit_p50_ms"
STAGE, ARG = "ed25519.pack", "templated"


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
    return 100.0 * sum(rows for rows, templated in packs
                       if templated == 1) / total
