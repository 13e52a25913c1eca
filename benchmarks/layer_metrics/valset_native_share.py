"""valset_native_share: of the validators whose merkle roots the
window's `valset.hash` stages built, the share in roots built by the
ONE C call (`native.valset_root`: leaves, hashes and tree over the
set's keys and powers): the summed `n` of the records that carry
`native` = 1 over the summed `n` of all that carry the arg. 0 means
every root was built from `Validator.bytes()` leaves in Python (the
library did not build, or a key had another length). Nothing, not 0,
where no `valset.hash` record carries `native` (a parent of the PR
that added the arg) or the program keeps no stage args."""
from harness import stages

LAYER = "validator set"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "commit_p50_ms"
STAGE, ARG = "valset.hash", "native"


def read(obs):
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    roots = [(r[4].get("n", 0), r[4][ARG]) for r in recs or ()
             if r[0] == STAGE and ARG in r[4]]
    total = sum(n for n, _ in roots)
    if not total:
        return None
    return 100.0 * sum(n for n, native in roots if native == 1) / total
