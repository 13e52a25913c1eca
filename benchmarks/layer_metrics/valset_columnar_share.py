"""valset_columnar_share: of the validators in the sets the window's
`valset.build` stages built, the share in sets built column-wise (the
sort, index, total power and proposer round as int64 array work): the
summed `n` of the records that carry `columnar` = 1 over the summed `n`
of all that carry the arg. Below 100 means a set fell back to the
per-member loops (a power or priority near the int64 limits). Nothing,
not 0, where no `valset.build` record carries `columnar` (a parent of
the PR that added the arg) or the program keeps no stage args."""
from harness import stages

LAYER = "validator set"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "commit_p50_ms"
STAGE, ARG = "valset.build", "columnar"


def read(obs):
    from cometbft_tpu.libs import tracing

    if (not hasattr(tracing, "stage_records")
            or stages.in_window(obs) is None):  # no window, clock or ring
        return None
    recs = stages.select(tracing.stage_records(), tracing.stages_dropped(),
                         obs["t0"], obs["t1"])
    sets = [(r[4].get("n", 0), r[4][ARG]) for r in recs or ()
            if r[0] == STAGE and ARG in r[4]]
    total = sum(n for n, _ in sets)
    if not total:
        return None
    return 100.0 * sum(n for n, columnar in sets if columnar == 1) / total
