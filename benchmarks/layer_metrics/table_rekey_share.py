"""table_rekey_share: of the validator-set tables the window made (the
`table.make` stages: one a table the cache lacked), the share made by
rekeying a cached table of the same keys, taken as a set (`path` =
"rekeyed": a power change, or a re-sort by power, with no curve column
computed) rather than patched or built (`table_builds`). Nothing, not
0, where no `table.make` record carries `path` in the window (a parent
of the PR that added the stage, or a window that made no table)."""
from harness import stage_args

LAYER = "table build"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "replay_rate"
STAGE, ARG = "table.make", "path"


def read(obs):
    paths = stage_args.values(obs, STAGE, ARG)
    if not paths:
        return None
    return 100.0 * sum(1 for p in paths if p == "rekeyed") / len(paths)
