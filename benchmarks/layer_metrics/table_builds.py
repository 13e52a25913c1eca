"""table_builds: validator-set window tables built or patched inside
the window (`table_cache_stats()` misses). 0 in a cell with one
validator set: a build that left set-up for the window shows here."""
LAYER = "table build"
UNIT, BETTER, SOURCE, MOVES = "count", "lower", "program_counter", "setup_s"


def read(obs):
    return obs.get("counters", {}).get("table_builds")
