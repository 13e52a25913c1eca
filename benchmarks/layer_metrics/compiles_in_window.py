"""compiles_in_window: backend compiles and persistent-cache loads
inside the measured window (`deviceledger.counters()` delta). Must be
0: a shape the warm-up missed moves work from set-up into the window."""
LAYER = "JAX runtime"
UNIT, BETTER, SOURCE, MOVES = "count", "lower", "program_counter", "setup_s"


def read(obs):
    c = obs.get("compile")
    return None if c is None else c["compiles"] + c["pcache_hits"]
