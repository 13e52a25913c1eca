"""setup_s: process start to the first measured operation: imports,
fixtures, table build, prime, warm-up of the cell's own shapes, and in
a run that compiles, compilation."""
UNIT, BETTER, SOURCE = "s", "lower", "host_clock"


def read(obs):
    return obs.get("setup_s")
