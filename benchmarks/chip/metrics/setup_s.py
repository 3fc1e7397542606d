"""Process start to window open: JAX and chip initialisation, weights,
plan, engine, and warm-up (with compilation, where the cache misses)."""
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
