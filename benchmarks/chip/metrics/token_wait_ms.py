"""Host time a step waiting for the decoded tokens (the program's
``sentinel.sync`` spans around ``jax.device_get`` in the window, over its
``sentinel.step`` spans): what of the device's work the host did not
overlap with its own.  Nothing to read from a program without these
spans."""
UNIT = "ms/step"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "scheduler: serve/engine.py ContinuousBatcher"
MOVES = "itl_p50_ms"


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    recs = obs.records(int(run.t_open * 1e9), int(run.t_close * 1e9))
    steps = sum(1 for r in recs if r.name == "sentinel.step")
    if not steps:
        return None
    ns = sum(r.end_ns - r.start_ns for r in recs
             if r.name == "sentinel.sync")
    return ns / steps / 1e6
