"""Programs JAX built inside the window per step: compiled by the backend
or loaded from the persistent compilation cache, each counted on the
program span that was innermost when JAX built it (``built + loaded`` of
every record in the window, over its ``sentinel.step`` spans).  Nothing to
read from a program without these spans."""
UNIT = "programs/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "model step: models/model.py forward, decode"
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
    return sum(r.built + r.loaded for r in recs) / steps
