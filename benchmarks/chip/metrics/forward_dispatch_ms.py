"""Host time a step inside the decode forward's call (the program's
``sentinel.forward`` spans in the window, over its ``sentinel.step``
spans): the dispatch of the one compiled decode program
(``engine.make_pool_decode_step``), which traces, lowers and loads nothing
after warm-up.  Nothing to read from a program without these spans."""
UNIT = "ms/step"
BETTER = "lower"
SOURCE = "host_clock"
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
    ns = sum(r.end_ns - r.start_ns for r in recs
             if r.name == "sentinel.forward")
    return ns / steps / 1e6
