"""Host time a step in the page manager's boundary-advance loops (the
program's ``sentinel.demote`` spans in the window, over its
``sentinel.step`` spans): one hot page copied to host memory per call, in
Python.  Nothing to read from a program without these spans."""
UNIT = "ms/step"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "page manager: models/kvcache.py PagedKVPools"
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
             if r.name == "sentinel.demote")
    return ns / steps / 1e6
