"""Median, over the requests that finished inside the window (the
program's ``sentinel.request`` records), of the host time of that
request's prefill: its ``sentinel.prefill`` spans, found by their ``rid``
among all records (a prompt may have been admitted before the window
opened) and inside the request's own life.  Nothing to read from a program
without these spans."""
import statistics

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "scheduler: serve/engine.py ContinuousBatcher"
MOVES = "ttft_p50_ms"


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    lo, hi = int(run.t_open * 1e9), int(run.t_close * 1e9)
    recs = obs.records()
    window = [r for r in recs if r.start_ns >= lo and r.end_ns <= hi]
    if not any(r.name == "sentinel.step" for r in window):
        return None
    prefill: dict = {}
    for r in recs:
        if r.name == "sentinel.prefill":
            prefill.setdefault(r.attrs["rid"], []).append(r)
    per_request = [
        sum(p.end_ns - p.start_ns for p in prefill.get(q.attrs["rid"], ())
            if q.start_ns <= p.start_ns and p.end_ns <= q.end_ns)
        for q in recs if q.name == "sentinel.request" and lo < q.end_ns <= hi]
    if not per_request:
        return None
    return statistics.median(per_request) / 1e6
