"""Mean host time of ``ContinuousBatcher.step()`` over the window's steps
that ran prefill (the engine's ``step_prefill_tokens`` entry > 0)."""
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "scheduler: serve/engine.py ContinuousBatcher"
MOVES = "ttft_p50_ms"


def read(run):
    t = [s.t1 - s.t0 for s in run.steps if s.prefill_tokens > 0]
    return sum(t) / len(t) * 1e3 if t else None
