"""Device time of the staging program (``jit(_cold_stage)`` in the trace's
``XLA Modules`` line) per step of the traced window."""
UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "cold staging: PagedKVPools.stage_cold"
MOVES = "itl_p50_ms"


def read(run):
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace.module_s.items() if "_cold_stage" in k)
    return t / len(run.steps) * 1e3 if t else None
