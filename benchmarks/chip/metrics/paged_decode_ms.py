"""Device time of the ``paged_decode`` kernel per step of the traced
window (every layer's call)."""
UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "kernel: kernels/paged_decode.py"
MOVES = "itl_p50_ms"


def read(run):
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace.op_s.items() if "paged_decode" in k)
    return t / len(run.steps) * 1e3 if t else None
