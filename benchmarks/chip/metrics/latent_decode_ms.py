"""Device time of the ``latent_decode`` kernel per step of the traced
window (every layer's call).  Nothing to read from a program without the
kernel."""
UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "kernel: kernels/paged_decode.py latent_decode"
MOVES = "itl_p50_ms"


def read(run):
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace.op_s.items() if "latent_decode" in k)
    return t / len(run.steps) * 1e3 if t else None
