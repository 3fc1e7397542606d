"""Device time of the ``held_experts`` kernel per step of the traced
window: every expert layer's call, in decode and in prefill.  The kernel's
own time only: the copies XLA makes of each layer's three expert banks
out of the stacked weights before the call (``%dynamic-slice_bitcast_
fusion.<n>``, a name the latent pools' slices share) are not in it.
Nothing to read from a program without the kernel."""
UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "kernel: kernels/held_experts.py"
MOVES = "itl_p50_ms"


def read(run):
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace.op_s.items() if "held_experts" in k)
    return t / len(run.steps) * 1e3 if t else None
