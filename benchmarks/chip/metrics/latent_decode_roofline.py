"""The ``latent_decode`` kernel's share of its roofline over the traced
window: the least time of every call (the larger of its operations over
peak bf16 FLOP/s and its bytes over HBM bandwidth, from the family's
``latent_decode_step``: the valid latent rows of the slots that decode,
read once for all heads, plus queries and outputs, in every call of the
step) over the kernel's device time."""
from benchmarks.chip import flops

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernel: kernels/paged_decode.py latent_decode"
MOVES = "itl_p50_ms"


def read(run):
    if run.trace is None or not hasattr(run.family, "latent_decode_step"):
        return None
    t = sum(v for k, v in run.trace.op_s.items() if "latent_decode" in k)
    if not t:
        return None
    least = 0.0
    for s in run.steps:
        if s.decode_lengths:
            f, b = run.family.latent_decode_step(run.shape, s.decode_lengths)
            least += flops.least_seconds(f, b, run.peak)[0]
    return 100.0 * least / t
