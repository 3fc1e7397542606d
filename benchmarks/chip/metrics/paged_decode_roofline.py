"""The ``paged_decode`` kernel's share of its roofline over the traced
window: the least time of every call (the larger of its operations over
peak bf16 FLOP/s and its bytes over HBM bandwidth, from the family's
``paged_decode_step``: the valid tokens of the slots that decode, plus
query and output, in every call of the step) over the kernel's device
time."""
from benchmarks.chip import flops

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernel: kernels/paged_decode.py"
MOVES = "itl_p50_ms"


def read(run):
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace.op_s.items() if "paged_decode" in k)
    if not t:
        return None
    least = 0.0
    for s in run.steps:
        if s.decode_lengths:
            f, b = run.family.paged_decode_step(run.shape, s.decode_lengths)
            least += flops.least_seconds(f, b, run.peak)[0]
    return 100.0 * least / t
