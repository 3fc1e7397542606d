"""The ``paged_decode`` kernel's share of its roofline over the traced
window: the least time of every call (the larger of its operations over
peak bf16 FLOP/s and its bytes over HBM bandwidth, from ``flops.py``: the
valid tokens of the slots that decode, plus query and output) over the
kernel's device time."""
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
            f, b = flops.paged_decode_call(run.shape, s.decode_lengths)
            least += run.shape.layers * flops.least_seconds(f, b, run.peak)[0]
    return 100.0 * least / t
