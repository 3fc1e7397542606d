"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window."""
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device: TPU v5e"
MOVES = "out_tok_per_s"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
