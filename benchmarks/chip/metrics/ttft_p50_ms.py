"""Median time to first token: from a request's submission to the end of
the step that delivered its first token, over every request submitted in
the window whose first token came inside it."""
import numpy as np

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    waits = run.first_token_waits()
    return float(np.percentile(waits, 50)) * 1e3 if waits else None
