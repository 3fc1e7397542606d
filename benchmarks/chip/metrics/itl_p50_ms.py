"""Median gap between consecutive output tokens of one request, over every
such gap that ends in the window."""
import numpy as np

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    gaps = run.token_gaps()
    return float(np.percentile(gaps, 50)) * 1e3 if gaps else None
