"""The roofline's least time, for operations and bytes a family counts.

Each family counts its own (``families/<family>.py``: ``decode_step``,
and ``paged_decode_step`` where its attention runs that kernel) as useful
work: the tokens that are valid in the slots that decode, not the pages a
kernel's grid visits or the slots that idle.  A multiply-add is two
operations; exponentials are not counted.
"""
from __future__ import annotations


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The roofline's least time and what bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
