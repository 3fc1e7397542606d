"""Operations and bytes the algorithm needs, computed from shapes.

Counted as useful work: the tokens that are valid in the slots that
decode, not the pages a kernel's grid visits or the slots that idle.
A multiply-add is two operations; exponentials are not counted.
"""
from __future__ import annotations

from benchmarks.chip.weights import Shape, param_count


def paged_decode_call(s: Shape, lengths, dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one ``paged_decode`` call, one layer: each
    active slot's query heads attend over its ``lengths[b]`` valid tokens.
    Bytes are the K and V of those tokens plus the query read and the
    output written."""
    ctx = sum(int(n) for n in lengths)
    flops = 4 * s.heads * s.head_dim * ctx                  # QK^T and PV
    kv = 2 * s.kv_heads * s.head_dim * ctx * dtype_bytes
    q_out = 2 * len(lengths) * s.heads * s.head_dim * dtype_bytes
    return flops, kv + q_out


def decode_step(s: Shape, lengths) -> int:
    """Operations of one decode forward of the active slots: every matrix
    of the stack and the head once per slot, plus attention over each
    slot's context in every layer."""
    p = param_count(s)
    attn, _ = paged_decode_call(s, lengths)
    return 2 * (p["stack"] + p["head"]) * len(lengths) + s.layers * attn


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The roofline's least time and what bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
