"""What every family's plain float32 reference shares.

A family's reference (``families/<family>.py``, ``logits_at``) is straight
``jax.numpy`` in float32 with every contraction at ``Precision.HIGHEST``;
the bf16 weights are widened exactly.  No kernel, no cache, no batching,
and nothing imported from the program.  It runs one sequence, padded to a
fixed length so that one program serves every request (a causal mask makes
the padding invisible to earlier positions), and returns the logits at the
positions asked for (``padded``).

With ``fp8=True`` every matrix product's operands are first rounded to
float8 (e4m3, one scale per tensor, ``fp8``): the control computed in the
precision below the configurations' bf16, which the comparison must fail.
``gaps`` is the number compared.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, b, to_fp8: bool):
    if to_fp8:
        a, b = fp8(a), fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rms(x, w, eps):
    """RMSNorm with the weight stored as ``w`` in ``x * (1 + w)``."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def rope(x, pos, theta):
    """x: (T, n, hd); rotate-half RoPE at positions pos (T,)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def padded(logits, tokens, pos, length: int, n_pos: int,
           to_fp8: bool) -> np.ndarray:
    """``logits(tokens, pos, to_fp8)`` of the sequence ``tokens`` padded to
    ``length`` and of ``pos`` padded to ``n_pos`` entries, so that every
    call has one shape; the rows of the positions asked for, on the host."""
    tokens = np.asarray(tokens, np.int32)
    pos = np.asarray(pos, np.int32)
    if tokens.shape[0] > length or pos.shape[0] > n_pos:
        raise ValueError(f"sequence {tokens.shape[0]} > {length} or "
                         f"{pos.shape[0]} positions > {n_pos}")
    tp = np.zeros(length, np.int32)
    tp[:tokens.shape[0]] = tokens
    pp = np.zeros(n_pos, np.int32)
    pp[:pos.shape[0]] = pos
    with jax.default_matmul_precision("highest"):
        out = logits(jnp.asarray(tp), jnp.asarray(pp), to_fp8)
    return np.asarray(out)[:pos.shape[0]]


def gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """How far below the reference's best logit each token's logit lies,
    position by position."""
    tokens = np.asarray(tokens)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]
