"""The plain float32 reference of the GQA decoder both configurations share.

Embedding, then per layer RMSNorm, GQA attention with RoPE (rotate-half,
the config's theta) under a causal mask, a residual, RMSNorm, a SwiGLU MLP
and a residual; a final RMSNorm and the head (the embedding table when
tied).  Straight ``jax.numpy`` in float32 with every contraction at
``Precision.HIGHEST``; the bf16 weights are widened exactly.  No kernel, no
cache, no batching, and nothing imported from the program.

As published, RMSNorm scales by a weight ``g``; the weights here are drawn
as ``g = 1 + w`` with ``w ~ N(0, 0.1)``, which is how the program stores
them.  ``norm_eps`` is the configuration file's ``rms_norm_eps``, the
published 1e-5, which the harness also sets in the program.

``logits_at`` runs one sequence, padded to a fixed length so that one
program serves every request (a causal mask makes the padding invisible to
earlier positions), and returns the logits at the positions asked for.
With ``fp8=True`` every matrix product's operands are first rounded to
float8 (e4m3, one scale per tensor): the control computed in the precision
below the configuration's bf16, which the comparison must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.weights import Shape

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, pos, theta):
    """x: (T, n, hd); rotate-half RoPE at positions pos (T,)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Shape, fp8: bool, x, lw):
    T = x.shape[0]
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    G = H // KV
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    pos = jnp.arange(T)
    h = _rms(x, lw["ln1"], s.norm_eps)
    q = _rope(_mm(h, lw["wq"], fp8).reshape(T, H, hd), pos, s.rope_theta)
    k = _rope(_mm(h, lw["wk"], fp8).reshape(T, KV, hd), pos, s.rope_theta)
    v = _mm(h, lw["wv"], fp8).reshape(T, KV, hd)
    q = q.reshape(T, KV, G, hd)
    if fp8:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    scores = jnp.einsum("tkgd,skd->kgts", q, k, precision=HIGHEST) \
        * hd ** -0.5
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    if fp8:
        p = _fp8(p)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(T, H * hd), lw["wo"], fp8)
    h = _rms(x, lw["ln2"], s.norm_eps)
    f = jax.nn.silu(_mm(h, lw["wg"], fp8)) * _mm(h, lw["wi"], fp8)
    return x + _mm(f, lw["wd"], fp8), None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits_at(s: Shape, w, tokens, pos, fp8):
    x = w["embed"][tokens].astype(jnp.float32)
    stack = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo", "wg",
                               "wi", "wd")}
    x, _ = jax.lax.scan(functools.partial(_layer, s, fp8), x, stack)
    h = _rms(x[pos], w["final_norm"].astype(jnp.float32), s.norm_eps)
    head = (w["embed"] if s.tied else w["head"])[:s.vocab]
    return _mm(h, head.astype(jnp.float32).T, fp8)


def logits_at(s: Shape, w: dict, tokens, pos, length: int, n_pos: int,
              fp8: bool = False) -> np.ndarray:
    """Logits (len(pos), vocab) of the sequence ``tokens`` at positions
    ``pos``; the sequence is padded to ``length`` and ``pos`` to
    ``n_pos`` entries so that every call has one shape."""
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
        out = _logits_at(s, w, jnp.asarray(tp), jnp.asarray(pp), fp8)
    return np.asarray(out)[:pos.shape[0]]


def gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """How far below the reference's best logit each token's logit lies,
    position by position."""
    tokens = np.asarray(tokens)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]
