"""Core layers: norms, rotary embeddings, MLPs, embeddings, param init.

Params are plain pytrees (nested dicts of jnp arrays). Initializers build a
parallel tree of logical axis names (for sharding) via the ``Param`` wrapper;
``split_params`` separates values from axes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import constrain


@dataclasses.dataclass
class Param:
    value: jax.Array
    axes: Tuple[Optional[str], ...]


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), tuple(p.axes)),
    lambda axes, ch: Param(ch[0], axes),
)


def split_params(tree):
    """Split a Param tree into (values, logical_axes)."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(x, Param))
    vals = [p.value if isinstance(p, Param) else p for p in leaves]
    axes = [p.axes if isinstance(p, Param) else (None,) * getattr(p, "ndim", 0)
            for p in leaves]
    return jax.tree.unflatten(treedef, vals), jax.tree.unflatten(treedef, axes)


def _init(key, shape, axes, scale=None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return Param(jax.random.normal(key, shape, dtype) * scale, axes)


def dense_init(key, d_in, d_out, axes, dtype=jnp.float32):
    return _init(key, (d_in, d_out), axes, dtype=dtype)


def rmsnorm(x, w, eps: float = 1e-6, plus_one: bool = False):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = w.astype(jnp.float32)
    y = y * (1.0 + w) if plus_one else y * w
    return y.astype(dt)


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap) if cap else x


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True)}[name]


# ---------------------------------------------------------------- rotary ----

def rope_freqs(head_dim: int, theta: float, yarn=None):
    """Rotary inverse frequencies (head_dim/2,); with ``yarn``
    (configs.base.YarnConfig) the YaRN blend of interpolated (``/ factor``)
    and extrapolated frequencies across its correction range."""
    freqs = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if yarn is None:
        return freqs
    low, high = yarn_correction_range(yarn, head_dim, theta)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = 1.0 - ramp              # 1: keep the dim's own frequency
    return freqs / yarn.factor * (1.0 - extra) + freqs * extra


def yarn_correction_range(yarn, head_dim: int, theta: float) -> tuple:
    """The dims ``[low, high]`` between which YaRN ramps from extrapolated
    to interpolated frequencies (``beta_fast`` / ``beta_slow`` rotations
    over the original context)."""
    def dim(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim(yarn.beta_fast)), 0),
            min(math.ceil(dim(yarn.beta_slow)), head_dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_softmax_scale(yarn) -> float:
    """The factor YaRN multiplies the attention's softmax scale by:
    ``yarn_mscale(factor, mscale_all_dim) ** 2`` (1 without YaRN)."""
    if yarn is None or not yarn.mscale_all_dim:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def apply_rope(x, positions, theta: float, yarn=None):
    """x: (..., seq, heads, head_dim); positions: (..., seq).  With
    ``yarn`` the frequencies are YaRN's and cos/sin scale by
    ``mscale(mscale) / mscale(mscale_all_dim)``."""
    freqs = rope_freqs(x.shape[-1], theta, yarn)                # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., seq, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) / \
            yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- MLP ----

def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, d_model, d_ff, ("embed", "mlp"), dtype),
        "wg": dense_init(k2, d_model, d_ff, ("embed", "mlp"), dtype),
        "wo": dense_init(k3, d_ff, d_model, ("mlp", "embed"), dtype),
    }


def mlp(params, x, act: str):
    h = act_fn(act)(x @ params["wg"]) * (x @ params["wi"])
    h = constrain(h, ("batch", "seq", "mlp"))
    return h @ params["wo"]


# ------------------------------------------------------------- embedding ----

def init_embedding(key, cfg, dtype=jnp.float32):
    n = cfg.padded_vocab
    scale = cfg.d_model ** -0.5
    p = {"table": _init(key, (n, cfg.d_model), ("vocab", "embed"),
                        scale=scale, dtype=dtype)}
    if cfg.num_codebooks:  # musicgen: one table per codebook
        keys = jax.random.split(key, cfg.num_codebooks)
        p["table"] = Param(
            jnp.stack([jax.random.normal(k, (n, cfg.d_model), dtype) * scale
                       for k in keys]),
            (None, "vocab", "embed"))
    return p


def embed(params, cfg, tokens):
    """tokens: (B, S) int32, or (B, S, K) for K codebooks."""
    t = params["table"]
    if cfg.num_codebooks:
        # (B, S, K) codes: index each codebook's table, sum the embeddings
        parts = [jnp.take(t[k], tokens[..., k], axis=0) for k in range(cfg.num_codebooks)]
        x = sum(parts)
    else:
        x = jnp.take(t, tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return constrain(x, ("batch", "seq_res", "embed"))


def unembed(params, cfg, x, head=None):
    """x: (B, S, d) -> logits (B, S, V) (or (B, S, K, V) for codebooks)."""
    if head is not None:
        t = head
    else:
        t = params["table"]
    if cfg.num_codebooks:
        logits = jnp.einsum("bsd,kvd->bskv", x, t)
    else:
        logits = x @ t.T
    logits = softcap(logits, cfg.final_softcap)
    return logits
