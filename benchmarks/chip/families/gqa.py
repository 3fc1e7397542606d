"""The dense GQA decoder: smollm-360m and internlm2-1.8b.

Embedding, then per layer RMSNorm, GQA attention with RoPE (rotate-half,
the config's theta) under a causal mask, a residual, RMSNorm, a SwiGLU MLP
and a residual; a final RMSNorm and the head (the embedding table when
tied).  The program runs it as one stacked period of ``attn`` layers.

Weights: one array per kind of matrix, layers stacked on the leading axis,
drawn normal with std ``1/sqrt(fan_in)`` (embedding and head
``d_model^-0.5``).  As published, RMSNorm scales by a weight ``g``; the
weights here are drawn as ``g = 1 + w`` with ``w ~ N(0, NORM_STD)``, which
is how the program stores them.  ``norm_eps`` is the configuration file's
``rms_norm_eps``, which ``program_config`` also sets in the program.

The interface is the one ``families/__init__.py`` states.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import (HIGHEST, fp8 as _fp8, mm, padded,
                                      rms, rope)
from benchmarks.chip.weights import NORM_STD, draw, key_of

# published key -> the program's ``ModelConfig`` field, checked equal
HF_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta",
           "tie_word_embeddings": "tie_embeddings", "hidden_act": "act"}


def program_config(file: dict, rehearse: bool):
    """The registry's configuration of ``file["arch"]``, checked against
    ``HF_KEYS``.  Every GQA configuration runs at its published size, so
    this family applies no cut: a key under ``reduced`` exits."""
    from repro.configs.base import get_config
    name = file["arch"]
    if file.get("reduced"):
        raise SystemExit(f"{name}: family gqa cannot apply the cuts "
                         f"{file['reduced']!r}; it applies none")
    cfg = get_config(name)
    for key, attr in HF_KEYS.items():
        if key in file and file[key] != getattr(cfg, attr):
            raise SystemExit(f"{name}: {key}={file[key]!r} but the "
                             f"program runs {attr}={getattr(cfg, attr)!r}")
    if cfg.head_dim != file["hidden_size"] // file["num_attention_heads"]:
        raise SystemExit(f"{name}: head_dim {cfg.head_dim}")
    if cfg.dtype != file["serving"]["dtype"]:
        raise SystemExit(f"{name}: dtype {cfg.dtype}")
    cfg = dataclasses.replace(cfg, norm_eps=float(file["rms_norm_eps"]))
    if rehearse:
        cfg = dataclasses.replace(cfg.reduced(), dtype=cfg.dtype)
    return cfg


@dataclass(frozen=True)
class Shape:
    """What the weights and the reference need to know of a model."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int              # real vocabulary (logits are taken over it)
    padded_vocab: int       # rows of the embedding table the program holds
    tied: bool
    rope_theta: float
    norm_eps: float
    dtype: str = "bfloat16"

    @classmethod
    def of(cls, cfg) -> "Shape":
        return cls(layers=cfg.num_layers, d_model=cfg.d_model,
                   heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                   head_dim=cfg.head_dim, d_ff=cfg.d_ff,
                   vocab=cfg.vocab_size, padded_vocab=cfg.padded_vocab,
                   tied=cfg.tie_embeddings, rope_theta=cfg.rope_theta,
                   norm_eps=cfg.norm_eps, dtype=cfg.dtype)


# --------------------------------------------------------------- weights --

@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, s: Shape):
    dt = jnp.bfloat16 if s.dtype == "bfloat16" else jnp.float32
    L, d, ff = s.layers, s.d_model, s.d_ff
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    shapes = {
        "embed": ((s.padded_vocab, d), d ** -0.5),
        "final_norm": ((d,), NORM_STD),
        "ln1": ((L, d), NORM_STD), "ln2": ((L, d), NORM_STD),
        "wq": ((L, d, qd), d ** -0.5), "wk": ((L, d, kvd), d ** -0.5),
        "wv": ((L, d, kvd), d ** -0.5), "wo": ((L, qd, d), qd ** -0.5),
        "wg": ((L, d, ff), d ** -0.5), "wi": ((L, d, ff), d ** -0.5),
        "wd": ((L, ff, d), ff ** -0.5),
    }
    if not s.tied:
        shapes["head"] = ((s.padded_vocab, d), d ** -0.5)
    return draw(key, shapes, dt)


def make(shape: Shape, seed: int) -> dict:
    return _make(key_of(seed), shape)


def to_program(w: dict, shape: Shape) -> dict:
    """The program's parameter tree (``repro.models.model``) over the same
    arrays: norms are stored as ``w`` in ``x * (1 + w)``, one stacked
    period of attention layers."""
    tree = {
        "embed": {"table": w["embed"]},
        "final_norm": w["final_norm"],
        "stack": {"slots": [{
            "ln1": w["ln1"], "ln2": w["ln2"],
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "mlp": {"wi": w["wi"], "wg": w["wg"], "wo": w["wd"]},
        }]},
    }
    if not shape.tied:
        tree["head"] = w["head"]
    return tree


def param_count(shape: Shape) -> dict:
    """Matrix parameters: of the layer stack, and of the head."""
    s = shape
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    per_layer = s.d_model * (2 * qd + 2 * kvd) + 3 * s.d_model * s.d_ff
    return {"stack": s.layers * per_layer, "head": s.vocab * s.d_model}


# ------------------------------------------------------------- reference --

def _layer(s: Shape, fp8: bool, x, lw):
    T = x.shape[0]
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    G = H // KV
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    pos = jnp.arange(T)
    h = rms(x, lw["ln1"], s.norm_eps)
    q = rope(mm(h, lw["wq"], fp8).reshape(T, H, hd), pos, s.rope_theta)
    k = rope(mm(h, lw["wk"], fp8).reshape(T, KV, hd), pos, s.rope_theta)
    v = mm(h, lw["wv"], fp8).reshape(T, KV, hd)
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
    x = x + mm(o.reshape(T, H * hd), lw["wo"], fp8)
    h = rms(x, lw["ln2"], s.norm_eps)
    f = jax.nn.silu(mm(h, lw["wg"], fp8)) * mm(h, lw["wi"], fp8)
    return x + mm(f, lw["wd"], fp8), None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits_at(s: Shape, w, tokens, pos, fp8):
    x = w["embed"][tokens].astype(jnp.float32)
    stack = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo", "wg",
                               "wi", "wd")}
    x, _ = jax.lax.scan(functools.partial(_layer, s, fp8), x, stack)
    h = rms(x[pos], w["final_norm"].astype(jnp.float32), s.norm_eps)
    head = (w["embed"] if s.tied else w["head"])[:s.vocab]
    return mm(h, head.astype(jnp.float32).T, fp8)


def logits_at(s: Shape, w: dict, tokens, pos, length: int, n_pos: int,
              fp8: bool = False):
    """Logits (len(pos), vocab) of the sequence ``tokens`` at positions
    ``pos``, padded to ``length`` tokens and ``n_pos`` positions."""
    return padded(functools.partial(_logits_at, s, w), tokens, pos, length,
                  n_pos, fp8)


# ------------------------------------------- counts (as flops.py counts) --

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


def paged_decode_step(s: Shape, lengths) -> tuple:
    """(operations, bytes) of every ``paged_decode`` call of one decode
    step: one call in each layer."""
    flops, nbytes = paged_decode_call(s, lengths)
    return s.layers * flops, s.layers * nbytes


def decode_step(s: Shape, lengths) -> int:
    """Operations of one decode forward of the active slots: every matrix
    of the stack and the head once per slot, plus attention over each
    slot's context in every layer."""
    p = param_count(s)
    attn, _ = paged_decode_step(s, lengths)
    return 2 * (p["stack"] + p["head"]) * len(lengths) + attn
