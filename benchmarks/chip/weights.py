"""Seeded random weights, made on the device in one jitted call.

``make`` returns the weights in a plain layout (one array per kind of
matrix, layers stacked on the leading axis), in the dtype they are served
in.  ``to_program`` hands the same arrays to the program in its parameter
tree; the plain reference (``reference.py``) reads the plain layout.  Both
sides make their weights from the seed, so the reference takes nothing the
program made.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp


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


NORM_STD = 0.1      # norm weights are 1 + w, w ~ N(0, NORM_STD)


def key_of(seed: int):
    """A PRNG key for any whole-number seed, also those past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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
    keys = jax.random.split(key, len(shapes))
    return {name: (jax.random.normal(k, shape, dt) * scale).astype(dt)
            for k, (name, (shape, scale)) in zip(keys, sorted(shapes.items()))}


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
