"""Mixture-of-Experts: top-k router + grouped capacity-based dispatch/combine.

GShard/GSPMD-style: tokens are split into groups of ``group_size``; each group
dispatches at most C = group_size*k*capacity_factor/E tokens per expert through
a one-hot einsum, experts run a gated MLP on (G, E, C, d), and a weighted
combine einsum scatters results back. Grouping bounds the dispatch tensor to
T * group_size * k * factor elements (vs T^2-ish ungrouped) and keeps the
group dim aligned with the data mesh axes while experts shard over "model"
(expert parallelism) — GSPMD inserts the all-to-all.

Shared experts (DeepSeek) run as a plain dense MLP on every token.

``MoEConfig.dropless`` selects the held-expert layer (``held_moe``) instead:
the layer is told which experts it holds (``first_expert`` / ``num_held``,
one shard of an expert-parallel group), routes over all ``num_experts``,
and computes only its own experts' part of the result, for every token
routed to them — no capacity, no dropped token, static shapes.  Nothing
stands in for the experts other shards hold.  The gate weights are the
config's choice (``norm_topk_prob``, ``route_topk``).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Param, act_fn, dense_init, init_mlp, mlp
from repro.sharding import constrain


def init_moe(key, cfg, dtype=jnp.float32):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    E, F = m.held, m.d_ff             # the router spans all num_experts

    def bank(k, din, dout, axes):
        w = jax.random.normal(k, (E, din, dout), dtype) * (din ** -0.5)
        return Param(w, axes)

    p = {
        "router": dense_init(ks[0], d, m.num_experts, ("embed", None), dtype),
        "wi": bank(ks[1], d, F, ("experts", "embed", "expert_mlp")),
        "wg": bank(ks[2], d, F, ("experts", "embed", "expert_mlp")),
        "wo": bank(ks[3], F, d, ("experts", "expert_mlp", "embed")),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks[4], d, F * m.num_shared_experts, dtype)
    return p


def route_topk(logits, k: int, normalize: bool) -> Tuple[jax.Array, jax.Array]:
    """The top-``k`` experts of router ``logits`` (..., E): ``(weights
    (..., k), indices (..., k))``.  ``normalize`` (``MoEConfig.
    norm_topk_prob``): the weights are the softmax over the chosen k
    logits, i.e. the chosen experts' probabilities renormalised to sum to
    1; otherwise they are the chosen experts' probabilities under the
    softmax over all E, as DeepSeek-V2 publishes its gate."""
    if normalize:
        vals, idx = jax.lax.top_k(logits, k)
        return jax.nn.softmax(vals, axis=-1), idx
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)


def _aux_loss(m, idx, gates):
    """Switch-style load balance: E * sum(frac_tokens_e * frac_prob_e)."""
    axes = tuple(range(idx.ndim - 1))
    frac_tokens = jnp.mean(jax.nn.one_hot(idx, m.num_experts,
                                          dtype=jnp.float32),
                           axis=axes + (idx.ndim - 1,))
    frac_prob = jnp.mean(gates, axis=axes)
    return m.num_experts * jnp.sum(frac_tokens * frac_prob) \
        * m.router_aux_weight


def moe_mlp(params, cfg, x, act: str):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    m = cfg.moe
    if m.dropless:
        return held_moe(params, cfg, x, act)
    B, S, d = x.shape
    T = B * S
    gsz = min(m.group_size, T)
    assert T % gsz == 0, f"tokens {T} not divisible by moe group size {gsz}"
    G = T // gsz
    E, K = m.num_experts, m.experts_per_token
    C = max(K, int(m.capacity_factor * gsz * K / E))

    xt = x.reshape(G, gsz, d)
    xt = constrain(xt, ("batch", None, "embed"))
    logits = jnp.einsum("gtd,de->gte", xt, params["router"]).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    weights, idx = route_topk(logits, K, m.norm_topk_prob)       # (G,gsz,K)

    # per-(group, expert) running count -> position within capacity buffer
    onehot_i = jax.nn.one_hot(idx, E, dtype=jnp.int32)           # (G,gsz,K,E)
    flat = onehot_i.reshape(G, gsz * K, E)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(G, gsz, K, E)
    pos = jnp.sum(pos * onehot_i, axis=-1)                       # (G,gsz,K)
    keep = (pos < C).astype(xt.dtype)

    oh_e = jax.nn.one_hot(idx, E, dtype=xt.dtype)                # (G,gsz,K,E)
    oh_c = jax.nn.one_hot(pos, C, dtype=xt.dtype)                # (G,gsz,K,C)
    disp = jnp.einsum("gtke,gtkc,gtk->gtec", oh_e, oh_c, keep)   # (G,gsz,E,C)
    comb = jnp.einsum("gtke,gtkc,gtk->gtec", oh_e, oh_c,
                      keep * weights.astype(xt.dtype))

    ex_in = jnp.einsum("gtd,gtec->gecd", xt, disp)               # (G,E,C,d)
    ex_in = constrain(ex_in, ("batch", "experts", "capacity", "embed"))
    h = act_fn(act)(jnp.einsum("gecd,edf->gecf", ex_in, params["wg"])) * \
        jnp.einsum("gecd,edf->gecf", ex_in, params["wi"])
    h = constrain(h, ("batch", "experts", "capacity", "expert_mlp"))
    ex_out = jnp.einsum("gecf,efd->gecd", h, params["wo"])
    ex_out = constrain(ex_out, ("batch", "experts", "capacity", "embed"))
    out = jnp.einsum("gecd,gtec->gtd", ex_out, comb).reshape(B, S, d)

    if "shared" in params:
        out = out + mlp(params["shared"], x, act)

    return out, _aux_loss(m, idx, gates)


def held_moe(params, cfg, x, act: str):
    """The held-expert layer: x (B, S, d) -> (out (B, S, d), aux_loss).

    The router keeps all ``num_experts`` outputs and picks ``k`` per token
    (``route_topk`` with the config's normalisation), in float32 at full
    precision, as published.  Each token's combine weight for a held
    expert ``first_expert + j`` is its gate weight where the router chose
    that expert, else 0; the held experts run as one ``held_experts``
    kernel call (kernels/held_experts.py) over every token, so no token is
    dropped and no shape depends on the routing.  The shared experts run
    on every token.  The result is this shard's part of the layer: the
    parts the other shards' experts add are not here."""
    from repro.kernels import ops as kernel_ops
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    logits = jnp.dot(xt.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gates = jax.nn.softmax(logits, axis=-1)
    weights, idx = route_topk(logits, m.experts_per_token, m.norm_topk_prob)
    held = m.first_expert + jnp.arange(m.held)
    comb = jnp.sum(jnp.where(idx[:, :, None] == held, weights[:, :, None],
                             0.0), axis=1)                        # (T, held)
    out = kernel_ops.held_experts(xt, comb, params["wg"], params["wi"],
                                  params["wo"], act=act).reshape(B, S, d)
    if "shared" in params:
        out = out + mlp(params["shared"], x, act)
    return out, _aux_loss(m, idx, gates)
