"""Unified layer stack: period-of-kinds blocks lowered as ``lax.scan``.

Every arch is ``prologue`` (unstacked layers) + ``num_periods`` repeats of a
``period`` of layer kinds (configs/base.py). Stacked params carry a leading
(num_periods,) dim per slot; the stack lowers to one scan so the HLO is
layer-count-independent, and Sentinel's migration interval maps onto blocks of
periods (core/offload.py regroups the same stacked params into
(n_blocks, periods_per_block, ...) and nests scans with offload at block
boundaries).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL, LSTM, MAMBA, MLA, MLSTM, SHARED_ATTN, SLSTM
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import Param, init_mlp, mlp, rmsnorm
from repro.sharding import constrain


# ------------------------------------------------------------------ init ----

def init_block(key, cfg, kind: str, dtype, *, dense_ff: int = 0):
    """Params for one layer of the given kind. dense_ff>0 forces a dense MLP
    (deepseek prologue)."""
    ks = jax.random.split(key, 4)
    norm = lambda: Param(jnp.zeros((cfg.d_model,), dtype), ("embed",))
    if kind in (ATTN, LOCAL, SHARED_ATTN):
        p = {"ln1": norm(), "attn": attn_mod.init_attention(ks[0], cfg, dtype),
             "ln2": norm()}
        if cfg.moe is not None and not dense_ff:
            p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[1], cfg.d_model, dense_ff or cfg.d_ff, dtype)
        return p
    if kind == MLA:
        p = {"ln1": norm(), "mla": attn_mod.init_mla(ks[0], cfg, dtype),
             "ln2": norm()}
        if cfg.moe is not None and not dense_ff:
            p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[1], cfg.d_model, dense_ff or cfg.d_ff, dtype)
        return p
    if kind == MAMBA:
        return {"ln1": norm(), "mamba": ssm_mod.init_mamba(ks[0], cfg, dtype)}
    if kind == MLSTM:
        return {"ln1": norm(), "mlstm": xlstm_mod.init_mlstm(ks[0], cfg, dtype)}
    if kind == SLSTM:
        return {"ln1": norm(), "slstm": xlstm_mod.init_slstm(ks[0], cfg, dtype)}
    if kind == LSTM:
        return {"lstm": xlstm_mod.init_lstm(ks[0], cfg, dtype)}
    raise ValueError(kind)


def stack_trees(trees: List[Any]):
    """Stack a list of Param trees along a new leading (num_periods,) axis."""
    def stack(*leaves):
        if isinstance(leaves[0], Param):
            return Param(jnp.stack([l.value for l in leaves]),
                         ("layers",) + tuple(leaves[0].axes))
        return jnp.stack(leaves)
    return jax.tree.map(stack, *trees, is_leaf=lambda x: isinstance(x, Param))


def init_stack(key, cfg, dtype):
    """Returns {"prologue": [...], "slots": [stacked per period-slot], "shared": ...}."""
    out: Dict[str, Any] = {}
    keys = jax.random.split(key, 3)
    if cfg.prologue:
        pk = jax.random.split(keys[0], len(cfg.prologue))
        out["prologue"] = [init_block(pk[i], cfg, kind, dtype, dense_ff=cfg.prologue_d_ff)
                           for i, kind in enumerate(cfg.prologue)]
    slots = []
    for s, kind in enumerate(cfg.period):
        if kind == SHARED_ATTN:
            slots.append({})  # weights live in out["shared"], one copy
            continue
        sk = jax.random.split(jax.random.fold_in(keys[1], s), cfg.num_periods)
        slots.append(stack_trees([init_block(sk[p], cfg, kind, dtype)
                                  for p in range(cfg.num_periods)]))
    out["slots"] = slots
    if SHARED_ATTN in cfg.period:
        out["shared"] = init_block(keys[2], cfg, SHARED_ATTN, dtype,
                                   dense_ff=cfg.d_ff)
    return out


# ----------------------------------------------------------------- apply ----

def apply_block(params, cfg, kind: str, x, positions, *, cache=None,
                cache_index=None, decode=False, dense_ff: int = 0,
                paged_view=None):
    """One layer. Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in (ATTN, LOCAL, SHARED_ATTN, MLA):
        h = rmsnorm(x, params["ln1"], cfg.norm_eps, plus_one=True)
        # explicit full-seq boundary: under sequence-parallel rules this is
        # the all-gather point (residual stays seq-sharded, attention sees
        # the whole sequence); a no-op otherwise
        h = constrain(h, ("batch", "seq", "embed"))
        if kind == MLA:
            a, new_cache = attn_mod.mla_attention(
                params["mla"], cfg, h, positions, cache=cache,
                cache_index=cache_index, paged_view=paged_view)
        else:
            a, new_cache = attn_mod.attention(
                params["attn"], cfg, h, positions,
                kind=ATTN if kind == SHARED_ATTN else kind,
                cache=cache, cache_index=cache_index, paged_view=paged_view)
        x = x + a
        h = rmsnorm(x, params["ln2"], cfg.norm_eps, plus_one=True)
        h = constrain(h, ("batch", "seq", "embed"))
        if "moe" in params:
            f, aux = moe_mod.moe_mlp(params["moe"], cfg, h, cfg.act)
        else:
            f = mlp(params["mlp"], h, cfg.act)
        x = x + f
        return constrain(x, ("batch", "seq_res", "embed")), new_cache, aux
    if kind == MAMBA:
        h = rmsnorm(x, params["ln1"], cfg.norm_eps, plus_one=True)
        y, new_cache = ssm_mod.mamba_block(params["mamba"], cfg, h,
                                           cache=cache, decode=decode)
        return constrain(x + y, ("batch", "seq_res", "embed")), new_cache, aux
    if kind == MLSTM:
        h = rmsnorm(x, params["ln1"], cfg.norm_eps, plus_one=True)
        y, new_cache = xlstm_mod.mlstm_block(params["mlstm"], cfg, h,
                                             cache=cache, decode=decode)
        return constrain(x + y, ("batch", "seq_res", "embed")), new_cache, aux
    if kind == SLSTM:
        h = rmsnorm(x, params["ln1"], cfg.norm_eps, plus_one=True)
        y, new_cache = xlstm_mod.slstm_block(params["slstm"], cfg, h,
                                             cache=cache, decode=decode)
        return constrain(x + y, ("batch", "seq_res", "embed")), new_cache, aux
    if kind == LSTM:
        y, new_cache = xlstm_mod.lstm_block(params["lstm"], cfg, x,
                                            cache=cache, decode=decode)
        return y, new_cache, aux
    raise ValueError(kind)


def _period_body(cfg, stack_params, shared_params, x, positions, caches,
                 cache_index, decode, paged_view=None):
    """Apply one period (all slots in order). caches: list per slot or None."""
    new_caches: List[Any] = []
    aux_total = jnp.zeros((), jnp.float32)
    for s, kind in enumerate(cfg.period):
        p = shared_params if kind == SHARED_ATTN else stack_params[s]
        c = caches[s] if caches is not None else None
        x, nc, aux = apply_block(p, cfg, kind, x, positions, cache=c,
                                 cache_index=cache_index, decode=decode,
                                 paged_view=paged_view)
        new_caches.append(nc)
        aux_total = aux_total + aux
    return x, new_caches, aux_total


def stack_forward(params, cfg, x, positions, *, caches=None, cache_index=None,
                  decode: bool = False, remat_policy=None,
                  unroll_periods: bool = False, mi_periods: int = 1,
                  tag_block_out: bool = False, paged_view=None):
    """Run prologue + scanned periods.

    params: raw value tree (Param wrappers stripped). caches: {"prologue": [...],
    "slots": [stacked per slot]} or None. Returns (x, new_caches, aux).

    Sentinel integration (core/offload.py):
      - mi_periods: the migration interval in periods. Periods are grouped
        into blocks of this size (outer scan over blocks, inner over periods);
        block boundaries are where long-lived residuals are saved/offloaded
        and everything inside a block is recomputed in backward (the
        reserved-pool analogue).
      - remat_policy: jax.checkpoint policy applied to the *block* body —
        e.g. save_and_offload_only_these_names(["block_out"]).
      - tag_block_out: checkpoint_name the block carry so the policy can
        offload it to pinned_host.
    """
    aux_total = jnp.zeros((), jnp.float32)
    new_pro: List[Any] = []
    if cfg.prologue:
        for i, kind in enumerate(cfg.prologue):
            c = caches["prologue"][i] if caches is not None else None
            x, nc, aux = apply_block(params["prologue"][i], cfg, kind, x, positions,
                                     cache=c, cache_index=cache_index, decode=decode,
                                     dense_ff=cfg.prologue_d_ff,
                                     paged_view=paged_view)
            new_pro.append(nc)
            aux_total = aux_total + aux

    shared = params.get("shared")
    slot_params = params["slots"]
    slot_caches = caches["slots"] if caches is not None else None

    if unroll_periods:
        # plain python loop (profiling mode: per-layer named_scopes)
        new_slot_caches = [] if slot_caches is not None else None
        for pidx in range(cfg.num_periods):
            pp = [jax.tree.map(lambda a: a[pidx], sp) for sp in slot_params]
            cc = ([jax.tree.map(lambda a: a[pidx], sc) if sc is not None else None
                   for sc in slot_caches] if slot_caches is not None else None)
            with jax.named_scope(f"period_{pidx}"):
                x, ncs, aux = _period_body(cfg, pp, shared, x, positions, cc,
                                           cache_index, decode, paged_view)
            aux_total = aux_total + aux
            if new_slot_caches is not None:
                new_slot_caches.append(ncs)
        if new_slot_caches is not None:
            per_slot = [stacked_from([ncs[s] for ncs in new_slot_caches])
                        for s in range(len(cfg.period))]
        else:
            per_slot = None
        return x, _pack_caches(cfg, new_pro, per_slot, caches), aux_total

    def body(carry, inputs):
        x, aux = carry
        sp, sc = inputs
        x, ncs, a = _period_body(cfg, sp, shared, x, positions, sc,
                                 cache_index, decode, paged_view)
        return (x, aux + a), ncs

    xs = (slot_params, slot_caches if slot_caches is not None
          else [None] * len(cfg.period))

    if mi_periods <= 1:
        if remat_policy is not None:
            body = jax.checkpoint(body, policy=remat_policy)
        (x, aux), new_slot_caches = jax.lax.scan(body, (x, aux_total), xs)
        return x, _pack_caches(cfg, new_pro, new_slot_caches, caches), aux

    # ---- Sentinel MI blocking: scan over blocks of mi_periods periods ----
    P = cfg.num_periods
    assert P % mi_periods == 0, (
        f"num_periods {P} not divisible by migration interval {mi_periods}")
    nb = P // mi_periods
    xs_blocked = jax.tree.map(
        lambda a: a.reshape((nb, mi_periods) + a.shape[1:]), xs)

    def block_body(carry, inputs):
        (x2, aux2), ncs = jax.lax.scan(body, carry, inputs)
        if tag_block_out:
            from jax.ad_checkpoint import checkpoint_name
            x2 = checkpoint_name(x2, "block_out")
        return (x2, aux2), ncs

    if remat_policy is not None:
        block_body = jax.checkpoint(block_body, policy=remat_policy)

    (x, aux), ncs_blocked = jax.lax.scan(block_body, (x, aux_total), xs_blocked)
    new_slot_caches = None
    if slot_caches is not None:
        new_slot_caches = jax.tree.map(
            lambda a: a.reshape((nb * mi_periods,) + a.shape[2:]), ncs_blocked)
    return x, _pack_caches(cfg, new_pro, new_slot_caches, caches), aux


def stacked_from(trees: List[Any]):
    if trees and trees[0] is None:
        return None
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


def _pack_caches(cfg, new_pro, new_slots, caches):
    if caches is None:
        return None
    return {"prologue": new_pro, "slots": new_slots}
