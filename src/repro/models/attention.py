"""Attention: GQA/MQA with causal / sliding-window / prefix-LM masks, logit
softcap, QK-norm, RoPE, KV caches — plus DeepSeek MLA (compressed latent cache).

One code path serves train (full seq), prefill (full seq + cache write) and
decode (q_len=1 against a cache). Grouped einsums avoid materializing repeated
KV heads.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL, MLA
from repro.models.layers import (Param, apply_rope, dense_init, rmsnorm,
                                 softcap, yarn_softmax_scale)
from repro.sharding import constrain

NEG_INF = -2.0 ** 20


# ----------------------------------------------------------------- masks ----

def attn_bias(q_pos, kv_pos, *, window: int = 0, prefix_len: int = 0,
              kv_len_valid=None):
    """Additive bias (..., Sq, Skv) from position vectors.

    q_pos: (B, Sq) or (Sq,); kv_pos: (Skv,).
    window > 0: sliding-window causal. prefix_len > 0: bidirectional prefix.
    kv_len_valid: (B,) number of valid cache entries (decode).
    """
    q = q_pos[..., :, None].astype(jnp.int32)
    k = kv_pos[None, :].astype(jnp.int32)
    ok = k <= q
    if window:
        ok &= (q - k) < window
    if prefix_len:
        ok |= k < prefix_len
    if kv_len_valid is not None:
        ok &= k < kv_len_valid[..., None, None]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ------------------------------------------------------------------- GQA ----

def init_attention(key, cfg, dtype=jnp.float32):
    H, KV, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, H * hd, ("embed", "heads"), dtype),
        "wk": dense_init(ks[1], d, KV * hd, ("embed", "kv_heads"), dtype),
        "wv": dense_init(ks[2], d, KV * hd, ("embed", "kv_heads"), dtype),
        "wo": dense_init(ks[3], H * hd, d, ("heads", "embed"), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = Param(jnp.ones((hd,), dtype), (None,))
        p["k_norm"] = Param(jnp.ones((hd,), dtype), (None,))
    return p


def _gqa_core(q, k, v, bias, softcap_val: float):
    """q: (B,Sq,KV,G,hd); k,v: (B,Skv,KV,hd); bias: (B|1, Sq, Skv)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = softcap(scores, softcap_val)
    scores = scores + bias[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out


def attention(params, cfg, x, positions, *, kind: str = ATTN,
              cache: Optional[dict] = None, cache_index=None,
              theta: Optional[float] = None,
              paged_view: Optional[dict] = None) -> Tuple[jax.Array, Optional[dict]]:
    """x: (B, Sq, d). cache: {"k","v"} fixed (B, Smax, KV, hd) buffers.

    Returns (out, updated_cache). cache_index: scalar write offset (decode).

    A cache holding the persistent page pools (``"k_hot"``,
    kvcache.PagedKVPools) selects the pools path: ``paged_view`` then
    carries the serving engine's page table, tiers and page size, decode
    reads KV through ``ops.paged_decode_attention`` (``_pool_decode_core``)
    and a prefill chunk through a gather of the slot's table row
    (``_pool_prefill_core``, ``paged_view["prefill"]``).
    """
    B, Sq, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV
    window = cfg.sliding_window if kind == LOCAL else 0
    if theta is None:
        theta = cfg.rope_theta if (kind == LOCAL or not cfg.rope_theta_global) \
            else cfg.rope_theta_global

    q = (x @ params["wq"]).reshape(B, Sq, H, hd)
    k = (x @ params["wk"]).reshape(B, Sq, KV, hd)
    v = (x @ params["wv"]).reshape(B, Sq, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    q = q.reshape(B, Sq, KV, G, hd)

    if cache is not None and "k_hot" in cache:
        # persistent page pools ARE the cache (kvcache.PagedKVPools): write
        # the new KV straight into its physical hot pages and read back
        # through the page table — no dense buffer exists on this path
        core = _pool_prefill_core if (
            paged_view is not None and paged_view.get("prefill")) \
            else _pool_decode_core
        out, new_cache = core(cfg, q, k, v, cache, cache_index,
                              paged_view, window, positions)
        out = out.reshape(B, Sq, H * hd)
        out = constrain(out, ("batch", "seq", "heads"))
        return out @ params["wo"], new_cache

    if cache is not None:
        # cache stores K/V with heads folded (B, Smax, KV*hd) for shardability
        kf = k.reshape(B, Sq, KV * hd)
        vf = v.reshape(B, Sq, KV * hd)
        if cache_index is not None and getattr(cache_index, "ndim", 0) >= 1:
            # per-slot write offsets (continuous batching): scatter row-wise
            rows = jnp.arange(B)
            k_all = cache["k"].at[rows, cache_index].set(kf[:, 0])
            v_all = cache["v"].at[rows, cache_index].set(vf[:, 0])
        else:
            off = cache_index if cache_index is not None else 0
            k_all = jax.lax.dynamic_update_slice_in_dim(cache["k"], kf, off, 1)
            v_all = jax.lax.dynamic_update_slice_in_dim(cache["v"], vf, off, 1)
        kv_pos = jnp.arange(k_all.shape[1])
        bias = attn_bias(positions, kv_pos, window=window,
                         prefix_len=cfg.num_prefix_tokens if cfg.prefix_lm else 0)
        new_cache = {"k": constrain(k_all, ("batch", "kv_seq", "kv_heads")),
                     "v": constrain(v_all, ("batch", "kv_seq", "kv_heads"))}
        Smax = k_all.shape[1]
        k_use = k_all.reshape(B, Smax, KV, hd)
        v_use = v_all.reshape(B, Smax, KV, hd)
    else:
        pos = positions[0] if positions.ndim > 1 else positions
        bias = attn_bias(positions, pos, window=window,
                         prefix_len=cfg.num_prefix_tokens if cfg.prefix_lm else 0)
        k_use, v_use, new_cache = k, v, None

    if bias.ndim == 2:
        bias = bias[None]
    out = _gqa_core(q, k_use, v_use, bias, cfg.attn_softcap)
    out = out.reshape(B, Sq, H * hd)
    out = constrain(out, ("batch", "seq", "heads"))
    return out @ params["wo"], new_cache


def _pool_prefill_core(cfg, q, k, v, cache, cache_index, paged_view, window,
                       positions):
    """Suffix/chunk prefill straight into the persistent page pools.

    One admitted slot (B == 1), ``Sq`` prompt tokens starting at logical
    position ``cache_index`` (a traced scalar).  ``paged_view`` carries the
    slot's own page-table row ``page_table (1, max_pages)`` / ``page_tier``
    plus ``{"prefill": True}`` — the python-bool dispatch flag ``attention``
    reads (it never becomes a traced value).  The new rows are scattered
    into the slot's physical hot pages, then attention gathers the FULL
    table row back (Skv = max_pages * page_tokens = max_seq), reading each
    page from the hot or cold pool by tier.  Gathering the full row keeps
    every reduction shape identical to the dense prefill path, which is
    what makes the computed rows bit-identical to a full-prompt prefill:
    rows beyond the valid region are finite stale data masked to exactly
    zero probability by ``attn_bias`` (exp(x + NEG_INF) == 0.0 in float32),
    the same way the dense path masks its zero-filled tail.  Shared-prefix
    pages below ``cache_index`` are read, never written — the engine caps
    the start offset so the write region covers only private pages.  The
    returned cache holds the hot pools only: prefill never writes a cold
    page.
    """
    B, Sq, KV, G, hd = q.shape
    assert B == 1, "pool prefill admits one slot at a time"
    page = paged_view["page_tokens"]
    table = paged_view["page_table"]           # (1, max_pages) this slot
    tier = paged_view["page_tier"]
    pos = jnp.asarray(cache_index, jnp.int32) \
        + jnp.arange(Sq, dtype=jnp.int32)
    phys = table[0, pos // page]               # physical hot page per token
    off = pos % page
    k_hot = cache["k_hot"].at[phys, :, off].set(k.reshape(Sq, KV, hd))
    v_hot = cache["v_hot"].at[phys, :, off].set(v.reshape(Sq, KV, hd))
    new_cache = {"k_hot": k_hot, "v_hot": v_hot}
    # full-row gather: (max_pages, KV, page, hd) -> (1, max_seq, KV, hd);
    # out-of-pool indices clamp and are discarded by the tier select.  A
    # forward without staged cold pools maps no cold page: hot pages only.
    k_all, v_all = k_hot[table[0]], v_hot[table[0]]
    if "k_cold" in cache:
        sel = (tier[0] != 1)[:, None, None, None]
        k_all = jnp.where(sel, k_all, cache["k_cold"][table[0]])
        v_all = jnp.where(sel, v_all, cache["v_cold"][table[0]])
    Skv = k_all.shape[0] * page
    k_all = jnp.swapaxes(k_all, 1, 2).reshape(1, Skv, KV, hd)
    v_all = jnp.swapaxes(v_all, 1, 2).reshape(1, Skv, KV, hd)
    bias = attn_bias(positions, jnp.arange(Skv), window=window)
    if bias.ndim == 2:
        bias = bias[None]
    out = _gqa_core(q, k_all, v_all, bias, cfg.attn_softcap)
    return out, new_cache


def _pool_decode_core(cfg, q, k, v, cache, cache_index, paged_view, window,
                      positions=None):
    """Decode attention with the persistent page pools as the cache.

    ``cache`` holds one attention layer's pools ({"k_hot","v_hot","k_cold",
    "v_cold"}, kvcache.PagedKVPools layout); ``paged_view`` carries the
    layer-independent page table / tier arrays (cached by the engine,
    re-uploaded only on layout deltas), the active-slot mask, and the
    garbage-page index.  The new token's KV is scattered into each slot's
    physical write page (inactive slots are redirected to the garbage page so
    lockstep decode can never corrupt a page a live slot references — the
    engine's pre-step CoW guarantees every active write page is exclusive),
    then attention reads the pools through ops.paged_decode_attention.
    Returns (out (B,1,KV,G,hd)-shaped, new_cache) — the new cache holds
    the hot pools only: decode never writes below a boundary.
    """
    from repro.kernels import ops as kernel_ops

    B, Sq, KV, G, hd = q.shape
    assert Sq == 1 and paged_view is not None and cache_index is not None, \
        "pool-form caches are decode-only (the engine prefills densely)"
    page = paged_view["page_tokens"]
    table_arr = paged_view["page_table"]
    tier_arr = paged_view["page_tier"]
    ci = jnp.asarray(cache_index, jnp.int32)
    ci = ci if ci.ndim >= 1 else jnp.broadcast_to(ci, (B,))
    rows = jnp.arange(B)
    phys = table_arr[rows, ci // page]
    active = paged_view.get("active")
    if active is not None:
        phys = jnp.where(active, phys, paged_view["garbage_page"])
    off = ci % page
    k_hot = cache["k_hot"].at[phys, :, off].set(k.reshape(B, KV, hd))
    v_hot = cache["v_hot"].at[phys, :, off].set(v.reshape(B, KV, hd))
    # the cold pools are read-only here (decode never writes below a
    # boundary) and are not returned; a forward without staged cold pools
    # maps no cold page, and the hot pools stand in for the cold operands
    out = kernel_ops.paged_decode_attention(
        q.reshape(B, KV * G, hd), k_hot, v_hot,
        cache.get("k_cold", k_hot), cache.get("v_cold", v_hot), table_arr,
        tier_arr, ci + 1, window=window, softcap_val=cfg.attn_softcap)
    return out, {"k_hot": k_hot, "v_hot": v_hot}


# ------------------------------------------------------------------- MLA ----

def init_mla(key, cfg, dtype=jnp.float32):
    d, H = cfg.d_model, cfg.num_heads
    dn, dr, dv, dc = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, H * (dn + dr), ("embed", "heads"), dtype),
        "w_dkv": dense_init(ks[1], d, dc, ("embed", None), dtype),
        "w_krope": dense_init(ks[2], d, dr, ("embed", None), dtype),
        "w_uk": dense_init(ks[3], dc, H * dn, (None, "heads"), dtype),
        "w_uv": dense_init(ks[4], dc, H * dv, (None, "heads"), dtype),
        "wo": dense_init(ks[5], H * dv, d, ("heads", "embed"), dtype),
    }
    if cfg.kv_norm:
        p["kv_norm"] = Param(jnp.zeros((dc,), dtype), (None,))
    return p


def mla_softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's ``mscale ** 2``."""
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 \
        * yarn_softmax_scale(cfg.rope_yarn)


def mla_attention(params, cfg, x, positions, *, cache: Optional[dict] = None,
                  cache_index=None, paged_view: Optional[dict] = None,
                  **_) -> Tuple[jax.Array, Optional[dict]]:
    """DeepSeek-V2 MLA. Cache holds the *compressed* latent (B, S, dc) + shared
    rope key (B, S, dr) — the paper's KV-cache compression; K/V are expanded
    from the latent at use time.  The latent is RMS-normed when
    ``cfg.kv_norm`` (kv_a_layernorm); the rope dims are YaRN-scaled when
    ``cfg.rope_yarn``.

    On the persistent page pools (a cache with ``ckv_hot``,
    kvcache.PagedKVPools) the latent pages are the cache: prefill writes
    through the slot's page-table row and attends over the gathered row
    (``_mla_pool_prefill``); decode absorbs ``w_uk`` into the query and
    reads the pools through the ``latent_decode`` kernel
    (``_mla_pool_decode``)."""
    B, Sq, d = x.shape
    H = cfg.num_heads
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim

    q = (x @ params["wq"]).reshape(B, Sq, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_yarn)

    ckv = x @ params["w_dkv"]                           # (B,Sq,dc)
    if cfg.kv_norm:
        ckv = rmsnorm(ckv, params["kv_norm"], cfg.norm_eps, plus_one=True)
    krope = apply_rope((x @ params["w_krope"])[:, :, None, :], positions,
                       cfg.rope_theta, cfg.rope_yarn)[:, :, 0, :]  # shared head

    if cache is not None and "ckv_hot" in cache:
        if paged_view is not None and paged_view.get("prefill"):
            out, new_cache = _mla_pool_prefill(params, cfg, q_nope, q_rope,
                                               ckv, krope, cache, cache_index,
                                               paged_view, positions)
        else:
            out, new_cache = _mla_pool_decode(params, cfg, q_nope, q_rope,
                                              ckv, krope, cache, cache_index,
                                              paged_view)
        out = constrain(out, ("batch", "seq", "heads"))
        return out @ params["wo"], new_cache

    if cache is not None:
        if cache_index is not None and getattr(cache_index, "ndim", 0) >= 1:
            rows = jnp.arange(B)
            ckv_all = cache["ckv"].at[rows, cache_index].set(ckv[:, 0])
            kr_all = cache["krope"].at[rows, cache_index].set(krope[:, 0])
            kv_pos = jnp.arange(ckv_all.shape[1])
        elif cache_index is not None:
            ckv_all = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, cache_index, 1)
            kr_all = jax.lax.dynamic_update_slice_in_dim(cache["krope"], krope, cache_index, 1)
            kv_pos = jnp.arange(ckv_all.shape[1])
        else:
            ckv_all = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, 0, 1)
            kr_all = jax.lax.dynamic_update_slice_in_dim(cache["krope"], krope, 0, 1)
            kv_pos = jnp.arange(ckv_all.shape[1])
        new_cache = {"ckv": constrain(ckv_all, ("batch", "kv_seq", None)),
                     "krope": constrain(kr_all, ("batch", "kv_seq", None))}
    else:
        ckv_all, kr_all, new_cache = ckv, krope, None
        kv_pos = positions[0] if positions.ndim > 1 else positions

    out = _mla_core(params, cfg, q_nope, q_rope, ckv_all, kr_all,
                    attn_bias(positions, kv_pos))
    out = constrain(out, ("batch", "seq", "heads"))
    return out @ params["wo"], new_cache


def _mla_core(params, cfg, q_nope, q_rope, ckv_all, kr_all, bias):
    """Attention in the expanded form: ``k_nope`` and ``v`` from the whole
    latent (B, Skv, dc), scores against ``[k_nope, krope]``.  Returns
    (B, Sq, H * dv)."""
    B, Sq, H, dn = q_nope.shape
    dv = cfg.v_head_dim
    Skv = ckv_all.shape[1]
    k_nope = (ckv_all @ params["w_uk"]).reshape(B, Skv, H, dn)
    v = (ckv_all @ params["w_uv"]).reshape(B, Skv, H, dv)

    scale = mla_softmax_scale(cfg)
    scores = (jnp.einsum("bqhd,bshd->bhqs", q_nope.astype(jnp.float32),
                         k_nope.astype(jnp.float32)) +
              jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                         kr_all.astype(jnp.float32))) * scale
    scores = scores + (bias[None] if bias.ndim == 2 else bias[:, None])
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v).reshape(
        B, Sq, H * dv)


def _mla_pool_prefill(params, cfg, q_nope, q_rope, ckv, krope, cache,
                      cache_index, paged_view, positions):
    """Suffix/chunk prefill of one slot (B == 1) straight into the latent
    page pools — ``_pool_prefill_core``'s contract for the latent: the new
    rows are scattered into the slot's physical hot pages through its
    page-table row, then attention reads the FULL row back (hot or staged
    cold pool by tier), so every shape matches the dense prefill's max_seq
    buffer.  Returns (out (1, Sq, H * dv), the hot pools)."""
    B, Sq = ckv.shape[:2]
    assert B == 1, "pool prefill admits one slot at a time"
    page = paged_view["page_tokens"]
    table = paged_view["page_table"]           # (1, max_pages) this slot
    tier = paged_view["page_tier"]
    pos = jnp.asarray(cache_index, jnp.int32) \
        + jnp.arange(Sq, dtype=jnp.int32)
    phys = table[0, pos // page]
    off = pos % page
    ckv_hot = cache["ckv_hot"].at[phys, off].set(ckv[0])
    kr_hot = cache["krope_hot"].at[phys, off].set(krope[0])
    ckv_all, kr_all = ckv_hot[table[0]], kr_hot[table[0]]
    if "ckv_cold" in cache:
        sel = (tier[0] != 1)[:, None, None]
        ckv_all = jnp.where(sel, ckv_all, cache["ckv_cold"][table[0]])
        kr_all = jnp.where(sel, kr_all, cache["krope_cold"][table[0]])
    Skv = ckv_all.shape[0] * page
    out = _mla_core(params, cfg, q_nope, q_rope,
                    ckv_all.reshape(1, Skv, -1), kr_all.reshape(1, Skv, -1),
                    attn_bias(positions, jnp.arange(Skv)))
    return out, {"ckv_hot": ckv_hot, "krope_hot": kr_hot}


def _mla_pool_decode(params, cfg, q_nope, q_rope, ckv, krope, cache,
                     cache_index, paged_view):
    """Absorbed decode over the latent page pools.  The new token's latent
    row and rope key are scattered into each slot's physical write page
    (inactive slots into the garbage page, as ``_pool_decode_core``); the
    query is absorbed into the latent, ``q_lat = q_nope @ w_uk^T`` per
    head, so the ``latent_decode`` kernel scores ``q_lat . ckv + q_rope .
    krope`` and returns ``softmax @ ckv``, which ``w_uv`` takes out of the
    latent.  Nothing is expanded from the cache.  Returns (out (B, 1,
    H * dv), the hot pools)."""
    from repro.kernels import ops as kernel_ops

    B, Sq, H, dn = q_nope.shape
    assert Sq == 1 and paged_view is not None and cache_index is not None, \
        "pool-form caches are decode-only (the engine prefills densely)"
    dc, dv = cfg.kv_lora_rank, cfg.v_head_dim
    page = paged_view["page_tokens"]
    table_arr = paged_view["page_table"]
    tier_arr = paged_view["page_tier"]
    ci = jnp.asarray(cache_index, jnp.int32)
    ci = ci if ci.ndim >= 1 else jnp.broadcast_to(ci, (B,))
    phys = table_arr[jnp.arange(B), ci // page]
    active = paged_view.get("active")
    if active is not None:
        phys = jnp.where(active, phys, paged_view["garbage_page"])
    off = ci % page
    ckv_hot = cache["ckv_hot"].at[phys, off].set(ckv[:, 0])
    kr_hot = cache["krope_hot"].at[phys, off].set(krope[:, 0])
    f32 = jnp.float32
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0],
                       params["w_uk"].reshape(dc, H, dn),
                       preferred_element_type=f32).astype(q_nope.dtype)
    o_lat = kernel_ops.latent_decode_attention(
        q_lat, q_rope[:, 0], ckv_hot, kr_hot,
        cache.get("ckv_cold", ckv_hot), cache.get("krope_cold", kr_hot),
        table_arr, tier_arr, ci + 1, scale=mla_softmax_scale(cfg))
    out = jnp.einsum("bhc,chv->bhv", o_lat, params["w_uv"].reshape(dc, H, dv),
                     preferred_element_type=f32).astype(q_nope.dtype)
    return out.reshape(B, 1, H * dv), {"ckv_hot": ckv_hot, "krope_hot": kr_hot}
