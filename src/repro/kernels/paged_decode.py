"""Paged flash-decode over tiered KV pools (Pallas TPU).

The serving-time cold-KV read: instead of materializing one dense buffer of
the cold prefix and the hot window before attention, KV lives in
fixed-size sequence *pages* split across two physical pools, head-major so
that one (page, head) block is a whole ``(page, D)`` tile —

  k_hot / v_hot    (n_hot,  KVH, page, D)  device memory (HBM)
  k_cold / v_cold  (n_cold, KVH, page, D)  the cold tier's pages

with a per-slot page table mapping logical page i of slot b to a physical
page in one of the pools:

  page_table (B, NP) int32   physical index into the pool named by the tier
  page_tier  (B, NP) int32   0 = hot pool, 1 = cold pool

Each slot's *cold boundary* is simply the prefix of its tier row that is 1 —
per-slot, not global, which is what kills the page-grain false sharing the
paper argues against: a short slot's pages never ride along when a long
slot's history is demoted.

The pools the kernel reads are **persistent** in the serving engine
(models/kvcache.py::PagedKVPools): decode scatters each new token's KV into
its physical hot page through the same table before the kernel runs, admit /
demote / free mutate single pages, and the table arrays are re-uploaded only
when the PageTable's version changes.  The engine keeps the cold tier in
host memory (``pinned_host``) and stages it into HBM as a named, counted
step before the kernel runs (``PagedKVPools.stage_cold``): this compiler
implements no host->VMEM DMA, and its host->HBM DMA takes only blocks of
8 x 1024 32-bit words (32 KiB), 16x one (page, head) of smollm-360m at
16-token pages.  The ``pool_layout`` / ``gather_pools`` / ``pack_kv_pools``
helpers below build the pool layout *from a dense cache* — the one-shot form
used by model-level parity tests and ad-hoc callers, not by the engine's
steady-state loop (which never re-packs).

The kernel's grid is (batch, kv_head, logical page).  Page tables, tiers and
lengths ride in SMEM as scalar prefetch; each pool operand's BlockSpec
index map resolves logical page i to its physical ``(page, D)`` block, so
the Pallas pipeline double-buffers the page DMAs behind the online-softmax
update.  A page of the other tier repeats its pool's previous block index
(``_block_indices``), and the pipeline skips a copy whose block index did
not change — each step fetches only the page it reads.  Pages outside
``[lo, npages)`` (beyond the slot's length, or before the attention window)
neither compute nor fetch.

Oracle: repro.kernels.ref.paged_decode_attention_ref — the same page loop in
pure jnp, bit-exact against this kernel in interpret mode (same op sequence,
see kernels/decode_attention.masked_scores).

``latent_decode`` is the variant for multi-head latent attention (MLA): one
latent "head" of ``C + R`` lanes per token (``kv_lora_rank`` latent plus
the shared rope key), read by every query head.  Its pools are two, each
page-addressed by the same table as above:

  ckv_hot / ckv_cold  (n, page, C)   the latent rows, also the values
  kr_hot / kr_cold    (n, page, R)   the shared rope key rows

The queries come absorbed into the latent (``q_nope @ w_uk^T``, done by
models/attention.py before the call), so a score is ``q_lat . ckv +
q_rope . kr`` and the output ``softmax(scores) @ ckv`` stays in the latent
for the caller's ``w_uv``.  The grid is (batch, logical page): one page of
both pools per step for all heads at once, with the same index maps and
length skipping as ``paged_decode``.  Oracle:
repro.kernels.ref.latent_decode_attention_ref (bit-exact in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (NEG_INF, masked_scores,
                                            online_softmax_update)


def _kernel(hot_blk, cold_blk, tier_ref, len_ref, q_ref, kh_ref, vh_ref,
            kc_ref, vc_ref, o_ref, acc, m_s, l_s, *, page, window,
            softcap_val):
    b, i = pl.program_id(0), pl.program_id(2)
    del hot_blk, cold_blk                  # consumed by the index maps
    length = len_ref[b]
    npages = pl.cdiv(length, page)
    lo = jnp.maximum(0, (length - window) // page) if window else 0

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when((i >= lo) & (i < npages))
    def _page():
        hot = tier_ref[b, i] == 0
        k = jnp.where(hot, kh_ref[...], kc_ref[...]).astype(jnp.float32)
        v = jnp.where(hot, vh_ref[...], vc_ref[...]).astype(jnp.float32)
        s = masked_scores(q_ref[...].astype(jnp.float32), k, i * page,
                          length, window=window, softcap_val=softcap_val)
        acc[...], m_s[...], l_s[...] = online_softmax_update(
            s, v, acc[...], m_s[...], l_s[...])

    @pl.when(i == pl.num_programs(2) - 1)
    def _final():
        o_ref[...] = (acc[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


def _block_indices(page_table, page_tier, lengths, page, window, n_hot,
                   n_cold):
    """Per (slot, logical page) physical block of each pool for the index
    maps.  Page i reads page ``clip(i, lo, npages - 1)``; where that page
    lives in the other tier, the pool's index repeats the nearest page of
    its own tier in range (the previous one, else the next), so the pipeline
    re-uses the block already in VMEM instead of fetching a page nobody
    reads."""
    B, NP = page_table.shape
    i = jnp.arange(NP, dtype=jnp.int32)[None, :]
    npages = (lengths + page - 1) // page
    lo = jnp.maximum(0, (lengths - window) // page) if window \
        else jnp.zeros_like(lengths)
    in_range = (i >= lo[:, None]) & (i < npages[:, None])
    i_eff = jnp.clip(i, lo[:, None], jnp.maximum(npages - 1, 0)[:, None])

    def blocks(tier, n):
        own = in_range & (page_tier == tier)
        last = jax.lax.cummax(jnp.where(own, i, -1), axis=1)
        nxt = jax.lax.cummin(jnp.where(own, i, NP), axis=1, reverse=True)
        last_at = jnp.take_along_axis(last, i_eff, axis=1)
        next_at = jnp.take_along_axis(nxt, i_eff, axis=1)
        src = jnp.where(last_at >= 0, last_at,
                        jnp.where(next_at < NP, next_at, 0))
        phys = jnp.take_along_axis(page_table, src, axis=1)
        return jnp.clip(phys, 0, n - 1).astype(jnp.int32)

    return blocks(0, n_hot), blocks(1, n_cold)


def paged_decode_attention(q, k_hot, v_hot, k_cold, v_cold, page_table,
                           page_tier, lengths, *, window: int = 0,
                           softcap_val: float = 0.0, interpret: bool = False):
    """q: (B, H, D); pools (n, KVH, page, D); page_table/page_tier (B, NP);
    lengths: (B,) valid tokens per slot (>= 1). Returns (B, H, D)."""
    B, H, D = q.shape
    KVH, page = k_hot.shape[1], k_hot.shape[2]
    NP = page_table.shape[1]
    G = H // KVH
    page_table = page_table.astype(jnp.int32)
    page_tier = page_tier.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    hot_blk, cold_blk = _block_indices(page_table, page_tier, lengths, page,
                                       window, k_hot.shape[0],
                                       k_cold.shape[0])

    qg = q.reshape(B, KVH, G, D)
    kernel = functools.partial(_kernel, page=page, window=window,
                               softcap_val=softcap_val)
    hot_spec = pl.BlockSpec((None, None, page, D),
                            lambda b, h, i, hb, cb, tr, ln: (hb[b, i], h, 0, 0))
    cold_spec = pl.BlockSpec((None, None, page, D),
                             lambda b, h, i, hb, cb, tr, ln: (cb[b, i], h, 0, 0))
    row_spec = pl.BlockSpec((None, None, G, D),
                            lambda b, h, i, *_: (b, h, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, KVH, NP),
            in_specs=[row_spec, hot_spec, hot_spec, cold_spec, cold_spec],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((G, D), jnp.float32),
                            pltpu.VMEM((G,), jnp.float32),
                            pltpu.VMEM((G,), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(hot_blk, cold_blk, page_tier, lengths, qg, k_hot, v_hot, k_cold, v_cold)
    return out.reshape(B, H, D)


def latent_scores(q_lat, q_rope, ckv, kr, base, length, scale: float):
    """Scores of absorbed MLA queries against one latent page,
    ``(q_lat . ckv + q_rope . kr) * scale``, masked beyond ``length``.

    q_lat (..., H, C), q_rope (..., H, R); ckv (..., page, C), kr (...,
    page, R); base: the page's first key position.  Shared by the
    ``latent_decode`` kernel and its oracle, so the two run one op
    sequence."""
    nd = q_lat.ndim
    dims = (((nd - 1,), (nd - 1,)), (tuple(range(nd - 2)),) * 2)
    s = jax.lax.dot_general(q_lat, ckv, dims,
                            preferred_element_type=jnp.float32) \
        + jax.lax.dot_general(q_rope, kr, dims,
                              preferred_element_type=jnp.float32)
    s = s * scale
    kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    length = jnp.asarray(length)
    length = length.reshape(length.shape + (1,) * (s.ndim - length.ndim))
    return jnp.where(kpos < length, s, NEG_INF)


def _latent_kernel(hot_blk, cold_blk, tier_ref, len_ref, ql_ref, qr_ref,
                   ch_ref, kh_ref, cc_ref, kc_ref, o_ref, acc, m_s, l_s, *,
                   page, scale):
    b, i = pl.program_id(0), pl.program_id(1)
    del hot_blk, cold_blk                  # consumed by the index maps
    length = len_ref[b]

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(i < pl.cdiv(length, page))
    def _page():
        hot = tier_ref[b, i] == 0
        ckv = jnp.where(hot, ch_ref[...], cc_ref[...]).astype(jnp.float32)
        kr = jnp.where(hot, kh_ref[...], kc_ref[...]).astype(jnp.float32)
        s = latent_scores(ql_ref[...].astype(jnp.float32),
                          qr_ref[...].astype(jnp.float32), ckv, kr,
                          i * page, length, scale)
        acc[...], m_s[...], l_s[...] = online_softmax_update(
            s, ckv, acc[...], m_s[...], l_s[...])

    @pl.when(i == pl.num_programs(1) - 1)
    def _final():
        o_ref[...] = (acc[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


def latent_decode_attention(q_lat, q_rope, ckv_hot, kr_hot, ckv_cold,
                            kr_cold, page_table, page_tier, lengths, *,
                            scale: float, interpret: bool = False):
    """q_lat (B, H, C), q_rope (B, H, R); pools ckv (n, page, C), kr (n,
    page, R); page_table / page_tier (B, NP); lengths (B,) valid tokens per
    slot (>= 1).  Returns the attention output in the latent, (B, H, C)."""
    B, H, C = q_lat.shape
    R = q_rope.shape[-1]
    page = ckv_hot.shape[1]
    NP = page_table.shape[1]
    page_table = page_table.astype(jnp.int32)
    page_tier = page_tier.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    hot_blk, cold_blk = _block_indices(page_table, page_tier, lengths, page,
                                       0, ckv_hot.shape[0],
                                       ckv_cold.shape[0])

    def pool_spec(width, blk):
        return pl.BlockSpec(
            (None, page, width),
            lambda b, i, hb, cb, tr, ln: ((hb if blk == 0 else cb)[b, i],
                                          0, 0))

    def row_spec(width):
        return pl.BlockSpec((None, H, width), lambda b, i, *_: (b, 0, 0))

    return pl.pallas_call(
        functools.partial(_latent_kernel, page=page, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, NP),
            in_specs=[row_spec(C), row_spec(R), pool_spec(C, 0),
                      pool_spec(R, 0), pool_spec(C, 1), pool_spec(R, 1)],
            out_specs=row_spec(C),
            scratch_shapes=[pltpu.VMEM((H, C), jnp.float32),
                            pltpu.VMEM((H,), jnp.float32),
                            pltpu.VMEM((H,), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_decode",
    )(hot_blk, cold_blk, page_tier, lengths, q_lat, q_rope, ckv_hot, kr_hot,
      ckv_cold, kr_cold)


def pool_layout(cold_tokens, num_pages: int, page_tokens: int):
    """Layer-independent pool layout from per-slot cold boundaries.

    ``cold_tokens`` (len B, concrete ints): per-slot cold boundary in tokens;
    pages fully below the boundary go to the cold pool.  Physical page order
    deliberately interleaves slots (slot-major over logical pages) so tests
    exercise real indirection rather than an identity table.  Returns
    (page_table, page_tier, hot_idx, cold_idx) where the idx tuples list the
    (slot, logical_page) each physical pool page holds, in pool order —
    compute once per decode step, then gather every layer's pools from it.
    """
    B = len(cold_tokens)
    cold_pages = [int(c) // page_tokens for c in cold_tokens]
    hot_idx, cold_idx = [], []            # (b, i) per physical page, in order
    table = [[0] * num_pages for _ in range(B)]
    tier = [[0] * num_pages for _ in range(B)]
    for i in range(num_pages):            # slot-major interleave
        for b in range(B):
            if i < cold_pages[b]:
                table[b][i], tier[b][i] = len(cold_idx), 1
                cold_idx.append((b, i))
            else:
                table[b][i], tier[b][i] = len(hot_idx), 0
                hot_idx.append((b, i))
    return (jnp.asarray(table, jnp.int32), jnp.asarray(tier, jnp.int32),
            tuple(hot_idx), tuple(cold_idx))


def gather_pools(k_cache, v_cache, layout, page_tokens: int):
    """One layer's (k_hot, v_hot, k_cold, v_cold) pools, each
    (n, KVH, page, D), for a shared layout.  k_cache/v_cache: dense
    (B, S, KVH, D)."""
    B, S, KVH, D = k_cache.shape
    assert S % page_tokens == 0, (S, page_tokens)
    NP = S // page_tokens
    _, _, hot_idx, cold_idx = layout
    kp = k_cache.reshape(B, NP, page_tokens, KVH, D).transpose(0, 1, 3, 2, 4)
    vp = v_cache.reshape(B, NP, page_tokens, KVH, D).transpose(0, 1, 3, 2, 4)

    def gather(pages, idx):
        if not idx:
            return jnp.zeros((1, KVH, page_tokens, D), pages.dtype)
        bs = jnp.asarray([b for b, _ in idx])
        ps = jnp.asarray([i for _, i in idx])
        return pages[bs, ps]

    return (gather(kp, hot_idx), gather(vp, hot_idx),
            gather(kp, cold_idx), gather(vp, cold_idx))


def pack_kv_pools(k_cache, v_cache, cold_tokens, page_tokens: int):
    """Pack dense caches (B, S, KVH, D) into the paged pool layout.  Returns
    (k_hot, v_hot, k_cold, v_cold, page_table, page_tier); convenience over
    pool_layout + gather_pools for single-layer callers and tests."""
    layout = pool_layout(cold_tokens, k_cache.shape[1] // page_tokens,
                         page_tokens)
    return (*gather_pools(k_cache, v_cache, layout, page_tokens),
            layout[0], layout[1])
