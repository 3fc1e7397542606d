"""Top-level language model: embeddings -> layer stack -> head, plus the
train / prefill / decode entry points used by the launcher and serve engine.

Modality stubs per the assignment: musicgen consumes 4-codebook token ids
(EnCodec frontend stubbed); paligemma consumes precomputed SigLIP patch
embeddings as a bidirectional prefix + text tokens.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.layers import (Param, dense_init, embed, init_embedding,
                                 rmsnorm, split_params, unembed)
from repro.sharding import constrain


def param_dtype(cfg):
    return jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16


def init_params(key, cfg):
    """Returns a Param tree; use layers.split_params to get (values, axes)."""
    dtype = param_dtype(cfg)
    k_embed, k_stack, k_head, k_vis = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "embed": init_embedding(k_embed, cfg, dtype),
        "final_norm": Param(jnp.zeros((cfg.d_model,), dtype), ("embed",)),
        "stack": transformer.init_stack(k_stack, cfg, dtype),
    }
    if not cfg.tie_embeddings:
        n = cfg.padded_vocab
        shape = (cfg.num_codebooks, n, cfg.d_model) if cfg.num_codebooks \
            else (n, cfg.d_model)
        axes = (None, "vocab", "embed") if cfg.num_codebooks else ("vocab", "embed")
        p["head"] = Param(jax.random.normal(k_head, shape, dtype) *
                          (cfg.d_model ** -0.5), axes)
    if cfg.num_prefix_tokens:  # paligemma: projection of the (stub) patch embeds
        p["vision_proj"] = dense_init(k_vis, cfg.d_model, cfg.d_model,
                                      ("embed", "embed"), dtype)
    return p


def _inputs_to_h(params, cfg, batch):
    """batch: {"tokens": ...} (+ "prefix_embed" for vlm). Returns (h, positions)."""
    tokens = batch["tokens"]
    x = embed(params["embed"], cfg, tokens)
    B = x.shape[0]
    if cfg.num_prefix_tokens and "prefix_embed" in batch:
        pre = batch["prefix_embed"].astype(x.dtype) @ params["vision_proj"]
        x = jnp.concatenate([pre, x], axis=1)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return x, positions


def forward(params, cfg, batch, *, caches=None, cache_index=None,
            decode: bool = False, remat_policy=None, unroll_periods: bool = False,
            mi_periods: int = 1, tag_block_out: bool = False,
            positions=None, paged_view=None) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (logits, new_caches, aux_loss).

    paged_view: with ``caches`` holding the persistent page pools
    (kvcache.PagedKVPools), the serving engine's page table, tiers and page
    size; attention then reads KV through the pools
    (models/attention._pool_decode_core)."""
    with jax.named_scope("boundary_in"):
        if decode:
            x = embed(params["embed"], cfg, batch["tokens"])
            B, S = x.shape[:2]
            if positions is None:
                ci = jnp.asarray(cache_index, jnp.int32)
                positions = (ci[:, None] if ci.ndim >= 1 else
                             jnp.broadcast_to(ci[None, None], (B, S)))
        else:
            x, pos0 = _inputs_to_h(params, cfg, batch)
            if positions is None:      # suffix prefill supplies its own
                positions = pos0

    x, new_caches, aux = transformer.stack_forward(
        params["stack"], cfg, x, positions, caches=caches,
        cache_index=cache_index, decode=decode, remat_policy=remat_policy,
        unroll_periods=unroll_periods, mi_periods=mi_periods,
        tag_block_out=tag_block_out, paged_view=paged_view)

    with jax.named_scope("boundary_head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps, plus_one=True)
        head = params.get("head")
        table = head if head is not None else (
            params["embed"]["table"])
        logits = unembed(params["embed"], cfg, x, head=table)
        logits = constrain(logits, ("batch", "seq", "vocab")
                           if not cfg.num_codebooks else ("batch", "seq", None, "vocab"))
    return logits, new_caches, aux


def loss_fn(params, cfg, batch, *, remat_policy=None, unroll_periods=False,
            mi_periods: int = 1, tag_block_out: bool = False):
    """Causal LM loss (masked to the real vocab; padded logits excluded)."""
    logits, _, aux = forward(params, cfg, batch, remat_policy=remat_policy,
                             unroll_periods=unroll_periods,
                             mi_periods=mi_periods, tag_block_out=tag_block_out)
    labels = batch["labels"]
    V = cfg.padded_vocab
    logits = logits.astype(jnp.float32)
    if V != cfg.vocab_size:  # mask padded vocab entries out of the softmax
        pad = jnp.full((V - cfg.vocab_size,), -1e9, jnp.float32)
        logits = logits.at[..., cfg.vocab_size:].add(pad)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if cfg.num_prefix_tokens:  # vlm: loss only over the text suffix
        nll = nll[:, cfg.num_prefix_tokens:]
    return jnp.mean(nll) + aux


def prefill(params, cfg, batch, max_seq: Optional[int] = None):
    """Run the full prompt, returning (last_logits, caches)."""
    from repro.models import kvcache
    tokens = batch["tokens"]
    B = tokens.shape[0]
    S = tokens.shape[1] + (cfg.num_prefix_tokens if "prefix_embed" in batch else 0)
    caches = kvcache.init_cache(cfg, B, max_seq or S, param_dtype(cfg))
    # prefill writes the first S positions; attention uses full-seq buffers
    logits, new_caches, _ = forward(params, cfg, batch, caches=caches)
    return logits[:, -1], new_caches


def prefill_suffix(params, cfg, batch, *, caches, start, paged_view=None):
    """Run prefill over a prompt *suffix* against pre-existing KV state.

    ``batch["tokens"]`` holds only ``tokens[start:]`` of the prompt; the KV
    of the first ``start`` tokens is already materialized in ``caches`` (a
    shared-prefix donor's physical pages on the pools layout, or a dense
    cache a previous chunk wrote into).  Positions and the cache write
    offset both begin at ``start``, and each new row attends back over the
    whole valid prefix, so the computed rows are bit-identical to the same
    rows of a full-prompt ``prefill`` — the shared-prefix compute skip and
    chunked prefill both reduce to calling this per suffix/chunk.

    On the pools layout ``paged_view`` carries the admitted slot's page-
    table row (plus ``{"prefill": True}``) and ``caches`` is the live
    ``PagedKVPools`` tree: attention writes the suffix KV straight into the
    slot's physical hot pages and reads back through the table
    (models/attention._pool_prefill_core).  Returns
    ``(last_row_logits, new_caches)``.
    """
    tokens = batch["tokens"]
    B, L = tokens.shape[0], tokens.shape[1]
    start = jnp.asarray(start, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None],
                                 (B, L)) + start
    logits, new_caches, _ = forward(params, cfg, batch, caches=caches,
                                    cache_index=start, positions=positions,
                                    paged_view=paged_view)
    return logits[:, -1], new_caches


def decode_step(params, cfg, tokens, caches, cache_index):
    """One token for every sequence. tokens: (B, 1) (or (B, 1, K))."""
    logits, new_caches, _ = forward(params, cfg, {"tokens": tokens},
                                    caches=caches, cache_index=cache_index,
                                    decode=True)
    return logits[:, -1], new_caches
