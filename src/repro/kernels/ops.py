"""Kernel dispatch: jit'd public ops that pick the Pallas TPU kernel or the
pure-jnp oracle (CPU / debugging) per backend and flag.

``use_pallas(True)`` or env REPRO_USE_PALLAS=1 forces the Pallas path (with
interpret=True automatically on CPU so tests exercise the kernel body).
"""
from __future__ import annotations

import os
from functools import partial

import jax

from repro.kernels import ref as _ref

_FORCE = {"pallas": os.environ.get("REPRO_USE_PALLAS", "") == "1"}


def use_pallas(on: bool = True):
    _FORCE["pallas"] = on


def _pallas_enabled() -> bool:
    return _FORCE["pallas"] or jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, softcap_val=0.0):
    if _pallas_enabled():
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap_val=softcap_val, interpret=_interpret())
    return _ref.attention_ref(q, k, v, causal=causal, window=window,
                              softcap_val=softcap_val)


def decode_attention(q, k_cache, v_cache, lengths, *, window=0, softcap_val=0.0):
    if _pallas_enabled():
        from repro.kernels import decode_attention as da
        return da.decode_attention(q, k_cache, v_cache, lengths, window=window,
                                   softcap_val=softcap_val, interpret=_interpret())
    return _ref.decode_attention_ref(q, k_cache, v_cache, lengths, window=window,
                                     softcap_val=softcap_val)


def paged_decode_attention(q, k_hot, v_hot, k_cold, v_cold, page_table,
                           page_tier, lengths, *, window=0, softcap_val=0.0):
    """Flash-decode over paged, tiered KV pools (hot=device, cold=host).
    See kernels/paged_decode.py for the pool/page-table layout.  The pools
    may be larger than the table addresses (the engine's persistent pools
    carry free pages and a trailing garbage page); the kernel only visits
    pages the table maps for each slot's length."""
    if _pallas_enabled():
        from repro.kernels import paged_decode as pd
        return pd.paged_decode_attention(
            q, k_hot, v_hot, k_cold, v_cold, page_table, page_tier, lengths,
            window=window, softcap_val=softcap_val, interpret=_interpret())
    return _ref.paged_decode_attention_ref(
        q, k_hot, v_hot, k_cold, v_cold, page_table, page_tier, lengths,
        window=window, softcap_val=softcap_val)


def latent_decode_attention(q_lat, q_rope, ckv_hot, kr_hot, ckv_cold,
                            kr_cold, page_table, page_tier, lengths, *,
                            scale):
    """Flash-decode of absorbed MLA queries over paged, tiered latent pools
    (one 576-wide latent head read by every query head).  See
    kernels/paged_decode.py::latent_decode_attention."""
    if _pallas_enabled():
        from repro.kernels import paged_decode as pd
        return pd.latent_decode_attention(
            q_lat, q_rope, ckv_hot, kr_hot, ckv_cold, kr_cold, page_table,
            page_tier, lengths, scale=scale, interpret=_interpret())
    return _ref.latent_decode_attention_ref(
        q_lat, q_rope, ckv_hot, kr_hot, ckv_cold, kr_cold, page_table,
        page_tier, lengths, scale=scale)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _held_experts_pallas(x, comb, wg, wi, wo, act):
    from repro.kernels import held_experts as he
    return he.held_experts(x, comb, wg, wi, wo, act=act,
                           interpret=_interpret())


def _held_fwd(x, comb, wg, wi, wo, act):
    return _held_experts_pallas(x, comb, wg, wi, wo, act), \
        (x, comb, wg, wi, wo)


def _held_bwd(act, res, g):
    _, vjp = jax.vjp(partial(_ref.held_experts_ref, act=act), *res)
    return vjp(g)


_held_experts_pallas.defvjp(_held_fwd, _held_bwd)


def held_experts(x, comb, wg, wi, wo, *, act="silu"):
    """The held experts' gated MLPs over every row, each row weighted by
    its combine weight per expert (kernels/held_experts.py); the gradient
    is the oracle's."""
    if _pallas_enabled():
        return _held_experts_pallas(x, comb, wg, wi, wo, act)
    return _ref.held_experts_ref(x, comb, wg, wi, wo, act=act)


def ssd(x, dt, A, Bm, Cm, *, chunk=256, h0=None):
    if _pallas_enabled():
        from repro.kernels import mamba2 as m2
        return m2.ssd(x, dt, A, Bm, Cm, chunk=chunk, h0=h0, interpret=_interpret())
    return _ref.ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=chunk, h0=h0)


ssd_decode = _ref.ssd_decode_ref     # single-step: pure jnp is already optimal
slstm = _ref.slstm_ref

# mLSTM execution mode: 0 = sequential scan (baseline), N = chunkwise-parallel
# with chunk length N (the xlstm §Perf lever; REPRO_MLSTM_CHUNK or set below)
_MLSTM = {"chunk": int(os.environ.get("REPRO_MLSTM_CHUNK", "0"))}


def mlstm_chunk_mode(chunk: int):
    _MLSTM["chunk"] = chunk


def mlstm(q, k, v, log_i, log_f, *, state=None):
    c = _MLSTM["chunk"]
    if c and q.shape[1] > 1 and q.shape[1] % c == 0:
        return _ref.mlstm_chunked_ref(q, k, v, log_i, log_f, chunk=c,
                                      state=state)
    return _ref.mlstm_ref(q, k, v, log_i, log_f, state=state)
