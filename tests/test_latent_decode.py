"""The ``latent_decode`` kernel and the absorbed MLA decode over latent page
pools.

The kernel in interpret mode is bit-exact against its jnp oracle, with some
pages cold and with all hot.  ``mla_attention`` on the pools (the query
absorbed into the latent, ``w_uk`` before the kernel and ``w_uv`` after it)
gives what the dense, expanded ``mla_attention`` gives for the same cache,
through the oracle and through the kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import ops, ref
from repro.kernels.paged_decode import latent_decode_attention, pool_layout
from repro.models import attention

B, H, C, R, PAGE, NP = 3, 4, 32, 8, 4, 5
TABLE = [[0, 1, 2, 3, 4], [5, 0, 6, 1, 7], [2, 3, 8, 4, 5]]


@pytest.mark.parametrize("tiers", [
    [[1, 1, 0, 0, 0], [1, 0, 0, 0, 0], [0, 0, 0, 0, 0]],
    [[0] * NP] * B,
], ids=["some_cold", "all_hot"])
def test_latent_kernel_is_bit_exact_against_its_oracle(tiers):
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    ql, qr = jax.random.normal(k[0], (B, H, C)), jax.random.normal(k[1], (B, H, R))
    pools = (jax.random.normal(k[2], (9, PAGE, C)),
             jax.random.normal(k[3], (9, PAGE, R)),
             jax.random.normal(k[4], (7, PAGE, C)),
             jax.random.normal(k[5], (7, PAGE, R)))
    table, tier = jnp.asarray(TABLE, jnp.int32), jnp.asarray(tiers, jnp.int32)
    lengths = jnp.asarray([17, 9, 3], jnp.int32)
    got = latent_decode_attention(ql, qr, *pools, table, tier, lengths,
                                  scale=0.3, interpret=True)
    want = ref.latent_decode_attention_ref(ql, qr, *pools, table, tier,
                                           lengths, scale=0.3)
    assert got.shape == (B, H, C)
    assert jnp.array_equal(got, want)


def _cfg():
    return get_config("deepseek-v2-lite-16b").reduced()


def _pack(dense, cold_tokens):
    """Dense (B, S, W) rows as (hot, cold) pools and the layout's table."""
    Bn, S, W = dense.shape
    table, tier, hot_idx, cold_idx = pool_layout(cold_tokens, S // PAGE, PAGE)
    pages = dense.reshape(Bn, S // PAGE, PAGE, W)

    def gather(idx):
        if not idx:
            return jnp.zeros((1, PAGE, W), dense.dtype)
        return pages[jnp.asarray([b for b, _ in idx]),
                     jnp.asarray([i for _, i in idx])]
    return gather(hot_idx), gather(cold_idx), table, tier


@pytest.mark.parametrize("pallas", [False, True], ids=["oracle", "kernel"])
@pytest.mark.parametrize("cold_tokens", [[8, 4], [0, 0]],
                         ids=["some_cold", "all_hot"])
def test_absorbed_pool_decode_matches_dense_mla(cold_tokens, pallas,
                                                monkeypatch):
    cfg = _cfg()
    monkeypatch.setitem(ops._FORCE, "pallas", pallas)
    from repro.models.layers import split_params
    params, _ = split_params(attention.init_mla(jax.random.PRNGKey(1), cfg))
    params["kv_norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                params["kv_norm"].shape)
    S, d = 16, cfg.d_model
    kx = jax.random.split(jax.random.PRNGKey(3), 2)
    prompt = jax.random.normal(kx[0], (2, 12, d))
    empty = {"ckv": jnp.zeros((2, S, cfg.kv_lora_rank)),
             "krope": jnp.zeros((2, S, cfg.qk_rope_dim))}
    _, cache = attention.mla_attention(
        params, cfg, prompt, jnp.broadcast_to(jnp.arange(12), (2, 12)),
        cache=empty)
    ci = jnp.asarray([11, 9], jnp.int32)       # each slot's next position
    x = jax.random.normal(kx[1], (2, 1, d))
    want, dense = attention.mla_attention(params, cfg, x, ci[:, None],
                                          cache=cache, cache_index=ci)

    ckv_hot, ckv_cold, table, tier = _pack(cache["ckv"], cold_tokens)
    kr_hot, kr_cold, _, _ = _pack(cache["krope"], cold_tokens)
    pools = {"ckv_hot": ckv_hot, "krope_hot": kr_hot}
    if any(cold_tokens):
        pools.update(ckv_cold=ckv_cold, krope_cold=kr_cold)
    view = {"page_table": table, "page_tier": tier, "page_tokens": PAGE}
    got, new = attention.mla_attention(params, cfg, x, ci[:, None],
                                       cache=pools, cache_index=ci,
                                       paged_view=view)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the new token's latent row and rope key landed in its hot page
    for b in range(2):
        p = int(table[b, int(ci[b]) // PAGE])
        o = int(ci[b]) % PAGE
        assert jnp.array_equal(new["ckv_hot"][p, o], dense["ckv"][b, ci[b]])
        assert jnp.array_equal(new["krope_hot"][p, o],
                               dense["krope"][b, ci[b]])
