"""ops.paged_decode_attention wired into the model attention layer: with a
plan, the serving engine's decode reads KV through the persistent page
pools (hot/cold + per-slot page table) and serves exactly the all-HBM
reference's tokens; ``pack_kv_pools`` lays a dense cache out as pools."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.configs.base import get_config
from repro.core.hardware import TPU_V5E
from repro.models import model
from repro.models.layers import split_params
from repro.serve import engine


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def test_paged_kernel_batcher_matches_reference(setup):
    """End to end: ContinuousBatcher with a plan decodes through the pools
    and produces exactly the tokens of the all-HBM reference run."""
    cfg, params = setup
    max_seq, slots = 32, 2
    requests = [(7, 6), (9, 5), (6, 7)]
    trace = engine.serve_trace_for(get_config("smollm-360m"), requests,
                                   slots=slots, layer_group=8)
    plan = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    plan = dataclasses.replace(plan, hot_window=max_seq // 2,
                               slot_hot_windows=[4, 8], page_tokens=4)

    def run(p):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p)
        key = jax.random.PRNGKey(3)
        for plen, d in requests:
            key, sub = jax.random.split(key)
            b.submit(jax.random.randint(sub, (plen,), 0,
                                        cfg.vocab_size).astype(jnp.int32), d)
        return b.run(), b

    base, _ = run(None)
    paged, b = run(plan)
    assert base == paged
    assert len(base) == len(requests)
    # the persistent pools really are the cache: boundaries advanced in the
    # page table, pool data moved, and nothing was ever dense-re-packed
    assert b.pool is not None and b.caches is None
    assert any(b.ptable.cold_tokens(s) > 0 for s in range(slots))
    assert b.pool.stats["repacks"] == 0
    assert b.pool.stats["page_copies"] > 0
    b.ptable.check()


def test_paged_view_respects_page_table_tiering(setup):
    """pack_kv_pools splits at the per-slot boundaries the engine derives:
    cold pages land in the cold pool, and the table covers the buffer."""
    from repro.kernels.paged_decode import pack_kv_pools
    cfg, _ = setup
    B, S, page = 2, 16, 4
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    k = jax.random.normal(jax.random.PRNGKey(0), (B, S, KV, hd))
    v = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
    kh, vh, kc, vc, table, tier = pack_kv_pools(k, v, [8, 4], page)
    assert int(tier.sum()) == (8 + 4) // page       # cold pages counted
    assert kc.shape[0] == (8 + 4) // page
    assert table.shape == (B, S // page)
    # every logical page resolves inside its pool
    for b in range(B):
        for i in range(S // page):
            pool = kc if int(tier[b, i]) else kh
            assert 0 <= int(table[b, i]) < pool.shape[0]
