"""The page manager holds MLA latent KV as pages: the ``ckv`` / ``krope``
pools of every MLA layer under the one page table, demoted into host
memory and staged back byte for byte, ``cold_stage_bytes`` counting the
bytes read from host memory; the MLA stack admits by pool-direct prefill and serves the
dense path's tokens, tiered and all-fast."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.configs.base import get_config
from repro.core.hardware import TPU_V5E
from repro.models import kvcache, model
from repro.models.layers import split_params
from repro.serve import engine

MAX_SEQ, PAGE = 32, 4


def _cfg(dtype="float32"):
    return dataclasses.replace(get_config("deepseek-v2-lite-16b").reduced(),
                               dtype=dtype)


def test_latent_pool_layout_and_page_size():
    cfg = _cfg()
    pool = kvcache.PagedKVPools(cfg, 2, MAX_SEQ, PAGE)
    entries = list(pool._attn_entries())
    assert len(entries) == cfg.num_layers - cfg.num_periods + 1
    for stacked, e in entries:
        lead = (cfg.num_periods,) if stacked else ()
        assert set(e) == {"ckv_hot", "krope_hot", "ckv_cold", "krope_cold"}
        n = pool.table.n_hot + 1
        assert e["ckv_hot"].shape == lead + (n, PAGE, cfg.kv_lora_rank)
        assert e["krope_hot"].shape == lead + (n, PAGE, cfg.qk_rope_dim)
        assert e["ckv_cold"].sharding.memory_kind == \
            kvcache.host_memory_kind()
    # no layer keeps a dense per-slot cache on the pools path
    assert not [p for p, _ in jax.tree_util.tree_flatten_with_path(
        pool.tree)[0] if "'ckv'" in jax.tree_util.keystr(p)]
    published = get_config("deepseek-v2-lite-16b")
    assert kvcache.pool_widths(published) == (512, 64)
    tpu = types.SimpleNamespace(platform="tpu")
    # the 64-lane rope pool fills 128-lane cold rows at even page sizes
    assert kvcache.pool_page_tokens(published, 15, 2048, tpu) == 16
    assert kvcache.pool_page_tokens(published, 3, 2048, tpu) == 4


def test_latent_pages_demote_and_stage_byte_exact():
    cfg = _cfg("bfloat16")
    pool = kvcache.PagedKVPools(cfg, 2, MAX_SEQ, PAGE, jnp.bfloat16)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    for _, e in pool._attn_entries():
        for name in ("ckv_hot", "krope_hot"):
            e[name] = jax.random.normal(next(keys), e[name].shape,
                                        jnp.bfloat16)
    for _ in range(3):
        pool.table.alloc(1, 0)
    hot = [pool.table.table[1][i] for i in range(2)]
    before = [pool.hot_page(e, s, p) for s, e in pool._attn_entries()
              for p in hot]
    for _ in range(2):
        assert pool.demote_boundary(1)
    assert pool.stats["page_copies"] == 2
    cold = [pool.table.table[1][i] for i in range(2)]
    assert pool.table.tier[1][:2] == [1, 1]

    staged = pool.forward_tree(stage=True)
    it = iter(before)
    for (stacked, e), part in zip(pool._attn_entries(), (
            [("prologue", i) for i, k in enumerate(cfg.prologue)]
            + [("slots", s) for s, k in enumerate(cfg.period)])):
        st = staged[part[0]][part[1]]
        for c in cold:
            want = next(it)
            got = (st["ckv_cold"][:, c], st["krope_cold"][:, c]) if stacked \
                else (st["ckv_cold"][c], st["krope_cold"][c])
            for g, w in zip(got, want):
                assert g.dtype == w.dtype and jnp.array_equal(g, w)
    # the staged prefix: both cold pages lie below it
    per_token = (cfg.kv_lora_rank + cfg.qk_rope_dim) * 2 * cfg.num_layers
    assert max(cold) < pool.staged_pages() <= pool.table.n_cold
    assert pool.stats["cold_stage_bytes"] == \
        pool.staged_pages() * PAGE * per_token


def _stream(cfg, params, hot_window):
    base = get_config("deepseek-v2-lite-16b")
    trace = engine.serve_trace_for(base, [(9, 6), (11, 6)], slots=2,
                                   layer_group=8)
    plan = dataclasses.replace(
        runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes()),
        hot_window=hot_window, slot_hot_windows=[hot_window] * 2,
        page_tokens=PAGE)
    b = engine.ContinuousBatcher(params, cfg, 2, MAX_SEQ, plan=plan)
    assert b._pool_prefill_ok
    for p in PROMPTS:
        b.submit(p, 7)
    return b, b.run()


PROMPTS = [jnp.arange(9, dtype=jnp.int32) % 50 + 3,
           (jnp.arange(3, 14, dtype=jnp.int32) * 7) % 500]


@pytest.mark.parametrize("hot_window", [PAGE, MAX_SEQ],
                         ids=["tiered", "allfast"])
def test_mla_stack_serves_dense_tokens_through_the_pools(hot_window):
    cfg = _cfg()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    b, got = _stream(cfg, params, hot_window)
    want = [list(map(int, engine.generate(params, cfg, {"tokens": p[None]},
                                          7)[0])) for p in PROMPTS]
    assert sorted(got) == sorted(want)
    c = b.counters()
    assert c["admit_page_writes"] > 0 and c["repacks"] == 0
    if hot_window < MAX_SEQ:
        assert c["page_copies"] > 0 and c["cold_stage_bytes"] > 0
    else:
        assert c["page_copies"] == 0 and c["cold_stage_bytes"] == 0
    np.testing.assert_equal(b.ptable.check(), None)
