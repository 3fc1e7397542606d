"""Physical page pools as the source of truth: PageTable refcount/CoW/share
invariants, the persistent-pool batcher doing zero dense re-packs and zero
boundary host-syncs in steady state, and copy-on-write prefix sharing
producing logits bit-identical to independent (unshared) decoding."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.configs.base import get_config
from repro.core.hardware import TPU_V5E
from repro.models import kvcache, model
from repro.models.layers import split_params
from repro.serve import engine


# ------------------------------------------------------- table invariants ----

def test_share_maps_same_physical_pages():
    pt = kvcache.PageTable(slots=3, pages_per_slot=4, page_tokens=8)
    pt.splice_slot(0, tokens=30, cold_tokens=16)
    assert pt.share(1, 0, 3) == 3
    pt.check()
    # same physical pages, same tiers, refcount 2 everywhere shared
    for i in range(3):
        assert pt.table[1][i] == pt.table[0][i]
        assert pt.tier[1][i] == pt.tier[0][i]
        assert pt.refcount(0, i) == 2 and pt.is_shared(1, i)
    # the shared prefix inherits a valid cold-prefix pattern
    assert pt.cold_pages(1) == 2
    # a fourth, private page continues the slot normally
    pt.alloc(1, 0)
    pt.check()
    assert pt.refcount(1, 3) == 1


def test_share_guards():
    pt = kvcache.PageTable(slots=2, pages_per_slot=4, page_tokens=8)
    pt.splice_slot(0, tokens=16, cold_tokens=0)
    pt.alloc(1, 0)
    with pytest.raises(ValueError, match="empty slot"):
        pt.share(1, 0, 1)                 # dst must be empty
    pt.free_slot(1)
    with pytest.raises(ValueError, match="cannot share"):
        pt.share(1, 0, 3)                 # src only has 2 pages


def test_cow_gives_private_page_and_preserves_invariants():
    pt = kvcache.PageTable(slots=2, pages_per_slot=4, page_tokens=8)
    pt.splice_slot(0, tokens=32, cold_tokens=8)
    pt.share(1, 0, 4)
    src, new, tier = pt.cow(1, 2)
    pt.check()
    assert tier == 0 and new != src
    assert pt.table[1][2] == new and pt.table[0][2] == src
    assert pt.refcount(1, 2) == 1 and pt.refcount(0, 2) == 1
    # CoW of a *cold* shared page stays cold (cold-prefix invariant holds)
    src_c, new_c, tier_c = pt.cow(1, 0)
    pt.check()
    assert tier_c == 1 and pt.cold_pages(1) == 1
    # exclusive pages are a no-op
    assert pt.cow(1, 2) is None


def test_refcounted_free_keeps_pages_alive():
    """Freeing the donor slot must not release pages the sharer still
    references — the page returns to the free list only at refcount zero."""
    pt = kvcache.PageTable(slots=2, pages_per_slot=2, page_tokens=4)
    pt.splice_slot(0, tokens=8, cold_tokens=0)
    pt.share(1, 0, 2)
    free_before = len(pt.hot_free)
    pt.free_slot(0)
    pt.check()
    assert len(pt.hot_free) == free_before       # still referenced by slot 1
    assert all(pt.refcount(1, i) == 1 for i in range(2))
    pt.free_slot(1)
    pt.check()
    assert len(pt.hot_free) == free_before + 2   # now truly free


def test_shared_demote_moves_bytes_once():
    """N sharers demoting the same logical page produce ONE cold copy: the
    first demotion copies, later ones reuse the twin with a refcount bump."""
    pt = kvcache.PageTable(slots=3, pages_per_slot=2, page_tokens=4)
    pt.splice_slot(0, tokens=8, cold_tokens=0)
    pt.share(1, 0, 2)
    pt.share(2, 0, 2)
    c0, src0, copied0 = pt.demote(0, 0)
    c1, src1, copied1 = pt.demote(1, 0)
    c2, src2, copied2 = pt.demote(2, 0)
    pt.check()
    assert copied0 and not copied1 and not copied2
    assert c0 == c1 == c2 and src0 == src1 == src2
    assert pt.cold_ref[c0] == 3
    # all three boundaries advanced without further data movement
    assert all(pt.cold_pages(s) == 1 for s in range(3))


# ------------------------------------------------ pools: steady-state cost ----

@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _plan(max_seq, windows, page):
    trace = engine.serve_trace_for(get_config("smollm-360m"),
                                   [(7, 6), (9, 5)], slots=2, layer_group=8)
    pl = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    return dataclasses.replace(pl, hot_window=max_seq // 2,
                               slot_hot_windows=windows, page_tokens=page)


def test_pool_steady_state_zero_repacks_zero_syncs(setup, monkeypatch):
    """The acceptance gate: with the persistent pools, steady-state step()
    never re-packs the dense cache into pools (gather_pools/pool_layout are
    poisoned), and a step with no layout event uploads no table and copies
    no page."""
    import repro.kernels.paged_decode as pd

    def poisoned(*a, **k):
        raise AssertionError("dense->pool re-pack on the persistent-pool path")

    monkeypatch.setattr(pd, "gather_pools", poisoned)
    monkeypatch.setattr(pd, "pool_layout", poisoned)

    cfg, params = setup
    max_seq, page = 32, 4
    plan = _plan(max_seq, [16, 16], page)     # huge windows: no demotions
    b = engine.ContinuousBatcher(params, cfg, 2, max_seq, plan=plan)
    b.submit(jnp.arange(5, dtype=jnp.int32), 8)
    b.submit(jnp.arange(6, dtype=jnp.int32), 8)
    assert b.step()                            # admits + first decode
    steady_steps = 0
    while any(b.active):
        before = dict(b.pool.stats)
        version = b.ptable.version
        if not b.step():
            break
        if b.ptable.version == version:        # no admit/alloc/demote event
            steady_steps += 1
            assert b.pool.stats["table_uploads"] == before["table_uploads"]
            assert b.pool.stats["page_copies"] == before["page_copies"]
            assert b.pool.stats["admit_page_writes"] == \
                before["admit_page_writes"]
    assert steady_steps > 0                    # the loop really went steady
    assert b.pool.stats["repacks"] == 0
    # layout uploads are event-driven, bounded by table mutations
    assert b.pool.stats["table_uploads"] <= b.ptable.version + 1


def test_pool_decode_writes_land_in_physical_pages(setup):
    """Decode really writes through the page table: after a run, the hot
    pool pages of a slot hold the KV the dense path would hold (the pools
    are the only storage — scribbling the pool changes the next logits)."""
    cfg, params = setup
    max_seq, page = 32, 4
    plan = _plan(max_seq, [16, 16], page)
    b = engine.ContinuousBatcher(params, cfg, 2, max_seq, plan=plan)
    b.submit(jnp.arange(5, dtype=jnp.int32), 4)
    for _ in range(3):
        b.step()
    view = b.pool.forward_tree(stage=b.pool.has_cold())
    logits_ref, _, _ = model.forward(
        params, cfg, {"tokens": b.last_tok[:, None]}, caches=view,
        cache_index=b.lengths, decode=True,
        paged_view=b.pool.paged_view(b._active_mask))
    # zero slot 0's first physical hot page -> attention must change
    entry = view["slots"][0]
    phys = b.ptable.table[0][0]
    wiped = {**entry, "k_hot": entry["k_hot"].at[:, phys].set(0.0)}
    tree = {"prologue": list(view["prologue"]),
            "slots": [wiped] + list(view["slots"][1:])}
    logits_wiped, _, _ = model.forward(
        params, cfg, {"tokens": b.last_tok[:, None]}, caches=tree,
        cache_index=b.lengths, decode=True,
        paged_view=b.pool.paged_view(b._active_mask))
    assert not jnp.allclose(logits_ref[0], logits_wiped[0], atol=1e-4)


# ------------------------------------------------- sharing: bit-identical ----

def test_shared_prefix_slots_bit_identical_and_cheaper(setup):
    """Two slots decoding from one shared system prompt: tokens equal the
    all-HBM reference, logits bit-identical to the unshared pool run, and
    strictly fewer physical pages + migration bytes."""
    cfg, params = setup
    max_seq, slots, page = 32, 2, 4
    plan = _plan(max_seq, [4, 8], page)       # small windows: demotions occur
    sys_p = jax.random.randint(jax.random.PRNGKey(7), (9,), 0,
                               cfg.vocab_size).astype(jnp.int32)
    users = [jax.random.randint(jax.random.PRNGKey(8 + i), (2 + i,), 0,
                                cfg.vocab_size).astype(jnp.int32)
             for i in range(2)]
    reqs = [(jnp.concatenate([sys_p, u]), 6) for u in users]

    def drive(p, shared):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p)
        for t, d in reqs:
            b.submit(t, d, prefix_key="sys" if shared else None)
        logit_log = []
        while b.queue or any(b.active):
            b._admit()
            if not any(b.active):
                break
            b.step()
            logit_log.append(b.last_tok)
        return b.outputs, b, logit_log

    out_base, _, _ = drive(None, False)
    out_s, b_s, log_s = drive(plan, True)
    out_u, b_u, log_u = drive(plan, False)
    assert out_s == out_u == out_base
    # bit-identical decode trajectories shared vs unshared
    for a, b in zip(log_s, log_u):
        assert jnp.array_equal(a, b)
    # the system prompt's full pages existed once, not twice
    assert b_s.pool.peak_pages < b_u.pool.peak_pages
    assert b_s.sim_migration_bytes < b_u.sim_migration_bytes
    assert b_s.pool.stats["admit_page_writes"] < \
        b_u.pool.stats["admit_page_writes"]
    b_s.ptable.check()
    b_u.ptable.check()


def _cold_kinds(pool):
    return {e[k].sharding.memory_kind for _, e in pool._attn_entries()
            for k in ("k_cold", "v_cold")}


def test_pool_cold_arrays_stay_in_host_memory(setup):
    """The cold tier's arrays stay in host memory through admit, demote and
    CoW — every transfer is explicit — and a 20% plan's tokens equal the
    all-HBM engine's."""
    cfg, params = setup
    max_seq, slots, page = 32, 2, 4
    host = kvcache.host_memory_kind()
    trace = engine.serve_trace_for(get_config("smollm-360m"),
                                   [(11, 6), (12, 6)], slots=slots,
                                   layer_group=8)
    plan = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    plan = dataclasses.replace(plan, hot_window=4, slot_hot_windows=None,
                               page_tokens=page)
    sys_p = jax.random.randint(jax.random.PRNGKey(7), (9,), 0,
                               cfg.vocab_size).astype(jnp.int32)
    reqs = [(jnp.concatenate([sys_p, jnp.arange(2 + i, dtype=jnp.int32)]),
             6) for i in range(2)]

    def drive(p):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p)
        for t, d in reqs:
            b.submit(t, d, prefix_key="sys" if p is not None else None)
        return b.run(), b

    want, _ = drive(None)
    b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=plan)
    assert _cold_kinds(b.pool) == {host}
    for t, d in reqs:
        b.submit(t, d, prefix_key="sys")
    b._admit()                                     # admit + demotions
    assert b.pool.stats["page_copies"] > 0
    assert _cold_kinds(b.pool) == {host}
    # CoW of a cold page: the sharer's first page is cold and shared
    assert b.ptable.tier[1][0] == 1 and b.ptable.is_shared(1, 0)
    src = b.ptable.table[1][0]
    assert b.pool.cow_for_write(1, 0)
    new = b.ptable.table[1][0]
    assert new != src
    assert _cold_kinds(b.pool) == {host}
    for _, e in b.pool._attn_entries():
        assert jnp.array_equal(kvcache.to_device(e["k_cold"][new]),
                               kvcache.to_device(e["k_cold"][src]))
    got = b.run()
    assert _cold_kinds(b.pool) == {host}
    assert got == want
    b.ptable.check()


def test_shared_prefix_logits_bit_identical_one_step(setup):
    """One decode step, logits only: slot 1 sharing slot 0's prefix pages
    produces exactly the logits of a private-pages run (same values read
    through a different physical mapping)."""
    cfg, params = setup
    max_seq, page = 32, 4
    plan = _plan(max_seq, [16, 16], page)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (8,), 0,
                                cfg.vocab_size).astype(jnp.int32)

    def one(shared):
        b = engine.ContinuousBatcher(params, cfg, 2, max_seq, plan=plan)
        b.submit(prompt, 3, prefix_key="p" if shared else None)
        b.submit(prompt, 3, prefix_key="p" if shared else None)
        b._admit()
        pv = b.pool.paged_view(b._active_mask)
        logits, _, _ = model.forward(
            params, cfg, {"tokens": b.last_tok[:, None]},
            caches=b.pool.forward_tree(stage=b.pool.has_cold()),
            cache_index=b.lengths, decode=True, paged_view=pv)
        return logits, b

    l_shared, b_shared = one(True)
    l_priv, _ = one(False)
    assert jnp.array_equal(l_shared, l_priv)
    # and the shared run really aliased the prompt's full pages
    assert b_shared.ptable.is_shared(1, 0)
    assert b_shared.ptable.table[1][0] == b_shared.ptable.table[0][0]


# ----------------------------------------------------- runtime surface -------

def test_shared_trace_counts_bytes_once():
    from repro.runtime.synthetic import synthetic_shared_prefix_trace
    ts = synthetic_shared_prefix_trace(shared=True)
    tu = synthetic_shared_prefix_trace(shared=False)
    # identical byte geometry per request, smaller physical peak when shared
    assert ts.num_steps == tu.num_steps
    assert sum(o.bytes for o in ts.objects) == sum(o.bytes for o in tu.objects)
    assert ts.peak_kv_bytes() < tu.peak_kv_bytes()
    fast = 0.2 * tu.peak_kv_bytes()
    rs = runtime.simulate(ts, TPU_V5E, fast, "sentinel")
    ru = runtime.simulate(tu, TPU_V5E, fast, "sentinel")
    assert rs.bytes_s2f + rs.bytes_f2s < ru.bytes_s2f + ru.bytes_f2s
    assert rs.detail["peak_kv"] < ru.detail["peak_kv"]
    # plan sizing consumes the deduped peak
    pl = runtime.plan(ts, TPU_V5E, fast)
    assert pl.slot_hot_windows and pl.page_tokens == ts.block_tokens
    assert runtime.PlacementPlan.from_json(pl.to_json()).to_json() == \
        pl.to_json()
