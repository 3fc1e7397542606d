"""Per-slot cold boundaries end-to-end: PageTable alloc/free/splice
invariants, planner slot windows, boundary monotonicity under slot refill,
and the pools ContinuousBatcher matching the all-HBM reference — on the
GQA stack and on every other kind of stack the registry holds."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.configs.base import get_config
from repro.core import planner
from repro.core.hardware import TPU_V5E
from repro.models import kvcache, model
from repro.models.layers import split_params
from repro.serve import engine


# ------------------------------------------------------------ page table ----

def test_page_table_alloc_free_splice_invariants():
    pt = kvcache.PageTable(slots=3, pages_per_slot=4, page_tokens=8)
    n_cold = pt.splice_slot(0, tokens=30, cold_tokens=16)
    pt.check()
    assert (n_cold, pt.n_pages[0], pt.cold_pages(0)) == (2, 4, 2)
    pt.splice_slot(1, tokens=9, cold_tokens=0)
    pt.check()
    assert pt.cold_tokens(1) == 0 and pt.n_pages[1] == 2
    # demotion advances the cold boundary one page at a time
    pt.demote(1, 0)
    pt.check()
    assert pt.cold_tokens(1) == 8
    # refill releases every page back to its pool
    before_hot, before_cold = len(pt.hot_free), len(pt.cold_free)
    released = pt.free_slot(0)
    pt.check()
    assert released == 4
    assert len(pt.hot_free) == before_hot + 2
    assert len(pt.cold_free) == before_cold + 2
    # splice after free reuses pages without leaking
    pt.splice_slot(0, tokens=32, cold_tokens=32)
    pt.check()
    assert pt.cold_pages(0) == 4


def test_page_table_guards():
    pt = kvcache.PageTable(slots=1, pages_per_slot=2, page_tokens=4,
                           hot_pages=2, cold_pages=1)
    pt.alloc(0, 0)
    with pytest.raises(ValueError, match="cold-prefix"):
        pt.alloc(0, 1)                    # cold after hot breaks the prefix
    pt.alloc(0, 0)
    with pytest.raises(ValueError, match="exhausted"):
        pt.alloc(0, 0)                    # pages_per_slot exhausted
    with pytest.raises(ValueError, match="not the cold boundary"):
        pt.demote(0, 1)
    pt.demote(0, 0)
    with pytest.raises(ValueError, match="cold pool exhausted"):
        pt.demote(0, 1)                   # cold pool only had one page


# --------------------------------------------------------------- planner ----

def test_plan_serve_slot_windows():
    from repro.core import hmsim
    reqs = hmsim.synthetic_requests(12)
    trace = hmsim.build_serve_trace(reqs, num_slots=4, num_layers=8,
                                    kv_token_bytes=4096, weight_bytes=50e6,
                                    flops_per_token=2e9)
    pl = planner.plan_serve(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    assert pl.page_tokens == trace.block_tokens
    assert pl.slot_hot_windows and len(pl.slot_hot_windows) == trace.num_slots
    for w in pl.slot_hot_windows:
        assert w >= trace.block_tokens               # reserve-pool floor
        assert w % trace.block_tokens == 0           # page-quantized
    # per-slot cold boundaries: page-aligned and monotone in sequence length
    prev = -1
    for seq_len in range(0, 200, 7):
        c = pl.cold_len_slot(1, seq_len)
        assert c % pl.page_tokens == 0
        assert c >= prev
        prev = c
    # a slot serving more KV byte-seconds never gets a smaller window
    w = planner.slot_kv_weights(trace)
    order = sorted(range(len(w)), key=lambda s: w[s])
    windows = [pl.slot_hot_windows[s] for s in order]
    assert windows == sorted(windows)


# ------------------------------------------------------------------- e2e ----

@pytest.fixture(scope="module")
def served():
    """Run the same request stream through the dense reference and the
    pools, logging every slot's length, cold boundary, activity and plan
    target after each step of the pools run."""
    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 32, 2
    requests = [(7, 6), (9, 5), (6, 7)]

    trace = engine.serve_trace_for(get_config("smollm-360m"), requests,
                                   slots=slots, layer_group=8)
    plan = planner.plan_serve(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    # small per-slot windows so decode actually crosses page boundaries
    plan = dataclasses.replace(plan, hot_window=max_seq // 2,
                               slot_hot_windows=[4, 8], page_tokens=4)

    def run(p):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p)
        key = jax.random.PRNGKey(3)
        boundary_log = []
        for plen, d in requests:
            key, sub = jax.random.split(key)
            b.submit(jax.random.randint(sub, (plen,), 0,
                                        cfg.vocab_size).astype(jnp.int32), d)
        results = []
        while b.queue or any(b.active):
            if not b.step():
                break
            if p is not None:
                lengths = [int(x) for x in b.lengths]
                boundary_log.append((
                    lengths,
                    [b.ptable.cold_tokens(s) for s in range(b.B)],
                    list(b.active),
                    [p.cold_len_slot(s, lengths[s], b.page_tokens)
                     for s in range(b.B)]))
                b.ptable.check()
            for i in range(b.B):
                if not b.active[i] and b.outputs[i]:
                    results.append(b.outputs[i])
                    b.outputs[i] = []
        return results, b, boundary_log

    base, _, _ = run(None)
    pools, b_pools, log = run(plan)
    return base, pools, b_pools, log


def test_paged_batcher_matches_all_hbm(served):
    base, pools, b_pools, _ = served
    assert b_pools.pool is not None
    assert base == pools
    assert len(base) == 3


def test_per_slot_boundary_monotone_under_refill(served):
    """Within one residency a slot's cold boundary only advances (and stays
    page-aligned, at or below the slot's length); it resets only when the
    slot is refilled with a new request."""
    *_, b_pools, log = served
    page = b_pools.page_tokens
    assert b_pools.sim_migration_bytes > 0       # boundaries actually moved
    assert any(any(bd > 0 for bd in bounds) for _, bounds, _, _ in log)
    for (len_prev, bd_prev, _, _), (len_now, bd_now, _, _) in zip(log,
                                                                  log[1:]):
        for s in range(len(bd_now)):
            assert bd_now[s] % page == 0
            assert bd_now[s] <= len_now[s]
            if len_now[s] == len_prev[s] + 1:    # same residency, one decode
                assert bd_now[s] >= bd_prev[s]
    b_pools.ptable.check()


def test_paged_table_consistent_with_boundaries(served):
    """After every step, each active slot's cold pages in the PageTable are
    exactly the plan's per-slot boundary at the slot's length (and the
    table's invariants held: ``check`` ran after each step)."""
    *_, log = served
    assert log
    for lengths, bounds, active, targets in log:
        for s in range(len(bounds)):
            if active[s]:
                assert bounds[s] == targets[s], (s, lengths[s])


@pytest.mark.parametrize("arch", ["paligemma-3b", "gemma2-2b", "zamba2-7b",
                                  "lstm-ptb", "granite-moe-3b-a800m"])
def test_every_stack_serves_dense_tokens_through_the_pools(arch):
    """A plan puts every kind of stack on the pools — prefix-LM, local and
    global attention, mamba with shared attention, a stack with no pooled
    layer, MoE — and the tokens equal the dense no-plan reference's while
    pages demote."""
    cfg = get_config(arch).reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    slots, max_seq, new = 2, 64, 6
    prompts = [5, 20, 33]
    trace = engine.serve_trace_for(cfg, [(p, new) for p in prompts],
                                   slots=slots)
    plan = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    plan = dataclasses.replace(plan, hot_window=8, page_tokens=8,
                               slot_hot_windows=None)

    def run(p):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p)
        key = jax.random.PRNGKey(5)
        for plen in prompts:
            key, sub = jax.random.split(key)
            b.submit(jax.random.randint(sub, (plen,), 0,
                                        cfg.vocab_size).astype(jnp.int32),
                     new)
        return b.run(), b

    base, _ = run(None)
    pools, b = run(plan)
    assert b.pool.stats["page_copies"] > 0
    assert pools == base
    assert len(base) == len(prompts)
