"""Sentinel-Serve: serving-phase trace model, policy registry, decode-phase
planner, and the tiered continuous-batching runtime (cold KV prefix on host
matching the all-HBM reference bit-for-bit)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core import hmsim, planner
from repro.core.hardware import TPU_V5E
from repro.core.policies import (POLICIES, ServePolicy, get_policy,
                                 list_policies, register_policy)
from repro.models import kvcache, model
from repro.models.layers import split_params
from repro.serve import engine


@pytest.fixture(scope="module")
def trace():
    """Synthetic serving trace with realistic byte geometry (4KB KV/token
    per layer-group, 8 groups, 4 slots, mixed prompt/decode lengths)."""
    reqs = hmsim.synthetic_requests(12)
    return hmsim.build_serve_trace(reqs, num_slots=4, num_layers=8,
                                   kv_token_bytes=4096, weight_bytes=50e6,
                                   flops_per_token=2e9)


# ------------------------------------------------------------- registry ----

def test_policy_registry_dispatch():
    assert {"prefer_fast", "lru_page", "sentinel"} <= set(list_policies())
    for name in list_policies():
        cls = get_policy(name)
        assert issubclass(cls, ServePolicy) and cls.name == name
    with pytest.raises(KeyError, match="unknown serve policy"):
        get_policy("nope")


def test_policy_registration_roundtrip():
    @register_policy("_test_noop")
    class Noop(ServePolicy):
        pass
    try:
        assert get_policy("_test_noop") is Noop
        assert "_test_noop" in list_policies()
    finally:
        POLICIES.pop("_test_noop")


# ---------------------------------------------------------------- trace ----

def test_decode_trace_access_invariants(trace):
    """Every KV object's accesses are monotone in token index, start at
    birth, and stay within the owning request's lifetime."""
    assert trace.objects and trace.num_steps > 0
    for o in trace.objects:
        assert o.accesses, f"object {o.uid} never accessed"
        assert o.accesses == sorted(set(o.accesses))          # monotone
        assert o.accesses[0] == o.birth
        assert o.birth <= o.accesses[-1] <= o.death
        assert 0 <= o.token_start < o.token_end


def test_trace_blocks_partition_token_stream(trace):
    """Per (request, layer), the KV blocks tile [0, prompt+decode) without
    gaps or overlap."""
    by_req_layer = {}
    for o in trace.objects:
        by_req_layer.setdefault((o.req, o.layer), []).append(o)
    for (req, layer), objs in by_req_layer.items():
        objs.sort(key=lambda o: o.token_start)
        assert objs[0].token_start == 0
        for a, b in zip(objs, objs[1:]):
            assert a.token_end == b.token_start
        assert all(o.death == objs[0].death for o in objs)


def test_trace_accounting(trace):
    """Reads/admits/births/frees index exactly the object set."""
    from_reads = {o.uid for objs in trace.reads.values() for o in objs}
    born = {o.uid for objs in trace.admits.values() for o in objs} | \
           {o.uid for objs in trace.births.values() for o in objs}
    freed = {o.uid for objs in trace.frees.values() for o in objs}
    uids = {o.uid for o in trace.objects}
    assert from_reads == born == freed == uids
    assert trace.peak_kv_bytes() > 0
    assert trace.rs_bytes() > 0


# ------------------------------------------------------------- policies ----

def test_sentinel_beats_page_grain_at_20pct(trace):
    """The serving restatement of the paper's core claim: lifetime-aware
    object-granular placement beats page-grain reactive LRU (and static
    prefer-fast) when fast memory is scarce."""
    fast = 0.2 * trace.peak_kv_bytes()
    sent = hmsim.simulate_serve(trace, TPU_V5E, fast, "sentinel")
    lru = hmsim.simulate_serve(trace, TPU_V5E, fast, "lru_page")
    pf = hmsim.simulate_serve(trace, TPU_V5E, fast, "prefer_fast")
    assert sent.decode_throughput >= lru.decode_throughput
    assert sent.decode_throughput >= pf.decode_throughput
    assert sent.slow_bytes_accessed < lru.slow_bytes_accessed


def test_policies_agree_at_full_fast(trace):
    """With fast memory >= peak KV, object-grain policies hit the compute
    bound exactly; page-grain keeps a small padding/false-sharing residue."""
    fast = 1.1 * trace.peak_kv_bytes()
    sent = hmsim.simulate_serve(trace, TPU_V5E, fast, "sentinel").time
    pf = hmsim.simulate_serve(trace, TPU_V5E, fast, "prefer_fast").time
    lru = hmsim.simulate_serve(trace, TPU_V5E, fast, "lru_page").time
    assert sent <= pf * 1.001
    assert sent <= lru and lru <= sent * 1.10


def test_more_fast_memory_never_hurts_serving(trace):
    tputs = []
    for frac in (0.1, 0.3, 0.6, 1.0):
        r = hmsim.simulate_serve(trace, TPU_V5E,
                                 frac * trace.peak_kv_bytes(), "sentinel")
        tputs.append(r.decode_throughput)
    for a, b in zip(tputs, tputs[1:]):
        assert b >= a * 0.98


# -------------------------------------------------------------- planner ----

def test_plan_serve_constraints(trace):
    pl = planner.plan_serve(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    assert pl.policy == "sentinel"
    assert pl.hot_window >= trace.block_tokens        # reserve-pool floor
    assert pl.lookahead >= 1
    assert pl.sim is not None and pl.decode_throughput > 0
    assert pl.candidates and any(c.space_ok for c in pl.candidates)
    # cold prefix shrinks to zero once the buffer fits the hot window
    assert pl.cold_len(pl.hot_window) == 0
    assert pl.cold_len(pl.hot_window + 7) == 7


# ------------------------------------------------------- cache splice ----

def test_splice_slot_matches_direct_write():
    cfg = get_config("smollm-360m").reduced()
    max_seq, B = 32, 3
    big = kvcache.init_cache(cfg, B, max_seq, jnp.float32)
    one = jax.tree.map(
        lambda a: jnp.ones_like(a[:, :1] if a.ndim >= 2 and a.shape[1] == B
                                else a[:1]),
        kvcache.init_cache(cfg, B, max_seq, jnp.float32))
    out = kvcache.splice_slot(big, one, 1, B)
    for leaf in jax.tree.leaves(out):
        total = float(jnp.sum(leaf))
        per_slot = leaf.size / B
        assert total == pytest.approx(per_slot)


# ------------------------------------------------------------------ e2e ----

def test_tiered_batcher_matches_all_hbm():
    """A plan alone puts the ContinuousBatcher on the persistent pools
    (cold pages in host memory), and it produces exactly the tokens of the
    all-HBM reference run."""
    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 32, 2
    requests = [(7, 4), (9, 4), (8, 4)]

    def run(plan):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=plan)
        key = jax.random.PRNGKey(3)
        for plen, d in requests:
            key, sub = jax.random.split(key)
            b.submit(jax.random.randint(sub, (plen,), 0,
                                        cfg.vocab_size).astype(jnp.int32), d)
        return b.run(), b

    trace = engine.serve_trace_for(get_config("smollm-360m"), requests,
                                   slots=slots, layer_group=8)
    plan = planner.plan_serve(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    # one window for every slot, short enough that each request's oldest
    # page goes cold before it finishes
    plan = dataclasses.replace(plan, hot_window=max_seq // 4, page_tokens=4,
                               slot_hot_windows=None)
    assert plan.cold_len(max_seq) == 3 * max_seq // 4  # real cold prefix

    base, b_base = run(None)
    tiered, b = run(plan)
    assert b_base.pool is None and b.pool is not None
    assert b.pool.stats["page_copies"] > 0             # pages went cold
    assert base == tiered
    assert len(base) == len(requests)
