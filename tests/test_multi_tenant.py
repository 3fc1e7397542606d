"""Multi-tenant serving on the unified runtime: the tenant/workload model,
the SLO-aware planner half, and the engine — per-tenant admission, quota-
respecting demotion, counters that match the simulator's prediction exactly,
and logits bit-identical to the all-HBM run."""
import dataclasses

import pytest

from repro import runtime
from repro.core.hardware import TPU_V5E
from repro.runtime.synthetic import synthetic_multi_tenant_trace


# ------------------------------------------------------------ tenant model ---

def _mini_traces(geoms=((4, 3), (6, 2))):
    from repro.core.hmsim import build_serve_trace
    return [build_serve_trace([(p, d), (p + 2, d)], num_slots=1,
                              num_layers=2, kv_token_bytes=64.0,
                              weight_bytes=1e3, flops_per_token=1e6)
            for p, d in geoms]


def test_merge_tenant_traces_disjoint_slots_and_tags():
    tenants = [runtime.Tenant("a", fast_quota_frac=0.5),
               runtime.Tenant("b", fast_quota_frac=0.5, arrival=3)]
    traces = _mini_traces()
    wl = runtime.MultiTenantWorkload(tenants, traces)
    tr = wl.trace
    assert tr.num_slots == 2 and wl.slot_tenants == ["a", "b"]
    assert {o.tenant for o in tr.objects} == {"a", "b"}
    # tenant b's whole schedule is shifted by its arrival offset
    b_objs = [o for o in tr.objects if o.tenant == "b"]
    src_b = traces[1].objects
    assert min(o.birth for o in b_objs) == min(o.birth for o in src_b) + 3
    assert tr.num_steps == max(traces[0].num_steps, traces[1].num_steps + 3)
    # per-step activity is the sum of the interleaved streams
    assert sum(tr.active.values()) == \
        sum(traces[0].active.values()) + sum(traces[1].active.values())
    # slots are disjoint: tenant a in slot 0, tenant b in slot 1
    assert {o.slot for o in tr.objects if o.tenant == "a"} == {0}
    assert {o.slot for o in tr.objects if o.tenant == "b"} == {1}
    # uids were re-issued without collision
    uids = [o.uid for o in tr.objects]
    assert len(uids) == len(set(uids))


def test_merge_rejects_mismatched_geometry_and_dup_ids():
    from repro.core.hmsim import build_serve_trace
    t0 = _mini_traces()[0]
    t1 = build_serve_trace([(4, 3)], num_slots=1, num_layers=3,
                           kv_token_bytes=64.0)
    with pytest.raises(ValueError, match="model geometry"):
        runtime.merge_tenant_traces([runtime.Tenant("a"),
                                     runtime.Tenant("b")], [t0, t1])
    with pytest.raises(ValueError, match="unique"):
        runtime.MultiTenantWorkload([runtime.Tenant("x"),
                                     runtime.Tenant("x")], _mini_traces())


def test_merge_namespaces_shared_keys_per_tenant():
    """Two tenants' independently-built traces both using prefix_id 0 hold
    physically distinct prompts: merged keys are namespaced by default, and
    only ids declared platform-wide via ``shared_prefix_ids`` coalesce."""
    from repro.core.hmsim import build_serve_trace

    def mk():
        return [build_serve_trace([(32, 4, 0), (32, 4, 0)], num_slots=1,
                                  num_layers=2, kv_token_bytes=64.0,
                                  shared_prefix_tokens=32)
                for _ in range(2)]

    tenants = [runtime.Tenant("a"), runtime.Tenant("b")]
    ns, _ = runtime.merge_tenant_traces(tenants, mk())
    keys_ns = {o.shared_key for o in ns.objects if o.shared_key}
    assert all(k[0] in ("a", "b") for k in keys_ns)   # tenant-namespaced
    plat, _ = runtime.merge_tenant_traces(tenants, mk(),
                                          shared_prefix_ids=(0,))
    keys_p = {o.shared_key for o in plat.objects if o.shared_key}
    assert all(k[0] == 0 for k in keys_p)             # verbatim, coalesced
    # platform-wide sharing dedups the prompt once more across tenants
    assert plat.peak_kv_bytes() < ns.peak_kv_bytes()


def test_normalized_quotas():
    ts = [runtime.Tenant("a", fast_quota_frac=0.5), runtime.Tenant("b"),
          runtime.Tenant("c")]
    q = runtime.normalized_quotas(ts)
    assert q["a"] == 0.5 and q["b"] == q["c"] == pytest.approx(0.25)
    # oversubscribed explicit quotas are rescaled to sum 1
    q2 = runtime.normalized_quotas([runtime.Tenant("a", fast_quota_frac=1.0),
                                    runtime.Tenant("b", fast_quota_frac=3.0)])
    assert q2 == {"a": pytest.approx(0.25), "b": pytest.approx(0.75)}
    assert sum(q.values()) <= 1.0 + 1e-9


# ---------------------------------------------------------------- planner ----

def test_plan_multi_tenant_fields_and_roundtrip():
    wl = synthetic_multi_tenant_trace()
    fast = 0.2 * wl.trace.peak_kv_bytes()
    pl = runtime.plan(wl, TPU_V5E, fast)
    assert pl.policy == "sentinel_slo"
    assert pl.slot_tenants == wl.slot_tenants
    assert pl.tenant_quotas == dict(sorted(wl.tenant_quotas.items()))
    # the winning sim's per-tenant accounting rides on the plan: peaks for
    # both tenants, zero violations for the SLO policy
    assert set(pl.tenant_fast_bytes) == {"chatty", "bursty"}
    assert pl.tenant_violations is None
    # windows are sized inside each tenant's share and page-quantized
    assert all(w % pl.page_tokens == 0 for w in pl.slot_hot_windows)
    assert len(pl.slot_hot_windows) == wl.trace.num_slots
    s = pl.to_json()
    back = runtime.PlacementPlan.from_json(s)
    assert back.to_json() == s and back == pl
    assert back.slot_tenants == pl.slot_tenants
    assert back.tenant_fast_bytes == pl.tenant_fast_bytes


def test_tenant_blind_policy_measured_against_same_quotas():
    """runtime.plan on a tenanted workload with a quota-blind policy still
    reports the violation accounting (measured, not enforced)."""
    wl = synthetic_multi_tenant_trace()
    fast = 0.2 * wl.trace.peak_kv_bytes()
    pl = runtime.plan(wl, TPU_V5E, fast, policy="sentinel")
    assert pl.policy == "sentinel"
    assert pl.tenant_violations and sum(pl.tenant_violations.values()) >= 1


# ----------------------------------------------------------------- engine ----

@pytest.fixture(scope="module")
def tenant_run():
    """One pools-layout multi-tenant run: the batcher, its plan, the request
    stream, and the all-HBM reference outputs."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve import engine

    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 32, 4
    chatty = [(5, 5), (6, 4), (7, 5)]
    bursty = [(12, 6), (11, 5)]
    tenants = [runtime.Tenant("chatty", fast_quota_frac=0.5, slo_slack=1.05),
               runtime.Tenant("bursty", fast_quota_frac=0.5, slo_slack=2.0)]
    traces = [engine.serve_trace_for(get_config("smollm-360m"), rs, slots=2,
                                     layer_group=8)
              for rs in (chatty, bursty)]
    wl = runtime.MultiTenantWorkload(tenants, traces)
    plan = runtime.plan(wl, TPU_V5E, 0.2 * wl.trace.peak_kv_bytes())
    # shrink the planned windows to the reduced max_seq so demotions occur
    plan = dataclasses.replace(plan, hot_window=max_seq // 2,
                               slot_hot_windows=[4, 8, 4, 8], page_tokens=4)
    reqs = []
    key = jax.random.PRNGKey(3)
    for tn, stream in (("chatty", chatty), ("bursty", bursty)):
        for p, d in stream:
            key, sub = jax.random.split(key)
            reqs.append((jax.random.randint(sub, (p,), 0, cfg.vocab_size)
                         .astype(jnp.int32), d, tn))

    def drive(p):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p,
                                     slot_tenants=plan.slot_tenants)
        for t, d, tn in reqs:
            b.submit(t, d, tenant=tn)
        return b.run(), b

    out_ref, _ = drive(None)
    out, b = drive(plan)
    return {"engine": engine, "plan": plan, "b": b, "out": out,
            "out_ref": out_ref, "reqs": reqs, "slots": slots,
            "max_seq": max_seq}


def test_engine_tenant_admission_respects_slots(tenant_run):
    """Requests only ever ran in their own tenant's slots: every slot's
    pages belong to one tenant, and both tenants got all their tokens."""
    b = tenant_run["b"]
    assert b.slot_tenants == ["chatty", "chatty", "bursty", "bursty"]
    want = sum(d for _, d, _ in tenant_run["reqs"])
    assert sum(len(o) for o in tenant_run["out"]) == want
    # an unknown tenant tag would queue forever — submit rejects it up front
    with pytest.raises(ValueError, match="owns no batch slot"):
        b.submit(tenant_run["reqs"][0][0], 2, tenant="Bursty")


def test_engine_matches_simulator_counters_exactly(tenant_run):
    """The agreement contract: predicted migration bytes, pool counters and
    per-tenant fast-byte peaks equal the real batcher's, integer for
    integer, on the deterministic trace."""
    b, engine = tenant_run["b"], tenant_run["engine"]
    pred = engine.predict_pool_counters(
        [(int(t.shape[0]), d, tn) for t, d, tn in tenant_run["reqs"]],
        tenant_run["plan"], slots=tenant_run["slots"],
        max_seq=tenant_run["max_seq"], page_tokens=b.page_tokens,
        row_bytes=b._row_bytes)
    assert pred["migration_bytes"] == b.sim_migration_bytes
    # the per-decode-step series the CostModel prices: integer-exact per
    # step, and its sum is the aggregate counter
    assert pred["step_migration_bytes"] == b.step_migration_bytes
    assert sum(pred["step_migration_bytes"]) == b.sim_migration_bytes
    assert pred["page_copies"] == b.pool.stats["page_copies"]
    assert pred["admit_page_writes"] == b.pool.stats["admit_page_writes"]
    assert pred["tenant_hot_peak"] == b.tenant_hot_peak
    assert set(b.tenant_hot_peak) == {"chatty", "bursty"}
    assert all(v > 0 for v in b.tenant_hot_peak.values())


def test_engine_tenant_logits_bit_identical_to_all_hbm(tenant_run):
    """Quota-respecting tiering never changes a logit: the tenant-tagged
    pools run reproduces the all-HBM reference tokens exactly."""
    assert tenant_run["out"] == tenant_run["out_ref"]
    assert tenant_run["b"].sim_migration_bytes > 0   # it really demoted
    tenant_run["b"].ptable.check()
