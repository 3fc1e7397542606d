"""The online re-planner held to a clairvoyant-regret differential.

The offline planner's contract is "profile once, place forever"; the online
loop (runtime/online.py) breaks the repeatability assumption on purpose, so
its tests are differential: every piecewise-stationary drift workload is
replayed through the per-segment clairvoyant oracle (a fresh
``runtime.plan`` with full knowledge at each segment's first step) and the
online planner's predicted-time regret against that plan sequence is gated
at ≤ 10%, with hysteresis churn within budget, zero SLO violations across
re-plans, and every applied ``PlanDelta`` byte-identical to the fresh plan
it was diffed from.  The engine half pins ``apply_plan`` /
``predict_pool_counters(plan_schedule=)`` agreement integer-exactly across
a re-plan boundary, and hypothesis fuzzes the delta path end to end."""
import dataclasses

import pytest

from repro import runtime
from repro.runtime import (DriftSegment, DriftWorkload, OnlineReplanner,
                           TPU_V5E_COST, plan_churn_bytes, plan_delta,
                           replay_drift)
from repro.runtime.synthetic import drift_workloads

REGRET_BOUND = 0.10
MIG_FACTOR = 1.3


def _is_lend(ev):
    return ev.reason.startswith(("lend:", "reclaim:"))


@pytest.fixture(scope="module")
def reports():
    """One online replay per canonical drift workload, default knobs, 20%
    fast memory — the exact configuration ``bench_runtime --drift`` gates."""
    out = {}
    for name, wl in drift_workloads().items():
        fast = 0.2 * wl.peak_kv_bytes()
        out[name] = (wl, replay_drift(wl, TPU_V5E_COST, fast))
    return out


# ----------------------------------------------------- regret differential ---

def test_regret_within_bound_on_every_drift_workload(reports):
    """The headline gate: ≤10% predicted-time regret vs the per-segment
    clairvoyant plan sequence, having actually re-planned (not by luck)."""
    for name, (wl, rep) in reports.items():
        assert rep.regret <= REGRET_BOUND, (name, rep.regret)
        drift_evs = [e for e in rep.events if not _is_lend(e) and e.applied]
        assert drift_evs, f"{name}: the online loop never re-planned"
        # detection is prompt: a re-plan lands within two windows of at
        # least one segment boundary
        bounds, t = [], 0
        for seg in wl.segments[:-1]:
            t += seg.num_steps
            bounds.append(t)
        lag = 2 * int(rep.knobs["window"])
        assert any(0 <= e.step - b <= lag for e in drift_evs
                   for b in bounds), (name, [e.step for e in drift_evs])


def test_online_beats_static_stale_plan(reports):
    """The loop must pay for itself: never slower than serving the whole
    drift under segment-0's stale plan, in time and tokens/sec."""
    for name, (wl, rep) in reports.items():
        assert rep.online_s <= rep.static_s, name
        assert rep.online_tokens_per_s >= rep.static_tokens_per_s, name


def test_migration_bytes_within_clairvoyant_factor(reports):
    for name, (wl, rep) in reports.items():
        assert rep.online_mig_bytes <= \
            MIG_FACTOR * rep.clairvoyant_mig_bytes, name


def test_zero_slo_violations_across_replans(reports):
    """Re-planning never trades away a tenant's guarantee: every plan the
    online loop served under (stale, fresh, lent) ran violation-free."""
    for name, (wl, rep) in reports.items():
        assert rep.tenant_violations == {}, (name, rep.tenant_violations)


# ------------------------------------------------------------- delta chain ---

def test_delta_chain_reconstructs_every_applied_plan(reports):
    """Applying the emitted deltas in order to the initial plan reproduces
    every intermediate plan byte-for-byte — an applied delta IS the fresh
    plan, which is what makes deltas safe to ship to a live engine."""
    for name, (wl, rep) in reports.items():
        p = rep.plan0
        for ev in rep.events:
            if not ev.applied:
                continue
            assert ev.delta.base_digest == p.digest(), (name, ev.step)
            p = p.apply_delta(ev.delta)
            assert p.to_json() == ev.plan.to_json(), (name, ev.step)


def test_drift_replan_is_bit_identical_to_fresh_plan(reports):
    """At each detected shift the applied plan equals a from-scratch
    ``runtime.plan`` on that segment's workload, byte-for-byte."""
    for name, (wl, rep) in reports.items():
        seen = set()
        for ev in rep.events:
            if _is_lend(ev) or not ev.applied or ev.segment in seen:
                continue
            seen.add(ev.segment)           # first drift re-plan per segment
            fresh = runtime.plan(wl.segments[ev.segment].workload,
                                 TPU_V5E_COST,
                                 rep.knobs["fast_bytes"],
                                 objective="latency")
            assert ev.plan.to_json() == fresh.to_json(), (name, ev.step)
        assert seen, name


def test_delta_applies_only_in_emission_order():
    wl = drift_workloads()["prompt_shift"]
    rep = replay_drift(wl, TPU_V5E_COST, 0.2 * wl.peak_kv_bytes())
    ev = next(e for e in rep.events if not _is_lend(e) and e.applied)
    stale = ev.plan                        # delta was diffed from plan0
    with pytest.raises(ValueError, match="emission order"):
        stale.apply_delta(ev.delta)
    # and the delta's JSON round-trips byte-identically (the wire format)
    s = ev.delta.to_json()
    assert runtime.PlanDelta.from_json(s).to_json() == s


# -------------------------------------------------------------- hysteresis ---

def test_min_dwell_spaces_drift_replans(reports):
    for name, (wl, rep) in reports.items():
        steps = [e.step for e in rep.events if not _is_lend(e)]
        dwell = int(rep.knobs["min_dwell"])
        assert all(b - a >= dwell for a, b in zip(steps, steps[1:])), name


def test_churn_budget_is_respected_and_suppresses(reports):
    """Cumulative re-layout bytes stay inside the budget; with a zero
    budget every window-shrinking re-plan is suppressed (emitted with
    ``applied=False``) and nothing moves."""
    for name, (wl, rep) in reports.items():
        assert rep.churn_bytes <= rep.churn_budget_bytes, name
    wl = drift_workloads()["prompt_shift"]
    rep = replay_drift(wl, TPU_V5E_COST, 0.2 * wl.peak_kv_bytes(),
                       churn_budget_bytes=0.0)
    assert rep.churn_bytes == 0.0
    suppressed = [e for e in rep.events if not e.applied]
    assert suppressed and all(e.churn_bytes > 0 for e in suppressed)


def test_replanner_refuses_history_carrying_policies():
    tr = drift_workloads()["prompt_shift"].segments[0].workload
    fast = 0.2 * tr.peak_kv_bytes()
    pl = runtime.plan(tr, TPU_V5E_COST, fast, policy="lru_page",
                      objective="latency")
    rpl = OnlineReplanner(TPU_V5E_COST, fast)
    with pytest.raises(ValueError, match="supports_replan"):
        rpl.adopt(pl)


def test_plan_churn_bytes_counts_only_shrinks():
    tr = drift_workloads()["prompt_shift"].segments[0].workload
    pl = runtime.plan(tr, TPU_V5E_COST, 0.2 * tr.peak_kv_bytes(),
                      objective="latency")
    grown = dataclasses.replace(pl, slot_hot_windows=[
        w + pl.page_tokens for w in pl.slot_hot_windows])
    assert plan_churn_bytes(pl, grown, 64.0) == 0.0      # growth is free
    assert plan_churn_bytes(grown, pl, 64.0) == \
        len(pl.slot_hot_windows) * pl.page_tokens * 64.0


# --------------------------------------------------------- elastic lending ---

def test_flash_crowd_lends_and_reclaims_slots(reports):
    """While the crowd tenant sleeps its slots are lent to the steady
    tenant (pure slot_tenants deltas, zero churn); when the crowd wakes the
    owners reclaim them before the drift re-plan lands."""
    wl, rep = reports["flash_crowd"]
    lends = [e for e in rep.events if e.reason.startswith("lend:")]
    reclaims = [e for e in rep.events if e.reason.startswith("reclaim:")]
    assert lends and reclaims
    for e in lends + reclaims:
        assert e.applied and e.churn_bytes == 0.0
        assert set(e.delta.changes) == {"slot_tenants"}
    first = next(e for e in rep.events if e.reason == "lend:crowd->steady")
    assert first.plan.slot_tenants == ["steady"] * 4
    # reclaim restores the true ownership recorded on the initial plan
    assert reclaims[0].plan.slot_tenants == rep.plan0.slot_tenants
    # lending is rate-limited to once per window
    steps = [e.step for e in lends + reclaims]
    steps.sort()
    assert all(b - a >= int(rep.knobs["window"])
               for a, b in zip(steps, steps[1:]))


def test_surge_lends_steady_slots_to_the_crowd(reports):
    """Lending is symmetric: in the surge segment the steady tenant drains
    first and its slots go to the crowd."""
    wl, rep = reports["flash_crowd"]
    assert any(e.reason == "lend:steady->crowd" for e in rep.events)


# -------------------------------------------- engine: apply_plan agreement ---

@pytest.fixture(scope="module")
def replan_run():
    """A pools-layout run that adopts two re-plans mid-stream — one as a
    ``PlanDelta`` (window shrink), one as a full plan (shrink + tenancy
    swap) — plus the all-HBM reference for bit-exactness."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve import engine

    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 32, 4
    chatty = [(5, 5), (6, 4), (7, 5), (5, 4)]
    bursty = [(12, 6), (11, 5), (10, 4)]
    tenants = [runtime.Tenant("chatty", fast_quota_frac=0.5, slo_slack=1.05),
               runtime.Tenant("bursty", fast_quota_frac=0.5, slo_slack=2.0)]
    traces = [engine.serve_trace_for(get_config("smollm-360m"), rs, slots=2,
                                     layer_group=8)
              for rs in (chatty, bursty)]
    wl = runtime.MultiTenantWorkload(tenants, traces)
    plan_a = runtime.plan(wl, TPU_V5E_COST, 0.2 * wl.trace.peak_kv_bytes())
    plan_a = dataclasses.replace(plan_a, hot_window=16,
                                 slot_hot_windows=[8, 8, 8, 8],
                                 page_tokens=4)
    plan_b = dataclasses.replace(plan_a, slot_hot_windows=[4, 8, 4, 8])
    delta_b = plan_delta(plan_a, plan_b, step=3, reason="test:shrink")
    plan_c = dataclasses.replace(plan_b, slot_hot_windows=[4, 4, 4, 4],
                                 slot_tenants=["bursty", "bursty",
                                               "chatty", "chatty"])
    reqs = []
    key = jax.random.PRNGKey(3)
    for tn, stream in (("chatty", chatty), ("bursty", bursty)):
        for p, d in stream:
            key, sub = jax.random.split(key)
            reqs.append((jax.random.randint(sub, (p,), 0, cfg.vocab_size)
                         .astype(jnp.int32), d, tn))

    def drive(p, schedule=()):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p,
                                     slot_tenants=plan_a.slot_tenants)
        for t, d, tn in reqs:
            b.submit(t, d, tenant=tn)
        results, moved = [], []
        pending = sorted(schedule, key=lambda e: e[0])
        while b.queue or any(b.active):
            while pending and pending[0][0] <= len(b.step_migration_bytes):
                moved.append(b.apply_plan(pending.pop(0)[1]))
            if not b.step():
                break
            for i in range(b.B):
                if not b.active[i] and b.outputs[i]:
                    results.append(b.outputs[i])
                    b.outputs[i] = []
        return results, b, moved

    schedule = [(3, delta_b), (6, plan_c)]
    out_ref, _, _ = drive(None)
    out, b, moved = drive(plan_a, schedule)
    return {"engine": engine, "b": b, "out": out, "out_ref": out_ref,
            "moved": moved, "reqs": reqs, "slots": slots, "max_seq": max_seq,
            "plan_a": plan_a, "schedule": schedule}


def test_engine_counters_match_replay_across_replan_boundary(replan_run):
    """The satellite fix, pinned: with re-plans landing between decode
    steps, the engine's marker-based per-step series and the segment-aware
    replay (``plan_schedule=``) agree integer-for-integer, and the series
    sums to the total on both sides (bytes moved by ``apply_plan`` land in
    the next step's entry instead of vanishing)."""
    b, engine = replan_run["b"], replan_run["engine"]
    pred = engine.predict_pool_counters(
        [(int(t.shape[0]), d, tn) for t, d, tn in replan_run["reqs"]],
        replan_run["plan_a"], slots=replan_run["slots"],
        max_seq=replan_run["max_seq"], page_tokens=b.page_tokens,
        row_bytes=b._row_bytes, plan_schedule=replan_run["schedule"])
    assert pred["step_migration_bytes"] == b.step_migration_bytes
    assert pred["migration_bytes"] == b.sim_migration_bytes
    assert sum(pred["step_migration_bytes"]) == pred["migration_bytes"]
    assert sum(b.step_migration_bytes) == b.sim_migration_bytes
    assert pred["page_copies"] == b.pool.stats["page_copies"]
    assert pred["admit_page_writes"] == b.pool.stats["admit_page_writes"]
    assert pred["tenant_hot_peak"] == b.tenant_hot_peak
    # the live counter export bundles the same numbers
    c = b.counters()
    assert c["sim_migration_bytes"] == b.sim_migration_bytes
    assert c["step_migration_bytes"] == b.step_migration_bytes
    assert c["page_copies"] == pred["page_copies"]


def test_engine_apply_plan_moves_bytes_and_stays_consistent(replan_run):
    """Both adoptions really demoted pages (shrunken windows), the tenancy
    swap took effect for later admissions, and the page table is green."""
    b, moved = replan_run["b"], replan_run["moved"]
    assert len(moved) == 2 and moved[0] > 0        # the shrink delta copied
    assert b.slot_tenants == ["bursty", "bursty", "chatty", "chatty"]
    b.ptable.check()


def test_engine_replans_never_change_a_logit(replan_run):
    """Re-planning only moves KV between tiers: every request's decoded
    tokens are identical to the all-HBM reference run (as multisets — the
    tenancy swap may reorder completions across slots)."""
    got = sorted(tuple(o) for o in replan_run["out"])
    ref = sorted(tuple(o) for o in replan_run["out_ref"])
    assert got == ref


def test_engine_apply_plan_validates_geometry(replan_run):
    b = replan_run["b"]
    bad = dataclasses.replace(replan_run["plan_a"],
                              slot_tenants=["chatty"] * 3)
    with pytest.raises(ValueError, match="geometry mismatch"):
        b.apply_plan(bad)
    with pytest.raises(ValueError, match="re-paged in place"):
        b.apply_plan(dataclasses.replace(replan_run["plan_a"],
                                         page_tokens=3))


# ----------------------------------------------------------- hypothesis ------
# Guarded import (NOT importorskip at module level — that would skip the
# differential suite above with it); CI installs hypothesis under the
# deterministic HYPOTHESIS_PROFILE=ci registered in conftest.py.

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - CI installs it
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    from repro.core.hmsim import build_serve_trace

    def _mk_trace(reqs, slots):
        return build_serve_trace(reqs, num_slots=slots, num_layers=2,
                                 kv_token_bytes=256.0, weight_bytes=1e4,
                                 flops_per_token=1e6)

    @st.composite
    def plan_pairs(draw):
        slots = draw(st.integers(1, 3))
        frac = draw(st.sampled_from([0.15, 0.25, 0.4]))

        def plan_one():
            n = draw(st.integers(2, 5))
            reqs = [(draw(st.integers(4, 40)), draw(st.integers(2, 8)))
                    for _ in range(n)]
            tr = _mk_trace(reqs, slots)
            return runtime.plan(tr, TPU_V5E_COST,
                                max(1.0, frac * tr.peak_kv_bytes()),
                                policy="sentinel", objective="latency",
                                lookaheads=(2, 4))
        return plan_one(), plan_one()

    @given(plan_pairs())
    @settings(max_examples=20, deadline=None)
    def test_property_delta_apply_equals_fresh_plan(pair):
        """For ANY two plans: the diff applies back to the fresh plan
        byte-identically, the delta survives a JSON round trip unchanged,
        and a no-change diff is None."""
        old, new = pair
        d = plan_delta(old, new, step=1, reason="fuzz")
        if old.to_json() == new.to_json():
            assert d is None
            return
        assert d is not None
        assert old.apply_delta(d).to_json() == new.to_json()
        # the wire format: disk and memory deltas apply identically
        wire = runtime.PlanDelta.from_json(d.to_json())
        assert wire.to_json() == d.to_json()
        assert old.apply_delta(wire).to_json() == new.to_json()
        assert plan_delta(old, old) is None
        if new.digest() != old.digest():
            with pytest.raises(ValueError, match="emission order"):
                new.apply_delta(d)

    @st.composite
    def table_programs(draw):
        slots = draw(st.integers(1, 3))
        pg = draw(st.sampled_from([2, 4]))
        pages = draw(st.integers(2, 6))
        lens = [draw(st.integers(0, pages * pg)) for _ in range(slots)]
        rounds = draw(st.lists(
            st.tuples(*[st.floats(0.0, 1.0) for _ in range(slots)]),
            min_size=1, max_size=4))
        return slots, pg, pages, lens, rounds

    @given(table_programs())
    @settings(max_examples=25, deadline=None)
    def test_property_replan_demotions_keep_page_table_green(prog):
        """The delta-application path on the layout machinery: any sequence
        of re-plan cold-boundary targets leaves ``PageTable.check()`` green,
        never promotes (boundaries are monotone), bumps ``version`` on every
        page moved, and conserves bytes (pages demoted == cold pages)."""
        from repro.models.kvcache import PageTable
        slots, pg, pages, lens, rounds = prog
        pt = PageTable(slots, pages, pg)
        for s, ln in enumerate(lens):
            for _ in range(-(-ln // pg)):
                pt.alloc(s, 0)
        pt.check()
        demoted = [0] * slots
        for targets in rounds:
            for s, f in enumerate(targets):
                # a re-plan target: page-quantized, never past the length
                target = int(f * lens[s]) // pg * pg
                before = pt.cold_tokens(s)
                while pt.cold_tokens(s) < target:
                    v0 = pt.version
                    pt.demote(s, pt.cold_pages(s))
                    demoted[s] += 1
                    assert pt.version > v0
                    pt.check()
                assert pt.cold_tokens(s) >= before   # monotone, no promote
        for s in range(slots):
            assert pt.cold_pages(s) == demoted[s]
        pt.check()

    @st.composite
    def drift_cases(draw):
        slots = 2

        def seg(i):
            base = draw(st.sampled_from([12, 80]))
            n = draw(st.integers(2, 4))
            reqs = [(base + draw(st.integers(0, 6)),
                     draw(st.integers(6, 12))) for _ in range(n)]
            return DriftSegment(f"s{i}", _mk_trace(reqs, slots))
        nseg = draw(st.integers(2, 3))
        wl = DriftWorkload("fuzz", tuple(seg(i) for i in range(nseg)))
        frac = draw(st.sampled_from([0.2, 0.35, 0.5]))
        budget = draw(st.sampled_from([0.0, None]))
        return wl, frac, budget

    @given(drift_cases())
    @settings(max_examples=10, deadline=None)
    def test_property_random_drift_schedules(case):
        """Random drift schedules through the whole loop: the delta chain
        reconstructs every applied plan, churn stays within budget, the
        report serializes, and suppression really suppresses."""
        wl, frac, budget = case
        rep = replay_drift(wl, TPU_V5E_COST, frac * wl.peak_kv_bytes(),
                           window=4, min_dwell=4, lookaheads=(2, 4),
                           policy="sentinel", churn_budget_bytes=budget)
        assert rep.churn_bytes <= rep.churn_budget_bytes
        assert rep.online_s > 0 and rep.clairvoyant_s > 0
        p = rep.plan0
        for ev in rep.events:
            if ev.applied:
                p = p.apply_delta(ev.delta)
                assert p.to_json() == ev.plan.to_json()
        if budget == 0.0:
            assert all(e.churn_bytes == 0.0 for e in rep.events
                       if e.applied)
        import json
        json.loads(rep.to_json())          # the report is wire-clean
else:
    @pytest.mark.skip(reason="hypothesis not installed (CI installs it; the "
                             "differential suite above still ran)")
    def test_property_suites_need_hypothesis():
        pass
