"""Sentinel-Serve: simulated decode throughput, fast-memory fraction x batch
slots x placement policy — plus gates on the real pools engine.

The serving analogue of the paper's Fig. 10 sweep, dispatched entirely
through the unified runtime API (``runtime.plan`` + the one policy
registry): per-slot, per-layer KV blocks are the data objects; ``sentinel``
(lifetime-aware, object-granular, look-ahead prefetch via the decode-phase
planner) against the page-grain reactive LRU daemon and static PreferHBM
placement.  ``--policies`` accepts *any* registered policy — including the
training-native ``sentinel_mi`` / ``ial`` / ``all_slow`` — because every
policy runs on every workload.

    PYTHONPATH=src python -m benchmarks.bench_serve
    PYTHONPATH=src python -m benchmarks.bench_serve \
        --arch smollm-360m --fracs 0.1,0.2 --slots 4 --policies sentinel,lru_page
    PYTHONPATH=src python -m benchmarks.bench_serve \
        --objective latency --shared-prefix --json BENCH_serve.json

Exits non-zero if the Sentinel object policy loses to the best page-grain
baseline at the paper's headline 20% fast-memory fraction — the CI smoke
gate.  ``--objective latency`` additionally runs the time-domain sweep:
every policy's recorded per-step traffic is priced on the shared default
``CostModel`` (``core.hardware.default_cost_model``) and the gates move
from migration bytes to *simulated seconds* — at 20% fast memory
``sentinel`` must be at least as fast as ``lru_page`` in predicted time
and within 8% of ``all_fast`` (the paper's headline parity claim), and the
latency-objective planner must pick ``alpha_migration`` somewhere it beats
the bytes-objective plan's predicted time.  ``--shared-prefix`` runs the
N-tenants x one-system-prompt workload shared vs unshared — simulator
sweep plus the pool engine with ``prefix_key`` sharing — and gates shared
migration bytes AND peak pool bytes strictly below the unshared run at 20%
fast memory.  ``--tenants``
runs the adversarial multi-tenant SLO mix and gates ``sentinel_slo`` at
zero per-tenant quota violations (exactly where the tenant-blind
``sentinel`` violates at least one tenant's guarantee) with aggregate
migration bytes within 1.2x of the blind run.  ``--disagg`` runs the
prefill/decode disaggregation gates: the ``DisaggregatedEngine`` must
emit bit-identical tokens to the single-device pools engine with zero
steady-state re-packs, its cross-device migration ledger must equal the
planner's predicted edge traffic integer-exactly, and ``price_disagg``
must show disaggregated tokens/sec at or above colocated at equal total
HBM under a prefill-heavy mix.  ``--prefill`` runs the cache-aware prefill
gates on the real pool engine: shared-prefix admits must *run* strictly
fewer prefill tokens than the unshared stream (the donor pages' compute is
skipped), and on a burst mix the chunked engine must keep the p95 priced
decode-step gap strictly below one-shot admission at tokens/sec no worse —
both bit-identical to the dense all-HBM reference.  ``--json`` publishes every row (and the
gate verdicts) for trend tracking across PRs.
"""
from __future__ import annotations

import argparse
import json

from repro import runtime
from repro.configs.base import get_config
from repro.launch.cache import setup_compile_cache
from repro.core import hmsim
from repro.core.hardware import PAPER_HM, TPU_V5E
from repro.serve.engine import serve_trace_for

ARCH = "smollm-360m"
FRACS = (0.1, 0.2, 0.4, 0.8)
SLOTS = (4, 8)
# default sweep: the serving-native trio (any registered policy is allowed)
SERVE_POLICIES = ("lru_page", "prefer_fast", "sentinel")


def build_trace(cfg, slots: int) -> hmsim.ServeTrace:
    # full-size byte geometry (real KV/weight volumes decide placement
    # quality), coarsened to one object per 8-layer KV block so the pure-
    # Python sweep stays a smoke test
    reqs = hmsim.synthetic_requests(3 * slots)
    return serve_trace_for(cfg, reqs, slots=slots, layer_group=8)


def run(arch: str = ARCH, fracs=FRACS, slots_list=SLOTS, policies=None):
    cfg = get_config(arch)
    pols = policies or list(SERVE_POLICIES)
    rows = [("bench_serve", "hw", "slots", "fast_frac", "policy",
             "tok_per_s", "slowdown", "migrations", "slow_gb")]
    verdicts = []
    for hw, hw_name in ((TPU_V5E, "tpu-v5e"), (PAPER_HM, "paper-hm")):
        for slots in slots_list:
            trace = build_trace(cfg, slots)
            peak = trace.peak_kv_bytes()
            # plan once at the headline fraction; the chosen look-ahead is a
            # property of the access schedule, not of the budget
            pl = runtime.plan(trace, hw, 0.2 * peak)
            for frac in fracs:
                fast = frac * peak
                best = {}
                for pol in pols:
                    knobs = ({"lookahead": pl.lookahead}
                             if pol == "sentinel" else {})
                    r = runtime.simulate(trace, hw, fast, pol, **knobs)
                    best[pol] = r
                    rows.append(("bench_serve", hw_name, slots, frac, pol,
                                 round(r.decode_throughput, 1),
                                 round(r.slowdown, 4), r.migrations,
                                 round(r.slow_bytes_accessed / 1e9, 3)))
                if abs(frac - 0.2) < 1e-9 and \
                        {"sentinel", "lru_page"} <= set(best):
                    page = best["lru_page"].decode_throughput
                    verdicts.append((hw_name, slots,
                                     best["sentinel"].decode_throughput, page))
    return rows, verdicts


def run_latency(arch: str = ARCH, fracs=FRACS, slots_list=SLOTS):
    """Time-domain sweep (``--objective latency``): price each policy's
    recorded per-step traffic on the shared default cost model and compare
    predicted seconds, the measurement ``runtime.plan(objective="latency")``
    selects by.  Returns rows, the 20% gate inputs
    ``(slots, sentinel_s, lru_page_s, all_fast_s)``, and the cells where the
    latency-objective plan picked ``alpha_migration`` and beat the
    bytes-objective plan's predicted time."""
    from repro.core.hardware import default_cost_model
    cm = default_cost_model()
    cfg = get_config(arch)
    rows = [("bench_serve_latency", "slots", "fast_frac", "policy",
             "pred_tok_per_s", "pred_slowdown", "pred_time_s")]
    gates = []
    alpha_wins = []
    for slots in slots_list:
        trace = build_trace(cfg, slots)
        peak = trace.peak_kv_bytes()
        for frac in fracs:
            fast = frac * peak
            pl_lat = runtime.plan(trace, cm, fast, objective="latency")
            pl_byt = runtime.plan(trace, cm, fast)
            t_bytes = cm.price_result(pl_byt.sim).time
            reps = {}
            for pol in ("sentinel", "lru_page", "all_fast"):
                knobs = ({"lookahead": pl_lat.lookahead}
                         if pol == "sentinel" else {})
                r = runtime.simulate(trace, cm, fast, pol, **knobs)
                reps[pol] = rep = cm.price_result(r)
                rows.append(("bench_serve_latency", slots, frac, pol,
                             round(rep.tokens_per_s, 1),
                             round(rep.slowdown, 4), round(rep.time, 6)))
            rows.append(("bench_serve_latency", slots, frac,
                         f"plan:{pl_lat.policy}",
                         round(pl_lat.predicted_decode_throughput, 1),
                         round(pl_lat.predicted_time
                               / max(reps["all_fast"].time, 1e-30), 4),
                         round(pl_lat.predicted_time, 6)))
            if abs(frac - 0.2) < 1e-9:
                gates.append((slots, reps["sentinel"].time,
                              reps["lru_page"].time, reps["all_fast"].time))
            if pl_lat.policy == "alpha_migration" and \
                    pl_lat.predicted_time < t_bytes:
                alpha_wins.append((slots, frac,
                                   round(pl_lat.predicted_time, 6),
                                   round(t_bytes, 6)))
    return rows, gates, alpha_wins


def run_shared_prefix(fracs=FRACS):
    """Prefix-sharing sweep on the unified surface: the N-tenants x one
    system prompt workload, shared (KV blocks of the common prefix are one
    physical allocation) vs the byte-identical unshared stream, under the
    ``sentinel`` policy.  Returns rows and the 20% gate inputs
    (shared/unshared migration bytes and physical peaks)."""
    from repro.runtime.synthetic import synthetic_shared_prefix_trace
    ts = synthetic_shared_prefix_trace(shared=True)
    tu = synthetic_shared_prefix_trace(shared=False)
    peak_s, peak_u = ts.peak_kv_bytes(), tu.peak_kv_bytes()
    rows = [("bench_serve_shared", "fast_frac", "mode", "tok_per_s",
             "migration_mb", "peak_mb")]
    gate = None
    for frac in fracs:
        fast = frac * peak_u                   # matched budget for both
        rs = runtime.simulate(ts, TPU_V5E, fast, "sentinel")
        ru = runtime.simulate(tu, TPU_V5E, fast, "sentinel")
        for mode, r, peak in (("shared", rs, peak_s), ("unshared", ru, peak_u)):
            rows.append(("bench_serve_shared", frac, mode,
                         round(r.decode_throughput, 1),
                         round((r.bytes_s2f + r.bytes_f2s) / 1e6, 4),
                         round(peak / 1e6, 4)))
        if abs(frac - 0.2) < 1e-9:
            gate = (rs.bytes_s2f + rs.bytes_f2s,
                    ru.bytes_s2f + ru.bytes_f2s, peak_s, peak_u)
    return rows, gate


def run_tenants(fracs=FRACS):
    """Multi-tenant SLO sweep on the unified surface: the adversarial
    chatty-vs-bursty mix (``synthetic_multi_tenant_trace``) under the
    tenant-blind ``sentinel`` vs the SLO-aware ``sentinel_slo``, both
    measured against the same per-tenant guarantees.  Returns rows plus the
    20% gate inputs: (blind violations, slo violations, blind migration
    bytes, slo migration bytes)."""
    from repro.runtime.synthetic import synthetic_multi_tenant_trace
    wl = synthetic_multi_tenant_trace()
    peak = wl.trace.peak_kv_bytes()
    rows = [("bench_serve_tenants", "fast_frac", "policy", "tok_per_s",
             "violations", "migration_mb", "tenant_fast_mb")]
    gate = None
    for frac in fracs:
        fast = frac * peak
        rb = runtime.simulate(wl, TPU_V5E, fast, "sentinel",
                              tenant_quotas=wl.tenant_quotas)
        rs = runtime.simulate(wl, TPU_V5E, fast, "sentinel_slo",
                              tenant_quotas=wl.tenant_quotas,
                              tenant_slack=wl.tenant_slack)
        for pol, r in (("sentinel", rb), ("sentinel_slo", rs)):
            # flat comma-free encoding: CSV rows keep a fixed column count
            per_tenant = "|".join(
                f"{k}:{round(v / 1e6, 3)}"
                for k, v in sorted(r.tenant_fast_bytes.items()))
            rows.append(("bench_serve_tenants", frac, pol,
                         round(r.decode_throughput, 1),
                         sum(r.tenant_violations.values()),
                         round((r.bytes_s2f + r.bytes_f2s) / 1e6, 4),
                         per_tenant))
        if abs(frac - 0.2) < 1e-9:
            gate = (sum(rb.tenant_violations.values()),
                    sum(rs.tenant_violations.values()),
                    rb.bytes_s2f + rb.bytes_f2s, rs.bytes_s2f + rs.bytes_f2s)
    return rows, gate


def run_shared_prefix_engine(arch: str = ARCH):
    """Real-engine prefix sharing: the persistent-pool batcher decoding two
    tenants off one system prompt, with and without ``prefix_key`` sharing.
    Gates on (a) tokens identical to the all-HBM reference in both runs and
    (b) shared migration bytes AND peak pool bytes strictly below
    unshared."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve import engine

    cfg = get_config(arch).reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 32, 2
    sys_p = jax.random.randint(jax.random.PRNGKey(7), (9,), 0,
                               cfg.vocab_size).astype(jnp.int32)
    reqs = []
    for i in range(4):
        user = jax.random.randint(jax.random.PRNGKey(11 + i), (2 + i,), 0,
                                  cfg.vocab_size).astype(jnp.int32)
        reqs.append((jnp.concatenate([sys_p, user]), 5 + i % 2))
    trace = serve_trace_for(get_config(arch),
                            [(int(t.shape[0]), d, 0) for t, d in reqs],
                            slots=slots, layer_group=8,
                            shared_prefix_tokens=int(sys_p.shape[0]))
    plan = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    plan = dataclasses.replace(plan, hot_window=max_seq // 2,
                               slot_hot_windows=[4, 8], page_tokens=4)

    def drive(p, shared):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p)
        for t, d in reqs:
            b.submit(t, d, prefix_key="sys" if shared else None)
        out = b.run()
        if b.pool is None:
            return out, 0.0, 0.0
        page_bytes = b.page_tokens * b._row_bytes
        return out, b.sim_migration_bytes, b.pool.peak_pages * page_bytes

    base, _, _ = drive(None, False)
    out_s, mig_s, peak_s = drive(plan, True)
    out_u, mig_u, peak_u = drive(plan, False)
    match = base == out_s == out_u
    rows = [("bench_serve_shared_engine", "mode", "migration_kb", "peak_kb",
             "tokens_match"),
            ("bench_serve_shared_engine", "shared", round(mig_s / 1e3, 3),
             round(peak_s / 1e3, 3), match),
            ("bench_serve_shared_engine", "unshared", round(mig_u / 1e3, 3),
             round(peak_u / 1e3, 3), match)]
    return rows, (match, mig_s, mig_u, peak_s, peak_u)


def run_prefill(arch: str = ARCH):
    """Cache-aware prefill gates on the real pool engine.

    (a) Shared-prefix compute skip: admitting N requests off one system
        prompt with ``prefix_key`` set must *run* strictly fewer prefill
        tokens than the byte-identical unshared stream — the rows whose KV
        maps onto the donor's pages are never recomputed
        (``prefill_compute_tokens`` / ``prefill_skipped_tokens``) — with
        tokens identical to the dense all-HBM reference.
    (b) Chunked prefill: on a burst mix (one long-decode anchor slot plus a
        crowd of long prompts) the chunked engine must emit the same token
        set as one-shot admission while its p95 priced decode-step gap
        drops and tokens/sec does not: each engine step is priced through
        ``CostModel.step_time`` — ``chunked_prefill=True`` folds the step's
        prefill tokens into the pipe maximum (chunks hide behind decode),
        the one-shot run serializes them after the step.

    Returns rows and the verdict tuple ``(match_skip, compute_shared,
    compute_unshared, match_chunk, p95_chunk, p95_oneshot, tok_s_chunk,
    tok_s_oneshot)``.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.hardware import default_cost_model
    from repro.models import model
    from repro.models.layers import split_params
    from repro.runtime.costmodel import StepTraffic
    from repro.serve import engine

    cfg = get_config(arch).reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 32, 2
    cm = default_cost_model()

    def drive(p, reqs, chunk=0, keys=None):
        if p is not None:
            p = dataclasses.replace(p, prefill_chunk_tokens=chunk)
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p)
        for i, (t, d) in enumerate(reqs):
            b.submit(t, d, prefix_key=keys[i] if keys else None)
        results, deltas, prev = [], [], 0
        while b.queue or b._jobs or any(b.active):
            if not b.step():
                break
            for i in range(slots):
                if not b.active[i] and b.outputs[i]:
                    results.append(b.outputs[i])
                    b.outputs[i] = []
            cur = sum(len(r) for r in results) \
                + sum(len(o) for o in b.outputs)
            deltas.append(cur - prev)
            prev = cur
        return results, deltas, b.counters()

    def canon(outs):
        return sorted(tuple(o) for o in outs)

    # --- (a) shared-prefix compute skip ------------------------------------
    sys_p = jax.random.randint(jax.random.PRNGKey(7), (9,), 0,
                               cfg.vocab_size).astype(jnp.int32)
    sreqs = []
    for i in range(4):
        user = jax.random.randint(jax.random.PRNGKey(11 + i), (2 + i,), 0,
                                  cfg.vocab_size).astype(jnp.int32)
        sreqs.append((jnp.concatenate([sys_p, user]), 5 + i % 2))
    strace = serve_trace_for(get_config(arch),
                             [(int(t.shape[0]), d, 0) for t, d in sreqs],
                             slots=slots, layer_group=8,
                             shared_prefix_tokens=int(sys_p.shape[0]))
    splan = runtime.plan(strace, TPU_V5E, 0.2 * strace.peak_kv_bytes())
    splan = dataclasses.replace(splan, hot_window=max_seq // 2,
                                slot_hot_windows=[4, 8], page_tokens=4)
    base_s, _, _ = drive(None, sreqs)
    out_sh, _, cnt_sh = drive(splan, sreqs, keys=["sys"] * 4)
    out_un, _, cnt_un = drive(splan, sreqs)
    match_skip = canon(base_s) == canon(out_sh) == canon(out_un)
    comp_sh = cnt_sh["prefill_compute_tokens"]
    comp_un = cnt_un["prefill_compute_tokens"]

    # --- (b) chunked prefill on a burst mix --------------------------------
    lens = [(6, 18), (20, 5), (18, 5), (19, 4)]
    key, breqs = jax.random.PRNGKey(5), []
    for plen, d in lens:
        key, sub = jax.random.split(key)
        breqs.append((jax.random.randint(sub, (plen,), 0,
                                         cfg.vocab_size).astype(jnp.int32),
                      d))
    btrace = serve_trace_for(get_config(arch), lens, slots=slots,
                             layer_group=8)
    bplan = runtime.plan(btrace, TPU_V5E, 0.2 * btrace.peak_kv_bytes())
    bplan = dataclasses.replace(bplan, hot_window=max_seq // 2,
                                slot_hot_windows=[4, 8], page_tokens=4)
    base_b, _, _ = drive(None, breqs)
    out_1, d_1, c_1 = drive(bplan, breqs, chunk=0)
    out_c, d_c, c_c = drive(bplan, breqs, chunk=8)
    match_chunk = canon(base_b) == canon(out_1) == canon(out_c)

    # per-step gap pricing: decode tokens (output-count delta) and prefill
    # tokens drawn from the engines' own step series; weight/KV streaming is
    # identical in both runs, so the gap is priced on what the chunker
    # actually moves — the compute pipe and the per-token KV reads
    ft = getattr(btrace, "flops_per_token", 0.0) or 1e9
    rb = btrace.num_layers * btrace.kv_token_bytes

    def gaps(deltas, prefill_tokens, chunked):
        sp = list(prefill_tokens) + [0] * (len(deltas) - len(prefill_tokens))
        out = []
        for dtok, ptok in zip(deltas, sp):
            tr = StepTraffic(flops=dtok * ft, fast_read=dtok * rb,
                             tokens=dtok, prefill_flops=ptok * ft)
            out.append(cm.step_time(tr, chunked_prefill=chunked))
        return out

    def p95(series):
        s = sorted(series)
        return s[int(round(0.95 * (len(s) - 1)))] if s else 0.0

    g_1 = gaps(d_1, c_1["step_prefill_tokens"], chunked=False)
    g_c = gaps(d_c, c_c["step_prefill_tokens"], chunked=True)
    p95_1, p95_c = p95(g_1), p95(g_c)
    tok_1 = sum(d_1) / max(sum(g_1), 1e-30)
    tok_c = sum(d_c) / max(sum(g_c), 1e-30)

    ft_s = getattr(strace, "flops_per_token", 0.0) or 1e9
    rows = [("bench_serve_prefill", "metric", "value"),
            ("bench_serve_prefill", "tokens_match_skip", match_skip),
            ("bench_serve_prefill", "prefill_compute_tokens_shared", comp_sh),
            ("bench_serve_prefill", "prefill_compute_tokens_unshared",
             comp_un),
            ("bench_serve_prefill", "prefill_skipped_tokens",
             cnt_sh["prefill_skipped_tokens"]),
            ("bench_serve_prefill", "prefill_gflops_saved",
             round((comp_un - comp_sh) * ft_s / 1e9, 4)),
            ("bench_serve_prefill", "tokens_match_chunk", match_chunk),
            ("bench_serve_prefill", "p95_gap_oneshot_us",
             round(p95_1 * 1e6, 4)),
            ("bench_serve_prefill", "p95_gap_chunked_us",
             round(p95_c * 1e6, 4)),
            ("bench_serve_prefill", "tok_s_oneshot", round(tok_1, 1)),
            ("bench_serve_prefill", "tok_s_chunked", round(tok_c, 1))]
    return rows, (match_skip, comp_sh, comp_un, match_chunk,
                  p95_c, p95_1, tok_c, tok_1)


def run_disagg(arch: str = ARCH):
    """Prefill/decode disaggregation: the real engine pair plus the
    planner-side throughput model.

    (c) ``DisaggregatedEngine`` must emit bit-identical tokens to the
        single-device ``ContinuousBatcher`` in the pools layout with zero
        steady-state re-packs; (b) its cross-device migration ledger must
        equal ``predict_pool_counters``'s predicted edge traffic exactly;
        (a) ``price_disagg`` must show disaggregated tokens/sec at or above
        colocated at equal total HBM under a prefill-heavy mix.

    Returns rows and the verdict tuple
    ``(match, repacks, xdev_actual, xdev_pred, tok_s_disagg, tok_s_coloc)``.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.hardware import default_cost_model
    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve import engine
    from repro.serve.disagg import DisaggregatedEngine, price_disagg
    from repro.serve.engine import predict_pool_counters

    cfg = get_config(arch).reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 32, 2
    requests = [(7, 6), (9, 5), (6, 7), (8, 6)]
    trace = serve_trace_for(get_config(arch), requests, slots=slots,
                            layer_group=8)
    plan = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    plan = dataclasses.replace(plan, hot_window=max_seq // 2,
                               slot_hot_windows=[4, 8], page_tokens=4)

    def drive(eng_cls, **kw):
        b = eng_cls(params, cfg, slots, max_seq, plan=plan, **kw)
        key = jax.random.PRNGKey(3)
        for plen, d in requests:
            key, sub = jax.random.split(key)
            b.submit(jax.random.randint(sub, (plen,), 0,
                                        cfg.vocab_size).astype(jnp.int32), d)
        return b.run(), b

    out_c, _ = drive(engine.ContinuousBatcher)
    out_d, bd = drive(DisaggregatedEngine)
    match = out_c == out_d
    repacks = bd.counters()["repacks"]
    xdev = bd.xdev_migration_bytes
    pred = predict_pool_counters(requests, plan, slots=slots,
                                 max_seq=max_seq,
                                 page_tokens=bd.page_tokens,
                                 row_bytes=bd._row_bytes)
    xdev_pred = pred["xdev_migration_bytes"]

    # (a) the planner-side throughput model on a prefill-heavy mix: long
    # prompts, short decodes — the regime disaggregation exists for
    heavy = [(480, 24), (512, 16), (448, 32), (500, 20)]
    htrace = serve_trace_for(get_config(arch), heavy, slots=len(heavy),
                             layer_group=8)
    priced = price_disagg(htrace, default_cost_model(),
                          0.2 * htrace.peak_kv_bytes())
    tok_c = priced["colocated"].tokens_per_s
    tok_d = priced["disagg"].tokens_per_s

    rows = [("bench_serve_disagg", "metric", "value"),
            ("bench_serve_disagg", "tokens_match", match),
            ("bench_serve_disagg", "repacks", repacks),
            ("bench_serve_disagg", "xdev_migration_kb",
             round(xdev / 1e3, 3)),
            ("bench_serve_disagg", "xdev_predicted_kb",
             round(xdev_pred / 1e3, 3)),
            ("bench_serve_disagg", "edge_stream_mb",
             round(priced["edge_bytes"] / 1e6, 4)),
            ("bench_serve_disagg", "colocated_tok_s", round(tok_c, 1)),
            ("bench_serve_disagg", "disagg_tok_s", round(tok_d, 1))]
    return rows, (match, repacks, xdev, xdev_pred, tok_d, tok_c)


def _sharded_scenario(arch: str, devs) -> dict:
    """The live 2-shard engine on ``devs`` (>= 4 devices: 2 prefill + 2
    decode, and the first two for the single-decode comparison): wall-clock
    tok/s single vs sharded, the per-edge ledger against the replay, and one
    decode step's overlap with a concurrent prefill->decode KV stream."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp

    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve.disagg import DisaggregatedEngine
    from repro.serve.engine import predict_pool_counters

    cfg = get_config(arch).reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 64, 4
    requests = [(48, 12)] * 4        # prefill-heavy: long prompts, short gen
    trace = serve_trace_for(get_config(arch), requests, slots=slots,
                            layer_group=8)
    plan = runtime.plan(trace, TPU_V5E, 0.3 * trace.peak_kv_bytes())
    plan = dataclasses.replace(plan, hot_window=32, slot_hot_windows=None,
                               page_tokens=8)
    halves = [s % 2 for s in range(slots)]

    def drive(devices, sd, seed=3):
        b = DisaggregatedEngine(params, cfg, slots, max_seq,
                                plan=dataclasses.replace(plan,
                                                         slot_devices=sd),
                                devices=devices)
        key = jax.random.PRNGKey(seed)
        for plen, d in requests:
            key, sub = jax.random.split(key)
            b.submit(jax.random.randint(sub, (plen,), 0,
                                        cfg.vocab_size).astype(jnp.int32), d)
        t0 = time.perf_counter()
        outs = b.run()
        dt = time.perf_counter() - t0
        return sum(len(o) for o in outs) / dt, b

    drive(devs[:2], None)                      # compile warmup, both shapes
    drive(devs, halves)
    tps1, _ = drive(devs[:2], None)
    tps2, b2 = drive(devs, halves)
    b2.mesh_table.check()
    pred = predict_pool_counters(
        requests, dataclasses.replace(plan, slot_devices=halves),
        slots=slots, max_seq=max_seq, page_tokens=b2.page_tokens,
        row_bytes=b2._row_bytes, dense_admit=True)
    ledger_exact = (dict(b2.mesh_table.edge_bytes)
                    == pred["edge_migration_bytes"])

    # measured overlap: one decode step with vs without a concurrent
    # KV-page stream over the prefill->decode edge.  Primed on a fresh
    # engine so every timed step has all slots active and no admissions in
    # flight.
    b = DisaggregatedEngine(params, cfg, slots, max_seq,
                            plan=dataclasses.replace(plan,
                                                     slot_devices=halves),
                            devices=devs)
    key = jax.random.PRNGKey(5)
    for plen, _d in requests:
        key, sub = jax.random.split(key)
        b.submit(jax.random.randint(sub, (plen,), 0,
                                    cfg.vocab_size).astype(jnp.int32), 12)
    while b.queue or b._jobs:
        b.step()
    b.step()                                   # compile the decode step

    width = cfg.num_kv_heads * cfg.head_dim
    payload = jnp.zeros((cfg.num_layers, 2, 4, b.page_tokens, width),
                        jnp.float32)
    payload = jax.device_put(payload, b.prefill_devices[0])
    payload.block_until_ready()
    stream_bytes = float(payload.size * 4)

    def t_stream():
        t0 = time.perf_counter()
        y = jax.device_put(payload, b.decode_devices[0])
        y.block_until_ready()
        return time.perf_counter() - t0

    def t_step(with_stream):
        t0 = time.perf_counter()
        y = jax.device_put(payload, b.decode_devices[0]) if with_stream \
            else None
        b.step()
        jax.block_until_ready(b.last_tok)
        if y is not None:
            y.block_until_ready()
        return time.perf_counter() - t0

    def med(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    stream_s = med([t_stream() for _ in range(5)])
    plain, both = [], []
    while any(b.active) and len(plain) < 8:
        plain.append(t_step(False))
        if any(b.active):
            both.append(t_step(True))
    plain_s, both_s = med(plain), med(both or [0.0])
    denom = min(stream_s, plain_s) or 1.0
    overlap = max(0.0, min(1.0, (plain_s + stream_s - both_s) / denom))
    return {"tok_s_single": tps1, "tok_s_sharded": tps2,
            "ledger_exact": ledger_exact, "stream_bytes": stream_bytes,
            "step_ms": plain_s * 1e3, "stream_ms": stream_s * 1e3,
            "step_with_stream_ms": both_s * 1e3, "overlap_frac": overlap,
            "devices": [d.device_kind for d in devs]}


def run_disagg_sharded(arch: str = ARCH, devices=None):
    """Multi-shard disaggregation: the planner-side scaling gate plus the
    measured KV-stream/decode overlap, run in this process on ``devices``
    (default: every device JAX sees; at least four — 2 prefill + 2 decode).
    On a CPU host, start the process with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

    Gates (deterministic, modeled): (a) ``price_disagg`` with two decode
    shards — each keeping the single run's per-device HBM — must price
    sharded tokens/sec at or above the single-decode disaggregated run on
    a prefill-heavy mix; (b) the live 2-shard engine's per-edge
    ``MeshPageTable`` ledger must equal ``predict_pool_counters``'s
    integer-exactly.  The wall-clock rows (sharded vs single tok/s; one
    decode step with vs without a concurrent prefill->decode KV-page
    stream, next to the cost model's edge-pipe time for the same bytes)
    are published, not gated — on forced host devices they share the same
    physical cores, so CPU wall-clock says nothing about a real mesh.
    """
    import jax

    from repro.core.hardware import default_cost_model
    from repro.serve.disagg import price_disagg

    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) < 4:
        raise ValueError(f"run_disagg_sharded needs 4 devices, got "
                         f"{len(devices)}")
    cm = default_cost_model()
    heavy = [(480, 24), (512, 16), (448, 32), (500, 20)]
    htrace = serve_trace_for(get_config(arch), heavy, slots=len(heavy),
                             layer_group=8)
    fast = 0.2 * htrace.peak_kv_bytes()
    single = price_disagg(htrace, cm, fast)
    # two shards, each with the SAME per-device HBM as the single run:
    # scaling out adds devices, it does not shrink them
    sharded = price_disagg(htrace, cm, 2 * fast, decode_devices=2)
    tok_1 = single["disagg"].tokens_per_s
    tok_n = sharded["disagg"].tokens_per_s

    rec = _sharded_scenario(arch, devices[:4])
    modeled_stream_ms = (rec["stream_bytes"] / cm.link_bw * 1e3
                         if cm.link_bw else float("inf"))

    rows = [("bench_serve_disagg_sharded", "metric", "value"),
            ("bench_serve_disagg_sharded", "modeled_single_tok_s",
             round(tok_1, 1)),
            ("bench_serve_disagg_sharded", "modeled_sharded_tok_s",
             round(tok_n, 1)),
            ("bench_serve_disagg_sharded", "ledger_exact",
             rec["ledger_exact"]),
            ("bench_serve_disagg_sharded", "wall_single_tok_s",
             round(rec["tok_s_single"], 2)),
            ("bench_serve_disagg_sharded", "wall_sharded_tok_s",
             round(rec["tok_s_sharded"], 2)),
            ("bench_serve_disagg_sharded", "step_ms",
             round(rec["step_ms"], 3)),
            ("bench_serve_disagg_sharded", "stream_ms_measured",
             round(rec["stream_ms"], 3)),
            ("bench_serve_disagg_sharded", "stream_ms_modeled",
             round(modeled_stream_ms, 6)),
            ("bench_serve_disagg_sharded", "step_with_stream_ms",
             round(rec["step_with_stream_ms"], 3)),
            ("bench_serve_disagg_sharded", "overlap_frac",
             round(rec["overlap_frac"], 3))]
    return rows, (tok_n, tok_1, rec["ledger_exact"], rec)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default=ARCH)
    ap.add_argument("--fracs", default=",".join(map(str, FRACS)),
                    help="comma-separated fast-memory fractions of peak KV")
    ap.add_argument("--slots", default=",".join(map(str, SLOTS)),
                    help="comma-separated batch-slot counts")
    ap.add_argument("--policies", default="",
                    help="comma-separated subset of "
                         f"{runtime.list_policies()}")
    ap.add_argument("--objective", default="bytes",
                    choices=["bytes", "latency"],
                    help="latency: also run the time-domain sweep on the "
                         "default CostModel and gate on predicted seconds")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="also run the prefix-sharing sweep (simulator + "
                         "persistent-pool engine) and gate shared strictly "
                         "below unshared at 20%% fast memory")
    ap.add_argument("--tenants", action="store_true",
                    help="also run the multi-tenant SLO sweep and gate "
                         "sentinel_slo at zero quota violations (where "
                         "tenant-blind sentinel violates) with migration "
                         "bytes within 1.2x, at 20%% fast memory")
    ap.add_argument("--prefill", action="store_true",
                    help="also run the cache-aware prefill gates: shared-"
                         "prefix admits compute strictly fewer prefill "
                         "tokens than unshared, chunked prefill keeps the "
                         "p95 priced decode-step gap below one-shot at "
                         "tokens/sec no worse, both bit-identical to the "
                         "dense all-HBM reference")
    ap.add_argument("--disagg", action="store_true",
                    help="also run the prefill/decode disaggregation gates: "
                         "bit-identical tokens vs the single-device engine "
                         "with zero re-packs, cross-device migration bytes "
                         "equal to the planner's predicted edge traffic, "
                         "disaggregated tokens/sec at or above colocated "
                         "at equal total HBM (prefill-heavy mix), plus the "
                         "2-shard gates on a forced 4-device host mesh: "
                         "modeled sharded tok/s at or above single-decode "
                         "at equal per-shard HBM, the live 2-shard edge "
                         "ledger replay-exact, and the measured-vs-modeled "
                         "KV-stream/decode overlap published")
    ap.add_argument("--json", default="",
                    help="write rows + verdicts to this JSON file")
    args = ap.parse_args(argv)
    setup_compile_cache()

    fracs = tuple(float(x) for x in args.fracs.split(",") if x)
    slots_list = tuple(int(x) for x in args.slots.split(",") if x)
    policies = [p for p in args.policies.split(",") if p] or None

    rows, verdicts = run(args.arch, fracs, slots_list, policies)
    for r in rows:
        print(",".join(map(str, r)))
    ok = True
    checks = []
    if not verdicts:
        # the headline gate needs frac 0.2 and both sentinel + lru_page; be
        # loud that it did NOT run rather than exiting 0 indistinguishably
        checks.append({"check": "sentinel_vs_page@20%", "status": "SKIPPED",
                       "reason": "requires --fracs containing 0.2 and "
                                 "--policies containing sentinel,lru_page"})
        print("check,sentinel/page@20%,SKIPPED (needs frac 0.2 + both "
              "sentinel and lru_page policies)")
    for hw_name, slots, sent, page in verdicts:
        rel = sent / max(page, 1e-30)
        status = "OK" if rel >= 1.0 else "FAIL"
        ok &= rel >= 1.0
        checks.append({"check": "sentinel_vs_page@20%", "hw": hw_name,
                       "slots": slots, "ratio": round(rel, 4),
                       "status": status})
        print(f"check,{hw_name},slots={slots},sentinel/page@20%={rel:.3f},"
              f"{status}")

    latency_rows = []
    if args.objective == "latency":
        lrows, lgates, alpha_wins = run_latency(args.arch, fracs, slots_list)
        latency_rows += lrows
        for r in lrows:
            print(",".join(map(str, r)))
        if not lgates:
            checks.append({"check": "latency@20%", "status": "SKIPPED",
                           "reason": "requires --fracs containing 0.2"})
            print("check,latency@20%,SKIPPED (needs frac 0.2)")
        for slots, t_s, t_l, t_af in lgates:
            rel_af = t_s / max(t_af, 1e-30)
            l_ok = t_s <= t_l and rel_af <= 1.08
            ok &= l_ok
            checks.append({"check": "latency@20%", "slots": slots,
                           "sentinel_s": round(t_s, 6),
                           "lru_page_s": round(t_l, 6),
                           "all_fast_s": round(t_af, 6),
                           "sentinel_vs_all_fast": round(rel_af, 4),
                           "status": "OK" if l_ok else "FAIL"})
            print(f"check,latency@20%,slots={slots},"
                  f"sentinel={t_s:.6f}s,lru_page={t_l:.6f}s,"
                  f"vs_all_fast={rel_af:.4f},{'OK' if l_ok else 'FAIL'}")
        a_ok = bool(alpha_wins)
        ok &= a_ok
        checks.append({"check": "alpha_beats_bytes_plan",
                       "cells": [list(c) for c in alpha_wins],
                       "status": "OK" if a_ok else "FAIL"})
        print(f"check,alpha_beats_bytes_plan,cells={len(alpha_wins)},"
              f"{'OK' if a_ok else 'FAIL'}")

    shared_rows = []
    if args.shared_prefix:
        srows, gate = run_shared_prefix(fracs)
        shared_rows += srows
        for r in srows:
            print(",".join(map(str, r)))
        if gate is None:
            checks.append({"check": "shared_prefix@20%", "status": "SKIPPED",
                           "reason": "requires --fracs containing 0.2"})
            print("check,shared_prefix@20%,SKIPPED (needs frac 0.2)")
        else:
            mig_s, mig_u, peak_s, peak_u = gate
            s_ok = mig_s < mig_u and peak_s < peak_u
            ok &= s_ok
            checks.append({"check": "shared_prefix@20%",
                           "migration_shared_mb": round(mig_s / 1e6, 4),
                           "migration_unshared_mb": round(mig_u / 1e6, 4),
                           "peak_shared_mb": round(peak_s / 1e6, 4),
                           "peak_unshared_mb": round(peak_u / 1e6, 4),
                           "status": "OK" if s_ok else "FAIL"})
            print(f"check,shared_prefix@20%,mig={mig_s / 1e6:.4f}/"
                  f"{mig_u / 1e6:.4f}MB,peak={peak_s / 1e6:.4f}/"
                  f"{peak_u / 1e6:.4f}MB,{'OK' if s_ok else 'FAIL'}")
        erows, (match, mig_s, mig_u, peak_s, peak_u) = \
            run_shared_prefix_engine(args.arch)
        shared_rows += erows
        for r in erows:
            print(",".join(map(str, r)))
        e_ok = match and mig_s < mig_u and peak_s < peak_u
        ok &= e_ok
        checks.append({"check": "shared_prefix_engine",
                       "tokens_match": match,
                       "migration_shared_kb": round(mig_s / 1e3, 3),
                       "migration_unshared_kb": round(mig_u / 1e3, 3),
                       "peak_shared_kb": round(peak_s / 1e3, 3),
                       "peak_unshared_kb": round(peak_u / 1e3, 3),
                       "status": "OK" if e_ok else "FAIL"})
        print(f"check,shared_engine,match={match},"
              f"mig={mig_s / 1e3:.3f}/{mig_u / 1e3:.3f}kB,"
              f"peak={peak_s / 1e3:.3f}/{peak_u / 1e3:.3f}kB,"
              f"{'OK' if e_ok else 'FAIL'}")

    tenant_rows = []
    if args.tenants:
        trows, gate = run_tenants(fracs)
        tenant_rows += trows
        for r in trows:
            print(",".join(map(str, r)))
        if gate is None:
            checks.append({"check": "tenant_slo@20%", "status": "SKIPPED",
                           "reason": "requires --fracs containing 0.2"})
            print("check,tenant_slo@20%,SKIPPED (needs frac 0.2)")
        else:
            v_blind, v_slo, mig_blind, mig_slo = gate
            # the SLO claim: guarantees hold exactly where the tenant-blind
            # policy breaks them, at bounded extra migration traffic
            t_ok = v_slo == 0 and v_blind >= 1 and \
                mig_slo <= 1.2 * mig_blind
            ok &= t_ok
            checks.append({"check": "tenant_slo@20%",
                           "violations_blind": v_blind,
                           "violations_slo": v_slo,
                           "migration_blind_mb": round(mig_blind / 1e6, 4),
                           "migration_slo_mb": round(mig_slo / 1e6, 4),
                           "status": "OK" if t_ok else "FAIL"})
            print(f"check,tenant_slo@20%,viol={v_blind}/{v_slo},"
                  f"mig={mig_slo / 1e6:.4f}/{mig_blind / 1e6:.4f}MB,"
                  f"{'OK' if t_ok else 'FAIL'}")

    prefill_rows = []
    if args.prefill:
        prows, (m_skip, comp_sh, comp_un, m_chunk,
                p95_c, p95_1, tok_c, tok_1) = run_prefill(args.arch)
        prefill_rows += prows
        for r in prows:
            print(",".join(map(str, r)))
        p_ok = m_skip and comp_sh < comp_un \
            and m_chunk and p95_c < p95_1 and tok_c >= tok_1
        ok &= p_ok
        checks.append({"check": "prefill",
                       "tokens_match_skip": m_skip,
                       "prefill_compute_tokens_shared": comp_sh,
                       "prefill_compute_tokens_unshared": comp_un,
                       "tokens_match_chunk": m_chunk,
                       "p95_gap_chunked_us": round(p95_c * 1e6, 4),
                       "p95_gap_oneshot_us": round(p95_1 * 1e6, 4),
                       "tok_s_chunked": round(tok_c, 1),
                       "tok_s_oneshot": round(tok_1, 1),
                       "status": "OK" if p_ok else "FAIL"})
        print(f"check,prefill,match={m_skip and m_chunk},"
              f"compute_tok={comp_sh}/{comp_un},"
              f"p95_gap={p95_c * 1e6:.4f}/{p95_1 * 1e6:.4f}us,"
              f"tok_s={tok_c:.1f}/{tok_1:.1f},"
              f"{'OK' if p_ok else 'FAIL'}")

    disagg_rows = []
    if args.disagg:
        drows, (match, repacks, xdev, xdev_pred, tok_d, tok_c) = \
            run_disagg(args.arch)
        disagg_rows += drows
        for r in drows:
            print(",".join(map(str, r)))
        d_ok = match and repacks == 0 and xdev == xdev_pred \
            and tok_d >= tok_c
        ok &= d_ok
        checks.append({"check": "disagg",
                       "tokens_match": match,
                       "repacks": repacks,
                       "xdev_migration_kb": round(xdev / 1e3, 3),
                       "xdev_predicted_kb": round(xdev_pred / 1e3, 3),
                       "disagg_tok_s": round(tok_d, 1),
                       "colocated_tok_s": round(tok_c, 1),
                       "status": "OK" if d_ok else "FAIL"})
        print(f"check,disagg,match={match},repacks={repacks},"
              f"xdev={xdev / 1e3:.3f}/{xdev_pred / 1e3:.3f}kB,"
              f"tok_s={tok_d:.1f}/{tok_c:.1f},"
              f"{'OK' if d_ok else 'FAIL'}")

        import jax
        n_dev = len(jax.devices())
        if n_dev < 4:
            print(f"check,disagg_sharded,SKIPPED (needs 4 devices, "
                  f"{n_dev} found)")
        else:
            srows, (tok_n, tok_1, ledger_exact, rec) = \
                run_disagg_sharded(args.arch)
            disagg_rows += srows
            for r in srows:
                print(",".join(map(str, r)))
            s_ok = tok_n >= tok_1 and ledger_exact
            ok &= s_ok
            checks.append({"check": "disagg_sharded",
                           "modeled_sharded_tok_s": round(tok_n, 1),
                           "modeled_single_tok_s": round(tok_1, 1),
                           "ledger_exact": ledger_exact,
                           "wall_sharded_tok_s":
                               round(rec["tok_s_sharded"], 2),
                           "wall_single_tok_s":
                               round(rec["tok_s_single"], 2),
                           "overlap": {
                               "step_ms": round(rec["step_ms"], 3),
                               "stream_ms_measured":
                                   round(rec["stream_ms"], 3),
                               "step_with_stream_ms":
                                   round(rec["step_with_stream_ms"], 3),
                               "overlap_frac":
                                   round(rec["overlap_frac"], 3)},
                           "status": "OK" if s_ok else "FAIL"})
            print(f"check,disagg_sharded,"
                  f"modeled_tok_s={tok_n:.1f}/{tok_1:.1f},"
                  f"ledger_exact={ledger_exact},"
                  f"overlap_frac={rec['overlap_frac']:.3f},"
                  f"{'OK' if s_ok else 'FAIL'}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": [list(r) for r in
                                rows + latency_rows
                                + shared_rows + tenant_rows + prefill_rows
                                + disagg_rows],
                       "checks": checks}, f, indent=2)
        print(f"wrote {args.json}")

    if not ok:
        raise SystemExit("serve benchmark gate failed (see checks above)")


if __name__ == "__main__":
    main()
