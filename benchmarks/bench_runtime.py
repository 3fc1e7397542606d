"""Unified-runtime smoke: one entry point, both workloads.

Runs a training-sim sweep and a serving sweep through the *same* surface —
``runtime.plan`` + the unified policy registry — on deterministic synthetic
workloads (no model tracing, no RNG), and publishes ``BENCH_runtime.json``
beside ``BENCH_serve.json`` for trend tracking across PRs.

    PYTHONPATH=src python -m benchmarks.bench_runtime --json BENCH_runtime.json

Gates (exit non-zero on failure):
  - on BOTH workloads at the paper's headline 20% fast-memory fraction, the
    lifetime-aware object policy must not lose to the page-grain reactive
    baseline (``sentinel_mi`` vs ``ial`` on training, ``sentinel`` vs
    ``lru_page`` on serving);
  - every plan — including a latency-objective plan carrying its serialized
    ``CostModel`` and predicted step times — must round-trip through
    ``PlacementPlan.to_json`` / ``from_json`` byte-identically
    (planner-drift canary);
  - with ``--drift``, the online re-planner (runtime/online.py) on every
    piecewise-stationary drift workload: predicted tokens/sec ≥ the
    static-stale plan's, regret vs the per-segment clairvoyant plan
    sequence ≤ 10%, migration bytes ≤ 1.3x clairvoyant, and hysteresis
    churn within budget.

Every row also carries the time-domain prediction (``pred_time_s``): the
policy's recorded per-step traffic priced on the machine's ``CostModel``.
"""
from __future__ import annotations

import argparse
import json

from repro import runtime
from repro.core.hardware import PAPER_HM, TPU_V5E
from repro.launch.cache import setup_compile_cache
from repro.runtime.synthetic import synthetic_profile, synthetic_serve_trace

FRACS = (0.1, 0.2, 0.4, 0.8)


def sweep(workload, hw, hw_name: str, kind: str, peak: float, policies,
          fracs=FRACS):
    """One (workload, hw) sweep: plan once, then simulate every policy at
    every fast-memory fraction."""
    pl = runtime.plan(workload, hw, 0.2 * peak)
    cm = runtime.as_cost_model(hw)
    rows, results = [], {}
    for frac in fracs:
        fast = frac * peak
        for pol in policies:
            knobs = {}
            if pol == "sentinel" and kind == "serving":
                knobs["lookahead"] = pl.lookahead
            if pol == "sentinel_mi" and kind == "training":
                knobs["mi"] = pl.mi
            r = runtime.simulate(workload, hw, fast, pol, **knobs)
            results[(frac, pol)] = r
            rows.append(("bench_runtime", kind, hw_name, frac, pol,
                         round(r.slowdown, 4),
                         round(r.decode_throughput, 1), r.migrations,
                         round(r.slow_bytes_accessed / 1e9, 4),
                         round(cm.price_result(r).time, 6)))
    return pl, rows, results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--json", default="",
                    help="write rows + checks to this JSON file")
    ap.add_argument("--drift", action="store_true",
                    help="also sweep the online re-planner over the "
                         "piecewise-stationary drift workloads and gate "
                         "online vs static-stale vs clairvoyant")
    args = ap.parse_args(argv)
    setup_compile_cache()

    prof = synthetic_profile()
    trace = synthetic_serve_trace()
    header = ("bench_runtime", "workload", "hw", "fast_frac", "policy",
              "slowdown", "tok_per_s", "migrations", "slow_gb",
              "pred_time_s")
    rows, checks = [header], []
    ok = True

    def gate(name: str, winner, loser, lo, hi):
        nonlocal ok
        ratio = lo / max(hi, 1e-30)
        status = "OK" if ratio <= 1.0 else "FAIL"
        ok &= ratio <= 1.0
        checks.append({"check": name, "winner": winner, "loser": loser,
                       "slowdown_ratio": round(ratio, 4), "status": status})
        print(f"check,{name},{winner}<= {loser},ratio={ratio:.4f},{status}")

    # ---- training workload: the MI planner through the unified surface ----
    pl_t, rows_t, res_t = sweep(
        prof, PAPER_HM, "paper-hm", "training",
        prof.peak_bytes(), ("all_slow", "ial", "lru", "sentinel",
                            "sentinel_mi"))
    rows += rows_t
    gate("training_sentinel_vs_page@20%", "sentinel_mi", "ial",
         res_t[(0.2, "sentinel_mi")].time, res_t[(0.2, "ial")].time)

    # ---- serving workload: the decode planner through the same surface ----
    pl_s, rows_s, res_s = sweep(
        trace, TPU_V5E, "tpu-v5e", "serving",
        trace.peak_kv_bytes(), ("all_slow", "lru_page", "prefer_fast",
                                "sentinel"))
    rows += rows_s
    gate("serving_sentinel_vs_page@20%", "sentinel", "lru_page",
         res_s[(0.2, "sentinel")].time, res_s[(0.2, "lru_page")].time)

    # ---- latency objective: plan by predicted time on the default model ----
    from repro.core.hardware import default_cost_model
    pl_lat = runtime.plan(trace, default_cost_model(),
                          0.2 * trace.peak_kv_bytes(), objective="latency")
    print(f"check,latency_plan,policy={pl_lat.policy},"
          f"pred_time={pl_lat.predicted_time:.6f}s,"
          f"pred_tok_per_s={pl_lat.predicted_decode_throughput:.1f}")

    # ---- plan serialization canary: byte-identical JSON round trip ----
    for kind, pl in (("training", pl_t), ("serving", pl_s),
                     ("serving_latency", pl_lat)):
        s = pl.to_json()
        stable = runtime.PlacementPlan.from_json(s).to_json() == s
        ok &= stable
        checks.append({"check": f"{kind}_plan_json_roundtrip",
                       "bytes": len(s),
                       "status": "OK" if stable else "FAIL"})
        print(f"check,{kind}_plan_json_roundtrip,bytes={len(s)},"
              f"{'OK' if stable else 'FAIL'}")

    # ---- online re-planning under drift: regret vs the clairvoyant plan ----
    drift = {}
    if args.drift:
        from repro.runtime import replay_drift
        from repro.runtime.synthetic import drift_workloads
        for name, wl in drift_workloads().items():
            rep = replay_drift(wl, default_cost_model(),
                               0.2 * wl.peak_kv_bytes())
            drift[name] = rep.to_dict()
            replans = sum(1 for e in rep.events if e.applied)
            print(f"drift,{name},regret={rep.regret:.4f},"
                  f"online_tok_s={rep.online_tokens_per_s:.1f},"
                  f"static_tok_s={rep.static_tokens_per_s:.1f},"
                  f"replans={replans},churn_mb={rep.churn_bytes / 1e6:.2f}")
            gate(f"drift_{name}_online_vs_static", "online", "static_stale",
                 rep.online_s, rep.static_s)
            gate(f"drift_{name}_regret<=10%", "online", "clairvoyant*1.1",
                 rep.online_s, (1.0 + 0.10) * rep.clairvoyant_s)
            gate(f"drift_{name}_migration<=1.3x_clairvoyant", "online",
                 "clairvoyant*1.3", rep.online_mig_bytes,
                 1.3 * rep.clairvoyant_mig_bytes)
            gate(f"drift_{name}_churn_within_budget", "churn", "budget",
                 rep.churn_bytes, rep.churn_budget_bytes)

    for r in rows:
        print(",".join(map(str, r)))
    if args.json:
        out = {"rows": [list(r) for r in rows],
               "plans": {"training": pl_t.to_dict(),
                         "serving": pl_s.to_dict(),
                         "serving_latency": pl_lat.to_dict()},
               "checks": checks}
        if drift:
            out["drift"] = drift
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
    if not ok:
        raise SystemExit("runtime benchmark gate failed (see checks above)")


if __name__ == "__main__":
    main()
