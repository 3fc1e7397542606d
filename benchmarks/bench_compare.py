"""Paper Figure 10 + Table 4: Sentinel vs IAL vs fast-only across models at
20% of peak footprint as fast memory; migration counts per step."""
from __future__ import annotations

from benchmarks.common import BENCH_ARCHS, bench_profile
from repro.core import hmsim, planner
from repro.core.hardware import PAPER_HM


def run(fast_frac: float = 0.25):
    rows = [("bench_compare", "arch", "sentinel_slowdown", "ial_slowdown",
             "lru_slowdown", "slow_only_slowdown", "sentinel_vs_ial_speedup",
             "sentinel_migs", "ial_migs", "planned_mi")]
    hw = PAPER_HM
    for arch in BENCH_ARCHS:
        cfg, prof = bench_profile(arch)
        peak = prof.peak_bytes()
        fast = fast_frac * peak
        base = hmsim.simulate_static(prof, hw, "fast").step_time
        slow = hmsim.simulate_static(prof, hw, "slow").step_time
        plan = planner.plan(prof, hw, fast)
        ial = hmsim.simulate_caching(prof, hw, fast, "ial")
        lru = hmsim.simulate_caching(prof, hw, fast, "lru")
        rows.append(("bench_compare", arch,
                     round(plan.sim.step_time / base, 3),
                     round(ial.step_time / base, 3),
                     round(lru.step_time / base, 3),
                     round(slow / base, 3),
                     round(ial.step_time / plan.sim.step_time, 3),
                     plan.sim.migrations, ial.migrations, plan.mi))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
