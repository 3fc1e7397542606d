"""Paper Figure 11: feature ablations — page-level false sharing, short-lived
space reservation, test-and-trial. Performance normalized to full Sentinel."""
from __future__ import annotations

from benchmarks.common import BENCH_ARCHS, bench_profile
from repro.core import hmsim, planner
from repro.core.hardware import PAPER_HM


def run(fast_frac: float = 0.25):
    rows = [("bench_ablation", "arch", "full", "having_false_sharing",
             "no_space_reservation", "no_test_and_trial")]
    hw = PAPER_HM
    for arch in BENCH_ARCHS[:4]:
        cfg, prof = bench_profile(arch)
        fast = fast_frac * prof.peak_bytes()
        plan = planner.plan(prof, hw, fast)
        mi = plan.mi
        full = plan.sim.step_time
        fs = hmsim.simulate_sentinel_tt(prof, hw, fast, mi,
                                        granularity="page",
                                        page_mode="original").step_time
        nores = hmsim.simulate_sentinel_tt(prof, hw, fast, mi,
                                           reserve_pool=False).step_time
        nott = hmsim.simulate_sentinel(prof, hw, fast, mi,
                                       stall_on_case3=True).step_time
        rows.append(("bench_ablation", arch, 1.0,
                     round(full / fs, 3), round(full / nores, 3),
                     round(full / nott, 3)))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
