"""Paper Figures 7 + 8 and Table 3: throughput vs migration interval (sweet
spot), Case 1/2/3 occurrences vs MI, and the steps used for profiling +
MI-determination + test-and-trial."""
from __future__ import annotations

from benchmarks.common import BENCH_ARCHS, bench_profile
from repro import runtime
from repro.core.hardware import PAPER_HM, TPU_V5E


def run_table3(fast_frac: float = 0.3):
    """Paper Table 3: '# of training steps for p, m & t' per model."""
    rows = [("bench_table3", "arch", "steps_profile", "steps_pmt_total",
             "tt_used")]
    for arch in BENCH_ARCHS:
        cfg, prof = bench_profile(arch)
        plan = runtime.plan(prof, PAPER_HM, fast_frac * prof.peak_bytes())
        rows.append(("bench_table3", arch, 1, plan.steps_used,
                     plan.sim.detail.get("tt_choice", "n/a")))
    return rows


def run(arch: str = "smollm-360m", fast_frac: float = 0.3):
    rows = [("bench_planner", "hw", "mi", "rel_throughput",
             "case1", "case2", "case3", "migrations", "is_planned_mi")]
    cfg, prof = bench_profile(arch)
    peak = prof.peak_bytes()
    for hw, name in ((PAPER_HM, "paper-hm"), (TPU_V5E, "tpu-v5e")):
        fast = fast_frac * peak
        base = runtime.simulate(prof, hw, fast, "all_fast").step_time
        plan = runtime.plan(prof, hw, fast)
        for mi in sorted({1, 2, 3, 4, 6, 8, 12, 16, plan.mi}):
            r = runtime.simulate(prof, hw, fast, "sentinel_mi", mi=mi)
            rows.append(("bench_planner", name, mi,
                         round(base / r.step_time, 4),
                         r.cases[1], r.cases[2], r.cases[3], r.migrations,
                         int(mi == plan.mi)))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    for r in run() + run_table3():
        print(",".join(map(str, r)))
