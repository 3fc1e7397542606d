"""Benchmark harness: one function per paper table/figure.
Prints ``name,...`` CSV rows. Usage: PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    t0 = time.time()
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    from benchmarks import (bench_ablation, bench_compare, bench_planner,
                            bench_profile, bench_sensitivity, roofline)

    sections = [
        ("Fig1-4/Tab1/Tab5: profiler distributions", bench_profile.run),
        ("Fig7/Fig8: migration-interval sweep", bench_planner.run),
        ("Table3: steps for profile+MI+test-and-trial", bench_planner.run_table3),
        ("Fig10/Tab4: Sentinel vs IAL vs fast-only", bench_compare.run),
        ("Fig11: ablations", bench_ablation.run),
        ("Fig12: fast-size sensitivity", bench_sensitivity.run),
        ("Fig13: depth sweep", bench_sensitivity.run_depth_sweep),
        ("Roofline (from dry-run artifacts)", roofline.run),
    ]
    failures = 0
    for title, fn in sections:
        print(f"# --- {title} ---", flush=True)
        try:
            for row in fn():
                print(",".join(map(str, row)), flush=True)
        except Exception as e:
            failures += 1
            print(f"# ERROR in {title}: {type(e).__name__}: {e}", flush=True)
    print(f"# benchmarks done in {time.time() - t0:.1f}s, "
          f"{failures} section failures")
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
