"""Paper Figures 1-4 + Tables 1/5: data-object distributions, false sharing,
profiling footprint overhead — from the jaxpr profiler."""
from __future__ import annotations

from benchmarks.common import BENCH_ARCHS, bench_profile
from repro.core import allocator


def run():
    rows = [("bench_profile", "arch", "objects", "frac_short_lived",
             "hot10_access_share", "false_shared_pages_frac",
             "profiling_overhead_frac", "peak_MB", "rs_MB")]
    for arch in BENCH_ARCHS:
        cfg, prof = bench_profile(arch)
        acts = [o for o in prof.objects if o.kind == "activation"]
        short = prof.short_lived(include_fused=True)
        hot = sorted(acts, key=lambda o: -o.reads)[:max(1, len(acts) // 10)]
        share = sum(o.reads for o in hot) / max(1, sum(o.reads for o in acts))
        fs = allocator.false_sharing_stats(prof)
        ov = allocator.profiling_overhead(prof)
        rows.append(("bench_profile", arch, len(prof.objects),
                     round(len(short) / max(1, len(acts)), 3),
                     round(share, 3),
                     round(fs["false_sharing_frac"], 3),
                     round(ov["overhead_frac"], 3),
                     round(prof.peak_bytes() / 1e6, 1),
                     round(prof.rs_bytes(1) / 1e6, 1)))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
