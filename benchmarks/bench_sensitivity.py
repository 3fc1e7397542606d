"""Paper Figures 12 + 13: performance vs fast-memory size, and the minimum
fast-memory size that matches fast-only across depth variants (ResNet-sweep
analogue: layer-count sweep of smollm)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmarks.common import BENCH_ARCHS, bench_profile
from repro.configs.base import get_config
from repro.core import hmsim, planner, profiler
from repro.core.hardware import PAPER_HM
from repro.models import model
from repro.models.layers import split_params


def run():
    rows = [("bench_sensitivity", "arch", "fast_frac", "slowdown")]
    hw = PAPER_HM
    for arch in BENCH_ARCHS[:4]:
        cfg, prof = bench_profile(arch)
        peak = prof.peak_bytes()
        base = hmsim.simulate_static(prof, hw, "fast").step_time
        for frac in (0.2, 0.3, 0.4, 0.6, 0.8, 1.0):
            pl = planner.plan(prof, hw, frac * peak)
            rows.append(("bench_sensitivity", arch, frac,
                         round(pl.sim.step_time / base, 4)))
    return rows


def run_depth_sweep():
    """Fig. 13 analogue: peak footprint grows ~linearly with depth while the
    fast memory needed for <=2% slowdown grows much slower."""
    rows = [("bench_depth", "layers", "peak_MB", "min_fast_MB",
             "min_fast_frac")]
    hw = PAPER_HM
    for L in (4, 8, 16):
        base_cfg = get_config("smollm-360m")
        cfg = dataclasses.replace(base_cfg, num_layers=L, d_model=256,
                                  num_heads=8, num_kv_heads=4, d_ff=1024,
                                  head_dim=32, vocab_size=2048,
                                  dtype="float32")
        params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
        pshapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        b = {"tokens": jax.ShapeDtypeStruct((8, 128), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 128), jnp.int32)}
        prof = profiler.trace_profile(
            jax.grad(lambda p, bb: model.loss_fn(p, cfg, bb,
                                                 unroll_periods=True)),
            pshapes, b, num_periods=cfg.num_periods)
        peak = prof.peak_bytes()
        base = hmsim.simulate_static(prof, hw, "fast").step_time
        lo, hi = 0.05, 1.0
        for _ in range(8):   # bisect the minimum adequate fast size
            mid = 0.5 * (lo + hi)
            pl = planner.plan(prof, hw, mid * peak)
            if pl.sim.step_time <= 1.02 * base:
                hi = mid
            else:
                lo = mid
        rows.append(("bench_depth", L, round(peak / 1e6, 1),
                     round(hi * peak / 1e6, 1), round(hi, 3)))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    for r in run() + run_depth_sweep():
        print(",".join(map(str, r)))
