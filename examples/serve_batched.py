"""Batched serving example: prefill a batch of prompts, decode in lockstep,
including a MusicGen-style 4-codebook stream and a PaliGemma-style
image-prefix request — then Sentinel-Serve tiered continuous batching: the
decode-phase planner picks a hot window, each slot's cold KV pages are held
in host memory, and the run on the page pools reproduces the all-HBM
outputs exactly.

    PYTHONPATH=src python examples/serve_batched.py

``--disagg`` instead demos prefill/decode disaggregation on two devices:
prefill runs on one, the finished KV pages stream over the device edge into
the decode pools, and the outputs match the single-device engine bit for
bit.  With ``JAX_PLATFORMS=cpu`` the two devices are forced host devices.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_batched.py --disagg
"""
import os
import sys

if "--disagg" in sys.argv and os.environ.get("JAX_PLATFORMS") == "cpu":
    # two host devices for the demo — must land before the jax import
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=2")

import time

import jax
import jax.numpy as jnp

from repro import runtime
from repro.configs.base import get_config
from repro.core.hardware import TPU_V5E
from repro.models import model
from repro.models.layers import split_params
from repro.serve import engine


def demo(arch: str, num_tokens: int = 16):
    cfg = get_config(arch).reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    key = jax.random.PRNGKey(1)
    B, S = 4, 12
    if cfg.num_codebooks:
        toks = jax.random.randint(key, (B, S, cfg.num_codebooks), 0,
                                  cfg.vocab_size)
    else:
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    prompts = {"tokens": toks.astype(jnp.int32)}
    if cfg.num_prefix_tokens:
        prompts["prefix_embed"] = jax.random.normal(
            key, (B, cfg.num_prefix_tokens, cfg.d_model))
    t0 = time.perf_counter()
    out = engine.generate(params, cfg, prompts, num_tokens)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"{arch:24s} generated {out.shape} in {dt:5.2f}s "
          f"({B * num_tokens / dt:7.1f} tok/s)")


def demo_tiered(arch: str = "smollm-360m", slots: int = 2, max_seq: int = 48):
    """Tiered continuous batching end-to-end: plan -> cold pages on host ->
    identical outputs to the all-HBM batcher."""
    cfg = get_config(arch).reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    requests = [(8 + i, 6) for i in range(2 * slots)]

    # plan on the serving trace (full-size byte geometry, grouped objects)
    trace = engine.serve_trace_for(get_config(arch), requests, slots=slots,
                                   layer_group=8)
    fast = 0.2 * trace.peak_kv_bytes()
    plan = runtime.plan(trace, TPU_V5E, fast)
    print(f"[plan] hot_window={plan.hot_window} tokens, "
          f"lookahead={plan.lookahead}, page_tokens={plan.page_tokens}")
    for pol in ("prefer_fast", "lru_page", "sentinel", "sentinel_mi"):
        r = runtime.simulate(trace, TPU_V5E, fast, pol)
        print(f"[sim]  {pol:12s} {r.decode_throughput:9.1f} tok/s "
              f"(slowdown {r.slowdown:.3f}, {r.migrations} migrations)")

    def run(p):
        b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=p)
        key = jax.random.PRNGKey(7)
        for (plen, d) in requests:
            key, sub = jax.random.split(key)
            toks = jax.random.randint(sub, (plen,), 0,
                                      cfg.vocab_size).astype(jnp.int32)
            b.submit(toks, d)
        t0 = time.perf_counter()
        out = b.run()
        return out, time.perf_counter() - t0, b

    # force a real cold prefix even if the planned window covers every
    # request: a one-page window sends the longer requests' oldest page cold
    import dataclasses
    tiered_plan = dataclasses.replace(plan, hot_window=8, page_tokens=8,
                                      slot_hot_windows=None)
    base, t_base, _ = run(None)
    tier, t_tier, b = run(tiered_plan)
    match = base == tier
    print(f"[e2e]  all-HBM {t_base:5.2f}s | page pools {t_tier:5.2f}s "
          f"({b.pool.stats['page_copies']} pages demoted, "
          f"{b.sim_migration_bytes / 1e3:.1f} KB to host) | "
          f"outputs match: {match}")
    assert match, "tiered decode diverged from the all-HBM reference"


def demo_disagg(arch: str = "smollm-360m", slots: int = 2,
                max_seq: int = 32):
    """Prefill/decode disaggregation across the forced 2-device host mesh:
    same plan, same requests, bit-identical outputs — with every admitted
    page crossing the prefill->decode edge as an accounted migration."""
    import dataclasses

    from repro.launch.mesh import disagg_groups
    from repro.serve.disagg import DisaggregatedEngine

    prefill_devs, decode_devs = disagg_groups()
    print(f"[mesh] {len(jax.devices())} devices: "
          f"prefill={prefill_devs} decode={decode_devs}")
    cfg = get_config(arch).reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    requests = [(7, 6), (9, 5), (6, 7), (8, 6)]
    trace = engine.serve_trace_for(get_config(arch), requests, slots=slots,
                                   layer_group=8)
    plan = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    plan = dataclasses.replace(plan, hot_window=max_seq // 2,
                               slot_hot_windows=[4, 8], page_tokens=4)

    def run(eng_cls, **kw):
        b = eng_cls(params, cfg, slots, max_seq, plan=plan, **kw)
        key = jax.random.PRNGKey(7)
        for (plen, d) in requests:
            key, sub = jax.random.split(key)
            toks = jax.random.randint(sub, (plen,), 0,
                                      cfg.vocab_size).astype(jnp.int32)
            b.submit(toks, d)
        t0 = time.perf_counter()
        out = b.run()
        return out, time.perf_counter() - t0, b

    base, t_base, _ = run(engine.ContinuousBatcher)
    dis, t_dis, bd = run(DisaggregatedEngine)
    match = base == dis
    print(f"[e2e]  single-device {t_base:5.2f}s | disaggregated "
          f"{t_dis:5.2f}s ({bd.xdev_migration_bytes / 1e3:.1f} kB over the "
          f"prefill->decode edge, {bd.counters()['repacks']} re-packs) | "
          f"outputs match: {match}")
    assert match, "disaggregated decode diverged from the single-device run"


if __name__ == "__main__":
    if "--disagg" in sys.argv:
        demo_disagg()
    else:
        for arch in ["smollm-360m", "gemma2-2b", "musicgen-medium",
                     "paligemma-3b", "zamba2-7b", "xlstm-1.3b"]:
            demo(arch)
        demo_tiered()
