"""Serving CLI.

Dense batched generate on the reduced (CPU-sized) config::

    python -m repro.launch.serve --arch smollm-360m -n 16

The tiered serving path — ``ContinuousBatcher`` on the persistent page
pools, decode through ``paged_decode``, cold KV in host memory — planned at
a fast-memory fraction of the stream's peak KV, at the model's published
widths (``--published``) or reduced::

    python -m repro.launch.serve --arch smollm-360m --tiered --published \\
        --fast-frac 0.2

``tiered_server`` is the one construction of that path; ``chip_smoke.py``
builds its runs with it too.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import runtime
from repro.configs.base import get_config, list_archs
from repro.core import hardware
from repro.launch.cache import setup_compile_cache
from repro.models import model
from repro.models.layers import split_params
from repro.serve import engine


def request_stream(cfg, *, n: int, prompt_lens, decode_tokens: int,
                   seed: int):
    """``n`` requests ``(prompt, decode_tokens)``: prompt lengths cycle
    through ``prompt_lens``, token ids are drawn from ``seed``."""
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(n):
        key, sub = jax.random.split(key)
        plen = prompt_lens[i % len(prompt_lens)]
        out.append((jax.random.randint(sub, (plen,), 0, cfg.vocab_size)
                    .astype(jnp.int32), decode_tokens))
    return out


def tiered_server(cfg, params, requests, *, slots: int, max_seq: int,
                  fast_frac: float, hw, all_fast: bool = False,
                  device=None, pool_prefill: bool = True):
    """A ``ContinuousBatcher`` on the tiered pools path, on ``device``, with
    ``requests`` queued.  The plan comes from the stream's serve trace at
    ``fast_frac`` of its peak KV bytes; ``all_fast`` keeps every page hot
    under the same plan geometry (the all-HBM control of the same kernel).
    ``pool_prefill=False`` admits by dense prefill, as the disaggregated
    engine does.  Returns ``(batcher, plan)``."""
    trace = engine.serve_trace_for(cfg, [(len(t), d) for t, d in requests],
                                   slots=slots, params=params,
                                   layer_group=8)
    plan = runtime.plan(trace, hw, fast_frac * trace.peak_kv_bytes())
    if all_fast:
        plan = dataclasses.replace(plan, hot_window=max_seq,
                                   slot_hot_windows=None)
    b = engine.ContinuousBatcher(params, cfg, slots, max_seq, plan=plan,
                                 device=device,
                                 pool_prefill=pool_prefill)
    for tokens, d in requests:
        b.submit(tokens, d)
    return b, plan


def hardware_for(device) -> hardware.HWSpec:
    """The planner's hardware description for ``device``: looked up by its
    ``device_kind`` on an accelerator; the v5e's when planning from a CPU."""
    if device.platform == "cpu":
        return hardware.TPU_V5E
    return hardware.for_device_kind(device.device_kind)


def _generate(args, cfg, params):
    key = jax.random.PRNGKey(args.seed + 1)
    if cfg.num_codebooks:
        toks = jax.random.randint(key, (args.batch, args.prompt_len,
                                        cfg.num_codebooks), 0, cfg.vocab_size)
    else:
        toks = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                  cfg.vocab_size)
    prompts = {"tokens": toks.astype(jnp.int32)}
    if cfg.num_prefix_tokens:
        prompts["prefix_embed"] = jax.random.normal(
            key, (args.batch, cfg.num_prefix_tokens, cfg.d_model))
    scfg = engine.ServeConfig(temperature=args.temperature,
                              max_seq=args.prompt_len + args.num_tokens + 8)
    out = engine.generate(params, cfg, prompts, args.num_tokens, scfg)
    jax.block_until_ready(out)
    return args.batch * args.num_tokens, out[0][:16]


def _tiered(args, cfg, params):
    requests = request_stream(cfg, n=args.requests,
                              prompt_lens=[args.prompt_len],
                              decode_tokens=args.num_tokens, seed=args.seed)
    b, plan = tiered_server(cfg, params, requests, slots=args.batch,
                            max_seq=args.max_seq, fast_frac=args.fast_frac,
                            hw=hardware_for(jax.devices()[0]))
    outs = b.run()
    c = b.counters()
    print(f"[serve] plan hot_window={plan.hot_window} "
          f"page_tokens={b.page_tokens} | pages demoted {c['page_copies']}, "
          f"{c['sim_migration_bytes'] / 1e6:.2f} MB to host, "
          f"{c['cold_stage_bytes'] / 1e6:.2f} MB staged back")
    return sum(len(o) for o in outs), outs[0][:16]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4,
                    help="batch rows (tiered: engine slots)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("-n", "--num-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--published", action="store_true",
                    help="the model's published widths (default: reduced)")
    ap.add_argument("--tiered", action="store_true",
                    help="serve through the tiered pools path")
    ap.add_argument("--fast-frac", type=float, default=0.2,
                    help="tiered: fast memory as a fraction of peak KV")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch)
    if not args.published:
        cfg = cfg.reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(args.seed),
                                               cfg))
    t0 = time.perf_counter()
    total, head = (_tiered if args.tiered else _generate)(args, cfg, params)
    dt = time.perf_counter() - t0
    print(f"[serve] {args.arch}: {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, compiles included) on "
          f"{jax.devices()[0].device_kind}")
    print(head)


if __name__ == "__main__":
    main()
