"""Smoke run of the tiered serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the disaggregated path on four chips

One chip: smollm-360m at its published widths (32 layers, d=960, 15/5 heads
of 64, bf16, random weights from ``--seed``) served by ``ContinuousBatcher``
on the persistent page pools, decode through the ``paged_decode`` kernel,
planned at 20% of the stream's peak KV in fast memory so that cold pages
live in ``pinned_host``.  The same requests then run under an all-fast plan
(every page hot, the same kernel); the two must give identical tokens.

``--chips 4``: only the disaggregated engine — 2 prefill + 2 decode chips
(``launch.mesh.disagg_groups``) — against the colocated engine on one of
those chips: identical tokens, and the ``MeshPageTable`` edge ledger equal
to ``predict_pool_counters``.

Every time printed is a smoke timing of one run, not a benchmark.  The last
line of standard output is ``{"ok": true, "device": {...}}``; the script
exits non-zero, without that line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

SLOTS, MAX_SEQ, N_REQUESTS, DECODE_TOKENS = 8, 2048, 12, 32
PROMPT_LENS = (512, 1024, 1536)


def check(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke: check failed: {what}")
    print(f"  ok: {what}")


def pool_placement(pool) -> set:
    """``(device, memory kind)`` of every attention pool array; the kind is
    None for the device's own memory."""
    out = set()
    for _, e in pool._attn_entries():
        for a in e.values():
            kind = a.sharding.memory_kind
            dev, = a.devices()
            out.add((dev, None if kind == dev.default_memory().kind
                     else kind))
    return out


def decode_step_text(b) -> str:
    """StableHLO of the engine's compiled decode step (lowered, not run),
    to show which kernel the step calls."""
    caches = b.pool.forward_tree(stage=b.pool.has_cold())
    return b._decode_step.lower(b.params, b.last_tok, caches, b.lengths,
                                *b.pool.arrays(), b._active_mask).as_text()


def serve(make, label):
    """Build an engine with ``make()``, run it to completion; returns
    (engine, outputs, seconds).  The whole of it is one ``smoke.serve``
    span, so the programs it compiled or loaded from the persistent cache
    are those counted on its records."""
    from repro import obs
    t0 = time.perf_counter()
    with obs.span("smoke.serve", run=label):
        b = make()
        outs = b.run()
        jax.block_until_ready(b.last_tok)
    dt = time.perf_counter() - t0
    recs = obs.records(int(t0 * 1e9), time.perf_counter_ns())
    print(f"  {label}: {len(outs)} requests, "
          f"{sum(len(o) for o in outs)} tokens in {dt:.3f} s (smoke timing); "
          f"programs compiled {sum(r.built for r in recs)}, loaded from the "
          f"persistent cache {sum(r.loaded for r in recs)}")
    return b, outs, dt


def one_chip(cfg, params, requests, hw):
    from repro.launch.serve import tiered_server

    def make(all_fast):
        return lambda: tiered_server(cfg, params, requests, slots=SLOTS,
                                     max_seq=MAX_SEQ, fast_frac=0.2, hw=hw,
                                     all_fast=all_fast)[0]

    tiered, out_t, dt_t = serve(make(False), "tiered (20% fast)")
    fast, out_f, dt_f = serve(make(True), "all-fast")
    ct = tiered.counters()
    dev = jax.devices()[0]
    placement = pool_placement(tiered.pool)
    text = decode_step_text(tiered)
    print(f"  page_tokens {tiered.page_tokens}, pages demoted "
          f"{ct['page_copies']}, migration bytes "
          f"{ct['sim_migration_bytes']:.0f}, cold stages {ct['cold_stages']} "
          f"({ct['cold_stage_bytes']} bytes staged into HBM)")
    print(f"  cold pool memory kind: "
          f"{sorted(k for _, k in placement if k)}")
    print(f"  decode step contains tpu_custom_call: "
          f"{'tpu_custom_call' in text}")
    print(f"  run seconds (smoke timing, compiles included): tiered "
          f"{dt_t:.3f}, all-fast {dt_f:.3f}")
    check(len(out_t) == len(requests)
          and all(len(o) == DECODE_TOKENS for o in out_t),
          f"all {len(requests)} requests answered with {DECODE_TOKENS} "
          f"tokens each")
    check(ct["sim_migration_bytes"] > 0 and ct["page_copies"] > 0,
          "cold pages were demoted")
    check(placement == {(dev, None), (dev, "pinned_host")},
          "hot pools live in HBM, cold pools in pinned_host")
    check(fast.counters()["sim_migration_bytes"] == 0,
          "the all-fast run demoted nothing")
    check("tpu_custom_call" in text, "the decode step calls the Pallas "
          "kernel (tpu_custom_call)")
    check(out_t == out_f, "tiered tokens equal all-fast tokens")


def four_chips(cfg, params, requests, hw):
    import dataclasses

    from repro.launch.mesh import disagg_groups
    from repro.launch.serve import tiered_server
    from repro.serve.disagg import DisaggregatedEngine
    from repro.serve.engine import predict_pool_counters

    devices = jax.devices()
    check(len(devices) == 4, f"four devices ({len(devices)} found)")
    prefill, decode = disagg_groups(devices)
    print(f"  prefill group {[d.id for d in prefill]}, decode group "
          f"{[d.id for d in decode]}")
    shards = [s % len(decode) for s in range(SLOTS)]
    # the colocated twin admits by dense prefill as the disaggregated engine
    # does (pool-direct prefill attends through another kernel on a TPU),
    # on a device other than the default one so that a pool array left on
    # the default device shows up in the placement check
    home = decode[-1]
    colo, out_c, _ = serve(lambda: tiered_server(
        cfg, jax.device_put(params, home), requests, slots=SLOTS,
        max_seq=MAX_SEQ, fast_frac=0.2, hw=hw, device=home,
        pool_prefill=False)[0], f"colocated on device {home.id}")

    # the plan is the colocated run's, with the batch slots dealt to the
    # two decode shards
    plan = dataclasses.replace(colo.plan, slot_devices=shards)
    t0 = time.perf_counter()
    dis = DisaggregatedEngine(params, colo.cfg, SLOTS, MAX_SEQ, plan=plan,
                              devices=devices)
    for tokens, d in requests:
        dis.submit(tokens, d)
    out_d = dis.run()
    jax.block_until_ready(dis.last_tok)
    dt = time.perf_counter() - t0
    print(f"  disaggregated: {len(out_d)} requests, "
          f"{sum(len(o) for o in out_d)} tokens in {dt:.3f} s (smoke timing)")
    dis.mesh_table.check()
    pred = predict_pool_counters(
        [(len(t), d) for t, d in requests], plan, slots=SLOTS,
        max_seq=MAX_SEQ, page_tokens=dis.page_tokens,
        row_bytes=dis._row_bytes, dense_admit=True)
    ledger = dict(dis.mesh_table.edge_bytes)
    print(f"  edge ledger {ledger}")
    check(len(out_d) == len(requests), "all requests answered")
    check(ledger == pred["edge_migration_bytes"],
          "MeshPageTable edge ledger equals predict_pool_counters")
    check(dis.counters()["repacks"] == 0, "zero dense re-packs")
    check(all(pool_placement(p) == {(d, None), (d, "pinned_host")}
              for p, d in [(colo.pool, home)]
              + list(zip(dis.pools, dis.decode_devices))),
          "every pool lives on its own device: hot pages in its HBM, cold "
          "pages in its pinned_host")
    check(dis.counters()["page_copies"] > 0, "cold pages were demoted")
    check(out_d == out_c, "disaggregated tokens equal colocated tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs.base import get_config
    from repro.launch.cache import setup_compile_cache
    from repro.launch.serve import hardware_for, request_stream
    from repro.models import model
    from repro.models.layers import split_params

    cache_dir = setup_compile_cache()
    cfg = get_config("smollm-360m")
    print(f"device {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache_dir}")
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} of {cfg.head_dim}, "
          f"{cfg.dtype}; {N_REQUESTS} requests of {PROMPT_LENS} prompt "
          f"tokens x {DECODE_TOKENS} decode, {SLOTS} slots, max_seq "
          f"{MAX_SEQ}")
    hw = hardware_for(dev)
    params, _ = split_params(model.init_params(jax.random.PRNGKey(args.seed),
                                               cfg))
    requests = request_stream(cfg, n=N_REQUESTS, prompt_lens=PROMPT_LENS,
                              decode_tokens=DECODE_TOKENS,
                              seed=args.seed + 1)
    if args.chips == 4:
        four_chips(cfg, params, requests, hw)
    else:
        one_chip(cfg, params, requests, hw)
        stats = dev.memory_stats() or {}
        print(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
