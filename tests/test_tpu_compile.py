"""Compiles for a described TPU v5e, with no chip attached: the serving
path's kernels and host-tier page ops at smollm-360m widths.  What the
chip's compiler would refuse, it refuses here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers
import every test file.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# smollm-360m: 15 query / 5 KV heads of 64; 8 slots of 2048 tokens in
# 16-token pages; 32 layers
B, H, KVH, D, PAGE, MAX_SEQ, LAYERS = 8, 15, 5, 64, 16, 2048, 32
NP = MAX_SEQ // PAGE
N = B * NP


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture
def compile_for(chip):
    """``compile_for(fn, *shapes)``: compile ``fn`` for the described chip
    with JAX's persistent cache off (it cannot read back a compile for a
    device that is not attached)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def run(fn, *args, **jit_kw):
        return jax.jit(fn, **jit_kw).lower(*args).compile()
    yield run
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def shapes(chip):
    dev = SingleDeviceSharding(chip)
    host = SingleDeviceSharding(chip, memory_kind="pinned_host")

    def sds(shape, dtype=jnp.bfloat16, where=dev):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)
    return sds, host


def _kernel_args(sds, host, cold_host):
    """Kernel operands; with ``cold_host`` the cold pools are the engine's
    page-major host storage (n, R, 128) in pinned_host."""
    cold = sds((N, KVH * PAGE * D // 128, 128), where=host) if cold_host \
        else sds((N, KVH, PAGE, D))
    return (sds((B, H, D)), sds((N + 1, KVH, PAGE, D)),
            sds((N + 1, KVH, PAGE, D)), cold, cold,
            sds((B, NP), jnp.int32), sds((B, NP), jnp.int32),
            sds((B,), jnp.int32))


@pytest.mark.parametrize("case", [
    "page_tables_in_smem",        # was: (1, NP) / (1,) SMEM blocks
    "per_head_page_slices",       # was: a (page, D=64) slice of (n,page,KVH,D)
    "cold_pools_in_pinned_host",  # was: ANY memory space on a host operand
])
def test_paged_decode_compiles_for_v5e(compile_for, shapes, case):
    """The decode kernel at smollm-360m widths compiles to a Mosaic custom
    call; each case is one refusal the earlier kernel met on this chip."""
    from repro.kernels.paged_decode import paged_decode_attention
    from repro.models import kvcache
    sds, host = shapes
    cold_host = case == "cold_pools_in_pinned_host"

    def step(q, kh, vh, kc, vc, table, tier, lengths, chunks):
        if cold_host:                    # the engine's staging step
            kc = kvcache._cold_stage(kc, chunks, (KVH, PAGE, D))
            vc = kvcache._cold_stage(vc, chunks, (KVH, PAGE, D))
        return paged_decode_attention(q, kh, vh, kc, vc, table, tier,
                                      lengths, window=0, interpret=False)

    compiled = compile_for(step, *_kernel_args(sds, host, cold_host),
                           sds((), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_prefill_widths(compile_for, shapes):
    from repro.kernels.flash_attention import flash_attention
    sds, _ = shapes
    q = sds((1, H, 1536, D))
    kv = sds((1, KVH, 1536, D))
    compiled = compile_for(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["demote_put", "page_get", "stage"])
def test_cold_page_ops_compile_for_v5e(compile_for, shapes, chip, op):
    """The pools' host-memory page ops on the full page-major cold pool:
    a demotion writes one page in place, a read fetches one page, the
    staging step brings the pool's occupied prefix into HBM in the
    kernel's layout."""
    from repro.models import kvcache
    sds, host = shapes
    rows = (KVH * PAGE * D) // 128
    cold = sds((N, LAYERS, rows, 128), where=host)
    page = sds((1, LAYERS, rows, 128))
    if op == "demote_put":
        compiled = kvcache._cold_put_fn(chip).lower(
            cold, page, sds((), jnp.int32)).compile()
    elif op == "page_get":
        compiled = compile_for(kvcache.cold_page_get.__wrapped__, cold,
                               sds((), jnp.int32))
    else:
        compiled = compile_for(kvcache._cold_stage.__wrapped__, cold,
                               sds((), jnp.int32), (KVH, PAGE, D),
                               static_argnums=(2,))
    assert compiled.as_text()


# internlm2-1.8b: 8 KV heads of 128, 24 layers
IL_KVH, IL_D, IL_LAYERS = 8, 128, 24


@pytest.mark.parametrize("pool", ["smollm", "internlm2", "deepseek_ckv",
                                  "deepseek_krope", "deepseek_dense_ckv"])
def test_cold_stage_copies_chunks_from_host(compile_for, shapes, pool):
    """The staging program at each cell's pool shapes (8 slots x 128
    pages of 16 tokens): a loop of host slices of one chunk each, run as
    many times as a runtime scalar says, into an HBM pool of the whole
    pool's shape; besides that pool it takes HBM for about one chunk, where
    a copy of the whole pool took as much again as the pool."""
    from repro.models import kvcache
    sds, host = shapes
    lead, page = {
        "smollm": ((LAYERS,), (KVH, PAGE, D)),
        "internlm2": ((IL_LAYERS,), (IL_KVH, PAGE, IL_D)),
        "deepseek_ckv": ((DS_MOE,), (PAGE, DS_C)),
        "deepseek_krope": ((DS_MOE,), (PAGE, DS_R)),
        "deepseek_dense_ckv": ((), (PAGE, DS_C))}[pool]
    cold = sds((N,) + lead + (math.prod(page) // 128, 128), where=host)
    compiled = compile_for(kvcache._cold_stage.__wrapped__, cold,
                           sds((), jnp.int32), page, static_argnums=(2,))
    text = compiled.as_text()
    assert "dynamic-slice-start" in text and " while(" in text
    mem = compiled.memory_analysis()
    chunk = cold.size // N * kvcache.stage_chunk(N) * 2
    assert mem.output_size_in_bytes == cold.size * 2
    assert mem.temp_size_in_bytes < 2 * chunk


@pytest.mark.usefixtures("compile_for")     # for its persistent cache off
def test_decode_step_compiles_at_published_width(shapes, monkeypatch):
    """The engine's compiled decode step of smollm-360m through the page
    pools, hot pools donated, with the compiled kernel (steered in this
    test: the CPU backend would pick the jnp reference)."""
    from repro.configs.base import get_config
    from repro.kernels import ops
    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve import engine
    sds, _ = shapes
    monkeypatch.setitem(ops._FORCE, "pallas", True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("smollm-360m")
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda k: split_params(model.init_params(k, cfg))[0],
        jax.random.PRNGKey(0)))
    hot = sds((LAYERS, N + 1, KVH, PAGE, D))
    cold = sds((LAYERS, N, KVH, PAGE, D))
    caches = {"prologue": [], "slots": [{"k_hot": hot, "v_hot": hot,
                                         "k_cold": cold, "v_cold": cold}]}

    step = engine.make_pool_decode_step(cfg, PAGE, N)
    compiled = step.lower(params, sds((B,), jnp.int32), caches,
                          sds((B,), jnp.int32), sds((B, NP), jnp.int32),
                          sds((B, NP), jnp.int32),
                          sds((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "input_output_alias" in text        # the hot pools, in place


# DeepSeek-V2-Lite: 16 heads absorbed into a 512-wide latent plus a 64-wide
# shared rope key; 8 held experts of width 1408 (one chip of an 8-chip
# expert-parallel group), d_model 2048; 27 layers, the first dense
DS_H, DS_C, DS_R, DS_D, DS_F, DS_HELD, DS_MOE = 16, 512, 64, 2048, 1408, 8, 26


@pytest.mark.parametrize("cold_host", [False, True], ids=["hbm", "staged"])
def test_latent_decode_compiles_for_v5e(compile_for, shapes, cold_host):
    """The latent kernel at DeepSeek-V2-Lite widths, its cold pools read
    from HBM or staged from the page-major host storage."""
    from repro.kernels.paged_decode import latent_decode_attention
    from repro.models import kvcache
    sds, host = shapes
    if cold_host:
        cold = (sds((N, PAGE * DS_C // 128, 128), where=host),
                sds((N, PAGE * DS_R // 128, 128), where=host))
    else:
        cold = (sds((N, PAGE, DS_C)), sds((N, PAGE, DS_R)))

    def step(ql, qr, ch, kh, cc, kc, table, tier, lengths, chunks):
        if cold_host:
            cc = kvcache._cold_stage(cc, chunks, (PAGE, DS_C))
            kc = kvcache._cold_stage(kc, chunks, (PAGE, DS_R))
        return latent_decode_attention(ql, qr, ch, kh, cc, kc, table, tier,
                                       lengths, scale=0.1147,
                                       interpret=False)

    compiled = compile_for(step, sds((B, DS_H, DS_C)), sds((B, DS_H, DS_R)),
                           sds((N + 1, PAGE, DS_C)), sds((N + 1, PAGE, DS_R)),
                           *cold, sds((B, NP), jnp.int32),
                           sds((B, NP), jnp.int32), sds((B,), jnp.int32),
                           sds((), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [B, 1536], ids=["decode", "prefill"])
def test_held_experts_compiles_for_v5e(compile_for, shapes, rows):
    from repro.kernels.held_experts import held_experts
    sds, _ = shapes
    bank_in = sds((DS_HELD, DS_D, DS_F))
    compiled = compile_for(
        lambda x, c, wg, wi, wo: held_experts(x, c, wg, wi, wo,
                                              interpret=False),
        sds((rows, DS_D)), sds((rows, DS_HELD), jnp.float32), bank_in,
        bank_in, sds((DS_HELD, DS_F, DS_D)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["demote_put", "stage"])
@pytest.mark.parametrize("width", [DS_C, DS_R], ids=["ckv", "krope"])
def test_latent_cold_page_ops_compile_for_v5e(compile_for, shapes, chip, op,
                                              width):
    from repro.models import kvcache
    sds, host = shapes
    rows = PAGE * width // 128
    cold = sds((N, DS_MOE, rows, 128), where=host)
    if op == "demote_put":
        compiled = kvcache._cold_put_fn(chip).lower(
            cold, sds((1, DS_MOE, rows, 128)), sds((), jnp.int32)).compile()
    else:
        compiled = compile_for(kvcache._cold_stage.__wrapped__, cold,
                               sds((), jnp.int32), (PAGE, width),
                               static_argnums=(2,))
    assert compiled.as_text()


@pytest.mark.usefixtures("compile_for")
def test_mla_decode_step_compiles_at_published_width(shapes, monkeypatch):
    """The engine's compiled decode step of DeepSeek-V2-Lite (8 of 64
    experts held) through the latent pools: one ``latent_decode`` and one
    ``held_experts`` kernel in it, the hot pools donated."""
    from repro.configs.base import get_config
    from repro.kernels import ops
    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve import engine
    sds, _ = shapes
    monkeypatch.setitem(ops._FORCE, "pallas", True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    base = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, num_held=DS_HELD))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda k: split_params(model.init_params(k, cfg))[0],
        jax.random.PRNGKey(0)))

    def pools(lead):
        return {"ckv_hot": sds(lead + (N + 1, PAGE, DS_C)),
                "krope_hot": sds(lead + (N + 1, PAGE, DS_R)),
                "ckv_cold": sds(lead + (N, PAGE, DS_C)),
                "krope_cold": sds(lead + (N, PAGE, DS_R))}
    caches = {"prologue": [pools(())], "slots": [pools((DS_MOE,))]}
    step = engine.make_pool_decode_step(cfg, PAGE, N)
    compiled = step.lower(params, sds((B,), jnp.int32), caches,
                          sds((B,), jnp.int32), sds((B, NP), jnp.int32),
                          sds((B, NP), jnp.int32),
                          sds((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "%latent_decode" in text and "%held_experts" in text
    assert "%paged_decode" not in text
    assert "input_output_alias" in text
