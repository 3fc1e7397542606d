"""The decode step on the persistent page pools is one compiled program
(``engine.make_pool_decode_step``).  On the same pool state it gives the
eager ``model.forward`` its next tokens and hot pools bit for bit, with the
cold pools staged (a tiered plan) and without (every page hot)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.configs.base import get_config
from repro.core.hardware import TPU_V5E
from repro.models import model
from repro.models.layers import split_params
from repro.serve import engine

MAX_SEQ, PAGE = 32, 4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hot_window", [4, MAX_SEQ], ids=["tiered", "allfast"])
def test_compiled_step_matches_eager_forward(dtype, hot_window):
    cfg = dataclasses.replace(get_config("smollm-360m").reduced(),
                              dtype=dtype)
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    trace = engine.serve_trace_for(get_config("smollm-360m"),
                                   [(9, 6), (11, 6)], slots=2, layer_group=8)
    plan = dataclasses.replace(
        runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes()),
        hot_window=hot_window, slot_hot_windows=[hot_window] * 2,
        page_tokens=PAGE)
    b = engine.ContinuousBatcher(params, cfg, 2, MAX_SEQ, plan=plan)
    b.submit(jnp.arange(9, dtype=jnp.int32), 6)
    b.submit(jnp.arange(3, 14, dtype=jnp.int32), 6)
    for _ in range(3):
        assert b.step()
    stage = b.pool.has_cold()
    assert stage == (hot_window < MAX_SEQ)

    logits, want, _ = model.forward(
        params, cfg, {"tokens": b.last_tok[:, None]},
        caches=b.pool.forward_tree(stage=stage), cache_index=b.lengths,
        decode=True, paged_view=b.pool.paged_view(b._active_mask))
    want_tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)

    # the eager call donates nothing: the same pools feed the compiled step
    tok, got = b._decode_step(b.params, b.last_tok,
                              b.pool.forward_tree(stage=stage), b.lengths,
                              *b.pool.arrays(), b._active_mask)
    assert tok.dtype == jnp.int32
    assert jnp.array_equal(tok, want_tok)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and jnp.array_equal(g, w)
