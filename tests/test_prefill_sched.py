"""Cache-aware prefill scheduling: the shared-prefix compute skip and
chunked prefill are exact program transformations — every path must be
bit-identical to one-shot, all-HBM, full-prompt prefill.

Covers (1) ``model.prefill_suffix`` against full prefill, forking exactly
at a page boundary and mid-page; (2) the pool engine's donor-page skip
(``prefill_compute_tokens`` strictly below unshared at identical tokens);
(3) chunked admission across chunk sizes, interleaving with a decoding
anchor slot; (4) a re-plan landing mid-prefill (jobs resume under the new
plan); (5) ``predict_pool_counters(prefill_chunk_tokens=...)`` replaying a
chunked engine's books integer-exactly; (6) a hypothesis fuzz over chunk
size x shared-prefix length."""
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

MAX_SEQ, SLOTS, PAGE = 32, 2, 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _plan(windows=(16, 16)):
    trace = engine.serve_trace_for(get_config("smollm-360m"),
                                   [(7, 6), (9, 5)], slots=SLOTS,
                                   layer_group=8)
    pl = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    return dataclasses.replace(pl, hot_window=MAX_SEQ // 2,
                               slot_hot_windows=list(windows),
                               page_tokens=PAGE)


def _toks(key, n, cfg):
    return jax.random.randint(jax.random.PRNGKey(key), (n,), 0,
                              cfg.vocab_size).astype(jnp.int32)


def _drive(params, cfg, plan, reqs, *, keys=None, chunk=0,
           replan_at=None, new_plan=None):
    """Run a batcher to completion; returns (sorted output tuples, engine,
    whether a re-plan landed while a prefill job was in flight)."""
    b = engine.ContinuousBatcher(params, cfg, SLOTS, MAX_SEQ, plan=plan,
                                 prefill_chunk_tokens=chunk)
    for i, (t, d) in enumerate(reqs):
        b.submit(t, d, prefix_key=keys[i] if keys else None)
    results, steps, mid_prefill = [], 0, False
    while b.queue or b._jobs or any(b.active):
        if not b.step():
            break
        steps += 1
        if replan_at is not None and steps == replan_at:
            mid_prefill = bool(b._jobs)
            b.apply_plan(new_plan)
        for i in range(b.B):
            if not b.active[i] and b.outputs[i]:
                results.append(tuple(b.outputs[i]))
                b.outputs[i] = []
        assert steps < 500
    return sorted(results), b, mid_prefill


# ------------------------------------------------ model-level bit-identity ---

@pytest.mark.parametrize("fork", [PAGE, 2 * PAGE, PAGE + 2, 2 * PAGE + 3])
def test_prefill_suffix_matches_full_prefill(setup, fork):
    """Chunk boundary exactly on a page edge and mid-page: running the
    prompt as prefix-then-suffix against the prefix's dense cache produces
    the full prefill's last logits bit-for-bit."""
    cfg, params = setup
    S = 3 * PAGE + 1
    tokens = _toks(3, S, cfg)[None]
    full, _ = model.prefill(params, cfg, {"tokens": tokens})
    _, caches = model.prefill(params, cfg, {"tokens": tokens[:, :fork]},
                              max_seq=S)
    last, _ = model.prefill_suffix(params, cfg,
                                   {"tokens": tokens[:, fork:]},
                                   caches=caches, start=fork)
    assert jnp.array_equal(full, last)


# ------------------------------------------------- shared-prefix skip --------

@pytest.mark.parametrize("prefix_len", [2 * PAGE, 2 * PAGE + 1])
def test_shared_admit_skips_donor_pages(setup, prefix_len):
    """Sharing forks exactly at a page boundary and mid-page: the follower
    admits compute only over its suffix (strictly fewer prefill tokens than
    the unshared run), tokens identical to the dense all-HBM reference."""
    cfg, params = setup
    plan = _plan()
    sys_p = _toks(7, prefix_len, cfg)
    reqs = [(jnp.concatenate([sys_p, _toks(11 + i, 2 + i, cfg)]), 5)
            for i in range(3)]
    base, _, _ = _drive(params, cfg, None, reqs)
    out_s, b_s, _ = _drive(params, cfg, plan, reqs,
                           keys=["sys"] * len(reqs))
    out_u, b_u, _ = _drive(params, cfg, plan, reqs)
    assert base == out_s == out_u
    c_s, c_u = b_s.counters(), b_u.counters()
    assert c_s["prefill_compute_tokens"] < c_u["prefill_compute_tokens"]
    assert c_u["prefill_skipped_tokens"] == 0
    # every follower skips the donor's *full* pages (mid-page rows recompute)
    skip_each = (prefix_len // PAGE) * PAGE
    assert c_s["prefill_skipped_tokens"] == (len(reqs) - 1) * skip_each
    assert c_s["prefill_compute_tokens"] + c_s["prefill_skipped_tokens"] \
        == c_u["prefill_compute_tokens"]
    b_s.ptable.check()


# ------------------------------------------------- chunked admission ---------

@pytest.mark.parametrize("chunk", [PAGE, 2 * PAGE, 3 * PAGE])
def test_chunked_prefill_bit_identical_across_chunk_sizes(setup, chunk):
    """Long prompts admitted in page-aligned chunks while an anchor slot
    keeps decoding: same token set as one-shot admission and as the dense
    all-HBM engine, for every chunk size."""
    cfg, params = setup
    plan = _plan()
    reqs = [(_toks(3, 5, cfg), 14), (_toks(4, 18, cfg), 4),
            (_toks(5, 15, cfg), 4)]
    base, _, _ = _drive(params, cfg, None, reqs)
    one, b1, _ = _drive(params, cfg, plan, reqs, chunk=0)
    chk, bc, _ = _drive(params, cfg, plan, reqs, chunk=chunk)
    assert base == one == chk
    cc = bc.counters()
    assert cc["prefill_compute_tokens"] == \
        b1.counters()["prefill_compute_tokens"]
    # the chunker really split the admissions: some step ran a partial prompt
    sp = cc["step_prefill_tokens"]
    assert max(sp) <= max(chunk, PAGE) * SLOTS
    assert sum(sp) == cc["prefill_compute_tokens"]
    bc.ptable.check()


def test_chunk_requires_pool_layout(setup):
    """Chunks are written into the pools: the dense reference (no plan) and
    the dense one-shot admission (``pool_prefill=False``) refuse them."""
    cfg, params = setup
    with pytest.raises(ValueError, match="pool"):
        engine.ContinuousBatcher(params, cfg, SLOTS, MAX_SEQ, plan=None,
                                 prefill_chunk_tokens=PAGE)
    with pytest.raises(ValueError, match="pool"):
        engine.ContinuousBatcher(params, cfg, SLOTS, MAX_SEQ, plan=_plan(),
                                 prefill_chunk_tokens=PAGE,
                                 pool_prefill=False)


def test_replan_lands_mid_prefill(setup):
    """A plan delta applied while a job is mid-prefill: the job resumes
    under the new plan and the run stays bit-identical to the dense
    reference."""
    cfg, params = setup
    plan = _plan()
    shrunk = dataclasses.replace(plan, hot_window=8,
                                 slot_hot_windows=[4, 8])
    reqs = [(_toks(3, 5, cfg), 14), (_toks(4, 20, cfg), 4),
            (_toks(5, 16, cfg), 4)]
    base, _, _ = _drive(params, cfg, None, reqs)
    out, b, mid = _drive(params, cfg, plan, reqs, chunk=PAGE,
                         replan_at=2, new_plan=shrunk)
    assert mid                      # the re-plan really hit an in-flight job
    assert base == out
    assert b.plan.hot_window == 8
    b.ptable.check()


# ------------------------------------------------- replay exactness ----------

def test_predict_pool_counters_chunked_integer_exact(setup):
    """The pure-Python replay with ``prefill_chunk_tokens`` mirrors a
    chunked engine's books integer-for-integer: migration total and series,
    page copies, admit writes."""
    cfg, params = setup
    plan = _plan(windows=(4, 8))       # small windows: demotions occur
    requests = [(5, 9), (17, 4), (14, 5), (9, 6)]
    reqs = [(_toks(20 + i, p, cfg), d) for i, (p, d) in enumerate(requests)]
    for chunk in (0, PAGE, 2 * PAGE):
        _, b, _ = _drive(params, cfg, plan, reqs, chunk=chunk)
        pred = engine.predict_pool_counters(
            requests, plan, slots=SLOTS, max_seq=MAX_SEQ,
            page_tokens=b.page_tokens, row_bytes=b._row_bytes,
            prefill_chunk_tokens=chunk)
        cnt = b.counters()
        assert pred["migration_bytes"] == cnt["sim_migration_bytes"]
        assert pred["step_migration_bytes"] == cnt["step_migration_bytes"]
        assert pred["page_copies"] == cnt["page_copies"]
        assert pred["admit_page_writes"] == cnt["admit_page_writes"]


# ------------------------------------------------- hypothesis fuzz -----------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                    # optional dev dep: skip, don't error
    HAVE_HYP = False


if HAVE_HYP:
    @given(chunk=st.sampled_from([0, PAGE, 2 * PAGE, 3 * PAGE]),
           prefix_len=st.integers(1, 3 * PAGE),
           seed=st.integers(0, 3))
    @settings(max_examples=8, deadline=None)
    def test_fuzz_chunk_x_prefix_bit_identical(setup, chunk, prefix_len,
                                               seed):
        """Random chunk size x shared-prefix length x request mix: the
        shared, chunked pool engine always reproduces the dense all-HBM
        token set."""
        cfg, params = setup
        plan = _plan()
        sys_p = _toks(40 + seed, prefix_len, cfg)
        reqs = [(jnp.concatenate([sys_p, _toks(50 + seed + i, 1 + (seed + i) % 5,
                                               cfg)]), 3 + (seed + i) % 4)
                for i in range(3)]
        base, _, _ = _drive(params, cfg, None, reqs)
        out, b, _ = _drive(params, cfg, plan, reqs,
                           keys=["sys"] * len(reqs), chunk=chunk)
        assert base == out
        b.ptable.check()
