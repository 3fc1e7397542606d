"""The program's span recorder (``repro.obs``) and the spans the serving
engine opens: one tree per ``step()``, request records that agree with the
engine's own outputs, a bounded ring, programs built counted on the span
that built them, and spans that land in a profiler trace."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs, runtime
from repro.configs.base import get_config
from repro.core.hardware import TPU_V5E
from repro.models import model
from repro.models.layers import split_params
from repro.serve import engine

PROMPTS = (9, 5, 12)            # three requests for two slots: one queues
BUDGETS = (4, 3, 5)
SPAN_NAMES = {"sentinel.step", "sentinel.admit", "sentinel.prefill",
              "sentinel.decode", "sentinel.forward", "sentinel.demote",
              "sentinel.stage_cold", "sentinel.table", "sentinel.sync"}
PARENT = {"sentinel.admit": "sentinel.step",
          "sentinel.decode": "sentinel.step",
          "sentinel.sync": "sentinel.step",
          "sentinel.prefill": "sentinel.admit",
          "sentinel.forward": "sentinel.decode"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


@pytest.fixture(scope="module", params=[0, 4], ids=["one-shot", "chunked"])
def served(request, setup):
    """The persistent pools with 4-token hot windows, so that admissions
    and decode steps demote and stage; driven step by step, recording the
    window of every step and the step at which each slot bound anew."""
    cfg, params = setup
    max_seq, page = 32, 4
    trace = engine.serve_trace_for(get_config("smollm-360m"),
                                   [(7, 6), (9, 5)], slots=2, layer_group=8)
    plan = dataclasses.replace(
        runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes()),
        hot_window=4, slot_hot_windows=[4, 4], page_tokens=page)
    b = engine.ContinuousBatcher(params, cfg, 2, max_seq, plan=plan,
                                 prefill_chunk_tokens=request.param)
    t0 = time.perf_counter_ns()
    rids = [b.submit(jnp.arange(n, dtype=jnp.int32) % cfg.vocab_size, d)
            for n, d in zip(PROMPTS, BUDGETS)]
    steps, bound = [], []          # (lo, hi); (step index, slot, outputs)
    lists = [id(o) for o in b.outputs]
    while b.queue or b._jobs or any(b.active):
        lo = time.perf_counter_ns()
        assert b.step()
        steps.append((lo, time.perf_counter_ns()))
        for s, o in enumerate(b.outputs):
            if id(o) != lists[s]:          # _activate gave the slot a list
                lists[s] = id(o)
                bound.append((len(steps) - 1, s, o))
    return b, rids, steps, bound, obs.records(t0, time.perf_counter_ns())


def test_each_step_is_one_tree(served):
    b, _, steps, _, recs = served
    seen = set()
    for i, (lo, hi) in enumerate(steps):
        mine = [r for r in recs if r.start_ns >= lo and r.end_ns <= hi
                and r.name != "sentinel.request"]
        by_id = {r.id: r for r in mine}
        roots = [r for r in mine if r.parent is None]
        assert [r.name for r in roots] == ["sentinel.step"]
        assert roots[0].attrs["step"] == i
        for r in mine:
            assert r.name in SPAN_NAMES
            assert lo <= r.start_ns <= r.end_ns <= hi
            if r.name in PARENT:
                assert by_id[r.parent].name == PARENT[r.name], r
            node = r                   # every span descends from the step
            while node.parent is not None:
                node = by_id[node.parent]
            assert node is roots[0]
        seen |= {r.name for r in mine}
    assert seen == SPAN_NAMES
    assert sum(r.attrs["prefill_tokens"] for r in recs
               if r.name == "sentinel.step") == sum(PROMPTS)
    assert sum(r.attrs["pages"] for r in recs
               if r.name == "sentinel.demote") == b.pool.stats["page_copies"]
    assert sum(r.attrs["bytes"] for r in recs
               if r.name == "sentinel.stage_cold") == \
        b.pool.stats["cold_stage_bytes"]


def test_requests_match_the_engine(served):
    b, rids, steps, bound, recs = served
    assert rids == sorted(rids) and len(set(rids)) == len(rids)
    reqs = sorted((r for r in recs if r.name == "sentinel.request"),
                  key=lambda r: r.attrs["first_token_ns"])
    # FIFO admission: requests bind, and first tokens come, in rid order
    assert [r.attrs["rid"] for r in reqs] == rids
    assert len(bound) == len(reqs)
    for r, (i, slot, out), n, budget in zip(reqs, bound, PROMPTS, BUDGETS):
        lo, hi = steps[i]
        assert r.attrs["slot"] == slot and r.attrs["prompt_tokens"] == n
        assert lo <= r.attrs["first_token_ns"] <= hi
        assert r.start_ns <= r.attrs["bind_ns"] <= r.attrs["first_token_ns"]
        assert len(out) == budget              # the engine's own outputs
        prefill = [p for p in recs if p.name == "sentinel.prefill"
                   and p.attrs["rid"] == r.attrs["rid"]]
        assert all(p.attrs["slot"] == slot for p in prefill)
        assert sum(p.attrs["end"] - p.attrs["start"] for p in prefill) == n
        assert all(r.start_ns <= p.start_ns and p.end_ns <= r.end_ns
                   for p in prefill)


def test_compiled_decode_builds_nothing_after_warm_up(setup):
    """Past a warm-up, the compiled decode step is built or loaded on no
    step, through admissions, page-boundary demotions and a copy-on-write
    of a shared prefix page: one program in all.  Each step donates the hot
    pools it was handed, and no later work reads them."""
    cfg, params = setup
    max_seq, page = 32, 4
    trace = engine.serve_trace_for(get_config("smollm-360m"),
                                   [(7, 6), (9, 5)], slots=2, layer_group=8)
    plan = dataclasses.replace(
        runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes()),
        hot_window=4, slot_hot_windows=[4, 4], page_tokens=page)
    b = engine.ContinuousBatcher(params, cfg, 2, max_seq, plan=plan)
    handed = []                    # the hot pools of every decode call
    compiled = b._decode_step

    def decode_step(params, last_tok, caches, *rest):
        handed.append([e[k] for e in caches["slots"]
                       for k in ("k_hot", "v_hot")])
        return compiled(params, last_tok, caches, *rest)
    b._decode_step = decode_step

    t_warm = time.perf_counter_ns()
    b.submit(jnp.arange(9, dtype=jnp.int32), 3)           # warm-up
    while b.queue or b._jobs or any(b.active):
        assert b.step()
    assert b.pool.has_cold()
    warm = len(handed)

    t0 = time.perf_counter_ns()
    system = jnp.arange(20, 29, dtype=jnp.int32) % cfg.vocab_size
    for n, budget in ((2, 6), (3, 5), (1, 4)):
        b.submit(jnp.concatenate([system, jnp.arange(n, dtype=jnp.int32)]),
                 budget, prefix_key="system")
    steps = 0
    while b.queue or b._jobs or any(b.active):
        if steps == 1:
            # the sharer's first page is the donor's, demoted and shared
            assert b.ptable.is_shared(1, 0)
            copies = b.pool.stats["page_copies"]
            assert b.pool.cow_for_write(1, 0)
            assert b.pool.stats["page_copies"] == copies + 1
        assert b.step()
        steps += 1
        assert all(a.is_deleted() for a in handed[-1])
        assert not any(a.is_deleted() for _, e in b.pool._attn_entries()
                       for a in e.values())
    assert len(handed) == warm + steps
    recs = obs.records(t0, time.perf_counter_ns())
    forwards = [r for r in recs if r.name == "sentinel.forward"]
    assert len(forwards) == steps
    assert [r.built + r.loaded for r in forwards] == [0] * steps
    assert sum(r.name == "sentinel.request" for r in recs) == 3
    # one program in all: every step had staged cold pages, and the
    # inputs kept one placement from the first call on
    assert sum(r.built + r.loaded for r in obs.records(t_warm)
               if r.name == "sentinel.forward") == 1
    assert sum(r.attrs["pages"] for r in recs
               if r.name == "sentinel.demote") > 0


def test_ring_stays_at_its_bound():
    first = obs.add("probe.first", 0, 1)
    for _ in range(obs.RING_RECORDS):
        with obs.span("probe"):
            pass
    recs = obs.records()
    assert len(recs) == obs.RING_RECORDS
    assert first not in recs and recs[-1].name == "probe"


def test_programs_built_count_on_the_innermost_span():
    x = jnp.ones((8,))
    jax.block_until_ready(x)
    unique = float(time.perf_counter_ns() % 1000003)   # never cached before
    with obs.span("probe.outer") as outer_attrs:
        with obs.span("probe.inner") as inner_attrs:
            jax.jit(lambda v: v * unique + 1)(x).block_until_ready()
        jax.jit(lambda v: v * unique - 1)(x).block_until_ready()
    by_attrs = {id(r.attrs): r for r in obs.records()
                if r.name.startswith("probe.")}
    inner, outer = by_attrs[id(inner_attrs)], by_attrs[id(outer_attrs)]
    assert (inner.built, inner.loaded) == (1, 0)
    assert (outer.built, outer.loaded) == (1, 0)
    assert inner.parent == outer.id


def test_spans_land_in_the_profile(tmp_path):
    from benchmarks.chip import trace
    f = jax.jit(lambda v: (v @ v).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with obs.span("sentinel.step"):
                    with obs.span("sentinel.forward"):
                        f(x).block_until_ready()
    profile = jax.profiler.ProfileData.from_file(trace.xplane_file(
        str(tmp_path)))
    spans = trace.host_spans(profile, "sentinel.")
    assert sorted(n for n, _, _ in spans) == \
        ["sentinel.forward"] * 2 + ["sentinel.step"] * 2
    (_, lo, hi), = [s for s in trace.host_spans(
        profile, ("bench.window", "sentinel.")) if s[0] == "bench.window"]
    assert all(lo <= s <= e <= hi for _, s, e in spans)
    steps = [s for s in spans if s[0] == "sentinel.step"]
    for _, s, e in spans:
        assert any(a <= s and e <= b for _, a, b in steps)
