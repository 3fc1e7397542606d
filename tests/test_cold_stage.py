"""Cold staging copies the occupied prefix of each cold pool: cold pages
are handed out lowest free index first, the page table keeps the cold
high-water mark, and ``PagedKVPools.stage_cold`` brings the pages below it,
in whole chunks, into an HBM pool of the whole pool's shape.  Every cold
page the table maps reads what a whole-pool staging would give, byte for
byte, and the counters and the span count the pages and bytes that moved."""
import dataclasses
import random
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs.base import ATTN, get_config
from repro.models import kvcache

SLOTS, MAX_SEQ, PAGE = 4, 128, 4     # 32 pages a slot, 128 cold: 2 chunks


def _gqa():
    """Reduced smollm with one unstacked attention layer before the stack."""
    cfg = get_config("smollm-360m").reduced()
    return dataclasses.replace(cfg, prologue=(ATTN,),
                               num_layers=cfg.num_layers + 1)


def _mla():
    return get_config("deepseek-v2-lite-16b").reduced()


CONFIGS = {"gqa": _gqa, "mla": _mla}


def _whole_pool(cold, page_shape):
    """The whole page-major host pool in the kernel's layout, as staging
    read it before it copied only the occupied prefix."""
    x = kvcache.to_device(cold)
    x = x.reshape(x.shape[:-2] + page_shape)
    return jnp.moveaxis(x, 0, -1 - len(page_shape))


def _fill_hot(pool, key):
    """Fresh random contents in every hot pool, so demoted pages differ."""
    for _, entry in pool._attn_entries():
        for n in pool._names(entry):
            key, sub = jax.random.split(key)
            entry[f"{n}_hot"] = jax.random.normal(
                sub, entry[f"{n}_hot"].shape, entry[f"{n}_hot"].dtype)
    return key


def _grow(pool, slot, pages, cold):
    """Give an empty ``slot`` ``pages`` hot pages and demote ``cold`` of
    them."""
    for _ in range(pages):
        pool.table.alloc(slot, 0)
    for _ in range(cold):
        pool.demote_boundary(slot)


def _fragmented(name, seed=0):
    """A pool after admits, demotions and frees in a seeded order, with its
    cold pages scattered over both chunks."""
    pool = kvcache.PagedKVPools(CONFIGS[name](), SLOTS, MAX_SEQ, PAGE,
                                jnp.bfloat16)
    rng = random.Random(seed)
    key = jax.random.PRNGKey(seed)
    for round_ in range(3):
        key = _fill_hot(pool, key)
        for s in range(SLOTS):
            if pool.table.n_pages[s] and rng.random() < 0.5:
                pool.free_slot(s)
            if not pool.table.n_pages[s]:     # the first round fills up
                n = rng.randint(24 if round_ == 0 else 8, 32)
                _grow(pool, s, n, n - 2 if round_ == 0
                      else rng.randint(1, n - 2))
        pool.table.check()
    return pool


def _spans_since(t0):
    return [r for r in obs.records(t0) if r.name == "sentinel.stage_cold"]


def _mapped(table):
    return sorted({table.table[s][i] for s in range(table.slots)
                   for i in range(table.n_pages[s]) if table.tier[s][i] == 1})


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_staged_pools_equal_whole_pool_staging_at_every_mapped_page(name):
    pool = _fragmented(name, seed=1)
    mapped = _mapped(pool.table)
    # the mark reaches the second chunk, with free pages below it
    assert pool.table.cold_high == mapped[-1] + 1 > max(64, len(mapped))
    staged = pool.forward_tree(stage=True)
    kinds = {"prologue": pool.cfg.prologue, "slots": pool.cfg.period}
    seen = set()
    for part, entries in pool.tree.items():
        for i, entry in enumerate(entries):
            if kinds[part][i] not in kvcache.POOL_KINDS:
                continue
            lead = 1 if part == "slots" else 0
            seen.add(lead)
            for n in pool._names(entry):
                got = staged[part][i][f"{n}_cold"]
                want = _whole_pool(entry[f"{n}_cold"], pool.page_shapes[n])
                assert got.shape == want.shape and got.dtype == want.dtype
                idx = jnp.asarray(mapped)
                assert jnp.array_equal(jnp.take(got, idx, axis=lead),
                                       jnp.take(want, idx, axis=lead)), n
    assert seen == {0, 1}                  # prologue and stacked layers


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counters_and_span_count_the_staged_prefix(name):
    pool = _fragmented(name, seed=2)
    t, chunk = pool.table, kvcache.stage_chunk(pool.table.n_cold)
    assert chunk == 64 and t.n_cold == 128
    staged = []
    for step in range(3):
        if step:                            # free the slot that reaches up
            top = max(range(SLOTS), key=lambda s: max(
                [t.table[s][i] for i in range(t.cold_pages(s))], default=-1))
            pool.free_slot(top)
            _grow(pool, top, 4, 2)
        mapped = _mapped(t)
        pages = min(-(-t.cold_high // chunk) * chunk, t.n_cold)
        assert pages >= mapped[-1] + 1 and pages == pool.staged_pages()
        before = dict(pool.stats)
        t0 = time.perf_counter_ns()
        pool.forward_tree(stage=True)
        (rec,) = _spans_since(t0)
        page_bytes = sum(e[f"{n}_cold"].nbytes // t.n_cold
                         for _, e in pool._attn_entries()
                         for n in pool._names(e))
        moved = pool.stats["cold_stage_bytes"] - before["cold_stage_bytes"]
        assert moved == rec.attrs["bytes"] == pages * page_bytes
        assert pool.stats["cold_stage_pages"] - \
            before["cold_stage_pages"] == rec.attrs["pages"] == pages
        assert rec.attrs["mapped"] == len(mapped)
        assert pool.stats["cold_stages"] == before["cold_stages"] + 1
        staged.append(pages)
    assert staged[0] == 128 and staged[-1] == 64   # the mark falls back


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_pool_with_no_cold_page_stages_nothing(name):
    pool = kvcache.PagedKVPools(CONFIGS[name](), SLOTS, MAX_SEQ, PAGE,
                                jnp.bfloat16)
    _grow(pool, 0, 6, 2)
    _grow(pool, 1, 5, 0)
    assert pool.has_cold()
    pool.free_slot(0)                       # its cold pages go back
    assert not pool.has_cold() and pool.table.cold_high == 0
    assert pool.staged_pages() == 0
    t0 = time.perf_counter_ns()
    staged = pool.forward_tree(stage=True)
    (rec,) = _spans_since(t0)
    assert rec.attrs == {"bytes": 0, "pages": 0, "mapped": 0}
    assert pool.stats["cold_stage_bytes"] == pool.stats["cold_stage_pages"] \
        == 0
    for leaf in jax.tree.leaves(staged):
        assert leaf.sharding.memory_kind != kvcache.host_memory_kind()


@pytest.mark.parametrize("seed", range(6))
def test_page_table_hands_out_the_lowest_free_cold_page(seed):
    rng = random.Random(seed)
    t = kvcache.PageTable(slots=4, pages_per_slot=12, page_tokens=4,
                          hot_pages=40, cold_pages=30)
    for _ in range(400):
        s = rng.randrange(t.slots)
        op = rng.random()
        lowest = min(t.cold_free, default=None)
        if op < 0.35 and t.n_pages[s] < t.pages_per_slot and t.hot_free:
            t.alloc(s, 0)
        elif op < 0.6 and t.cold_pages(s) < t.n_pages[s] and t.cold_free:
            cold, _, copied = t.demote(s, t.cold_pages(s))
            assert not copied or cold == lowest
        elif op < 0.7 and t.n_pages[s] == t.cold_pages(s) < t.pages_per_slot \
                and t.cold_free:
            assert t.alloc(s, 1) == lowest
        elif op < 0.8:
            d = rng.randrange(t.slots)
            if d != s and not t.n_pages[d] and t.n_pages[s]:
                t.share(d, s, rng.randint(1, t.n_pages[s]))
        elif op < 0.88 and t.n_pages[s]:
            i = rng.randrange(t.n_pages[s])
            free = t.cold_free if t.tier[s][i] == 1 else t.hot_free
            if free and t.is_shared(s, i):
                r = t.cow(s, i)
                assert r[2] == 0 or r[1] == lowest
        else:
            t.free_slot(s)
        t.check()
        used = [p for p, r in enumerate(t.cold_ref) if r]
        assert t.cold_high == (used[-1] + 1 if used else 0)


@pytest.mark.parametrize("n,chunk", [(1024, 64), (128, 64), (16, 16),
                                     (96, 48), (100, 50), (67, 1)])
def test_stage_chunk_tiles_the_pool(n, chunk):
    assert kvcache.stage_chunk(n) == chunk
