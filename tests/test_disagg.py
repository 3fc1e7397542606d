"""Prefill/decode disaggregation: the mesh page table and the engine pair.

Deterministic unit tests for ``MeshPageTable``'s namespace, cross-device
migration, and byte-conservation ledgers (the randomized op-program suite
lives in test_disagg_properties.py behind the optional hypothesis dep),
plus the ISSUE's engine acceptance row: ``DisaggregatedEngine`` emits
bit-identical tokens to the single-device ``ContinuousBatcher`` with zero
steady-state re-packs, and its cross-device ledger equals
``predict_pool_counters``'s predicted edge traffic integer-exactly.
"""
import dataclasses

import pytest

from repro.models.kvcache import MeshPageTable, PageTable

DEVS, SLOTS, NP, PG = 3, 2, 4, 8
PAGE_BYTES = float(PG * 64)


def make_mesh():
    return MeshPageTable([PageTable(SLOTS, NP, PG) for _ in range(DEVS)],
                         page_bytes=PAGE_BYTES)


# ------------------------------------------------------------ namespace ----

def test_global_namespace_unique():
    m = make_mesh()
    seen = set()
    for d in range(DEVS):
        for s in range(SLOTS):
            g = m.gslot(d, s)
            assert g not in seen
            seen.add(g)
            assert m.owner(g) == (d, s)
    assert seen == set(range(m.slots))
    with pytest.raises(ValueError):
        m.gslot(0, SLOTS)
    with pytest.raises(ValueError):
        m.owner(m.slots)


def test_share_refused_across_devices():
    m = make_mesh()
    src = m.gslot(0, 0)
    m.alloc(src, 0)
    with pytest.raises(ValueError):
        m.share(m.gslot(1, 0), src, 1)
    # same-device sharing still delegates through
    m.share(m.gslot(0, 1), src, 1)
    assert m.refcount(src, 0) == 2


def test_migrate_within_device_refused():
    m = make_mesh()
    g = m.gslot(0, 0)
    m.alloc(g, 0)
    with pytest.raises(ValueError):
        m.migrate_slot(g, m.gslot(0, 1))


def test_migrate_validates_before_mutating():
    """A refused migration must leave both tables and every ledger alone."""
    m = make_mesh()
    src = m.gslot(0, 0)
    for _ in range(NP):
        m.alloc(src, 0)
    dst = m.gslot(1, 0)
    m.alloc(dst, 0)                          # NP + 1 > pages_per_slot
    with pytest.raises(ValueError):
        m.migrate_slot(src, dst)
    assert m.n_pages(src) == NP and m.n_pages(dst) == 1
    assert m.edge_bytes == {} and m.host_internal_bytes == 0.0
    m.check()


def test_migrate_moves_shared_page_as_private_copy():
    m = make_mesh()
    src, sharer, dst = m.gslot(0, 0), m.gslot(0, 1), m.gslot(1, 0)
    m.alloc(src, 0)
    m.share(sharer, src, 1)
    assert m.refcount(src, 0) == 2
    out = m.migrate_slot(src, dst)
    assert out == {"pages": 1, "hot_bytes": PAGE_BYTES, "cold_bytes": 0.0}
    # the sharer keeps the original physical page, now exclusive
    assert m.refcount(sharer, 0) == 1
    assert m.refcount(dst, 0) == 1
    assert m.edge_bytes == {("dev0", "dev1"): PAGE_BYTES}
    m.check()


def test_cold_pages_rehome_inside_host_memory():
    m = make_mesh()
    src, dst = m.gslot(0, 0), m.gslot(1, 0)
    m.alloc(src, 0)
    m.alloc(src, 0)
    m.demote(src, 0)                         # does not remove the hot page
    # build a fully-cold slot: free and re-alloc one cold page
    m.free_slot(src)
    m.alloc(src, 1)
    out = m.migrate_slot(src, dst)
    assert out["cold_bytes"] == PAGE_BYTES and out["hot_bytes"] == 0.0
    assert m.edge_bytes == {}                # no device link touched
    assert m.host_internal_bytes == PAGE_BYTES
    m.check()


# ------------------------------------------------------------ the engines ----

@pytest.fixture(scope="module")
def engine_pair():
    import jax
    import jax.numpy as jnp

    from repro import runtime
    from repro.configs.base import get_config
    from repro.core.hardware import TPU_V5E
    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve import engine
    from repro.serve.disagg import DisaggregatedEngine
    from repro.serve.engine import serve_trace_for

    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    max_seq, slots = 32, 2
    requests = [(7, 6), (9, 5), (6, 7), (8, 6)]
    trace = serve_trace_for(get_config("smollm-360m"), requests,
                            slots=slots, layer_group=8)
    plan = runtime.plan(trace, TPU_V5E, 0.2 * trace.peak_kv_bytes())
    plan = dataclasses.replace(plan, hot_window=max_seq // 2,
                               slot_hot_windows=[4, 8], page_tokens=4)

    def drive(eng_cls, **kw):
        b = eng_cls(params, cfg, slots, max_seq, plan=plan, **kw)
        key = jax.random.PRNGKey(3)
        for plen, d in requests:
            key, sub = jax.random.split(key)
            b.submit(jax.random.randint(
                sub, (plen,), 0, cfg.vocab_size).astype(jnp.int32), d)
        return b.run(), b

    out_c, bc = drive(engine.ContinuousBatcher)
    out_d, bd = drive(DisaggregatedEngine)
    return requests, plan, (out_c, bc), (out_d, bd)


def test_disagg_engine_bit_identical(engine_pair):
    _, _, (out_c, _), (out_d, _) = engine_pair
    assert out_c == out_d


def test_disagg_engine_zero_repacks(engine_pair):
    _, _, _, (_, bd) = engine_pair
    assert bd.counters()["repacks"] == 0


def test_disagg_ledger_matches_prediction_exactly(engine_pair):
    from repro.serve.engine import predict_pool_counters
    requests, plan, (_, bc), (_, bd) = engine_pair
    pred = predict_pool_counters(requests, plan, slots=2, max_seq=32,
                                 page_tokens=bd.page_tokens,
                                 row_bytes=bd._row_bytes)
    assert bd.xdev_migration_bytes == pred["xdev_migration_bytes"]
    assert bd.xdev_migration_bytes > 0
    # the decode-side tiering accounting is untouched by disaggregation
    assert bd.sim_migration_bytes == bc.sim_migration_bytes
    bd.mesh_table.check()


def test_disagg_requires_pools_layout():
    """The disaggregated engine streams pages into decode pools, which only
    a plan builds: no plan, no engine."""
    import jax

    from repro.configs.base import get_config
    from repro.models import model
    from repro.models.layers import split_params
    from repro.serve.disagg import DisaggregatedEngine

    cfg = get_config("smollm-360m").reduced()
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError, match="requires a plan"):
        DisaggregatedEngine(params, cfg, 2, 32, plan=None)


def test_price_disagg_prefill_heavy_wins():
    """The planner-side model of the ISSUE's throughput gate: under a
    prefill-heavy mix, disaggregated tokens/sec at or above colocated at
    equal total HBM, with the KV stream priced on the device edge."""
    from repro.core.hardware import default_cost_model
    from repro.serve.disagg import price_disagg
    from repro.serve.engine import serve_trace_for
    from repro.configs.base import get_config

    cfg = get_config("smollm-360m")
    heavy = [(480, 24), (512, 16), (448, 32), (500, 20)]
    trace = serve_trace_for(cfg, heavy, slots=4, layer_group=8)
    res = price_disagg(trace, default_cost_model(),
                       0.2 * trace.peak_kv_bytes())
    assert res["disagg"].tokens_per_s >= res["colocated"].tokens_per_s
    assert res["edge_bytes"] > 0
    assert set(res["graph"].names) == {"dev0", "dev1", "host"}


def test_disagg_groups_split():
    from repro.launch.mesh import disagg_groups
    one = ["a"]
    p, d = disagg_groups(one)
    assert p == d == one                     # degenerate single device
    # decode leads (it owns the pools + the default device) and takes the
    # larger share on odd counts
    p, d = disagg_groups(["a", "b", "c"])
    assert d == ["a", "b"] and p == ["c"]
    p, d = disagg_groups(["a", "b", "c", "d"])
    assert d == ["a", "b"] and p == ["c", "d"]
