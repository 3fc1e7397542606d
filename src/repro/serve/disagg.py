"""Prefill/decode disaggregation over a device mesh.

Prefill and decode contend for different resources: prefill is a
compute-bound burst over the whole prompt, decode a bandwidth-bound
steady-state loop.  Colocated on one device, every admission serializes a
prompt's worth of compute into the decode stream (the cost model's
``extra_*`` channels).  Disaggregated, prefill runs on its own device group
and the finished KV pages stream over the device↔device edge into the
decode pools — the admission cost becomes a *pipe* (the link), overlapped
behind decode, instead of serialized compute.

``DisaggregatedEngine`` subclasses ``ContinuousBatcher`` on the pools
layout.  ``devices`` is split by ``launch.mesh.disagg_groups``; prefill
runs on the prefill group and the finished ``(last, fresh)`` KV streams to
the decode side, where every decode device is a *shard*:

  * each decode device owns its own ``PagedKVPools`` + ``PageTable``, all
    of them under one ``MeshPageTable`` global slot namespace (names
    ``("prefill", "dev0", ..., "devN-1")``; the single-decode-device case
    keeps the original ``("prefill", "decode")`` pair and the original
    code paths, bit for bit);
  * the plan's ``slot_devices`` (``runtime.plan_serving(...,
    decode_devices=N)``) assigns each batch slot to its owning shard —
    prefix sharing is intra-shard only, and each step runs one sub-batch
    forward per shard against that shard's pools on that shard's device;
  * ``_alloc_admit_pages`` stages the admitted pages on the prefill
    device's ``PageTable`` and moves them into the owning shard's slot as
    a ``MeshPageTable.migrate_slot`` tier transition, so every page
    crossing an edge is a first-class, byte-conserving migration — the
    per-edge ledger matches ``predict_pool_counters()
    ["edge_migration_bytes"]`` integer-exactly, shared-prefix admits
    included (shared pages stay put on the decode side; only the private
    tail crosses);
  * ``apply_plan`` adopting a re-plan whose ``slot_devices`` moves an
    active slot re-homes it as the same first-class ``migrate_slot``
    transition (hot pages over the shard↔shard edge, cold pages host-
    internal), charged against the returned churn.

With ``tp_prefill=True`` and >1 prefill device, the prefill group runs the
prompt tensor-parallel under ``sharding.serve_rules``.  Measured on the
forced-multi-device CPU backend this is numerically equivalent but *not*
bit-exact to single-device prefill (~1e-6 relative drift from the
row-parallel psum reduction order), so it is opt-in; the default keeps
prefill on one device of the group and the engine's tokens bit-identical
to the colocated all-HBM engine.

Everything else (steady-state zero-re-pack decode, boundary demotions,
prefix sharing, plan adoption) is inherited unchanged.

``price_disagg`` is the planner-side model of the same trade: it prices a
workload colocated (prefill serialized, all the HBM) against disaggregated
(prefill stripped from the decode stream, KV streaming priced as a
``TierGraph`` edge pipe, decode on its own share of the HBM, optionally
split across N shards) — the ``bench_serve --disagg`` throughput gate.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.launch.mesh import disagg_groups
from repro.models import kvcache
from repro.runtime.plan import validate_slot_devices
from repro.runtime.policies import simulate
from repro.runtime.tiergraph import TierGraph
from repro.serve.engine import ContinuousBatcher


class DisaggregatedEngine(ContinuousBatcher):
    """A ``ContinuousBatcher`` whose prefill runs on a separate device group
    and whose decode batch is sharded across the decode group.

    ``devices`` (or a ``jax.sharding.Mesh``) is split by
    ``launch.mesh.disagg_groups``; with one device both groups alias it and
    the engine degrades gracefully (same program, same logits, the mesh
    page-table ledger still counts the logical edge traffic).  With N > 1
    decode devices each batch slot lives on exactly one shard — taken from
    the plan's ``slot_devices`` (round-robin when the plan carries none) —
    and decode runs one sub-batch forward per shard.  Requires a plan: the
    persistent pools are what make steady-state decode re-pack-free — the
    streamed pages land directly in the decode pools.
    """

    def __init__(self, params, cfg, batch_slots: int, max_seq: int,
                 scfg=None, plan=None, slot_tenants=None, devices=None,
                 tp_prefill: bool = False):
        if plan is None:
            raise ValueError("DisaggregatedEngine requires a plan (the "
                             "pools layout is planned)")
        prefill_devs, decode_devs = disagg_groups(devices)
        self.prefill_devices = list(prefill_devs)
        self.prefill_device = prefill_devs[0]
        self.decode_devices = list(decode_devs)
        self.decode_device = decode_devs[0]
        self.n_shards = len(self.decode_devices)
        params = jax.device_put(params, self.decode_device)
        # prompts prefill whole on the prefill group and stream across the
        # device edge — pool-direct suffix chunks would write the decode
        # pools from the wrong device, so the dense admission stays on (and
        # chunked prefill is refused)
        super().__init__(params, cfg, batch_slots, max_seq, scfg=scfg,
                         plan=plan, slot_tenants=slot_tenants,
                         device=self.decode_device, pool_prefill=False)
        pg = self.page_tokens
        self.device_hot_peak: dict = {}    # shard name -> peak hot pool bytes
        self._dev_note_version = None
        if self.n_shards == 1:
            # a plan placed for N shards cannot silently colocate
            sd = getattr(self.plan, "slot_devices", None)
            if sd is not None:
                validate_slot_devices(sd, batch_slots, 1)
            self.slot_devices = None
            self.pools = [self.pool]
            self.mesh_table = kvcache.MeshPageTable(
                [kvcache.PageTable(1, max_seq // pg, pg), self.ptable],
                names=("prefill", "decode"),
                page_bytes=pg * self._row_bytes)
        else:
            kinds = tuple(cfg.prologue) + tuple(cfg.period)
            if not all(k in kvcache.ATTN_KINDS for k in kinds) \
                    or cfg.num_prefix_tokens or cfg.num_codebooks:
                raise ValueError(
                    "multi-shard decode needs a pure-attention stack: every "
                    "layer's KV must live in the physical page pools (the "
                    "per-shard sub-batch forwards have no dense per-slot "
                    "caches to split)")
            sd = getattr(self.plan, "slot_devices", None)
            if sd is None:
                sd = [s % self.n_shards for s in range(batch_slots)]
            self.slot_devices = validate_slot_devices(sd, batch_slots,
                                                      self.n_shards)
            dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
            # one B-slot pool per shard, each on its device (cold pages in
            # that device's host memory): a slot's pages live
            # only in its owning shard's pool, and the global slot index
            # doubles as the local one — re-homing lands in an empty
            # same-index row
            self.pools = [self.pool] + [
                kvcache.PagedKVPools(cfg, batch_slots, max_seq, pg, dt,
                                     device=d)
                for d in self.decode_devices[1:]]
            self.params_shards = [self.params] + [
                jax.device_put(params, d) for d in self.decode_devices[1:]]
            self.mesh_table = kvcache.MeshPageTable(
                [kvcache.PageTable(1, max_seq // pg, pg)]
                + [p.table for p in self.pools],
                names=("prefill",) + tuple(
                    f"dev{d}" for d in range(self.n_shards)),
                page_bytes=pg * self._row_bytes)
        # one staging slot on the prefill side: a request's pages are born
        # there and migrate to their decode slot as one tier transition
        self._stage = self.mesh_table.gslot(0, 0)
        base_prefill = self._prefill           # the jitted model.prefill
        self.tp_prefill = bool(tp_prefill) and len(self.prefill_devices) > 1
        if self.tp_prefill:
            from repro import sharding as shd
            from repro.launch import specs
            pmesh = jax.sharding.Mesh(np.asarray(self.prefill_devices),
                                      ("model",))
            rules = shd.serve_rules(pmesh)
            p_sds, axes = specs.param_structs(cfg)
            self.params_prefill = jax.device_put(
                params, specs.shardings_from_axes(axes, rules, p_sds))
            self._prefill_mesh, self._prefill_rules = pmesh, rules
        else:
            self.params_prefill = jax.device_put(params, self.prefill_device)

        def prefill_remote(p, batch):
            del p                              # decode-side params unused
            if self.tp_prefill:
                with self._prefill_mesh, shd.axis_rules(self._prefill_rules):
                    last, fresh = base_prefill(self.params_prefill, batch)
            else:
                batch = jax.device_put(batch, self.prefill_device)
                last, fresh = base_prefill(self.params_prefill, batch)
            if self.n_shards == 1:
                # stream the finished KV over the device<->device edge
                return (jax.device_put(last, self.decode_device),
                        jax.device_put(fresh, self.decode_device))
            # multi-shard: the KV streams to the *owning* shard inside
            # _admit_pool; the last-row logits come back uncommitted so
            # last_tok never pins the shared decode state to one shard
            return jnp.asarray(jax.device_get(last)), fresh

        self._prefill = prefill_remote

    def _shard_of(self, slot: int) -> int:
        return self.slot_devices[slot] if self.n_shards > 1 else 0

    def _dev_label(self, d: int) -> str:
        return "decode" if self.n_shards == 1 else f"dev{d}"

    def _gslot(self, d: int, slot: int) -> int:
        return self.mesh_table.gslot(1 + d, slot)

    # ------------------------------------------------------------- admits --
    def _alloc_admit_pages(self, slot: int, n: int) -> None:
        d = self._shard_of(slot)
        need = n - self.pools[d].table.n_pages[slot]
        if need <= 0:
            return
        stage_table = self.mesh_table.tables[0]
        for _ in range(need):
            stage_table.alloc(0, 0)            # prefill writes land here
        self.mesh_table.migrate_slot(self._stage, self._gslot(d, slot))

    def _admit_pool(self, slot: int, tok_host, fresh, S: int, prefix_key):
        if self.n_shards == 1:
            return super()._admit_pool(slot, tok_host, fresh, S, prefix_key)
        pg = self.page_tokens
        d = self._shard_of(slot)
        pool = self.pools[d]
        # intra-shard only: physical pages cannot alias across HBMs, and a
        # slot's pages live only in its own shard's pool (a re-home moves
        # them, an ownership change drops a finished slot's), so a donor on
        # another shard holds no pages in ``pool`` and shares none
        shared_pages = self._share_prefix(slot, tok_host, prefix_key,
                                          pool=pool)
        n = -(-S // pg)
        self._alloc_admit_pages(slot, n)
        # the private tail's KV crosses the prefill->shard edge here
        fresh = jax.device_put(fresh, self.decode_devices[d])
        pool.admit_rows(fresh, slot, range(shared_pages, n))
        pool.splice_other(fresh, slot)
        with obs.span("sentinel.demote") as attrs:
            attrs["pages"] = self._demote_to(
                slot, self._slot_cold_target(slot, S), pool)

    # -------------------------------------------------------------- decode --
    def _pool_decode_step(self):
        if self.n_shards == 1:
            return super()._pool_decode_step()
        outs = []
        for d in range(self.n_shards):
            pool = self.pools[d]
            idxs = [s for s in range(self.B) if self.slot_devices[s] == d]
            act = [self.active[s] for s in idxs]
            if not any(act):
                continue
            for s in idxs:
                if self.active[s]:
                    pool.ensure_write_page(s, self._host_len[s])
            table_arr, tier_arr = pool.arrays()
            idx = np.asarray(idxs)
            # the shard's sub-batch, committed to its device: the one
            # compiled step builds once per device
            last_tok, lengths, table, tier, active = jax.device_put(
                (self.last_tok[idx], self.lengths[idx], table_arr[idx],
                 tier_arr[idx], np.asarray(act)), pool.device)
            caches = pool.forward_tree(stage=pool.has_cold())
            with obs.span("sentinel.forward", shard=d):
                tok, new_tree = self._decode_step(
                    self.params_shards[d], last_tok, caches, lengths, table,
                    tier, active)
            pool.adopt(new_tree)
            outs.append((idxs, tok))
        with obs.span("sentinel.demote") as attrs:
            attrs["pages"] = 0
            for s in range(self.B):
                if self.active[s]:
                    attrs["pages"] += self._demote_to(
                        s, self._slot_cold_target(s, self._host_len[s] + 1),
                        self.pools[self.slot_devices[s]])
        self._note_tenant_pages()
        tok = jax.device_get(self.last_tok).copy()
        for idxs, td in jax.device_get(outs):
            for i, s in enumerate(idxs):
                tok[s] = int(td[i])
        return jnp.asarray(tok, jnp.int32)

    # ----------------------------------------------------------- re-plans --
    def apply_plan(self, new_plan):
        """Adopt a re-plan on the sharded pools: demote active slots toward
        the new hot windows on their current owner, then re-home every
        active slot whose ``slot_devices`` entry moved as a first-class
        ``MeshPageTable.migrate_slot`` transition (hot pages over the
        shard↔shard edge, cold pages host-internal; a finished slot's stale
        pages are dropped, not copied).  Returns boundary bytes plus the
        re-homing bytes — the churn the online replanner weighs."""
        if self.n_shards == 1:
            return super().apply_plan(new_plan)
        if hasattr(new_plan, "changes"):       # a PlanDelta, not a plan
            new_plan = self.plan.apply_delta(new_plan)
        page = self._plan_page_tokens(new_plan)
        if page != self.page_tokens:
            raise ValueError(
                f"re-plan changes page geometry ({page} != "
                f"{self.page_tokens} tokens/page) — pools cannot be "
                "re-paged in place")
        tenants = getattr(new_plan, "slot_tenants", None)
        if tenants and len(tenants) != self.B:
            raise ValueError(
                f"slot_tenants has {len(tenants)} entries for {self.B} "
                f"batch slots (plan/batch geometry mismatch)")
        self.plan = new_plan
        if tenants:
            self.slot_tenants = list(tenants)
        mig0 = self.sim_migration_bytes
        # inactive slots are freed on their next admit
        with obs.span("sentinel.demote") as attrs:
            attrs["pages"] = 0
            for s in range(self.B):
                if self.active[s]:
                    attrs["pages"] += self._demote_to(
                        s, self._slot_cold_target(s, self._host_len[s]),
                        self.pools[self.slot_devices[s]])
        rehome = 0.0
        new_sd = getattr(new_plan, "slot_devices", None)
        if new_sd is not None:
            new_sd = validate_slot_devices(new_sd, self.B, self.n_shards)
            for s in range(self.B):
                old, new = self.slot_devices[s], new_sd[s]
                if old == new:
                    continue
                if self.active[s]:
                    rehome += self._rehome_slot(s, old, new)
                elif self.pools[old].table.n_pages[s]:
                    # a finished slot's stale pages are dropped on
                    # ownership change, not copied across the edge
                    self.pools[old].free_slot(s)
            self.slot_devices = new_sd
        # tenancy/ownership may have moved without a table event
        self._tenant_note_version = -1
        self._note_tenant_pages()
        return (self.sim_migration_bytes - mig0) + rehome

    def _rehome_slot(self, slot: int, old: int, new: int) -> float:
        """Move one live slot's pages between shards: the ``migrate_slot``
        tier transition for the table/ledger, plus the per-page pool data
        copy the table contract leaves to the caller.  Returns the bytes
        moved (hot over the edge + cold host-internal)."""
        src_pool, dst_pool = self.pools[old], self.pools[new]
        st, dt = src_pool.table, dst_pool.table
        n = st.n_pages[slot]
        if n == 0:
            return 0.0
        src_phys = list(st.table[slot][:n])
        src_tier = list(st.tier[slot][:n])
        base = dt.n_pages[slot]
        out = self.mesh_table.migrate_slot(self._gslot(old, slot),
                                           self._gslot(new, slot))
        dst_phys = list(dt.table[slot][base:base + n])
        dst_dev = self.decode_devices[new]
        for i in range(n):
            sp, dp = src_phys[i], dst_phys[i]
            for stacked, s_ent, d_ent in src_pool._attn_entries(dst_pool.tree):
                if src_tier[i] == 0:           # the shard<->shard edge copy
                    k, v = jax.device_put(
                        src_pool.hot_page(s_ent, stacked, sp), dst_dev)
                    dst_pool.set_hot_page(d_ent, stacked, dp, k, v)
                else:                          # re-homed into dst's host
                    k, v = jax.device_put(src_pool.cold_page(s_ent, sp),
                                          dst_dev)
                    dst_pool.set_cold_page(d_ent, dp, k, v)
        return out["hot_bytes"] + out["cold_bytes"]

    # ----------------------------------------------------------- counters --
    def _note_tenant_pages(self):
        """Per-tenant *and* per-shard hot-footprint peaks (distinct physical
        hot pages; a page counts once per device holding a copy), sampled at
        the same layout events as the base engine."""
        ver = tuple(p.table.version for p in self.pools)
        if ver == self._dev_note_version and self._tenant_note_version != -1:
            return                         # no layout event since last sample
        self._dev_note_version = ver
        self._tenant_note_version = self.pools[0].table.version
        per_t: dict = {}
        per_d: dict = {}
        for s in range(self.B):
            d = self._shard_of(s)
            t = self.pools[d].table
            hot = {(d, t.table[s][i]) for i in range(t.n_pages[s])
                   if t.tier[s][i] == 0}
            per_d.setdefault(self._dev_label(d), set()).update(hot)
            tn = self._slot_tenant(s)
            if tn is not None:
                per_t.setdefault(tn, set()).update(hot)
        page_bytes = self.page_tokens * self._row_bytes
        for tn, pages in per_t.items():
            v = len(pages) * page_bytes
            if v > self.tenant_hot_peak.get(tn, 0):
                self.tenant_hot_peak[tn] = v
        for dn, pages in per_d.items():
            v = len(pages) * page_bytes
            if v > self.device_hot_peak.get(dn, 0):
                self.device_hot_peak[dn] = v

    @property
    def xdev_migration_bytes(self) -> float:
        """Bytes that crossed a prefill->decode edge (the MeshPageTable
        ledger; matches ``predict_pool_counters(..., dense_admit=True)``
        integer-exactly, shared-prefix admits included — shared pages stay
        put on the decode side, only the private tail crosses)."""
        return sum(b for (src, _), b in self.mesh_table.edge_bytes.items()
                   if src == "prefill")

    @property
    def edge_migration_bytes(self) -> dict:
        """The full per-edge ledger ``{(src, dst): bytes}`` — admit streams
        plus re-homing transitions, byte-conserving by construction
        (``MeshPageTable.check``)."""
        return dict(self.mesh_table.edge_bytes)

    def counters(self) -> dict:
        out = super().counters()
        if self.n_shards > 1:
            for k in self.pools[0].stats:
                out[k] = sum(p.stats[k] for p in self.pools)
            out["table_version"] = sum(p.table.version for p in self.pools)
        out["xdev_migration_bytes"] = self.xdev_migration_bytes
        out["edge_migration_bytes"] = self.edge_migration_bytes
        out["device_hot_peak"] = dict(self.device_hot_peak)
        return out


def price_disagg(trace, cm, decode_fast_bytes: float, *,
                 policy: str = "sentinel", graph: Optional[TierGraph] = None,
                 decode_devices: int = 1, **knobs) -> dict:
    """Price a serving trace colocated vs disaggregated at equal total HBM.

    Colocated: one device with ``2 * decode_fast_bytes`` of HBM runs both
    phases; each admission's prefill serializes into the decode stream (the
    ``extra_*`` channels of the recorded ``StepTraffic``).  Disaggregated:
    decode keeps ``decode_fast_bytes`` (its half of the same total), the
    ``extra_*`` channels move to the prefill group, and the finished KV
    streams over the prefill->decode edge(s) of ``graph`` (default: the
    ``TierGraph.mesh`` with ``decode_devices`` decode shards plus the
    prefill device) priced per edge as a pipe — overlapped behind decode
    instead of serialized.  With ``decode_devices = N > 1`` the decode
    stream splits evenly across N shard pipes (each with its share of the
    HBM) and the slowest shard paces the step.

    The admitted-prefill tokens behind each step's KV stream are recovered
    from ``StepTraffic.extra_flops`` when the trace prices compute
    (``flops_per_token``), else from the admit byte channel
    ``StepTraffic.extra_fast`` (computed prefill tokens × KV row bytes);
    a trace carrying admissions that neither channel can attribute raises
    instead of silently pricing the stream as zero.

    Returns ``{"colocated": CostReport, "disagg": CostReport,
    "edge_bytes": float, "graph": TierGraph}``.
    """
    if decode_devices < 1:
        raise ValueError(f"price_disagg(decode_devices={decode_devices}): "
                         "need at least one decode shard")
    graph = graph if graph is not None else \
        TierGraph.mesh(decode_devices + 1, cm,
                       decode_fast_bytes / decode_devices)
    res_c = simulate(trace, cm, 2.0 * decode_fast_bytes, policy, **knobs)
    colocated = cm.price(res_c.step_traffic)
    res_d = simulate(trace, cm, decode_fast_bytes, policy, **knobs)
    kv_row = trace.num_layers * trace.kv_token_bytes
    flops_tok = getattr(trace, "flops_per_token", 0.0)
    if not flops_tok and not kv_row and \
            getattr(trace, "prefill_tokens", None):
        raise ValueError(
            "price_disagg cannot attribute the prefill->decode KV stream: "
            "the trace admits prompts but has neither flops_per_token nor "
            "kv_token_bytes, so no StepTraffic channel (extra_flops / "
            "extra_fast) carries the admitted tokens")
    N = decode_devices
    prefill_name = f"dev{N}"
    stripped, edge_flows, dev_series, edge_total = [], [], [], 0.0
    for tr in res_d.step_traffic:
        # prefill tokens admitted this step; their KV is what crosses the
        # device<->device link.  extra_flops attributes them when the trace
        # prices compute; the admit byte channel extra_fast (= computed
        # prefill tokens x KV row bytes) covers flops-less traces.
        if flops_tok:
            ptok = tr.extra_flops / flops_tok
        elif kv_row:
            ptok = tr.extra_fast / kv_row
        else:
            ptok = 0.0
        flow = ptok * kv_row
        edge_total += flow
        base = replace(tr, extra_flops=0.0, extra_fast=0.0,
                       prefill_flops=0.0, prefill_read=0.0)
        stripped.append(base)
        flows = {}
        per_dev = {}
        for d in range(N):
            per_dev[f"dev{d}"] = base if N == 1 else replace(
                base, flops=base.flops / N, fast_read=base.fast_read / N,
                slow_read=base.slow_read / N,
                demand_read=base.demand_read / N, mig_in=base.mig_in / N,
                mig_out=base.mig_out / N, migs=base.migs / N)
            if flow:
                flows[(prefill_name, f"dev{d}")] = flow / N
        # the prefill group's own pipe runs concurrently with the shards:
        # its prompt compute (the extra/prefill channels) is one more
        # max() arm, never serialized into the decode stream
        per_dev[prefill_name] = replace(
            tr, flops=0.0, fast_read=0.0, slow_read=0.0, demand_read=0.0,
            mig_in=0.0, mig_out=0.0, migs=0.0, stall=0.0)
        edge_flows.append(flows)
        dev_series.append(per_dev)
    disagg = cm.price_on_graph(stripped, graph, edge_flows,
                               device_traffic=dev_series)
    return {"colocated": colocated, "disagg": disagg,
            "edge_bytes": edge_total, "graph": graph}
