"""Batched serving engine: prefill + lockstep decode with KV caches.

``serve_step`` (one token for the whole batch against a filled cache) is the
function the decode-shape dry-run cells lower; ``generate`` drives it for the
examples/benchmarks with greedy or temperature sampling.

Sentinel-Serve: ``ContinuousBatcher`` serves from one of two caches.

  dense  (``plan=None``)  one all-HBM batched cache (kvcache.init_cache),
      decoded by the eager ``model.forward``: the reference the tiered
      path is held to, token for token.
  pools  (a ``ServePlan``)  the persistent physical page pools
      (kvcache.PagedKVPools) ARE the cache.  Each slot's cold prefix —
      whole pages older than the plan's hot window for that slot
      (``plan.cold_len_slot``) — lives in host memory, the rest in HBM;
      decode writes each token's KV into its physical hot page through the
      page table and attention reads the pools via
      ops.paged_decode_attention (or the latent kernel for MLA layers), in
      one compiled step (``make_pool_decode_step``).  Stateful layers keep
      their dense per-slot state inside the pools' tree.  Steady-state
      ``step()`` performs zero dense re-packs and zero boundary host-syncs —
      layout state lives host-side in the PageTable and changes only on
      admit / page-crossing / demote / free events.  Requests submitted
      with a ``prefix_key`` share their common prompt prefix *physically*:
      full pages below the fork point map to the same refcounted physical
      pages (copy-on-write on the first divergent write), so N tenants with
      one system prompt hold its KV once.

Logits are bit-identical between the two: the pools hold the same values,
only their placement (and therefore fetch bandwidth) differs.
``sim_migration_bytes`` counts every byte the batcher moves device<->host
(benchmarks/bench_serve.py --shared-prefix gates shared < unshared).

Every ``step()`` is one tree of ``repro.obs`` spans (``sentinel.step`` over
``sentinel.admit``, ``sentinel.prefill``, ``sentinel.decode``,
``sentinel.forward``, ``sentinel.demote``, ``sentinel.sync`` and the page
manager's ``sentinel.stage_cold`` / ``sentinel.table``), and every finished
request one ``sentinel.request`` record under the id ``submit`` returned.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import kvcache, model


@dataclass
class ServeConfig:
    max_seq: int = 2048
    temperature: float = 0.0     # 0 = greedy
    eos_id: int = -1             # -1: never stop early


@dataclass
class _PrefillJob:
    """Per-slot admission state machine: ``queued -> prefilling(done_tokens)
    -> active``.  A job binds one queued request to its batch slot; the
    prefill scheduler (``_drain_prefill``) advances ``done`` chunk by chunk
    — across decode steps when ``prefill_chunk_tokens`` bounds the per-step
    budget, and across ``apply_plan`` re-plans (nothing in the job refers to
    the plan; boundaries are applied at finalize)."""
    tokens: Any                  # device prompt (S,)
    tok_host: tuple              # host mirror, cached once at submit()
    S: int                       # prompt length
    budget: int                  # decode tokens requested
    prefix_key: Any
    tenant: Any
    done: int = 0                # prompt tokens whose KV is materialized
    shared_pages: int = 0        # full pages mapped onto the donor (skipped)
    started: bool = False        # pages freed/shared, donor registered
    last: Any = None             # last computed row's logits (1, vocab)


def make_prefill(cfg):
    def prefill(params, batch):
        return model.prefill(params, cfg, batch)
    return jax.jit(prefill)


def make_serve_step(cfg):
    """(params, tokens(B,1[,K]), caches, index) -> (logits, caches)."""
    def step(params, tokens, caches, index):
        return model.decode_step(params, cfg, tokens, caches, index)
    return jax.jit(step, donate_argnums=(2,))


def make_pool_decode_step(cfg, page_tokens: int, garbage_page: int):
    """The decode step on the persistent page pools as one compiled program:
    ``(params, last_tok (B,), caches, lengths (B,), page_table, page_tier,
    active (B,)) -> (next_tok int32 (B,), new hot-pool tree)``.

    ``caches`` is ``PagedKVPools.forward_tree``: the hot pools, plus the
    cold pools staged into HBM when any page is cold (two variants of the
    program).  It is donated, so the hot pools are updated in place and the
    staged copies die with the call.  The page geometry is static; the
    greedy token is taken inside, so the logits never leave the program."""
    def step(params, last_tok, caches, lengths, page_table, page_tier,
             active):
        view = {"page_table": page_table, "page_tier": page_tier,
                "page_tokens": page_tokens, "active": active,
                "garbage_page": garbage_page}
        logits, new_caches, _ = model.forward(
            params, cfg, {"tokens": last_tok[:, None]}, caches=caches,
            cache_index=lengths, decode=True, paged_view=view)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)
        return tok.astype(jnp.int32), new_caches
    return jax.jit(step, donate_argnums=(2,))


def sample(logits, key, temperature: float):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


class ContinuousBatcher:
    """Slot-based continuous batching: a fixed B-slot decode batch; finished
    or empty slots are refilled from a request queue via per-slot prefill
    (cache splice), so decode throughput never waits for stragglers.

    All slots decode in lockstep against per-slot lengths (the flash-decode
    kernel and the jnp path both mask by `lengths`), which is the standard
    TPU-friendly formulation of continuous batching.
    """

    def __init__(self, params, cfg, batch_slots: int, max_seq: int,
                 scfg: Optional[ServeConfig] = None, plan=None,
                 slot_tenants=None,
                 prefill_chunk_tokens: Optional[int] = None, device=None,
                 pool_prefill: bool = True):
        self.params, self.cfg = params, cfg
        self.B, self.max_seq = batch_slots, max_seq
        self.scfg = scfg or ServeConfig(max_seq=max_seq)
        self.plan = plan
        # prefill scheduling: at most this many prompt tokens of pending
        # prefill run per step() before the decode dispatch (0 = unlimited,
        # i.e. every admit prefills in one shot like the legacy path).
        # Defaults from the plan so `runtime.plan(...)` can carry the knob.
        if prefill_chunk_tokens is None:
            prefill_chunk_tokens = getattr(plan, "prefill_chunk_tokens", 0) \
                if plan is not None else 0
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        # multi-tenant plans partition the batch slots: a request tagged with
        # a tenant is only admitted into that tenant's slots, so one bursty
        # tenant can never occupy the whole batch.  ``slot_tenants=`` lets an
        # un-planned (all-HBM) reference run replay the same admission
        # schedule, keeping logits comparable slot for slot.
        if slot_tenants is None and plan is not None:
            slot_tenants = getattr(plan, "slot_tenants", None)
        self.slot_tenants = list(slot_tenants) if slot_tenants else None
        if self.slot_tenants and len(self.slot_tenants) != batch_slots:
            # silent wrap-around would mis-assign tenant ownership — the
            # plan must have been built for this batch geometry
            raise ValueError(
                f"slot_tenants has {len(self.slot_tenants)} entries for "
                f"{batch_slots} batch slots (plan/batch geometry mismatch)")
        self.tenant_hot_peak: dict = {}        # tenant -> peak hot pool bytes
        self._tenant_note_version = -1         # last-sampled table version
        dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        dt_bytes = 2 if dt == jnp.bfloat16 else 4
        self._row_bytes = kvcache.kv_token_bytes(cfg, dt_bytes) \
            * cfg.num_layers                       # KV bytes per token, all layers
        self.sim_migration_bytes = 0.0             # device<->host cold traffic
        # per-decode-step deltas of sim_migration_bytes (admission + boundary
        # demotions attributed to the step that performed them): the engine's
        # replayed traffic series, priced by a CostModel and matched
        # integer-exactly by predict_pool_counters()["step_migration_bytes"].
        # The series is tracked against a persistent high-water marker, not a
        # per-step local, so bytes moved BETWEEN steps (apply_plan adopting a
        # re-plan) land in the next step's entry instead of vanishing —
        # sum(step_migration_bytes) == sim_migration_bytes always.
        self.step_migration_bytes: list = []
        self._mig_accounted = 0.0
        self.caches = self.ptable = self.pool = None
        self.device = device                       # pools' device (None: default)
        if plan is not None:
            # persistent pools: the page table owns physical placement,
            # decode writes through it (no dense mirror to re-pack)
            page = self._plan_page_tokens(plan)
            self.page_tokens = page
            self.pool = kvcache.PagedKVPools(cfg, batch_slots, max_seq, page,
                                             dt, device=device)
            self.ptable = self.pool.table
            self._decode_step = make_pool_decode_step(cfg, page,
                                                      self.pool.garbage)
        else:
            self.caches = kvcache.init_cache(cfg, batch_slots, max_seq, dt)
        self.lengths = jnp.zeros((batch_slots,), jnp.int32)
        self.active = [False] * batch_slots
        self.budget = [0] * batch_slots         # tokens left to generate
        self.last_tok = jnp.zeros((batch_slots,), jnp.int32)
        self.outputs = [[] for _ in range(batch_slots)]
        # per-tenant admission queues (None = untagged): _pop_for_slot walks
        # only the queues a slot may draw from, instead of the old
        # O(slots x queue) scan over one flat list.  ``_qseq`` stamps global
        # FIFO order so cross-tenant arrival order is preserved exactly.
        self._queues: dict = {}
        self._qseq = 0
        # slot -> [rid, submit, bind, first token (ns), prompt length]
        self._requests: dict = {}
        # host-side mirrors: per-slot lengths and the active set, kept in
        # lockstep with the device arrays so per-step bookkeeping (page
        # targets, boundary advances) never reads a device array back
        self._host_len = [0] * batch_slots
        self._active_mask = jnp.zeros((batch_slots,), bool)
        self._active_inc = jnp.zeros((batch_slots,), jnp.int32)
        self._prefix_donor: dict = {}          # prefix_key -> (slot, tokens)
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, cfg, b, max_seq=max_seq))
        # prefill scheduler state + counters
        self._jobs: dict = {}                  # slot -> _PrefillJob
        self._step_prefill = 0                 # prompt tokens run this step
        self.step_prefill_tokens: list = []    # per-step series (counters())
        self.prefill_compute_tokens = 0        # prompt rows actually run
        self.prefill_skipped_tokens = 0        # rows skipped via shared pages
        # pool-direct prefill (suffix compute straight into the physical
        # pages) needs every layer's cache to be a pool entry: stacks of
        # attention and MLA layers only.  Everything else keeps the legacy
        # dense one-shot prefill + admit_rows splice, as does
        # ``pool_prefill=False`` — the disaggregated engine's admission (on
        # a TPU the two prefills attend through different kernels and are
        # not bit-identical).
        kinds = tuple(cfg.prologue) + tuple(cfg.period)
        self._pool_prefill_ok = (
            pool_prefill and self.pool is not None
            and all(k in kvcache.POOL_KINDS for k in kinds)
            and not cfg.prefix_lm and not cfg.num_prefix_tokens
            and not cfg.num_codebooks)
        if self.prefill_chunk_tokens and not self._pool_prefill_ok:
            raise ValueError(
                "prefill_chunk_tokens requires pool-direct prefill: a plan "
                "(the persistent pools) with an attention / MLA stack, on a "
                "colocated engine (the DisaggregatedEngine prefills whole "
                "prompts on its prefill group)")

    def _plan_page_tokens(self, plan) -> int:
        """The engine's page size for a plan: the plan's ``page_tokens``
        fitted to ``max_seq`` and, on a TPU, to lane-dense cold pages
        (kvcache.pool_page_tokens)."""
        return kvcache.pool_page_tokens(self.cfg, plan.page_tokens,
                                        self.max_seq, self.device)

    def submit(self, tokens, num_tokens: int, prefix_key=None, tenant=None):
        """Queue a request.  ``prefix_key`` (hashable) marks requests that
        share a common prompt prefix (e.g. one system prompt per tenant):
        on the pools layout their common full pages map to the same physical
        pages, refcounted, with copy-on-write past the fork point.
        ``tenant`` restricts admission to the tenant's own slots when the
        plan carries ``slot_tenants`` (untagged requests admit anywhere).
        Returns the request's id, increasing in submission order: the
        ``rid`` of its ``sentinel.prefill`` spans and ``sentinel.request``
        record."""
        if tenant is not None and self.slot_tenants and \
                tenant not in self.slot_tenants:
            # an unknown tag would never match a slot: the request would sit
            # in the queue forever and run() would drop it silently
            raise ValueError(f"tenant {tenant!r} owns no batch slot "
                             f"(slot_tenants={self.slot_tenants})")
        # host mirror cached once here: admissions (LCP against the donor)
        # and the chunker never re-run jax.device_get on the prompt
        tok_host = tuple(int(t) for t in jax.device_get(tokens))
        rid = self._qseq
        self._queues.setdefault(tenant, deque()).append(
            (rid, tokens, tok_host, num_tokens, prefix_key, tenant,
             time.perf_counter_ns()))
        self._qseq += 1
        return rid

    @property
    def queue(self) -> list:
        """Pending requests in global FIFO order, as ``(tokens, num_tokens,
        prefix_key, tenant)`` — the legacy flat-queue view (tests drive
        ``while b.queue or any(b.active)``); admission itself walks the
        per-tenant deques directly."""
        items = sorted((it for q in self._queues.values() for it in q),
                       key=lambda it: it[0])
        return [(t, n, pk, tn) for _, t, _, n, pk, tn, _ in items]

    def _slot_tenant(self, slot: int):
        return self.slot_tenants[slot] if self.slot_tenants else None

    def _pop_for_slot(self, slot: int):
        """Pop the earliest-submitted request admissible to ``slot`` (FIFO
        within each tenant; untagged requests match any slot).  Only the
        slot's own tenant queue and the untagged queue are consulted — the
        per-tenant split replaces the old O(slots x queue) flat scan while
        preserving global FIFO order exactly."""
        tn = self._slot_tenant(slot)
        best_q = None
        for key in (self._queues.keys() if tn is None else (None, tn)):
            q = self._queues.get(key)
            if q and (best_q is None or q[0][0] < best_q[0][0]):
                best_q = q
        return best_q.popleft() if best_q else None

    def _note_tenant_pages(self):
        """Record each tenant's current hot-pool footprint (distinct
        physical hot pages across its slots — shared pages count once) and
        fold it into the per-tenant peak counters the SLO report reads.
        Event-driven like the rest of the pools bookkeeping: the footprint
        can only change when the page table mutates, so a steady-state step
        is a single version compare."""
        if not self.slot_tenants or self.ptable is None:
            return
        if self.ptable.version == self._tenant_note_version:
            return                         # no layout event since last sample
        self._tenant_note_version = self.ptable.version
        per: dict = {}
        for s in range(self.B):
            tn = self._slot_tenant(s)
            if tn is None:
                continue
            per.setdefault(tn, set()).update(
                self.ptable.table[s][i]
                for i in range(self.ptable.n_pages[s])
                if self.ptable.tier[s][i] == 0)
        page_bytes = self.page_tokens * self._row_bytes
        for tn, pages in per.items():
            v = len(pages) * page_bytes
            if v > self.tenant_hot_peak.get(tn, 0):
                self.tenant_hot_peak[tn] = v

    def _refresh_active(self):
        """Re-derive the cached device-side active mask (event-driven: only
        called when a slot starts or finishes, never per step)."""
        self._active_mask = jnp.asarray(self.active, bool)
        self._active_inc = jnp.asarray(
            [1 if a else 0 for a in self.active], jnp.int32)

    def _slot_cold_target(self, slot: int, seq_len: int) -> int:
        """Slot's cold boundary at ``seq_len`` tokens, in whole engine pages
        (the plan's page_tokens may have been adjusted to divide max_seq)."""
        return self.plan.cold_len_slot(slot, seq_len, self.page_tokens)

    def _admit_pool(self, slot: int, tok_host, fresh, S: int, prefix_key):
        """Admit into the persistent pools: free the slot's page refs, map
        shared-prefix full pages onto the donor's physical pages, allocate
        private pages for the rest, write the prefilled rows into them, and
        advance the cold boundary by per-page demotion.  Every operation is
        an incremental delta on the slot's own pages.  (Legacy dense-prefill
        splice — the pool-direct scheduler writes through attention instead;
        this path remains for the disaggregated engine and mixed stacks.)"""
        pg = self.page_tokens
        # only full pages strictly below the write region are shared, so the
        # page decode writes into is never a shared page
        shared_pages = self._share_prefix(slot, tok_host, prefix_key)
        n = -(-S // pg)
        self._alloc_admit_pages(slot, n)
        self.pool.admit_rows(fresh, slot, range(shared_pages, n))
        self.pool.splice_other(fresh, slot)
        # cold boundary: demote page by page toward the plan's target (shared
        # pages already cold, or deduped through a twin, move zero bytes)
        with obs.span("sentinel.demote") as attrs:
            attrs["pages"] = self._demote_to(
                slot, self._slot_cold_target(slot, S))

    def _share_prefix(self, slot: int, tok_host, prefix_key,
                      cap: Optional[int] = None, pool=None) -> int:
        """The head of every pools admission into ``pool`` (default: the
        engine's own): free the slot's old pages (and the donor
        registrations that named them), map the full pages of the prompt's
        common prefix with the ``prefix_key``'s donor onto the donor's
        physical pages, and register this prompt as the new donor.  The
        shared pages are capped by the pages the donor holds in ``pool``
        and by ``cap`` (None: no further cap).  Returns the pages shared."""
        pool = pool or self.pool
        for key in [k for k, (s, _) in self._prefix_donor.items()
                    if s == slot]:
            del self._prefix_donor[key]
        pool.free_slot(slot)
        shared = 0
        if prefix_key is not None:
            donor = self._prefix_donor.get(prefix_key)
            if donor is not None and donor[0] != slot and \
                    pool.table.n_pages[donor[0]] > 0:
                lcp = 0
                for a, b in zip(tok_host, donor[1]):
                    if a != b:
                        break
                    lcp += 1
                shared = min(lcp // self.page_tokens,
                             pool.table.n_pages[donor[0]])
                if cap is not None:
                    shared = min(shared, cap)
                if shared:
                    pool.share(slot, donor[0], shared)
            self._prefix_donor[prefix_key] = (slot, tok_host)
        return shared

    def _demote_to(self, slot: int, target: int, pool=None) -> int:
        """Advance ``slot``'s cold boundary page by page to ``target``
        tokens in ``pool`` (default: the engine's own).  Returns the pages
        whose data moved; twin-deduped shared pages move none."""
        pool = pool or self.pool
        copied = 0
        while pool.table.cold_tokens(slot) < target:
            if pool.demote_boundary(slot):
                copied += 1
                self.sim_migration_bytes += self.page_tokens * self._row_bytes
        return copied

    def _alloc_admit_pages(self, slot: int, n: int) -> None:
        """Grow ``slot`` to ``n`` hot pages for an admission.  The seam the
        disaggregated engine overrides: there the pages are staged on the
        prefill device's table and cross the device↔device edge as a
        ``MeshPageTable.migrate_slot`` transition instead of being allocated
        in place."""
        for _ in range(self.ptable.n_pages[slot], n):
            self.ptable.alloc(slot, 0)

    def _admit(self):
        """Bind queued requests to free slots and advance pending prefill.

        Pool-direct stacks go through the admission state machine: binding
        creates a ``_PrefillJob`` (``queued -> prefilling``) and
        ``_drain_prefill`` runs page-aligned chunks up to the per-step
        budget; a slot flips ``-> active`` only when its whole prompt's KV
        is materialized.  Other stacks, and the dense reference, admit
        one-shot (``_admit_dense``)."""
        for slot in range(self.B):
            if self.active[slot] or slot in self._jobs:
                continue
            item = self._pop_for_slot(slot)
            if item is None:
                continue                   # no queued request for this tenant
            rid, tokens, tok_host, budget, prefix_key, tenant, t_sub = item
            S = int(tokens.shape[-1])
            self._requests[slot] = [rid, t_sub, time.perf_counter_ns(), None,
                                    S]
            if self._pool_prefill_ok:
                self._jobs[slot] = _PrefillJob(tokens, tok_host, S, budget,
                                               prefix_key, tenant)
            else:
                self._admit_dense(slot, tokens, tok_host, S, budget,
                                  prefix_key)
        if self._jobs:
            self._drain_prefill()

    def _admit_dense(self, slot: int, tokens, tok_host, S: int, budget: int,
                     prefix_key):
        """One-shot admission: dense full-prompt prefill, then its rows
        spliced into the pools or the dense cache (async dispatch: overlaps
        with in-flight decode work)."""
        with obs.span("sentinel.prefill", rid=self._requests[slot][0],
                      slot=slot, start=0, end=S):
            last, fresh = self._prefill(self.params,
                                        {"tokens": tokens[None]})
            if self.pool is not None:
                self._admit_pool(slot, tok_host, fresh, S, prefix_key)
            else:
                self.caches = kvcache.splice_slot(self.caches, fresh, slot,
                                                  self.B)
        self.prefill_compute_tokens += S
        self._step_prefill += S
        self._activate(slot, S, last, budget)

    def _activate(self, slot: int, S: int, last, budget: int):
        """Common tail of every admission: slot state flips to active with
        the prompt's last-row logits decoding its first token."""
        self.lengths = self.lengths.at[slot].set(S)
        self._host_len[slot] = S
        self.last_tok = self.last_tok.at[slot].set(
            jnp.argmax(last[0, :self.cfg.vocab_size]).astype(jnp.int32))
        self.active[slot] = True
        self.budget[slot] = budget
        self.outputs[slot] = [int(self.last_tok[slot])]
        self._requests[slot][3] = time.perf_counter_ns()
        self.budget[slot] -= 1
        self._refresh_active()
        self._note_tenant_pages()

    def _drain_prefill(self):
        """Run pending prefill jobs, at most ``prefill_chunk_tokens`` prompt
        tokens this step (0 = no budget: each job completes in one shot).
        Jobs drain in slot order; chunk ends are page-aligned except a final
        partial page, and the budget may overdraw by less than one page so a
        tiny budget still guarantees progress."""
        pg = self.page_tokens
        budget = self.prefill_chunk_tokens
        spent = 0
        for slot in sorted(self._jobs):
            job = self._jobs[slot]
            while slot in self._jobs:
                if budget and spent >= budget:
                    return                 # budget exhausted: resume next step
                if not job.started:
                    self._start_job(slot, job)
                pages_left = -(-(job.S - job.done) // pg)
                take = pages_left if not budget else \
                    min(pages_left, max(1, (budget - spent) // pg))
                end = min(job.S, job.done + take * pg)
                spent += end - job.done
                self._run_chunk(slot, job, end)
                if job.done >= job.S:
                    self._finish_job(slot, job)

    def _start_job(self, slot: int, job: _PrefillJob):
        """First touch of a job: free the slot's old pages, map shared-
        prefix full pages onto the donor's physical pages (the *compute
        skip*: those rows' KV is already materialized, so prefill starts at
        ``done = shared_pages * page_tokens``), and register this prompt as
        the new donor."""
        pg = self.page_tokens
        # capped three ways: full pages of common prefix, pages the donor
        # actually holds (a mid-prefill donor's pages are valid only up to
        # its own ``done``), and strictly below the prompt's last token —
        # the suffix pass must compute at least one row (the next-token
        # logits), and a shared page is never written
        shared = self._share_prefix(slot, job.tok_host, job.prefix_key,
                                    cap=(job.S - 1) // pg)
        job.shared_pages = shared
        job.done = shared * pg
        job.started = True
        self.prefill_skipped_tokens += job.done

    def _run_chunk(self, slot: int, job: _PrefillJob, end: int):
        """One page-aligned prefill chunk ``tokens[done:end]`` straight into
        the slot's physical pages (model.prefill_suffix with this slot's
        page-table row): attention writes the chunk's KV through the table
        and each row attends back over the donor pages + earlier chunks, so
        the rows are bit-identical to the same rows of a one-shot dense
        prefill."""
        pg = self.page_tokens
        with obs.span("sentinel.prefill", rid=self._requests[slot][0],
                      slot=slot, start=job.done, end=end):
            first = self.ptable.n_pages[slot]
            n = -(-end // pg)
            self._alloc_admit_pages(slot, n)
            self.pool.stats["admit_page_writes"] += n - first
            table, tier = self.pool.arrays()
            view = {"page_table": table[slot][None],
                    "page_tier": tier[slot][None], "page_tokens": pg,
                    "active": None, "prefill": True}
            # the chunk reads this slot's row only: stage the cold tier iff
            # the row maps a cold page (a shared prefix the donor demoted)
            caches = self.pool.forward_tree(
                stage=self.ptable.cold_pages(slot) > 0)
            job.last, new_tree = model.prefill_suffix(
                self.params, self.cfg,
                {"tokens": job.tokens[job.done:end][None]},
                caches=caches, start=job.done, paged_view=view)
            self.pool.adopt(new_tree)
        self.prefill_compute_tokens += end - job.done
        self._step_prefill += end - job.done
        job.done = end

    def _finish_job(self, slot: int, job: _PrefillJob):
        """Prompt fully materialized: advance the cold boundary to the
        *current* plan's target (re-plans adopted mid-prefill land here) and
        flip the slot active."""
        del self._jobs[slot]
        with obs.span("sentinel.demote") as attrs:
            attrs["pages"] = self._demote_to(
                slot, self._slot_cold_target(slot, job.S))
        self._activate(slot, job.S, job.last, job.budget)

    def _pool_decode_step(self):
        """One decode forward on the persistent-pools layout: write-page
        guarantee, the batched forward through the page-table view, and the
        post-step cold-boundary advance.  Returns the decoded tokens (B,)
        int32.  The seam the disaggregated engine overrides to run one
        sub-batch forward per decode shard against that shard's own pools."""
        # pre-step page guarantee per active slot: the write page exists
        # and is private (CoW fires here on the first divergent write
        # past a shared-prefix fork point — a no-op otherwise)
        for s in range(self.B):
            if self.active[s]:
                self.pool.ensure_write_page(s, self._host_len[s])
        table, tier = self.pool.arrays()
        caches = self.pool.forward_tree(stage=self.pool.has_cold())
        with obs.span("sentinel.forward"):
            tok, new_caches = self._decode_step(
                self.params, self.last_tok, caches, self.lengths, table, tier,
                self._active_mask)
        self.pool.adopt(new_caches)
        # advance each grown slot's own cold boundary by whole pages;
        # twin-deduped shared pages advance the boundary with zero copy
        with obs.span("sentinel.demote") as attrs:
            attrs["pages"] = sum(
                self._demote_to(s, self._slot_cold_target(
                    s, self._host_len[s] + 1))
                for s in range(self.B) if self.active[s])
        self._note_tenant_pages()
        return tok

    def step(self):
        """One lockstep decode step across all active slots — each slot writes
        its KV at its own length (vector cache_index -> row-wise scatter).

        On the pools layout the steady-state body is re-pack-free and
        host-sync-free: the caches handed to the model ARE the persistent
        pools, the page-table arrays are cached until the table mutates, and
        all boundary/length bookkeeping runs on host-side mirrors.  Layout
        work happens only at events (admit, a slot growing into a new page,
        a boundary advance).  The step is one ``sentinel.step`` span."""
        with obs.span("sentinel.step",
                      step=len(self.step_prefill_tokens)) as attrs:
            return self._step(attrs)

    def _step(self, attrs: dict):
        self._step_prefill = 0
        with obs.span("sentinel.admit"):
            self._admit()
        attrs.update(active=sum(self.active),
                     prefill_tokens=self._step_prefill)
        if not any(self.active):
            if self._jobs:
                # prefill-only step: the chunk budget ran but no slot is
                # ready to decode yet — still a step for accounting (the
                # migration/prefill series stay aligned with real steps)
                self.step_prefill_tokens.append(self._step_prefill)
                self.step_migration_bytes.append(
                    self.sim_migration_bytes - self._mig_accounted)
                self._mig_accounted = self.sim_migration_bytes
                return True
            return False
        with obs.span("sentinel.decode"):
            tok = self._pool_decode_step() if self.pool is not None \
                else self._dense_decode_step()
        self.last_tok = tok
        self.lengths = self.lengths + self._active_inc
        with obs.span("sentinel.sync"):
            tok_host = jax.device_get(tok)     # the decoded tokens themselves
        was_active = list(self.active)
        for slot in range(self.B):
            if not was_active[slot]:
                continue
            self._host_len[slot] += 1
            self.outputs[slot].append(int(tok_host[slot]))
            self.budget[slot] -= 1
            if self.budget[slot] <= 0 or \
                    int(tok_host[slot]) == self.scfg.eos_id:
                self.active[slot] = False
                self._finish_request(slot)
        if self.active != was_active:
            self._refresh_active()
        self.step_prefill_tokens.append(self._step_prefill)
        self.step_migration_bytes.append(
            self.sim_migration_bytes - self._mig_accounted)
        self._mig_accounted = self.sim_migration_bytes
        return True

    def _finish_request(self, slot: int):
        """The slot's request delivered its last token: one
        ``sentinel.request`` record from submission to now."""
        rid, t_submit, t_bind, t_first, S = self._requests.pop(slot)
        obs.add("sentinel.request", t_submit, time.perf_counter_ns(),
                rid=rid, slot=slot, bind_ns=t_bind, first_token_ns=t_first,
                prompt_tokens=S)

    def _dense_decode_step(self):
        """One decode forward on the dense no-plan cache: the all-HBM
        reference the pools are held to.  Returns the decoded tokens."""
        with obs.span("sentinel.forward"):
            logits, self.caches, _ = model.forward(
                self.params, self.cfg, {"tokens": self.last_tok[:, None]},
                caches=self.caches, cache_index=self.lengths, decode=True)
        return jnp.argmax(logits[:, -1, :self.cfg.vocab_size], axis=-1) \
            .astype(jnp.int32)

    def apply_plan(self, new_plan):
        """Adopt a re-plan (or an incremental ``runtime.PlanDelta``) on the
        live pools layout, between decode steps.

        The online replanner (``runtime/online.py``) emits deltas; applying
        one here re-targets every active slot's cold boundary under the new
        plan's hot windows through the page-table version machinery — page-
        grain demotions, refcount-aware, zero copies for twin-deduped shared
        pages — and re-partitions slot tenancy for subsequent admissions.
        Grown windows cost nothing (cold pages are never promoted back).
        In-flight prefill jobs are unaffected and resume under the new plan
        (their cold boundary is applied at finalize, from the plan current
        *then*).  Returns the migration bytes moved; they are attributed to
        the *next*
        decode step's ``step_migration_bytes`` entry, exactly as
        ``predict_pool_counters(..., plan_schedule=...)`` replays it."""
        if self.pool is None:
            raise ValueError("apply_plan requires a plan: the dense "
                             "reference (plan=None) has no pools to re-tier")
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
            attrs["pages"] = sum(
                self._demote_to(s, self._slot_cold_target(
                    s, self._host_len[s]))
                for s in range(self.B) if self.active[s])
        # tenancy may have moved without a table event — force a resample
        self._tenant_note_version = -1
        self._note_tenant_pages()
        return self.sim_migration_bytes - mig0

    def counters(self) -> dict:
        """The live counter export the online replanner profiles: the
        migration totals/series priced by the ``CostModel``, per-tenant hot-
        pool peaks, the pools' event counters, and the page-table layout
        version — the same shape ``predict_pool_counters`` predicts."""
        out = {"sim_migration_bytes": self.sim_migration_bytes,
               "step_migration_bytes": list(self.step_migration_bytes),
               "tenant_hot_peak": dict(self.tenant_hot_peak),
               "table_version": self.ptable.version if self.ptable else 0,
               "prefill_compute_tokens": self.prefill_compute_tokens,
               "prefill_skipped_tokens": self.prefill_skipped_tokens,
               # bytes of shared KV the skipped rows attend back into —
               # the StepTraffic.prefill_read term the cost model prices
               "prefill_read_bytes":
                   self.prefill_skipped_tokens * self._row_bytes,
               "step_prefill_tokens": list(self.step_prefill_tokens)}
        if self.pool is not None:
            out.update(self.pool.stats)
        return out

    def run(self):
        results = []
        while self.queue or self._jobs or any(self.active):
            if not self.step():
                break
            for i in range(self.B):
                if not self.active[i] and self.outputs[i]:
                    results.append(self.outputs[i])
                    self.outputs[i] = []
        return results


def predict_pool_counters(requests: Sequence[tuple], plan, *, slots: int,
                          max_seq: int, page_tokens: int, row_bytes: float,
                          slot_tenants=None,
                          plan_schedule: Sequence[tuple] = (),
                          prefill_chunk_tokens: int = 0,
                          dense_admit: bool = False,
                          slot_devices=None) -> dict:
    """Pure-Python replay of the pools-layout batcher's bookkeeping: given
    the request stream ``[(prompt, decode_tokens[, tenant[, prefix_key]]),
    ...]`` and a plan, predict ``sim_migration_bytes`` (total and the
    per-decode-step ``step_migration_bytes`` series a CostModel prices),
    the pool's ``page_copies`` / ``admit_page_writes`` counters, and the
    per-tenant hot-pool byte peaks
    — *exactly* (integer-for-integer) what a ``ContinuousBatcher`` with
    that plan will report
    on the same deterministic stream.  This is the engine/simulator
    agreement contract: the simulator predicts, the engine counts, the two
    never drift (``tests/test_multi_tenant.py`` pins it).

    ``prompt`` is either the prompt token *count* or the prompt token
    *sequence*; requests carrying a ``prefix_key`` must pass the sequence —
    the replay mirrors the engine's donor registry (LCP against the last
    prompt registered under the key, full pages mapped onto the donor's
    physical pages, refcounted, cold twins deduping shared demotions), so
    ``admit_page_writes`` / ``xdev_migration_bytes`` count only the private
    tail and stay integer-exact for shared-prefix admits.  ``dense_admit``
    replays the one-shot dense admission path (the disaggregated engine's
    ``_admit_pool``), whose shared-page cap differs from the pool-direct
    prefill scheduler's by the final-row carve-out.

    ``slot_devices`` (defaulting to the plan's) splits the replay across
    decode shards: sharing is intra-shard only, ``device_hot_peak`` tracks
    each shard's distinct-hot-page byte peak, and ``edge_migration_bytes``
    ledgers every ``(src, dst)`` device edge — prefill->shard admit streams
    and shard->shard slot re-homings (a ``plan_schedule`` entry whose plan
    moves an active slot's owner) — integer-exactly as the engine's
    ``MeshPageTable`` counts them.

    The replay mirrors the engine's event order: per step, binding of queued
    requests to free slots (FIFO within each tenant), the prefill drain
    (page-aligned chunks in slot order, at most ``prefill_chunk_tokens``
    prompt tokens per step — 0 replays the legacy one-shot admission),
    write-page growth for every active slot, then per-slot cold-boundary
    demotions toward the plan's target; peaks are sampled after each
    admission finalize and after each step's demotions, the same points the
    engine samples.  Steps in which only prefill ran (budget exhausted
    before any slot went active) still append a ``step_migration_bytes``
    entry, exactly as ``ContinuousBatcher.step()`` does.

    ``plan_schedule`` makes the replay *segment-aware* for online
    re-planning: ``[(step, new_plan_or_delta), ...]`` means "the engine
    called ``apply_plan`` before decode step ``step``".  The replay switches
    plans at exactly that point — re-targeting active slots' cold boundaries
    and re-partitioning slot tenancy — and, like the engine's marker-based
    accounting, attributes the re-layout bytes to that step's
    ``step_migration_bytes`` entry, so the two stay integer-identical
    across a re-plan boundary (sum of the series == the total on both
    sides)."""
    pg = page_tokens
    if slot_tenants is None and plan is not None:
        slot_tenants = getattr(plan, "slot_tenants", None)
    if slot_tenants and len(slot_tenants) != slots:
        raise ValueError(f"slot_tenants has {len(slot_tenants)} entries for "
                         f"{slots} slots (plan/batch geometry mismatch)")
    if slot_devices is None and plan is not None:
        slot_devices = getattr(plan, "slot_devices", None)
    if slot_devices:
        slot_devices = list(slot_devices)
        if len(slot_devices) != slots:
            raise ValueError(f"slot_devices has {len(slot_devices)} entries "
                             f"for {slots} slots")

    def parse(r):
        p = r[0]
        if isinstance(p, (list, tuple)):
            toks = tuple(int(t) for t in p)
            plen = len(toks)
        else:
            plen, toks = int(p), None
        pk = r[3] if len(r) > 3 else None
        if pk is not None and toks is None:
            raise ValueError("a prefix_key needs the prompt's token values "
                             "(pass the token sequence, not its length): "
                             "the replay LCPs them against the donor")
        return (plen, int(r[1]), r[2] if len(r) > 2 else None, toks, pk)

    queue = [parse(r) for r in requests]
    active = [False] * slots
    host_len = [0] * slots
    budget = [0] * slots
    # physical-page model, mirroring PageTable: per-slot phys ids + tiers
    # (cold-prefix), refcounts, and the cold-twin memo that dedupes shared
    # demotions — without prefix sharing it degenerates to the old counters
    ptab: list = [[] for _ in range(slots)]
    ptier: list = [[] for _ in range(slots)]
    hot_ref: dict = {}
    cold_ref: dict = {}
    cold_twin: dict = {}                   # hot phys -> its live cold twin
    twin_of: dict = {}
    donors: dict = {}                      # prefix_key -> (slot, tokens)
    next_phys = [0]
    mig = 0.0
    copies = admit_writes = 0
    peaks: dict = {}
    dev_peaks: dict = {}
    edge_bytes: dict = {}
    step_mig: list = []

    def slot_tn(s):
        return slot_tenants[s] if slot_tenants else None

    def dev(s):
        return slot_devices[s] if slot_devices else 0

    def dev_name(d):
        return f"dev{d}" if slot_devices else "decode"

    def fresh():
        next_phys[0] += 1
        return next_phys[0]

    def release(tier, phys):
        refs = cold_ref if tier else hot_ref
        refs[phys] -= 1
        if refs[phys] == 0:                # PageTable._release: memo death
            if tier == 0:
                twin = cold_twin.pop(phys, None)
                if twin is not None:
                    twin_of.pop(twin, None)
            else:
                src = twin_of.pop(phys, None)
                if src is not None:
                    cold_twin.pop(src, None)

    def free_slot(s):
        for t, p in zip(ptier[s], ptab[s]):
            release(t, p)
        ptab[s], ptier[s] = [], []

    def alloc(s):
        p = fresh()
        hot_ref[p] = 1
        ptab[s].append(p)
        ptier[s].append(0)

    def share(s, donor_slot, n):
        for i in range(n):
            p, t = ptab[donor_slot][i], ptier[donor_slot][i]
            (cold_ref if t else hot_ref)[p] += 1
            ptab[s].append(p)
            ptier[s].append(t)

    def cold_pages(s):
        c = 0
        for t in ptier[s]:
            if t != 1:
                break
            c += 1
        return c

    def note():
        per_t: dict = {}
        per_d: dict = {}
        for s in range(slots):
            hot = {p for p, t in zip(ptab[s], ptier[s]) if t == 0}
            per_d.setdefault(dev(s), set()).update(hot)
            tn = slot_tn(s)
            if tn is not None:
                per_t.setdefault(tn, set()).update(hot)
        for tn, pages in per_t.items():
            v = len(pages) * pg * row_bytes
            if v > peaks.get(tn, 0):
                peaks[tn] = v
        for d, pages in per_d.items():
            v = len(pages) * pg * row_bytes
            if v > dev_peaks.get(dev_name(d), 0):
                dev_peaks[dev_name(d)] = v

    def demote_one(s):
        # PageTable.demote: first sharer copies and memoizes a cold twin,
        # later sharers reuse it — shared bytes migrate exactly once
        nonlocal mig, copies
        idx = cold_pages(s)
        src = ptab[s][idx]
        twin = cold_twin.get(src)
        if twin is not None and cold_ref.get(twin, 0) > 0:
            cold_ref[twin] += 1
            cold_phys, copied = twin, False
        else:
            cold_phys = fresh()
            cold_ref[cold_phys] = 1
            copied = True
            if hot_ref[src] > 1:           # others still share: memoize
                cold_twin[src] = cold_phys
                twin_of[cold_phys] = src
        release(0, src)
        ptab[s][idx] = cold_phys
        ptier[s][idx] = 1
        if copied:
            mig += pg * row_bytes
            copies += 1

    def demote_to(s, target):
        while cold_pages(s) * pg < target:
            demote_one(s)

    def start_slot(s, prompt_len, toks, pk):
        # _start_job / _admit_pool head: stale donor registrations for the
        # slot die with its pages, then prefix-share against the donor —
        # intra-shard only (MeshPageTable refuses cross-device aliasing)
        for key in [k for k, (ds, _) in donors.items() if ds == s]:
            del donors[key]
        free_slot(s)
        shared = 0
        if pk is not None:
            donor = donors.get(pk)
            if donor is not None and donor[0] != s and ptab[donor[0]] \
                    and dev(donor[0]) == dev(s):
                lcp = 0
                for a, b in zip(toks, donor[1]):
                    if a != b:
                        break
                    lcp += 1
                cap = lcp // pg
                if not dense_admit:        # the suffix pass computes >= 1 row
                    cap = min(cap, (prompt_len - 1) // pg)
                shared = min(cap, len(ptab[donor[0]]))
                if shared:
                    share(s, donor[0], shared)
            donors[pk] = (s, toks)
        return shared

    schedule = sorted(((int(t), p) for t, p in plan_schedule),
                      key=lambda e: e[0])
    # slot -> [done, prompt, decode, started, tokens, prefix_key]
    jobs: dict = {}
    while queue or jobs or any(active):
        mig0 = mig
        while schedule and schedule[0][0] <= len(step_mig):
            _, nxt = schedule.pop(0)       # ContinuousBatcher.apply_plan
            if hasattr(nxt, "changes"):    # a PlanDelta, not a plan
                nxt = plan.apply_delta(nxt)
            plan = nxt
            tenants = getattr(plan, "slot_tenants", None)
            if tenants:
                if len(tenants) != slots:
                    raise ValueError(
                        f"slot_tenants has {len(tenants)} entries for "
                        f"{slots} slots (plan/batch geometry mismatch)")
                slot_tenants = list(tenants)
            for s in range(slots):
                if active[s]:
                    demote_to(s, plan.cold_len_slot(s, host_len[s], pg))
            new_sd = getattr(plan, "slot_devices", None)
            if new_sd and slot_devices and list(new_sd) != slot_devices:
                # slot re-homing: the demoted-first hot tail crosses the
                # shard<->shard edge (MeshPageTable.migrate_slot; cold pages
                # move host-internally and never touch a device edge)
                if len(new_sd) != slots:
                    raise ValueError(
                        f"slot_devices has {len(new_sd)} entries for "
                        f"{slots} slots")
                for s in range(slots):
                    if new_sd[s] == slot_devices[s]:
                        continue
                    if active[s]:
                        hot = sum(1 for t in ptier[s] if t == 0)
                        key = (dev_name(slot_devices[s]),
                               dev_name(new_sd[s]))
                        edge_bytes[key] = edge_bytes.get(key, 0.0) \
                            + hot * pg * row_bytes
                        # migrate_slot lands *exclusive* pages on the
                        # destination and releases the source refs (any
                        # remaining sharers keep the source pages, twin
                        # memos die with the refs)
                        moved = []
                        for t_, p in zip(ptier[s], ptab[s]):
                            release(t_, p)
                            p2 = fresh()
                            (cold_ref if t_ else hot_ref)[p2] = 1
                            moved.append(p2)
                        ptab[s] = moved
                    elif s not in jobs and ptab[s]:
                        # a finished slot's stale pages are dropped on
                        # ownership change, not copied across the edge
                        free_slot(s)
                slot_devices = list(new_sd)
            note()
        for s in range(slots):             # ContinuousBatcher._admit: bind
            if active[s] or s in jobs or not queue:
                continue
            tn_s = slot_tn(s)
            qi = next((i for i, q in enumerate(queue)
                       if tn_s is None or q[2] is None or q[2] == tn_s),
                      None)
            if qi is None:
                continue
            p, d, _, toks, pk = queue.pop(qi)
            jobs[s] = [0, p, d, False, toks, pk]   # queued -> prefilling(0)
        spent = 0                          # _drain_prefill: slot order,
        stop = False                       # page-aligned chunks, one budget
        for s in sorted(jobs):
            if stop:
                break
            job = jobs[s]
            while s in jobs:
                if prefill_chunk_tokens and spent >= prefill_chunk_tokens:
                    stop = True            # resume next step, all slots
                    break
                if not job[3]:             # _start_job: free + prefix share
                    job[0] = start_slot(s, job[1], job[4], job[5]) * pg
                    job[3] = True
                done, p = job[0], job[1]
                pages_left = -(-(p - done) // pg)
                take = pages_left if not prefill_chunk_tokens else \
                    min(pages_left,
                        max(1, (prefill_chunk_tokens - spent) // pg))
                end = min(p, done + take * pg)
                spent += end - done
                new = -(-end // pg) - len(ptab[s])
                if new:
                    admit_writes += new
                    key = ("prefill", dev_name(dev(s)))
                    edge_bytes[key] = edge_bytes.get(key, 0.0) \
                        + new * pg * row_bytes
                    for _ in range(new):
                        alloc(s)
                job[0] = end
                if end >= p:               # _finish_job -> active
                    del jobs[s]
                    demote_to(s, plan.cold_len_slot(s, p, pg))
                    host_len[s], active[s], budget[s] = p, True, job[2] - 1
                    note()
        if not any(active):
            if jobs:
                step_mig.append(mig - mig0)  # prefill-only step
                continue
            break
        for s in range(slots):             # pool.ensure_write_page
            if active[s] and len(ptab[s]) * pg < host_len[s] + 1:
                alloc(s)
        for s in range(slots):             # post-forward boundary advance
            if active[s]:
                demote_to(s, plan.cold_len_slot(s, host_len[s] + 1, pg))
        note()
        for s in range(slots):
            if active[s]:
                host_len[s] += 1
                budget[s] -= 1
                if budget[s] <= 0:
                    active[s] = False
        step_mig.append(mig - mig0)        # one engine decode step's delta
    # xdev_migration_bytes: the planner's predicted device<->device edge
    # traffic under prefill/decode disaggregation — every *private* admitted
    # page is prefilled on the prefill group and crosses the edge exactly
    # once; shared-prefix pages stay put on the decode side and never cross
    # (serve/disagg.py's MeshPageTable ledger matches it integer-exactly)
    return {"migration_bytes": mig, "page_copies": copies,
            "admit_page_writes": admit_writes, "tenant_hot_peak": peaks,
            "step_migration_bytes": step_mig,
            "device_hot_peak": dev_peaks,
            "edge_migration_bytes": edge_bytes,
            "xdev_migration_bytes": admit_writes * pg * row_bytes}


def serve_trace_for(cfg, requests: Sequence[tuple], *, slots: int,
                    params=None, block_tokens: int = 16,
                    recent_window: int = 32, history_period: int = 4,
                    dtype_bytes: int = 2, layer_group: int = 1,
                    shared_prefix_tokens: int = 0):
    """Build the serving-phase trace (hmsim.ServeTrace) for this model and
    request stream — the profiling step of the decode-phase planner.  KV
    bytes/token come from the cache geometry; weight bytes and flops/token
    from the parameter count (2N MACs/token) when ``params`` is given, else
    from the config's dense-layer dimensions.  ``layer_group`` coarsens the
    object granularity to one KV block per *group* of layers (same total
    bytes, fewer objects) — the simulator cost scales with object count while
    the byte geometry is what decides placement quality.

    Requests may be ``(prompt, decode)`` or ``(prompt, decode, prefix_id)``;
    with ``shared_prefix_tokens > 0``, requests carrying the same prefix_id
    share the KV blocks of their first ``shared_prefix_tokens`` prompt
    tokens (tagged via ``KVObject.shared_key`` — the trace-level mirror of
    the engine's physical page sharing)."""
    from repro.core import hmsim
    kv_tok = kvcache.kv_token_bytes(cfg, dtype_bytes)
    layers = max(1, -(-cfg.num_layers // max(1, layer_group)))
    if params is not None:
        n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    else:
        n_params = (12 * cfg.num_layers * cfg.d_model ** 2
                    + cfg.vocab_size * cfg.d_model)
    return hmsim.build_serve_trace(
        requests, num_slots=slots, num_layers=layers,
        kv_token_bytes=kv_tok * cfg.num_layers / layers,
        block_tokens=block_tokens,
        recent_window=recent_window, history_period=history_period,
        flops_per_token=2.0 * n_params,
        weight_bytes=float(n_params) * dtype_bytes,
        shared_prefix_tokens=shared_prefix_tokens)


def generate(params, cfg, prompts, num_tokens: int,
             scfg: Optional[ServeConfig] = None, key=None):
    """prompts: {"tokens": (B, S)[, "prefix_embed"]}. Returns (B, num_tokens)."""
    scfg = scfg or ServeConfig()
    key = key if key is not None else jax.random.PRNGKey(0)
    B = prompts["tokens"].shape[0]
    S = prompts["tokens"].shape[1] + (cfg.num_prefix_tokens
                                      if "prefix_embed" in prompts else 0)
    max_seq = max(scfg.max_seq, S + num_tokens)

    last_logits, caches = model.prefill(params, cfg, prompts, max_seq=max_seq)
    step_fn = make_serve_step(cfg)

    outs = []
    if cfg.num_codebooks:
        last_logits = last_logits.reshape(B, cfg.num_codebooks, -1)
    tok = sample(last_logits[..., :cfg.vocab_size], key, scfg.temperature)
    for i in range(num_tokens):
        outs.append(tok)
        feed = tok[:, None] if not cfg.num_codebooks else tok[:, None, :]
        logits, caches = step_fn(params, feed.astype(jnp.int32), caches,
                                 jnp.asarray(S + i, jnp.int32))
        key, sub = jax.random.split(key)
        if cfg.num_codebooks:
            logits = logits.reshape(B, cfg.num_codebooks, -1)
        tok = sample(logits[..., :cfg.vocab_size], sub, scfg.temperature)
    return jnp.stack(outs, axis=1)
