"""Cache construction for serving: per-layer-kind cache buffers, stacked over
periods to match the scanned layer stack.

Tier-aware storage (Sentinel-Serve): ``PagedKVPools`` splits each slot's KV
sequence, at page granularity, into a *cold prefix* (old tokens, host/slow
memory) and a *hot window* (recent tokens, HBM/fast memory), per the
decode-phase ``ServePlan``.  The cold tier lives in host memory —
``pinned_host`` on TPU, and on the CPU backend too, which exposes it beside
its ``device`` memory — so every op that reads a cold array next to device
arrays states its transfer (``to_device`` / ``PagedKVPools.stage_cold``) and
every write lands back in host memory explicitly (``to_host`` /
``cold_page_put``).
"""
from __future__ import annotations

import functools
import heapq
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ATTN, LOCAL, LSTM, MAMBA, MLA, MLSTM, SHARED_ATTN, SLSTM
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod


def init_layer_cache(cfg, kind: str, batch: int, max_seq: int, dtype):
    if kind in (ATTN, LOCAL, SHARED_ATTN):
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        # KV heads folded into one dim so odd head counts (5, 15...) still
        # shard over the model axis. Sliding-window layers only ever read the
        # last `window` entries, but we keep the full buffer for uniform
        # indexing (baseline; see §Perf for the windowed-cache optimization).
        return {"k": jnp.zeros((batch, max_seq, KV * hd), dtype),
                "v": jnp.zeros((batch, max_seq, KV * hd), dtype)}
    if kind == MLA:
        return {"ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype)}
    if kind == MAMBA:
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    if kind == MLSTM:
        return xlstm_mod.init_mlstm_cache(cfg, batch, dtype)
    if kind == SLSTM:
        return xlstm_mod.init_slstm_cache(cfg, batch, dtype)
    if kind == LSTM:
        return xlstm_mod.init_lstm_cache(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Cache pytree matching stack_forward's expectations: prologue caches are
    per-layer; slot caches carry a leading (num_periods,) dim."""
    pro = [init_layer_cache(cfg, kind, batch, max_seq, dtype)
           for kind in cfg.prologue]

    def stacked(kind):
        one = init_layer_cache(cfg, kind, batch, max_seq, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.num_periods,) + a.shape).copy()
            if cfg.num_periods > 1 else a[None], one)

    return {"prologue": pro, "slots": [stacked(k) for k in cfg.period]}


# ------------------------------------------------------- tiered (serve) ----

HOST_MEMORY_KINDS = ("pinned_host", "unpinned_host")


def host_memory_kind(device=None) -> str:
    """The memory kind the cold tier lives in on ``device`` (default: the
    first device).  A TPU must expose ``pinned_host``; elsewhere the first
    host kind the device exposes.  Raises when there is none — a cold tier
    silently left in device memory would make every tiering number a lie."""
    dev = device if device is not None else jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    if dev.platform == "tpu":
        if "pinned_host" not in kinds:
            raise RuntimeError(f"{dev} exposes no pinned_host memory "
                               f"(memory kinds: {sorted(kinds)})")
        return "pinned_host"
    for k in HOST_MEMORY_KINDS:
        if k in kinds:
            return k
    raise RuntimeError(f"{dev} exposes no host memory kind "
                       f"(memory kinds: {sorted(kinds)})")


def _device_of(a):
    """The device an array is committed to (numpy inputs: the default)."""
    devs = getattr(a, "devices", None)
    return next(iter(devs())) if devs is not None else jax.devices()[0]


def to_host(tree, device=None):
    """Place every array leaf in host memory (async copy; XLA overlaps it
    with whatever is executing — the migration channel).  ``device`` selects
    whose host path the copy rides; by default each leaf keeps its own
    device, so a sharded engine's cold pools stay with their shard."""
    def put(a):
        dev = device if device is not None else _device_of(a)
        return jax.device_put(a, jax.sharding.SingleDeviceSharding(
            dev, memory_kind=host_memory_kind(dev)))
    return jax.tree.map(put, tree)


def to_device(tree):
    """Bring host-resident leaves into their own device's memory."""
    def put(a):
        dev = _device_of(a)
        return jax.device_put(a, jax.sharding.SingleDeviceSharding(
            dev, memory_kind=dev.default_memory().kind))
    return jax.tree.map(put, tree)


# Cold pages in host memory.  A TPU program can read a host buffer and write
# one in place (async dynamic-slice / dynamic-update-slice DMAs) when the
# update spans the buffer's most-major dimension only and its rows are
# lane-dense — hence the page-major cold storage of ``PagedKVPools``.  The
# CPU backend has no in-program host placement: there a page update runs on
# a device copy of the pool, which is then placed back in host memory.

@functools.lru_cache(maxsize=None)
def _cold_put_fn(device):
    host = jax.sharding.SingleDeviceSharding(device,
                                             memory_kind="pinned_host")

    def put(cold, page, phys):
        cold = jax.device_put(cold, jax.memory.Space.Host)   # already there
        page = jax.device_put(page, jax.memory.Space.Host)
        return jax.lax.dynamic_update_index_in_dim(cold, page, phys, 0)
    return jax.jit(put, donate_argnums=0, out_shardings=host)


def cold_page_put(cold, page, phys):
    """``cold[phys] = page[0]`` for a host-resident page-major pool and a
    device-resident ``page`` of shape ``(1,) + cold.shape[1:]``; returns the
    new pool, in host memory."""
    dev = _device_of(cold)
    if dev.platform == "tpu":          # in place: only the page moves
        return _cold_put_fn(dev)(cold, page, phys)
    return to_host(to_device(cold).at[phys].set(page[0]), dev)


@jax.jit
def cold_page_get(cold, phys):
    """Device copy ``cold[phys:phys+1]`` of one page of a host pool."""
    return jax.device_put(jax.lax.dynamic_index_in_dim(cold, phys, 0),
                          jax.memory.Space.Device)


STAGE_CHUNK = 64     # pages of a cold pool one staging copy moves at most


def stage_chunk(n_pages: int) -> int:
    """Pages one staging copy of an ``n_pages`` cold pool moves: the
    largest divisor of ``n_pages`` up to ``STAGE_CHUNK``, so whole chunks
    tile the pool."""
    return next(c for c in range(min(STAGE_CHUNK, n_pages), 0, -1)
                if n_pages % c == 0)


@functools.partial(jax.jit, static_argnums=(2,))
def _cold_stage(cold, chunks, page_shape):
    """The first ``chunks`` chunks (``stage_chunk`` pages each) of the
    page-major host pool (n, [P,] R, C), as an HBM pool of the whole pool's
    shape in the kernel's layout ([P,] n, *page_shape): (KVH, page, D) for
    attention, (page, width) for a latent pool.  Every page keeps its
    index; those past the staged prefix read zero.  ``chunks`` is a runtime
    scalar, so one program stages every prefix: each chunk is one host
    slice along the most-major axis, written into the HBM pool in place."""
    n, lead = cold.shape[0], cold.shape[1:-2]
    chunk, axis = stage_chunk(n), len(lead)

    def body(i, out):
        part = jax.lax.dynamic_slice_in_dim(cold, i * chunk, chunk, 0)
        part = jax.device_put(part, jax.memory.Space.Device)
        part = part.reshape(part.shape[:-2] + page_shape)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.moveaxis(part, 0, axis), i * chunk, axis)
    out = jnp.zeros(lead + (n,) + page_shape, cold.dtype)
    return jax.lax.fori_loop(0, chunks, body, out)


def pool_widths(cfg) -> tuple:
    """Per-token widths of the page pools a pools-layout cache holds
    (``pool_pages``): ``KVH * D`` for attention, ``kv_lora_rank`` and
    ``qk_rope_dim`` for the latent pools of MLA layers."""
    out = []
    for kind in tuple(cfg.prologue) + tuple(cfg.period):
        for page in pool_pages(cfg, kind, 1).values():
            if math.prod(page) not in out:
                out.append(math.prod(page))
    return tuple(out)


def pool_page_tokens(cfg, requested: int, max_seq: int, device=None) -> int:
    """The page size the persistent pools use for a plan's ``page_tokens``:
    the largest size <= the request that divides ``max_seq``.  On a TPU it
    is first rounded up to a multiple of ``128 / gcd(128, width)`` tokens
    for every pool's per-token width (``pool_widths``: ``KVH * D``, or the
    latent's two), so that one cold page of one layer fills whole 128-lane
    rows — the layout a TPU program can write into host memory in place."""
    dev = device if device is not None else jax.devices()[0]
    quantum = 1
    if dev.platform == "tpu":
        for w in pool_widths(cfg):
            q = 128 // math.gcd(128, w)
            quantum = quantum * q // math.gcd(quantum, q)
    page = -(-max(1, int(requested)) // quantum) * quantum
    for p in range(page, 0, -quantum):
        if max_seq % p == 0:
            return p
    raise ValueError(f"max_seq {max_seq} has no page size that is a "
                     f"multiple of {quantum} tokens")


def kv_token_bytes(cfg, dtype_bytes: int = 2) -> float:
    """Mean KV-cache bytes per token per layer, averaged over ALL layer kinds
    (stateful kinds hold O(1) state and contribute zero), so that
    ``kv_token_bytes(cfg) * cfg.num_layers`` is the model's true per-token KV
    growth.  Feeds the serve-trace model and the decode-phase planner."""
    def one(kind):
        if kind in (ATTN, LOCAL, SHARED_ATTN):
            return 2 * cfg.num_kv_heads * cfg.head_dim * dtype_bytes
        if kind == MLA:
            return (cfg.kv_lora_rank + cfg.qk_rope_dim) * dtype_bytes
        return 0.0                    # stateful kinds: O(1) state, no KV growth
    total = sum(one(k) for k in cfg.prologue) + \
        cfg.num_periods * sum(one(k) for k in cfg.period)
    return total / cfg.num_layers if cfg.num_layers else 0.0


def splice_slot(big_tree, one_tree, slot: int, batch: int):
    """Write a single-request cache (batch 1) into row ``slot`` of a batched
    cache — the continuous-batching cache splice (None leaves pass
    through).  Dispatch is async: the copy
    overlaps with whatever decode work is already enqueued.

    Batch-axis position is decided by cache *structure*, not leaf shapes:
    ``slots`` subtree leaves carry a leading (num_periods,) dim (batch at
    axis 1), ``prologue`` leaves have batch at axis 0 — shape heuristics
    would silently mis-splice when a sliced sequence length collides with
    the slot count."""
    def one_leaf(stacked):
        def f(big, one):
            if big is None:
                return None
            if stacked:                                  # (P, B, ...)
                return big.at[:, slot].set(one[:, 0])
            return big.at[slot].set(one[0])              # (B, ...)
        return f

    none_leaf = lambda x: x is None
    if isinstance(big_tree, dict) and \
            set(big_tree) == {"prologue", "slots"}:      # init_cache layout
        return {"prologue": jax.tree.map(one_leaf(False),
                                         big_tree["prologue"],
                                         one_tree["prologue"],
                                         is_leaf=none_leaf),
                "slots": jax.tree.map(one_leaf(True), big_tree["slots"],
                                      one_tree["slots"], is_leaf=none_leaf)}
    # generic tree: fall back to the shape heuristic
    def guess(big, one):
        if big is None:
            return None
        stacked = big.ndim >= 2 and big.shape[1] == batch
        return one_leaf(stacked)(big, one)
    return jax.tree.map(guess, big_tree, one_tree, is_leaf=none_leaf)


# ------------------------------------------------------- paged (serve) ----
#
# Per-slot cold boundaries split the KV sequence at a *different* point per
# batch row, at page granularity.  ``PageTable`` is the metadata manager:
# logical (slot, page) -> physical page in the hot or cold pool, with
# alloc/free/share at page granularity and the cold-prefix invariant (a
# slot's cold pages are always a prefix of its logical pages).
# ``PagedKVPools`` below holds the pools it addresses — the layout
# kernels/paged_decode.py reads.


class PageTable:
    """Slot-local logical->physical page mapping over two physical pools —
    a true physical-page allocator with reference counting.

    Pages are ``page_tokens`` tokens of KV.  Each slot owns an ordered list
    of logical pages; page i lives either in the hot pool (tier 0) or the
    cold pool (tier 1).  Invariant: the cold pages of a slot form a prefix of
    its logical pages (the cold *boundary*), and within one residency a
    slot's boundary only moves forward — pages are demoted hot->cold as the
    hot window slides, never resurrected until the slot is refilled.

    Sharing (vLLM-style prefix sharing): ``share(dst, src, n)`` maps the
    first n logical pages of ``dst`` onto ``src``'s physical pages, bumping
    per-page refcounts.  A shared page is read-only; the first divergent
    write must go through ``cow`` (copy-on-write: the writer gets a private
    physical page).  Demoting a shared page gives the demoting slot a cold
    *twin* copy — memoized per hot page, so N sharers demoting the same
    logical page move its bytes exactly once.

    ``version`` increments on every mutation; callers caching ``as_arrays``
    output re-upload only when it changes (incremental layout deltas, never
    per-step rebuilds).

    Cold pages are handed out lowest free index first (``cold_free`` is a
    min-heap), and ``cold_high`` is 1 + the highest cold index in use, so
    the cold pages in use stay packed below a low mark that the staging
    step copies up to (``PagedKVPools.stage_cold``).  Hot pages come from a
    LIFO free list.
    """

    FREE = -1

    def __init__(self, slots: int, pages_per_slot: int, page_tokens: int,
                 hot_pages: Optional[int] = None,
                 cold_pages: Optional[int] = None):
        self.slots, self.pages_per_slot = slots, pages_per_slot
        self.page_tokens = page_tokens
        n = slots * pages_per_slot
        self.n_hot = hot_pages or n
        self.n_cold = cold_pages or n
        self.hot_free = list(range(self.n_hot - 1, -1, -1))
        self.cold_free = list(range(self.n_cold))     # a min-heap
        self.cold_high = 0
        self.hot_ref = [0] * self.n_hot
        self.cold_ref = [0] * self.n_cold
        self.table = [[self.FREE] * pages_per_slot for _ in range(slots)]
        self.tier = [[self.FREE] * pages_per_slot for _ in range(slots)]
        self.n_pages = [0] * slots
        self.cold_twin: Dict[int, int] = {}      # hot phys -> cold twin phys
        self._twin_of: Dict[int, int] = {}       # cold phys -> hot phys
        self.version = 0

    # ------------------------------------------------------------ queries --
    def cold_pages(self, slot: int) -> int:
        """Pages below the slot's cold boundary."""
        t = self.tier[slot]
        n = 0
        while n < self.n_pages[slot] and t[n] == 1:
            n += 1
        return n

    def cold_tokens(self, slot: int) -> int:
        return self.cold_pages(slot) * self.page_tokens

    def _refs(self, tier: int):
        return self.cold_ref if tier == 1 else self.hot_ref

    def _free(self, tier: int):
        return self.cold_free if tier == 1 else self.hot_free

    def refcount(self, slot: int, page_idx: int) -> int:
        return self._refs(self.tier[slot][page_idx])[
            self.table[slot][page_idx]]

    def is_shared(self, slot: int, page_idx: int) -> bool:
        return self.refcount(slot, page_idx) > 1

    def pages_in_use(self) -> int:
        """Distinct physical pages currently allocated across both pools."""
        return (self.n_hot - len(self.hot_free)
                + self.n_cold - len(self.cold_free))

    def as_arrays(self):
        """(page_table, page_tier) int32 arrays for kernels/paged_decode.py."""
        return (jnp.asarray(self.table, jnp.int32),
                jnp.asarray(self.tier, jnp.int32))

    # ---------------------------------------------------------- mutations --
    def _acquire(self, tier: int) -> int:
        pool = self._free(tier)
        if not pool:
            raise ValueError(f"{'cold' if tier else 'hot'} pool exhausted")
        if tier == 1:
            phys = heapq.heappop(pool)
            self.cold_high = max(self.cold_high, phys + 1)
        else:
            phys = pool.pop()
        self._refs(tier)[phys] = 1
        return phys

    def _release(self, tier: int, phys: int) -> None:
        refs = self._refs(tier)
        refs[phys] -= 1
        assert refs[phys] >= 0, f"tier {tier} page {phys}: negative refcount"
        if refs[phys] == 0:
            if tier == 0:
                self.hot_free.append(phys)
                # hot page gone: its cold twin (if any) lives on through its
                # own refs, but can no longer be reached for dedup
                twin = self.cold_twin.pop(phys, None)
                if twin is not None:
                    self._twin_of.pop(twin, None)
            else:
                heapq.heappush(self.cold_free, phys)
                while self.cold_high and not refs[self.cold_high - 1]:
                    self.cold_high -= 1
                src = self._twin_of.pop(phys, None)
                if src is not None:
                    self.cold_twin.pop(src, None)

    def alloc(self, slot: int, tier: int) -> int:
        """Append one logical page to ``slot`` in the given tier; returns the
        physical page id.  Raises when the slot or the pool is exhausted."""
        i = self.n_pages[slot]
        if i >= self.pages_per_slot:
            raise ValueError(f"slot {slot}: pages_per_slot exhausted")
        if tier == 1 and i != self.cold_pages(slot):
            raise ValueError(f"slot {slot}: cold alloc would break the "
                             "cold-prefix invariant")
        phys = self._acquire(tier)
        self.table[slot][i] = phys
        self.tier[slot][i] = tier
        self.n_pages[slot] = i + 1
        self.version += 1
        return phys

    def share(self, dst: int, src: int, n: int) -> int:
        """Map the first ``n`` logical pages of empty slot ``dst`` onto
        ``src``'s physical pages (prefix sharing).  Refcounts bump; tiers are
        inherited from ``src`` (a prefix of src's tier row is itself a valid
        cold-prefix pattern).  Returns the number of pages shared."""
        if self.n_pages[dst]:
            raise ValueError(f"slot {dst}: share requires an empty slot")
        if n > self.n_pages[src]:
            raise ValueError(f"slot {src}: only {self.n_pages[src]} pages "
                             f"allocated, cannot share {n}")
        for i in range(n):
            phys, tier = self.table[src][i], self.tier[src][i]
            self._refs(tier)[phys] += 1
            self.table[dst][i] = phys
            self.tier[dst][i] = tier
        self.n_pages[dst] = n
        if n:
            self.version += 1
        return n

    def cow(self, slot: int, page_idx: int) -> Optional[tuple]:
        """Copy-on-write: give ``slot`` a private physical page for logical
        page ``page_idx`` before a divergent write.  No-op (returns None)
        when the page is already exclusive; otherwise returns
        ``(src_phys, new_phys, tier)`` — the caller must copy the page's
        data from src to new in that tier's pool."""
        if page_idx >= self.n_pages[slot]:
            raise ValueError(f"slot {slot}: page {page_idx} not allocated")
        if not self.is_shared(slot, page_idx):
            return None
        tier = self.tier[slot][page_idx]
        src = self.table[slot][page_idx]
        new = self._acquire(tier)
        self._refs(tier)[src] -= 1
        self.table[slot][page_idx] = new
        self.version += 1
        return (src, new, tier)

    def free_slot(self, slot: int) -> int:
        """Release every page reference of ``slot`` (slot refill / request
        completion); a physical page returns to its free list only when its
        last reference drops.  Returns the number of references released."""
        n = self.n_pages[slot]
        for i in range(n):
            self._release(self.tier[slot][i], self.table[slot][i])
            self.table[slot][i] = self.tier[slot][i] = self.FREE
        self.n_pages[slot] = 0
        if n:
            self.version += 1
        return n

    def demote(self, slot: int, page_idx: int) -> tuple:
        """Move one page of ``slot`` hot->cold.  Only the page at the cold
        boundary may move (prefix invariant).

        Exclusive page: the classic move (hot page freed, cold page
        allocated, data must be copied).  Shared page: the demoting slot
        gets a cold *twin* — allocated and copied on the first demotion,
        reused (refcount bump, no copy) by every later sharer, so shared
        bytes migrate exactly once.  Returns ``(cold_phys, src_hot_phys,
        copied)``; the caller copies pool data src->cold iff ``copied``.
        """
        if page_idx != self.cold_pages(slot):
            raise ValueError(f"slot {slot}: demote({page_idx}) is not the "
                             f"cold boundary {self.cold_pages(slot)}")
        if page_idx >= self.n_pages[slot]:
            raise ValueError(f"slot {slot}: page {page_idx} not allocated")
        src = self.table[slot][page_idx]
        twin = self.cold_twin.get(src)
        if twin is not None and self.cold_ref[twin] > 0:
            self.cold_ref[twin] += 1
            cold_phys, copied = twin, False
        else:
            if not self.cold_free:
                raise ValueError("cold pool exhausted")
            cold_phys = self._acquire(1)
            copied = True
            if self.hot_ref[src] > 1:        # others still share: memoize
                self.cold_twin[src] = cold_phys
                self._twin_of[cold_phys] = src
        self._release(0, src)
        self.table[slot][page_idx] = cold_phys
        self.tier[slot][page_idx] = 1
        self.version += 1
        return (cold_phys, src, copied)

    def splice_slot(self, slot: int, tokens: int, cold_tokens: int) -> int:
        """Refill ``slot`` with a fresh request: free its pages, then allocate
        ceil(tokens/page) pages with the first ``cold_tokens`` worth cold.
        Returns the number of cold pages allocated."""
        self.free_slot(slot)
        n = -(-tokens // self.page_tokens) if tokens else 0
        n_cold = min(n, cold_tokens // self.page_tokens)
        for i in range(n):
            self.alloc(slot, 1 if i < n_cold else 0)
        return n_cold

    def check(self) -> None:
        """Assert structural invariants (used by the property tests)."""
        import collections as _c
        for tier, pool, refs in ((0, self.hot_free, self.hot_ref),
                                 (1, self.cold_free, self.cold_ref)):
            used = _c.Counter(self.table[s][i] for s in range(self.slots)
                              for i in range(self.n_pages[s])
                              if self.tier[s][i] == tier)
            for phys, r in enumerate(refs):
                assert r >= 0, f"tier {tier}: negative refcount at {phys}"
                assert used.get(phys, 0) == r, \
                    f"tier {tier}: page {phys} refcount {r} != " \
                    f"{used.get(phys, 0)} references (double alloc / leak)"
            assert not (set(used) & set(pool)), f"tier {tier}: used page free"
        assert all(self.cold_free[(i - 1) // 2] <= self.cold_free[i]
                   for i in range(1, len(self.cold_free))), "cold heap order"
        assert self.cold_high == 1 + max(
            (p for p, r in enumerate(self.cold_ref) if r), default=-1), \
            f"cold high-water mark {self.cold_high}"
        for s in range(self.slots):
            n, nc = self.n_pages[s], self.cold_pages(s)
            assert all(self.tier[s][i] == 1 for i in range(nc))
            assert all(self.tier[s][i] == 0 for i in range(nc, n))
            assert all(self.table[s][i] == self.FREE for i in
                       range(n, self.pages_per_slot))
        for src, twin in self.cold_twin.items():
            assert self.hot_ref[src] > 0, "twin memo for a freed hot page"
            assert self.cold_ref[twin] > 0, "twin memo for a freed cold page"
            assert self._twin_of.get(twin) == src


class MeshPageTable:
    """N per-device ``PageTable``s under one global logical slot namespace.

    The tier-graph runtime's allocator view of a device mesh: device ``d``'s
    hot pool is its own HBM, its cold pool a region of the one shared host
    memory.  Global slot ids are ``gslot = offset[d] + local_slot`` (offsets
    cumulative over per-device slot counts), so every logical slot names
    exactly one ``(device, slot)`` pair — the namespace-uniqueness
    invariant the property suite holds.

    Intra-device operations (alloc/share/cow/demote/free) delegate to the
    owning table unchanged, keeping all its refcount/CoW/twin semantics.
    ``migrate_slot`` is the new first-class tier transition: a slot's pages
    move to a slot on another device, hot pages crossing the device↔device
    edge, cold pages re-homing *inside* host memory (their bytes never touch
    a device link).  A shared page's mover pays a full private copy on the
    destination — the source physical page lives on for its remaining
    sharers (refcounts preserved, CoW memos cleaned by ``_release``) — so
    after migration every migrated page is exclusive.

    Byte conservation: every migrated page's payload is attributed to
    exactly one ledger entry — ``edge_bytes[(src_dev, dst_dev)]`` for hot
    pages, ``host_internal_bytes`` for cold — and the per-device
    ``bytes_out``/``bytes_in`` ledgers must always equal the edge sums
    (asserted by ``check()``).
    """

    def __init__(self, tables, names=None, page_bytes: float = 1.0):
        if not tables:
            raise ValueError("MeshPageTable needs at least one PageTable")
        self.tables = list(tables)
        self.names = list(names) if names is not None else \
            [f"dev{d}" for d in range(len(self.tables))]
        if len(self.names) != len(self.tables):
            raise ValueError(f"{len(self.tables)} tables but "
                             f"{len(self.names)} names")
        if len(set(self.names)) != len(self.names):
            raise ValueError(f"duplicate device names: {self.names}")
        pts = {t.page_tokens for t in self.tables}
        if len(pts) != 1:
            raise ValueError(f"tables disagree on page_tokens: {pts}")
        self.page_tokens = pts.pop()
        self.page_bytes = float(page_bytes)
        self.offsets = [0]
        for t in self.tables:
            self.offsets.append(self.offsets[-1] + t.slots)
        self.edge_bytes: Dict[tuple, float] = {}
        self.host_internal_bytes = 0.0
        self.bytes_out = {n: 0.0 for n in self.names}
        self.bytes_in = {n: 0.0 for n in self.names}

    # ------------------------------------------------------ the namespace --
    @property
    def num_devices(self) -> int:
        return len(self.tables)

    @property
    def slots(self) -> int:
        """Global logical slots across the mesh."""
        return self.offsets[-1]

    def gslot(self, dev: int, slot: int) -> int:
        if not 0 <= slot < self.tables[dev].slots:
            raise ValueError(f"device {dev}: no slot {slot}")
        return self.offsets[dev] + slot

    def owner(self, gslot: int) -> tuple:
        """The unique ``(device, local_slot)`` a global slot names."""
        if not 0 <= gslot < self.slots:
            raise ValueError(f"global slot {gslot} outside [0, {self.slots})")
        for d in range(len(self.tables)):
            if gslot < self.offsets[d + 1]:
                return d, gslot - self.offsets[d]
        raise AssertionError("unreachable")

    def _at(self, gslot: int):
        d, s = self.owner(gslot)
        return self.tables[d], d, s

    # ------------------------------------------- delegated intra-device ops --
    def n_pages(self, gslot: int) -> int:
        t, _, s = self._at(gslot)
        return t.n_pages[s]

    def cold_pages(self, gslot: int) -> int:
        t, _, s = self._at(gslot)
        return t.cold_pages(s)

    def refcount(self, gslot: int, page_idx: int) -> int:
        t, _, s = self._at(gslot)
        return t.refcount(s, page_idx)

    def alloc(self, gslot: int, tier: int) -> int:
        t, _, s = self._at(gslot)
        return t.alloc(s, tier)

    def share(self, dst: int, src: int, n: int) -> int:
        """Prefix sharing — intra-device only: a shared physical page can
        only be mapped by slots on the device whose pool holds it."""
        td, dd, sd = self._at(dst)
        ts, ds, ss = self._at(src)
        if dd != ds:
            raise ValueError(
                f"share across devices ({self.names[ds]} -> "
                f"{self.names[dd]}): physical pages cannot alias across "
                "HBMs — migrate_slot copies instead")
        return td.share(sd, ss, n)

    def cow(self, gslot: int, page_idx: int):
        t, _, s = self._at(gslot)
        return t.cow(s, page_idx)

    def demote(self, gslot: int, page_idx: int) -> tuple:
        t, _, s = self._at(gslot)
        return t.demote(s, page_idx)

    def free_slot(self, gslot: int) -> int:
        t, _, s = self._at(gslot)
        return t.free_slot(s)

    # -------------------------------------------- the cross-device transition --
    def migrate_slot(self, src: int, dst: int) -> dict:
        """Move every page of global slot ``src`` to global slot ``dst`` on
        another device, appending after ``dst``'s existing pages (a shared
        prefix admitted on the destination stays put; only the private tail
        crosses).  Tiers are preserved per page.  Validates capacity and the
        destination's cold-prefix invariant up front, so it either moves the
        whole slot or raises without mutating.  Returns the accounting
        summary ``{"pages", "hot_bytes", "cold_bytes"}``."""
        st, sd, ss = self._at(src)
        dt, dd, ds = self._at(dst)
        if sd == dd:
            raise ValueError(f"migrate_slot within device "
                             f"{self.names[sd]}: use share/splice instead")
        n = st.n_pages[ss]
        n_cold = st.cold_pages(ss)
        n_hot = n - n_cold
        if dt.n_pages[ds] + n > dt.pages_per_slot:
            raise ValueError(f"dst slot {ds} on {self.names[dd]}: "
                             f"{dt.n_pages[ds]}+{n} pages exceed "
                             f"pages_per_slot {dt.pages_per_slot}")
        if n_cold and dt.n_pages[ds] > dt.cold_pages(ds):
            raise ValueError(f"dst slot {ds} on {self.names[dd]}: cold "
                             "pages would land above its hot pages")
        if len(dt.hot_free) < n_hot or len(dt.cold_free) < n_cold:
            raise ValueError(f"{self.names[dd]}: pool exhausted "
                             f"(need {n_hot} hot / {n_cold} cold)")
        for i in range(n):
            tier = st.tier[ss][i]
            dt.alloc(ds, tier)     # the caller copies pool data per page
            if tier == 0:
                self.edge_bytes[(self.names[sd], self.names[dd])] = \
                    self.edge_bytes.get(
                        (self.names[sd], self.names[dd]), 0.0) \
                    + self.page_bytes
                self.bytes_out[self.names[sd]] += self.page_bytes
                self.bytes_in[self.names[dd]] += self.page_bytes
            else:
                # cold pools are regions of the one host memory: re-homing
                # copies inside it, no device link is touched
                self.host_internal_bytes += self.page_bytes
        st.free_slot(ss)
        return {"pages": n,
                "hot_bytes": n_hot * self.page_bytes,
                "cold_bytes": n_cold * self.page_bytes}

    # ----------------------------------------------------------- invariants --
    def pages_in_use(self) -> int:
        return sum(t.pages_in_use() for t in self.tables)

    def check(self) -> None:
        """Per-device structural invariants plus the mesh ledgers: every
        byte that left a device landed on exactly one edge."""
        for t in self.tables:
            t.check()
        out_sum = {n: 0.0 for n in self.names}
        in_sum = {n: 0.0 for n in self.names}
        for (s, d), b in self.edge_bytes.items():
            assert s in out_sum and d in in_sum, f"edge {(s, d)} names an " \
                f"unknown device"
            assert b >= 0, f"edge {(s, d)}: negative bytes"
            out_sum[s] += b
            in_sum[d] += b
        for name in self.names:
            assert out_sum[name] == self.bytes_out[name], \
                f"{name}: {self.bytes_out[name]} bytes departed but " \
                f"{out_sum[name]} attributed to edges"
            assert in_sum[name] == self.bytes_in[name], \
                f"{name}: {self.bytes_in[name]} bytes arrived but " \
                f"{in_sum[name]} attributed to edges"
        assert self.host_internal_bytes >= 0


# ------------------------------------------------- persistent pools (serve) --

ATTN_KINDS = (ATTN, LOCAL, SHARED_ATTN)
POOL_KINDS = ATTN_KINDS + (MLA,)     # layer kinds whose KV lives in pools


def pool_pages(cfg, kind: str, page_tokens: int) -> dict:
    """The page pools of one layer kind, by name, each with the shape of
    one hot page: attention's ``k`` / ``v`` head-major ``(KVH, page, D)``,
    the layout ``paged_decode`` reads; MLA's ``ckv`` / ``krope`` (the
    latent and the shared rope key) ``(page, width)``, one block that every
    query head reads.  Empty for a kind whose cache is not pooled."""
    if kind == MLA:
        return {"ckv": (page_tokens, cfg.kv_lora_rank),
                "krope": (page_tokens, cfg.qk_rope_dim)}
    if kind in ATTN_KINDS:
        page = (cfg.num_kv_heads, page_tokens, cfg.head_dim)
        return {"k": page, "v": page}
    return {}


class PagedKVPools:
    """Persistent physical KV page pools — the storage paged decode consumes.

    This inverts the ownership between logical caches and physical memory:
    instead of a dense per-slot cache that gets re-packed into pools every
    step, the pools ARE the cache.  Per attention layer the storage is

      k_hot / v_hot    ([P,] n_hot+1, KVH, page, D)   device memory (HBM)
      k_cold / v_cold  (n_cold, [P,] R, C)            host memory

    and per MLA layer two latent pools of the same form, each page one
    ``(page, width)`` block read by every query head:

      ckv_hot / krope_hot    ([P,] n_hot+1, page, kv_lora_rank | qk_rope)
      ckv_cold / krope_cold  (n_cold, [P,] R, C)

    addressed through one layer-independent :class:`PageTable` (``[P,]`` is
    the scanned period axis of stacked layers).  ``pool_pages`` gives each
    layer kind's pools and their page layout: attention's hot pools are
    head-major, the layout ``paged_decode`` reads.  The cold pools are
    *page-major*: one cold page of every stacked layer is one contiguous
    lane-dense block of ``R x C`` (``C = 128`` when ``KVH * page * D``
    tiles it), which is what lets a demotion write just that page into host
    memory.  Decode *writes into the hot pools* via the table
    (models/attention.py resolves each slot's write position to a physical
    hot page); admit / demote / free are incremental per-page deltas;
    ``as_arrays`` uploads of the table happen only when the table's
    ``version`` changes.  Every pool of every layer is addressed by the one
    table, so a page's admit / demote / stage / CoW moves that page of each
    pool.  Stateful layer caches keep their dense batched form inside
    ``tree``.

    The forward never sees the host pools: ``forward_tree`` hands it the hot
    pools plus, when any page is cold, the cold pools staged into HBM in the
    kernel's layout (``stage_cold``: the prefix of each cold pool up to the
    table's high-water mark, counted in ``cold_stage_bytes`` and
    ``cold_stage_pages``), and ``adopt`` takes back the updated hot pools.

    The extra hot page at index ``garbage`` (= n_hot) absorbs the lockstep
    writes of inactive batch slots, so a finished slot can never scribble
    over a physical page a live slot still references.

    ``stats`` counts the events the steady-state acceptance test pins to
    zero: ``repacks`` (dense->pool re-packs — never in this design),
    ``table_uploads`` (layout deltas), ``page_copies`` (demote/CoW data
    movement), ``admit_page_writes`` (prefill landing in the pools), and
    the staging steps' ``cold_stages``, ``cold_stage_pages`` (page indices
    staged, summed over steps) and ``cold_stage_bytes`` (bytes read from
    host memory, summed over pools).
    """

    def __init__(self, cfg, slots: int, max_seq: int, page_tokens: int,
                 dtype=jnp.bfloat16, hot_pages: Optional[int] = None,
                 cold_pages: Optional[int] = None, device=None):
        assert max_seq % page_tokens == 0, (max_seq, page_tokens)
        # the device that holds the hot pools and whose host memory holds
        # the cold ones (None: the default device) — a sharded engine gives
        # each shard's pool its own device.  Every array is committed to it,
        # so a compiled step sees one placement from its first call on
        self.device = device if device is not None else jax.devices()[0]
        self.cfg, self.num_slots = cfg, slots
        self.max_seq, self.page_tokens = max_seq, page_tokens
        self.num_pages = max_seq // page_tokens
        self.table = PageTable(slots, self.num_pages, page_tokens,
                               hot_pages, cold_pages)
        self.garbage = self.table.n_hot          # scratch page, never mapped
        # one page of each pool the config's layer kinds use: its hot
        # block, and its cold rows (lane-dense 128-wide where the page's
        # elements tile them)
        self.page_shapes = {}
        for kind in tuple(cfg.prologue) + tuple(cfg.period):
            self.page_shapes.update(pool_pages(cfg, kind, page_tokens))
        self.cold_rows = {}
        for name, shape in self.page_shapes.items():
            width = math.prod(shape)
            lanes = 128 if width % 128 == 0 else width
            self.cold_rows[name] = (width // lanes, lanes)
        self.tree = self._init_tree(dtype)
        self._cached_arrays = None
        self._cached_version = -1
        self.stats = {"repacks": 0, "table_uploads": 0, "page_copies": 0,
                      "admit_page_writes": 0, "cold_stages": 0,
                      "cold_stage_pages": 0, "cold_stage_bytes": 0}
        self.peak_pages = 0

    # --------------------------------------------------------- construction --
    def _pool_layer(self, kind: str, dtype, lead: tuple):
        if kind not in POOL_KINDS:
            one = init_layer_cache(self.cfg, kind, self.num_slots,
                                   self.max_seq, dtype)
            if lead:
                one = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], lead + a.shape)
                    .copy(), one)
            return jax.device_put(one, self.device)

        def hot(name):
            return jnp.zeros(lead + (self.table.n_hot + 1,)
                             + self.page_shapes[name], dtype,
                             device=self.device)

        def cold(name):                   # its own buffer: writes donate it
            return to_host(jnp.zeros((self.table.n_cold,) + lead
                                     + self.cold_rows[name], dtype,
                                     device=self.device), self.device)
        names = pool_pages(self.cfg, kind, self.page_tokens)
        return {**{f"{n}_hot": hot(n) for n in names},
                **{f"{n}_cold": cold(n) for n in names}}

    def _init_tree(self, dtype):
        cfg = self.cfg
        return {"prologue": [self._pool_layer(k, dtype, ())
                             for k in cfg.prologue],
                "slots": [self._pool_layer(k, dtype, (cfg.num_periods,))
                          for k in cfg.period]}

    def _names(self, entry) -> tuple:
        """The pools of one pooled layer's entry: ``k``, ``v`` or ``ckv``,
        ``krope``."""
        return tuple(n for n in self.page_shapes if f"{n}_hot" in entry)

    def _attn_entries(self, *others):
        """Yield (stacked, pool_entry[, other_entry...]) per pooled layer
        (attention and MLA)."""
        for i, kind in enumerate(self.cfg.prologue):
            if kind in POOL_KINDS:
                yield (False, self.tree["prologue"][i],
                       *(o["prologue"][i] for o in others))
        for s, kind in enumerate(self.cfg.period):
            if kind in POOL_KINDS:
                yield (True, self.tree["slots"][s],
                       *(o["slots"][s] for o in others))

    def _note(self):
        self.peak_pages = max(self.peak_pages, self.table.pages_in_use())

    # -------------------------------------------------------------- layout --
    def arrays(self):
        """(page_table, page_tier) device arrays, re-uploaded only when the
        PageTable mutated since the last call (incremental layout deltas)."""
        if self._cached_version != self.table.version:
            with obs.span("sentinel.table"):
                self._cached_arrays = self.table.as_arrays()
            self._cached_version = self.table.version
            self.stats["table_uploads"] += 1
        return self._cached_arrays

    def paged_view(self, active_mask) -> Dict[str, Any]:
        """The per-step view models/attention.py consumes.  Everything in it
        is either cached (table/tier arrays, active mask) or a python
        constant — building it costs no transfers in steady state."""
        table_arr, tier_arr = self.arrays()
        return {"page_table": table_arr, "page_tier": tier_arr,
                "page_tokens": self.page_tokens, "active": active_mask,
                "garbage_page": self.garbage}

    # ------------------------------------------------- the forward's view --
    def has_cold(self) -> bool:
        """Whether any physical cold page is in use."""
        return self.table.cold_high > 0

    def staged_pages(self) -> int:
        """Cold page indices a staging step copies: the table's high-water
        mark rounded up to whole ``stage_chunk`` chunks — every cold page
        the table maps lies below it."""
        chunk = stage_chunk(self.table.n_cold)
        return -(-self.table.cold_high // chunk) * chunk

    def stage_cold(self, entry) -> tuple:
        """One pooled layer's two cold pools (k, v or ckv, krope) staged
        from host memory into HBM in the kernel's layout, up to
        ``staged_pages`` — the named (``jit(_cold_stage)`` in a trace),
        counted cold read of a step."""
        names = self._names(entry)
        pages = self.staged_pages()
        chunks = pages // stage_chunk(self.table.n_cold)
        out = tuple(_cold_stage(entry[f"{n}_cold"], chunks,
                                self.page_shapes[n]) for n in names)
        self.stats["cold_stage_bytes"] += pages * sum(
            entry[f"{n}_cold"].nbytes // self.table.n_cold for n in names)
        return out

    def forward_tree(self, stage: bool):
        """The cache tree a forward consumes: hot pools and dense caches,
        plus — with ``stage`` — the staged cold pools.  Without ``stage`` no
        page the table maps may be cold, and attention reads the hot pools
        only.  The host pools are never handed to the forward.  Staging is
        one ``sentinel.stage_cold`` span with the bytes read from host
        memory, the page indices staged and the cold pages the table maps."""
        def view(entry, kind):
            if kind not in POOL_KINDS:
                return entry
            names = self._names(entry)
            out = {f"{n}_hot": entry[f"{n}_hot"] for n in names}
            if stage:
                out.update(zip((f"{n}_cold" for n in names),
                               self.stage_cold(entry)))
            return out

        def tree():
            return {"prologue": [view(e, k) for e, k in
                                 zip(self.tree["prologue"],
                                     self.cfg.prologue)],
                    "slots": [view(e, k) for e, k in
                              zip(self.tree["slots"], self.cfg.period)]}

        if not stage:
            return tree()
        with obs.span("sentinel.stage_cold") as attrs:
            staged = self.stats["cold_stage_bytes"]
            pages = self.staged_pages()
            self.stats["cold_stages"] += 1
            self.stats["cold_stage_pages"] += pages
            out = tree()
            attrs["bytes"] = self.stats["cold_stage_bytes"] - staged
            attrs["pages"] = pages
            attrs["mapped"] = self.table.n_cold - len(self.table.cold_free)
        return out

    def adopt(self, new_tree) -> None:
        """Take a forward's updated caches back: the hot pools of pooled
        layers (their cold pools stay where they are), every other layer's
        cache whole."""
        for part, kinds in (("prologue", self.cfg.prologue),
                            ("slots", self.cfg.period)):
            for i, kind in enumerate(kinds):
                if kind in POOL_KINDS:
                    self.tree[part][i] = dict(
                        self.tree[part][i],
                        **{f"{n}_hot": new_tree[part][i][f"{n}_hot"]
                           for n in self._names(self.tree[part][i])})
                else:
                    self.tree[part][i] = new_tree[part][i]

    # ------------------------------------------------------ page transfers --
    def hot_page(self, entry, stacked: bool, phys: int) -> tuple:
        """One hot page of the entry's two pools (k, v: ([P,] KVH, page,
        D); ckv, krope: ([P,] page, width)) as device arrays."""
        return tuple(entry[f"{n}_hot"][:, phys] if stacked
                     else entry[f"{n}_hot"][phys]
                     for n in self._names(entry))

    def set_hot_page(self, entry, stacked: bool, phys: int, *pages) -> None:
        for n, page in zip(self._names(entry), pages):
            key = f"{n}_hot"
            entry[key] = entry[key].at[:, phys].set(page) if stacked \
                else entry[key].at[phys].set(page)

    def cold_page(self, entry, phys: int) -> tuple:
        """One cold page of the entry's two pools, brought to the device in
        storage form (1, [P,] R, C)."""
        return tuple(cold_page_get(entry[f"{n}_cold"], phys)
                     for n in self._names(entry))

    def set_cold_page(self, entry, phys: int, *pages) -> None:
        """Write one cold page of each of the entry's pools (device arrays
        in storage form) into host memory."""
        for n, page in zip(self._names(entry), pages):
            entry[f"{n}_cold"] = cold_page_put(entry[f"{n}_cold"], page, phys)

    def _cold_form(self, page, name: str):
        """A hot page of pool ``name`` in cold storage form."""
        lead = page.shape[:page.ndim - len(self.page_shapes[name])]
        return page.reshape((1,) + lead + self.cold_rows[name])

    # ----------------------------------------------------------- mutations --
    def free_slot(self, slot: int) -> int:
        n = self.table.free_slot(slot)
        self._note()
        return n

    def share(self, dst: int, src: int, n: int) -> int:
        n = self.table.share(dst, src, n)
        self._note()
        return n

    def ensure_write_page(self, slot: int, pos: int) -> None:
        """Pre-step guarantee for the decode write at token ``pos``: the page
        holding ``pos`` exists and is private (CoW on a shared page — the
        first divergent write past a shared-prefix fork point)."""
        while self.table.n_pages[slot] * self.page_tokens < pos + 1:
            self.table.alloc(slot, 0)
        self._note()
        self.cow_for_write(slot, pos)

    def cow_for_write(self, slot: int, pos: int) -> bool:
        """Copy-on-write before a divergent write at token ``pos``; no-op on
        exclusive pages.  Returns True when a private copy was made."""
        idx = pos // self.page_tokens
        if idx >= self.table.n_pages[slot]:
            return False
        r = self.table.cow(slot, idx)
        if r is None:
            return False
        src, new, tier = r
        self._note()
        for stacked, entry in self._attn_entries():
            if tier == 1:
                self.set_cold_page(entry, new, *self.cold_page(entry, src))
            else:
                self.set_hot_page(entry, stacked, new,
                                  *self.hot_page(entry, stacked, src))
        self.stats["page_copies"] += 1
        return True

    def admit_rows(self, fresh, slot: int, pages) -> None:
        """Write whole pages of a batch-1 prefilled dense cache into the
        slot's private hot pages.  Shared pages are skipped by the caller —
        their physical pages already hold bit-identical data."""
        pages = list(pages)
        if not pages:
            return
        assert all(self.table.tier[slot][i] == 0 for i in pages), \
            "admit writes land in the hot pool"
        phys = [self.table.table[slot][i] for i in pages]
        pg = self.page_tokens

        def to_page(name, rows):
            """Fresh rows ([P,] pg, width) as one page of pool ``name``."""
            if len(self.page_shapes[name]) == 2:     # (pg, width) as it is
                return rows
            KVH, _, D = self.page_shapes[name]       # head-major
            rows = rows.reshape(rows.shape[:-1] + (KVH, D))
            return jnp.swapaxes(rows, -3, -2)

        for stacked, pool, fr in self._attn_entries(fresh):
            names = self._names(pool)
            for i, ph in zip(pages, phys):
                lo = i * pg
                # fresh (P, 1, S, width) when stacked, else (1, S, width)
                self.set_hot_page(pool, stacked, ph, *(
                    to_page(n, fr[n][:, 0, lo:lo + pg] if stacked
                            else fr[n][0, lo:lo + pg]) for n in names))
        self.stats["admit_page_writes"] += len(pages)

    def splice_other(self, fresh, slot: int) -> None:
        """Row-splice the stateful layer caches (every kind but the pooled
        ones) of a fresh batch-1 cache into the pool tree — same semantics
        as ``splice_slot`` on the dense layout."""
        def one(stacked):
            def f(big, small):
                if big is None:
                    return None
                if stacked:
                    return big.at[:, slot].set(small[:, 0])
                return big.at[slot].set(small[0])
            return f

        for i, kind in enumerate(self.cfg.prologue):
            if kind not in POOL_KINDS:
                self.tree["prologue"][i] = jax.tree.map(
                    one(False), self.tree["prologue"][i],
                    fresh["prologue"][i])
        for s, kind in enumerate(self.cfg.period):
            if kind not in POOL_KINDS:
                self.tree["slots"][s] = jax.tree.map(
                    one(True), self.tree["slots"][s], fresh["slots"][s])

    def demote_boundary(self, slot: int) -> bool:
        """Advance the slot's cold boundary one page.  Pool data moves
        hot->cold only when the PageTable allocated a fresh cold copy
        (exclusive page, or the first sharer to demote) — twin reuse by
        later sharers moves zero bytes, which is how shared pages' migration
        bytes are counted exactly once.  The copy writes that one page of
        every stacked layer into host memory.  Returns whether data was
        copied."""
        idx = self.table.cold_pages(slot)
        cold_phys, src, copied = self.table.demote(slot, idx)
        self._note()
        if copied:
            for stacked, entry in self._attn_entries():
                pages = self.hot_page(entry, stacked, src)
                self.set_cold_page(entry, cold_phys, *(
                    self._cold_form(p, n)
                    for p, n in zip(pages, self._names(entry))))
            self.stats["page_copies"] += 1
        return copied


def cache_logical_axes(cfg) -> Dict[str, Any]:
    """Logical sharding axes for every cache leaf (mirrors init_cache)."""
    def axes_layer(kind):
        if kind in (ATTN, LOCAL, SHARED_ATTN):
            return {"k": ("batch", "kv_seq", "kv_heads"),
                    "v": ("batch", "kv_seq", "kv_heads")}
        if kind == MLA:
            return {"ckv": ("batch", "kv_seq", "kv_latent"),
                    "krope": ("batch", "kv_seq", None)}
        if kind == MAMBA:
            return {"h": ("batch", "ssm_heads", None, None),
                    "conv": ("batch", None, "ssm_heads")}
        if kind == MLSTM:
            return {"state": (("batch", "heads", None, None),
                              ("batch", "heads", None),
                              ("batch", "heads")),
                    "conv": ("batch", None, "mlp")}
        if kind == SLSTM:
            return {"state": (("batch", "heads", None),) * 2 +
                             (("batch", "heads", None),) * 2,
                    "conv": ("batch", None, "mlp")}
        if kind == LSTM:
            return {"h": ("batch", "embed"), "c": ("batch", "embed")}
        raise ValueError(kind)

    from repro.sharding import is_axes_leaf
    pro = [axes_layer(k) for k in cfg.prologue]
    slots = [jax.tree.map(lambda ax: ("layers",) + tuple(ax), axes_layer(k),
                          is_leaf=is_axes_leaf)
             for k in cfg.period]
    return {"prologue": pro, "slots": slots}
