"""One run of one cell: set up, warm up, measure a closed loop, check the
served tokens against the plain reference, report.

The system under test is the program's tiered serving path as
``repro.launch.serve.tiered_server`` builds it: a ``ContinuousBatcher`` on
the persistent page pools (``paged=True``, ``use_paged_decode=True``) with
a plan from ``runtime.plan`` over ``engine.serve_trace_for`` of a small
profiling sample.  The measured window drives ``ContinuousBatcher.step()``
and nothing else of the program.  The configuration's family module
(``families/<family>.py``, which ``spec`` loads) gives the program's
configuration, the seeded weights and the plain reference.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from types import ModuleType

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference, spec, traffic
from benchmarks.chip import trace as xtrace
from benchmarks.chip.compiles import Compiles

REHEARSAL_SCALE = (16, 4)       # prompt and output lengths divided by these
REHEARSAL_MAX_SEQ = 256
SERVED_TOKENS_COMPARED = 300    # the correctness sample's size, at least
MAX_REQUESTS_COMPARED = 8


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ records ----

@dataclass
class Req:
    prompt: np.ndarray          # host prompt tokens
    budget: int
    t_submit: float = 0.0
    slot: int = -1
    out: list = field(default_factory=list)     # served tokens
    times: list = field(default_factory=list)   # when each was delivered
    done: bool = False

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


@dataclass
class Step:
    t0: float
    t1: float
    prefill_tokens: int         # the engine's step_prefill_tokens entry
    decode_lengths: list        # kernel length of each slot that decoded
    counters: dict              # deltas of the pools' stats over the step


@dataclass
class Run:
    """What a metric reader reads."""
    family: ModuleType          # families/<family>.py of the configuration
    shape: object               # the family's Shape
    peak: dict                  # the device's peaks (peaks.json), or None
    setup_s: float
    t_open: float
    t_close: float
    steps: list                 # the window's steps
    requests: list              # every request the loop submitted
    trace: object = None        # trace.Summary of a traced run

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open < t <= self.t_close

    def tokens_delivered(self) -> int:
        return sum(1 for r in self.requests for t in r.times
                   if self.in_window(t))

    def token_gaps(self) -> list:
        """Seconds between consecutive tokens of one request, for every
        such gap that ends inside the window."""
        return [b - a for r in self.requests
                for a, b in zip(r.times, r.times[1:]) if self.in_window(b)]

    def first_token_waits(self) -> list:
        """Seconds from submission to the end of the step that delivered
        the first token, for each request submitted in the window whose
        first token came inside it."""
        return [r.times[0] - r.t_submit for r in self.requests
                if r.t_submit >= self.t_open and r.times
                and self.in_window(r.times[0])]


# -------------------------------------------------------- closed loop ----

class ClosedLoop:
    """Clients that each submit their next request the moment the last one
    completes, and the engine's slot binding.  The engine binds queued
    requests FIFO to free slots in slot order (``ContinuousBatcher._admit``);
    the loop predicts each binding from that rule and checks it after every
    step against the slot's length, ``prompt + served - 1`` tokens."""

    def __init__(self, engine, deck, arrays, clients: int):
        self.b, self.deck, self.arrays = engine, deck, arrays
        self.clients = clients
        self.next = 0
        self.pending: deque = deque()
        self.by_slot: dict = {}
        self.requests: list = []
        self.mismatches = 0

    def track(self, req: Req) -> None:
        """A request the engine already holds in its queue."""
        self.pending.append(req)
        self.requests.append(req)

    def submit_next(self) -> None:
        i = self.next % len(self.deck)
        self.next += 1
        d = self.deck[i]
        req = Req(d.tokens, d.budget, t_submit=time.perf_counter())
        self.b.submit(self.arrays[i], req.budget)
        self.track(req)

    def top_up(self) -> None:
        """Every client without a request in flight submits its next."""
        while len(self.by_slot) + len(self.pending) < self.clients:
            self.submit_next()

    def step(self) -> Step:
        b = self.b
        jobs = getattr(b, "_jobs", {})
        free = [s for s in range(b.B) if not b.active[s] and s not in jobs]
        binds = list(zip(free, list(self.pending)[:len(free)]))
        before = dict(b.pool.stats)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            b.step()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.collect"):
            for s, r in binds:          # _admit bound each free slot
                self.pending.popleft()
                r.slot = s
                self.by_slot[s] = r
            lengths, finished = [], []
            for s, r in list(self.by_slot.items()):
                new = b.outputs[s][len(r.out):]
                fresh = not r.out
                r.out.extend(int(t) for t in new)
                r.times.extend([t1] * len(new))
                if new and not (fresh and len(new) == 1):
                    lengths.append(r.prompt_len + len(r.out) - 1)
                if b._host_len[s] != r.prompt_len + len(r.out) - 1:
                    self.mismatches += 1
                if not b.active[s] and s not in jobs:
                    if len(r.out) != r.budget:
                        self.mismatches += 1
                    r.done = True
                    finished.append(s)
            for s in finished:
                del self.by_slot[s]
            self.top_up()
        counters = {k: v - before.get(k, 0) for k, v in b.pool.stats.items()}
        return Step(t0, t1, int(b.step_prefill_tokens[-1]), lengths,
                    counters)


# ------------------------------------------------------------- set-up ----

def instrument() -> None:
    """Host spans around the program's layers, for a traced run's gap
    attribution: wrappers installed from here, the program unchanged."""
    from repro.models import kvcache, model
    from repro.serve import engine

    def wrap(owner, attr, name):
        fn = getattr(owner, attr)
        if getattr(fn, "_bench_span", False):
            return

        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        wrapped._bench_span = True
        setattr(owner, attr, wrapped)

    wrap(engine.ContinuousBatcher, "_admit", "bench.admit")
    wrap(engine.ContinuousBatcher, "_pool_decode_step", "bench.decode")
    wrap(kvcache.PagedKVPools, "stage_cold", "bench.stage_cold")
    wrap(kvcache.PagedKVPools, "demote_boundary", "bench.demote")
    wrap(kvcache.PagedKVPools, "arrays", "bench.table")
    wrap(model, "forward", "bench.forward")


def peaks_for(kind: str) -> dict:
    table = spec._load(spec.HERE / "peaks.json")["kinds"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json "
                         f"(known: {sorted(table)})")
    return table[kind]


# ------------------------------------------------------- correctness ----

def compare(family: ModuleType, shape, seed: int, finished: list,
            length: int, n_pos: int, control: bool = False) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over a seeded sample of finished requests that holds
    the longest; with ``control`` also that of the token the fp8 control
    puts first at each of the same positions, the control's tokens in the
    served tokens' place."""
    out = {"requests_compared": 0, "served_tokens_compared": 0,
           "max_logit_gap": float("nan")}
    if not finished:
        return out
    longest = max(finished, key=lambda r: r.prompt_len + len(r.out))
    rest = [r for r in finished if r is not longest]
    rng = traffic.rng_for(seed, 3)
    picked, n = [longest], len(longest.out)
    for i in rng.permutation(len(rest)):
        if n >= SERVED_TOKENS_COMPARED or len(picked) >= \
                MAX_REQUESTS_COMPARED:
            break
        picked.append(rest[i])
        n += len(rest[i].out)
    w = family.make(shape, seed)
    worst, worst_c = 0.0, 0.0
    for r in picked:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        pos = r.prompt_len - 1 + np.arange(len(r.out))
        ref = family.logits_at(shape, w, seq, pos, length, n_pos)
        worst = max(worst, float(reference.gaps(ref, r.out).max()))
        if control:
            c = family.logits_at(shape, w, seq, pos, length, n_pos,
                                 fp8=True)
            worst_c = max(worst_c,
                          float(reference.gaps(ref, c.argmax(-1)).max()))
    out.update(requests_compared=len(picked), served_tokens_compared=n,
               max_logit_gap=worst)
    if control:
        out["control_max_logit_gap"] = worst_c
    return out


# ---------------------------------------------------------------- run ----

def run(args, t_start: float, compiles: Compiles = None,
        control: bool = False) -> dict:
    """One run; returns the result line's object (without printing).  With
    ``control`` the fp8 control stands in the program's place: ``correct``
    and the compared ``max_logit_gap`` are the control's, and ``program``
    holds the served tokens' own decision and gap."""
    cell = spec.load_cell(args.workload)
    devs = jax.devices()
    dev = devs[0]
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devs) < cell.chips):
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} {dev.platform} device(s)")
    from repro.launch.cache import setup_compile_cache
    from repro.launch.serve import hardware_for, tiered_server
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = compiles or Compiles()
    t_init = time.perf_counter()

    family = cell.family
    cfg = family.program_config(cell.config, args.rehearse)
    shape = family.Shape.of(cfg)
    serving = cell.config["serving"]
    slots = int(serving["slots"])
    max_seq = REHEARSAL_MAX_SEQ if args.rehearse else int(serving["max_seq"])
    scale = REHEARSAL_SCALE if args.rehearse else (1, 1)
    # committed to the chip from the start, as the pools are: an eager op
    # whose inputs change from uncommitted to committed builds a new
    # program, which would otherwise happen in the window's first steps
    plain = jax.device_put(family.make(shape, args.seed), dev)
    params = family.to_program(plain, shape)
    jax.block_until_ready(params)
    t_weights = time.perf_counter()

    deck = traffic.deck(cell.mix, args.seed, shape.vocab, scale)
    arrays = [jnp.asarray(r.tokens) for r in deck]
    sample = traffic.sample(cell.mix, args.seed, shape.vocab, scale)
    jax.block_until_ready(arrays)
    pl = cell.placement
    b, plan = tiered_server(
        cfg, params, [(jnp.asarray(r.tokens), r.budget) for r in sample],
        slots=slots, max_seq=max_seq, fast_frac=float(pl["fast_frac"]),
        hw=hardware_for(dev), all_fast=bool(pl.get("all_fast", False)),
        device=dev)
    t_plan = time.perf_counter()

    mix = cell.mix
    if mix.get("loop") != "closed" or mix.get("think_s", 0) != 0:
        raise SystemExit(f"traffic {cell.mix_name}: only a closed loop with "
                         f"zero think time is generated")
    loop = ClosedLoop(b, deck, arrays, int(mix["clients"]))
    for r in sample:
        loop.track(Req(r.tokens, r.budget, t_submit=t_plan))
    c0 = compiles.snapshot()
    while loop.pending:                 # warm-up: the sample is admitted
        loop.step()
    loop.top_up()
    jax.block_until_ready(b.last_tok)
    c1 = compiles.snapshot()
    log(f"setup: plan hot_window {plan.hot_window}, page_tokens "
        f"{b.page_tokens}, slots {slots}, max_seq {max_seq}")

    trace_dir = None
    if args.trace:
        instrument()
        trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        xtrace.start(trace_dir)
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    steps = []
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            st = loop.step()
            steps.append(st)
            if st.t1 - t_open >= args.seconds:
                break
    t_close = steps[-1].t1
    c2 = compiles.snapshot()
    if args.trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    log(f"setup_s parts: jax_init {t_init - t_start:.4f}, weights "
        f"{t_weights - t_init:.4f}, plan_and_build {t_plan - t_weights:.4f}, "
        f"warmup {t_open - t_plan:.4f} (of which backend compile "
        f"{c1[1] - c0[1]:.4f} s in {c1[0] - c0[0]} programs, persistent-"
        f"cache loads {c1[3] - c0[3]:.4f} s in {c1[2] - c0[2]}); setup_s "
        f"{setup_s:.4f}")
    log(f"window: {len(steps)} steps, {t_close - t_open:.4f} s; backend "
        f"compiles in window {c2[0] - c1[0]} ({c2[1] - c1[1]:.4f} s); "
        f"persistent-cache loads in window {c2[2] - c1[2]} "
        f"({c2[3] - c1[3]:.4f} s)")
    log(f"memory_peak_bytes {peak_bytes}")
    log(f"counters: {b.counters()['page_copies']} pages demoted, "
        f"{b.pool.stats['cold_stages']} cold stages, "
        f"{b.pool.stats['cold_stage_bytes']} bytes staged")

    requests, mismatches = loop.requests, loop.mismatches
    attempted = sum(1 for r in requests if r.t_submit >= t_open)
    finished = [r for r in requests if r.done]
    del loop, b, plan, params, plain, arrays
    gc.collect()

    summary = None
    if args.trace:
        summary = xtrace.reduce(trace_dir)
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    mix_out = cell.mix["output_tokens"]["values"]
    n_pos = max(max(2, o // scale[1]) for o in mix_out)
    checks = compare(family, shape, args.seed, finished, max_seq, n_pos,
                     control)
    limit = float(cell.config["correct"]["max_logit_gap"])

    def decide(gap: float) -> bool:
        return (checks["requests_compared"] >= 1 and mismatches == 0
                and gap <= limit)
    # the control takes the program's place in the decision: its tokens,
    # scored by the same expression, decide ``correct``
    gap = checks["control_max_logit_gap" if control else "max_logit_gap"]
    correct = decide(gap)

    record = Run(family=family, shape=shape, peak=None if args.rehearse
                 else peaks_for(dev.device_kind), setup_s=setup_s,
                 t_open=t_open, t_close=t_close, steps=steps,
                 requests=requests, trace=summary)
    entries = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for entry in entries:
        value = spec.reader(entry).read(record)
        if value is not None and not args.rehearse:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        elif value is not None:
            log(f"rehearsal: {entry['name']} computed (a CPU number, not "
                f"reported)")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": 0, "metrics": metrics, "device": device}
    if summary is not None and not args.rehearse:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": xtrace.top(summary.op_s),
                               "idle_gaps": xtrace.top(summary.idle_s)}
    compared = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "slot_mismatches": {"value": mismatches, "limit": 0},
        "requests_compared": {"value": checks["requests_compared"],
                              "limit": 1},
    }
    if control:
        result["program"] = {"correct": decide(checks["max_logit_gap"]),
                             "max_logit_gap": checks["max_logit_gap"]}
    result["compared"] = compared
    result["served_tokens_compared"] = checks["served_tokens_compared"]
    return result


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def report(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output, with the
    comparison's key last."""
    compared = result.pop("compared")
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    result["compared"] = compared
    print(json.dumps(result), flush=True)
