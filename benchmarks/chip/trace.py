"""From the profiler's ``.xplane.pb`` to device busy time, per-op device
time and idle gaps, each gap attributed to the harness's host span that
was open during it.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU each chip is a
plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
operation run (a Pallas kernel under its ``name``, e.g. ``paged_decode``),
and its line ``XLA Modules`` one event per program run (``jit__cold_stage``
for the staging program).  Host planes (``/host:...``) hold the harness's
``jax.profiler.TraceAnnotation`` spans.  Times are nanoseconds on the
profile's one clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

import jax


def start(log_dir: str) -> None:
    """Start the profiler with the Python tracer off: the host spans are
    the harness's own annotations."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found "
                           f"{len(found)}")
    return found[0]


def tpu_devices(profile) -> list:
    """Per TPU chip: ``{"ops": [...], "modules": [...]}``, each a list of
    ``(name, start_ns, end_ns)``.  An op's event is named by its whole HLO
    instruction; the name kept is the part before `` = ``
    (``%paged_decode.14``, ``%while.16``, ``%copy-done``)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key:
                dev[key].extend((e.name.split(" = ", 1)[0], e.start_ns,
                                 e.end_ns) for e in line.events)
        out.append(dev)
    return out


def host_spans(profile, prefix: str) -> list:
    """``(name, start_ns, end_ns)`` of every host event named ``prefix*``."""
    return [(e.name, e.start_ns, e.end_ns)
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(events, lo, hi) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def gaps(busy, lo, hi) -> list:
    """The stretches of ``[lo, hi]`` that no busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def per_name(events) -> dict:
    out: dict = {}
    for n, s, e in events:
        out[n] = out.get(n, 0) + (e - s)
    return out


def attribute(gap_list, spans) -> dict:
    """Nanoseconds of idle per host span: each gap goes to the innermost
    open span at its midpoint.  The harness's spans come from one thread
    and nest, so one sweep with a stack finds it."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    out: dict = {}
    stack, i = [], 0
    for s, e in sorted(gap_list, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while i < len(spans) and spans[i][1] <= mid:
            while stack and stack[-1][2] < spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] < mid:
            stack.pop()
        owner = stack[-1][0] if stack else "(no span)"
        out[owner] = out.get(owner, 0) + (e - s)
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float                   # union of device op time, mean per chip
    devices: int
    op_s: dict = field(default_factory=dict)       # op name -> s per chip
    module_s: dict = field(default_factory=dict)   # program -> s per chip
    idle_s: dict = field(default_factory=dict)     # host span -> s per chip


def reduce(log_dir: str, window: str = "bench.window",
           prefix: str = "bench.", devices=tpu_devices) -> Summary:
    """Reduce the trace under ``log_dir`` over the host span ``window``.
    ``devices`` picks the device events out of the profile."""
    profile = jax.profiler.ProfileData.from_file(xplane_file(log_dir))
    spans = host_spans(profile, prefix)
    wins = [(s, e) for n, s, e in spans if n == window]
    if not wins:
        raise RuntimeError(f"no host span {window!r} in the trace")
    lo, hi = wins[0]
    devs = devices(profile)
    n = max(1, len(devs))
    out = Summary(window_s=(hi - lo) / 1e9, busy_s=0.0, devices=len(devs))

    def add(acc, d):
        for k, v in d.items():
            acc[k] = acc.get(k, 0.0) + v / 1e9 / n

    for dev in devs:
        ops = clip(dev["ops"], lo, hi)
        busy = union((s, e) for _, s, e in ops)
        out.busy_s += sum(e - s for s, e in busy) / 1e9 / n
        add(out.op_s, per_name(ops))
        add(out.module_s, per_name(clip(dev["modules"], lo, hi)))
        add(out.idle_s, attribute(gaps(busy, lo, hi), spans))
    return out


def top(d: dict, k: int = 10) -> list:
    return [[name, v] for name, v in sorted(d.items(), key=lambda x: -x[1])
            [:k]]
