"""Spans of the serving system's own layers, kept in memory.

``span(name, **attrs)`` brackets one piece of host work.  Entering it opens
a ``jax.profiler.TraceAnnotation`` of the same name, so that while a profile
is being taken the span lands in the host plane of the ``.xplane.pb`` on the
clock the device's operations share.  Leaving it appends one ``Record`` to a
bounded in-memory ring, whether or not a profile is running.  Spans nest per
thread: a record names the record of the span that was open around it.

Each record also counts the programs JAX built while it was the innermost
open span: ``built`` by the backend compiler, ``loaded`` from the persistent
compilation cache.  JAX reports ``backend_compile_duration`` for both kinds;
a ``cache_hits`` event just before it marks a load.

An operator reads ``records(lo_ns, hi_ns)`` in-process, with bounds on the
``time.perf_counter_ns`` clock (the clock ``time.perf_counter`` reads).
Nothing is written to a file.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Optional

import jax

RING_RECORDS = 1 << 16     # half an hour of serving at 40 records a second

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass(slots=True)
class Record:
    id: int
    name: str
    attrs: dict
    start_ns: int = 0
    end_ns: int = 0
    parent: Optional[int] = None    # id of the enclosing span's record
    built: int = 0                  # programs compiled inside, innermost
    loaded: int = 0                 # programs read from the persistent cache


_ring: deque = deque(maxlen=RING_RECORDS)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager for one span; ``with span(...) as attrs`` yields the
    record's attribute dict, so the body can add what it learns (pages
    copied, bytes staged) before the span closes."""
    __slots__ = ("record", "_annotation")

    def __init__(self, name: str, **attrs):
        self.record = Record(next(_ids), name, attrs)
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> dict:
        rec = self.record
        stack = _stack()
        if stack:
            rec.parent = stack[-1].id
        stack.append(rec)
        self._annotation.__enter__()
        rec.start_ns = time.perf_counter_ns()
        return rec.attrs

    def __exit__(self, *exc) -> bool:
        rec = self.record
        rec.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        _local.stack.pop()
        _ring.append(rec)
        return False


def add(name: str, start_ns: int, end_ns: int, **attrs) -> Record:
    """Append a record of work that no single span brackets, such as a
    request's life from submission to its last token (no parent)."""
    rec = Record(next(_ids), name, attrs, start_ns, end_ns)
    _ring.append(rec)
    return rec


def records(lo_ns: Optional[int] = None,
            hi_ns: Optional[int] = None) -> list:
    """The ring's records that lie inside ``[lo_ns, hi_ns]`` (either bound
    may be left open), in the order they closed."""
    return [r for r in list(_ring)
            if (lo_ns is None or r.start_ns >= lo_ns)
            and (hi_ns is None or r.end_ns <= hi_ns)]


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _local.hit = True


def _on_duration(event: str, duration: float, **_) -> None:
    if event != _COMPILE:
        return
    hit = getattr(_local, "hit", False)
    _local.hit = False
    stack = getattr(_local, "stack", None)
    if stack:
        if hit:
            stack[-1].loaded += 1
        else:
            stack[-1].built += 1


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
