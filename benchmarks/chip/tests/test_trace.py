"""The trace reduction: on synthetic intervals, and on a small profile
recorded here on the CPU, where XLA's CPU client threads stand in for the
device plane a TPU trace has."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import trace


def test_union_and_gaps():
    busy = trace.union([(5, 8), (0, 2), (1, 3), (8, 9), (7, 7)])
    assert busy == [(0, 3), (5, 9)]
    assert trace.gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert trace.gaps(busy, -2, 9) == [(-2, 0), (3, 5)]


def test_attribute_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.step", 10, 50),
             ("bench.decode", 20, 30), ("bench.step", 60, 90)]
    gaps = [(22, 24), (40, 44), (52, 58), (70, 80), (95, 99)]
    got = trace.attribute(gaps, spans)
    assert got == {"bench.decode": 2, "bench.step": 4 + 10,
                   "bench.window": 6 + 4}


def test_clip_and_per_name():
    ev = [("a", 0, 10), ("b", 5, 20), ("a", 30, 40)]
    clipped = trace.clip(ev, 8, 35)
    assert clipped == [("a", 8, 10), ("b", 8, 20), ("a", 30, 35)]
    assert trace.per_name(clipped) == {"a": 7, "b": 12}


def _cpu_devices(profile):
    """XLA's CPU client threads: the ops the CPU ran, as a device's ops."""
    skip = ("ThreadpoolListener", "ThunkExecutor", "end:")
    ops = [(e.name, e.start_ns, e.end_ns)
           for plane in profile.planes if plane.name == "/host:CPU"
           for line in plane.lines if line.name.startswith("tf_XLA")
           for e in line.events
           if e.duration_ns > 0 and not e.name.startswith(skip)]
    return [{"ops": ops, "modules": []}]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("prof"))
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    trace.start(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.work"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    return d


def test_reduce_recorded_profile(recorded):
    s = trace.reduce(recorded, devices=_cpu_devices)
    assert s.devices == 1
    assert 0.15 < s.window_s < 1.0
    assert 0 < s.busy_s < s.window_s
    assert any("dot" in k for k in s.op_s)
    assert s.idle_s.get("bench.sleep", 0) >= 0.14
    # busy and idle partition the window
    assert abs(s.busy_s + sum(s.idle_s.values()) - s.window_s) < 1e-6


def test_reduce_finds_no_tpu_here(recorded):
    s = trace.reduce(recorded)
    assert s.devices == 0 and s.busy_s == 0.0
