"""The readers of the program's spans (``repro.obs`` records) on a
hand-built run and synthetic records: per-step division, the window's
clipping, nothing to read without steps, and the median over requests.

The synthetic records lie in the first seconds of the ``perf_counter_ns``
clock, long before any record a live engine in this process writes."""
import pytest

from benchmarks.chip import harness, spec
from repro import obs

MS = 1_000_000                  # nanoseconds
S = 1000 * MS


def _run(t_open: float, t_close: float) -> harness.Run:
    return harness.Run(family=None, shape=None, peak=None, setup_s=0.0,
                       t_open=t_open, t_close=t_close, steps=[], requests=[])


def _reader(name: str):
    entry, = [m for m in spec.benchmark()["per_layer"] if m["name"] == name]
    return spec.reader(entry)


@pytest.fixture(scope="module")
def window():
    """A window [1 s, 2 s] holding four steps, each with a 10 ms forward
    (one built program, one loaded), 2 ms of demotion and 3 ms of waiting
    for tokens; around it, spans that must be left out: a step that began
    before the window, one after it, and their children."""
    def span(name, start, ms, **attrs):
        return obs.add(name, start, start + int(ms * MS), **attrs)

    for k in range(4):
        t = S + 100 * MS + k * 200 * MS
        span("sentinel.step", t, 50)
        fwd = span("sentinel.forward", t + MS, 10)
        fwd.built, fwd.loaded = 1, 1
        span("sentinel.demote", t + 20 * MS, 2, pages=3)
        span("sentinel.sync", t + 30 * MS, 3)
    for t in (S - 50 * MS, 2 * S + 100 * MS):       # outside, or straddling
        span("sentinel.step", t, 100)
        span("sentinel.forward", t + MS, 90).built = 7
        span("sentinel.demote", t + MS, 90)
        span("sentinel.sync", t + MS, 90)
    # requests: rid 100 submitted and prefilled before the window opened,
    # 101 with a stale span of its id from an earlier life, 102 finished
    # after the window closed
    span("sentinel.request", S - 150 * MS, 500, rid=100)
    span("sentinel.prefill", S - 100 * MS, 20, rid=100)
    span("sentinel.prefill", S + 150 * MS, 30, rid=100)
    span("sentinel.prefill", 200 * MS, 40, rid=101)
    span("sentinel.request", S + 400 * MS, 300, rid=101)
    span("sentinel.prefill", S + 450 * MS, 10, rid=101)
    span("sentinel.request", S + 500 * MS, 300, rid=103)
    span("sentinel.prefill", S + 510 * MS, 70, rid=103)
    span("sentinel.request", S + 900 * MS, 300, rid=102)
    span("sentinel.prefill", S + 910 * MS, 1, rid=102)
    return _run(1.0, 2.0)


@pytest.mark.parametrize("name, expected", [
    ("forward_dispatch_ms", 10.0),          # 4 x 10 ms over 4 steps
    ("programs_built_per_step", 2.0),       # 4 x (1 built + 1 loaded) / 4
    ("demote_ms", 2.0),
    ("token_wait_ms", 3.0),
    ("request_prefill_ms", 50.0),           # median of 50, 10 and 70 ms
])
def test_reader_on_synthetic_records(window, name, expected):
    assert _reader(name).read(window) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["forward_dispatch_ms",
                                  "programs_built_per_step", "demote_ms",
                                  "token_wait_ms", "request_prefill_ms"])
def test_nothing_to_read_without_steps(window, name):
    assert _reader(name).read(_run(3.0, 4.0)) is None
