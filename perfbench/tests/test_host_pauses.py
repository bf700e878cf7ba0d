"""perfbench/host_pauses.py and the five readers on it, over a ring filled by
hand: which steps are the window's, what is left out, and what a program
without the spans reads."""

import sys
import types

import pytest

from perfbench import cells, host_pauses
from perfbench.trace_reduce import Chip, Trace
from perfbench.trace_reduce import parse_op as Op


def reader(name):
    return cells.load_plugin("layer_metrics", name)


@pytest.fixture
def ring():
    from bagua_tpu.obs import spans

    spans.set_enabled(True)
    spans.span_ring.clear()
    yield spans
    spans.span_ring.clear()
    spans.set_enabled(None)


def ctx_of(window_calls, profiled=None):
    """A reader context whose window made ``window_calls`` calls and whose
    trace, if any, holds ``profiled`` executions of the step program."""
    trace = None
    if profiled is not None:
        modules = [Op("jit_bagua_step(1)", 100.0 * i, 100.0 * i + 90.0)
                   for i in range(profiled)]
        trace = Trace({0: Chip([], modules)}, [])
    return types.SimpleNamespace(
        spans={"bench/train_step": [0.001] * window_calls}, trace=trace)


def fill(ring, steps, pauses):
    """Root and ``step/prepare`` spans of steps 1..``steps`` (2 ms each, at
    second ``step``), and ``pauses``: (name, step, seconds, attrs)."""
    for step in range(1, steps + 1):
        ring.span_ring.close_span(0, {**ring.finished_span(
            "step/train_step", float(step), step + 0.004, step=step)})
        ring.span_ring.close_span(0, {**ring.finished_span(
            "step/prepare", step + 0.001, step + 0.003, step=step),
            "parent": "step/train_step", "depth": 1})
    for name, step, seconds, attrs in pauses:
        ring.span_ring.record_rare(ring.finished_span(
            name, step + 0.5, step + 0.5 + seconds, step=step, **attrs))


# 40 steps: 10 of set-up, a window of 18 (11..28), 12 profiled (29..40)
PAUSES = [
    ("host/gc", 3, 0.900, {"generation": 2}),       # set-up: the compile's
    ("host/gc", 11, 0.120, {"generation": 2}),      # the window's first step
    ("host/gc", 20, 0.045, {"generation": 1}),
    ("host/gc", 28, 0.700, {"generation": 2}),      # the last: start_trace's
    ("host/gc", 33, 0.300, {"generation": 2}),      # a profiled step
    ("host/blocked", 12, 0.060, {"cpu_s": 0.07}),
    ("host/blocked", 27, 0.210, {"cpu_s": 0.0}),
    ("host/blocked", 29, 5.000, {"cpu_s": 5.0}),    # profiled
    ("step/stall", 10, 2.000, {"baseline_p50": 0.1}),   # warm-up's last
    ("step/stall", 15, 3.100, {"baseline_p50": 0.1}),
    ("step/stall", 21, 0.250, {"baseline_p50": 0.1}),
    ("step/stall", 28, 1.300, {"baseline_p50": 0.1}),   # start_trace
]


def test_the_windows_steps_are_counted_back_from_the_profiled_ones(ring):
    fill(ring, 40, PAUSES)
    ctx = ctx_of(18, profiled=12)
    assert [s["step"] for s in host_pauses.window_spans(ctx, "host/gc")] \
        == [11, 20]
    assert reader("gc_pause_max_ms").reduce(ctx) == pytest.approx(120.0)
    assert reader("interp_blocked_max_ms").reduce(ctx) == pytest.approx(210.0)
    # less the detector's median at the time: what the stall cost
    assert reader("stall_max_ms").reduce(ctx) == pytest.approx(3000.0)
    assert reader("stalls_in_window").reduce(ctx) == 2
    assert reader("trainer_prepare_ms").reduce(ctx) == pytest.approx(2.0)


def test_without_a_trace_the_read_is_shifted_by_the_profiled_steps(ring):
    """The rehearsal has no device trace to count them in: steps 23..39 are
    read for 11..27, which the docstrings say."""
    fill(ring, 40, PAUSES)
    ctx = ctx_of(18)
    assert [s["step"] for s in host_pauses.window_spans(ctx, "host/gc")] \
        == [28, 33]
    assert reader("stalls_in_window").reduce(ctx) == 1


def test_a_steady_window_reads_zero_and_not_nothing(ring):
    fill(ring, 40, [])
    ctx = ctx_of(18, profiled=12)
    for name in ("gc_pause_max_ms", "interp_blocked_max_ms", "stall_max_ms"):
        assert reader(name).reduce(ctx) == 0.0
    assert reader("stalls_in_window").reduce(ctx) == 0


def test_a_program_without_the_spans_reads_nothing(ring, monkeypatch):
    """The parent of PR 52 has no ``bagua_tpu.obs.pauses``: every reader
    returns None, so its line leaves the metric out, and none raises."""
    fill(ring, 40, PAUSES)
    monkeypatch.setitem(sys.modules, "bagua_tpu.obs.pauses", None)
    ctx = ctx_of(18, profiled=12)
    for name in ("gc_pause_max_ms", "interp_blocked_max_ms", "stall_max_ms",
                 "stalls_in_window"):
        assert reader(name).reduce(ctx) is None
    ring.span_ring.clear()
    assert reader("trainer_prepare_ms").reduce(ctx) is None


def test_an_empty_ring_or_window_reads_nothing(ring):
    assert host_pauses.window_spans(ctx_of(18, 12), "host/gc") is None
    fill(ring, 40, PAUSES)
    assert host_pauses.window_spans(ctx_of(0, 12), "host/gc") is None
