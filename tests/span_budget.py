"""The span-overhead budget, shared by tests/test_obs.py and
tests/test_obs_http.py: (spans a steady step opens) x (cost of one span)
stays bounded — each factor held on its own.

The count is deterministic, so it is asserted EXACTLY: a new span on the
step path has to be added here on purpose.  The cost is an absolute ceiling
on the cheapest of several batches (profiler mirror included), with an order
of magnitude of headroom over the 4.4 us measured on the v5e machine's host
(PERF.md section 6) — not a share of an 8-device cpu-sim step of a toy
model, which moved with the box's load and failed under ``-n 6`` (ISSUE 24).
"""

import time

from bagua_tpu.obs import spans as obs_spans

#: what one steady ``train_step`` opens on the dispatching thread ...
STEADY_STEP_SPANS = ["step/dispatch", "step/hooks", "step/train_step",
                     "step/watchdog_handoff"]
#: ... plus ``watchdog/train_step[N]`` on the watchdog's waiter thread
SPANS_PER_STEADY_STEP = len(STEADY_STEP_SPANS) + 1
#: ceiling on one enter/exit pair; 5 spans x 50 us = 0.3 % of the shortest
#: step the benchmark measures (83 ms)
SPAN_COST_CEILING_S = 50e-6


def assert_span_budget(trainer, spans) -> None:
    """``spans``: the ring's spans recorded since the trainer was built,
    after a few steady steps."""
    last = trainer._step_counter
    one_time = ("trace/", "step/build")
    opened = sorted(
        sp["name"] for sp in spans
        if sp.get("step") == last and not sp["name"].startswith(one_time)
        and not sp["name"].startswith("watchdog/"))
    assert opened == STEADY_STEP_SPANS, opened
    deadline = time.monotonic() + 30
    watched = f"watchdog/train_step[{last}]"
    while not any(sp["name"] == watched
                  for sp in obs_spans.recorder.snapshot()):
        assert time.monotonic() < deadline, f"no {watched} span"
        time.sleep(0.01)
    batches = []
    for _ in range(5):  # the cheapest batch: the span's cost, not the load
        t0 = time.perf_counter()
        for _ in range(2000):
            with obs_spans.trace_span("overhead_probe"):
                pass
        batches.append((time.perf_counter() - t0) / 2000)
    per_span = min(batches)
    assert per_span < SPAN_COST_CEILING_S, (
        f"one span costs {per_span * 1e6:.2f} us, over the "
        f"{SPAN_COST_CEILING_S * 1e6:.0f} us ceiling")
