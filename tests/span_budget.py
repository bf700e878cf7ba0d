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

#: what one steady ``train_step`` opens on the dispatching thread: the root
#: span and the five children that tile it ...
STEADY_STEP_SPANS = ["step/dispatch", "step/end", "step/hooks",
                     "step/prepare", "step/train_step",
                     "step/watchdog_handoff"]
#: ... plus ``watchdog/train_step[N]`` on the watchdog's waiter thread
SPANS_PER_STEADY_STEP = len(STEADY_STEP_SPANS) + 1
#: ceiling on one enter/exit pair; 7 spans x 50 us = 0.5 % of the shortest
#: step the benchmark measures (68 ms)
SPAN_COST_CEILING_S = 50e-6
#: what of the root span its children may leave uncovered (``check_abort``,
#: ``begin_step`` and the children's own enter/exit pairs), on the cheapest
#: of the steady steps; an order of magnitude over the 0.1 ms the v5e
#: machine's host leaves.  What it catches is a section of the step that no
#: child covers any more, which the count above does not see
ROOT_SELF_CEILING_S = 2e-3
ROOT_CHILDREN = ["step/hooks", "step/prepare", "step/dispatch",
                 "step/watchdog_handoff", "step/end"]


def root_self_time(spans, step) -> float:
    """The root span of ``step`` less its direct children, after checking
    that they tile it: each inside the root, in order, none overlapping."""
    (root,) = [sp for sp in spans if sp.get("step") == step
               and sp["name"] == "step/train_step"]
    children = sorted((sp for sp in spans if sp.get("step") == step
                       and sp["parent"] == root["name"]
                       and sp["thread"] == root["thread"]
                       and sp["depth"] == root["depth"] + 1),
                      key=lambda sp: sp["t0"])
    assert [sp["name"] for sp in children] == ROOT_CHILDREN
    edges = [root["t0"]] + [t for sp in children
                            for t in (sp["t0"], sp["t1"])] + [root["t1"]]
    assert edges == sorted(edges), "a child overlaps its neighbour"
    return root["dur_s"] - sum(sp["dur_s"] for sp in children)


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
    # the root's children tile it on every steady step (the first built the
    # step); the cheapest step is the trainer's own self time, not the load
    self_s = min(root_self_time(spans, step) for step in range(2, last + 1))
    assert 0 <= self_s < ROOT_SELF_CEILING_S, self_s
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
