"""Step-span tracer: a host-side structured timeline of what this process
was doing.

Counterpart of the reference's OpenTelemetry span pipeline — the Rust
backend opens a ``tensor_ready`` span per gradient and a custom exporter
POSTs batches to the autotune sidecar (bagua-core-internal/src/lib.rs:305-308,
bagua-opentelemetry/src/exporter/mod.rs:15-59).  Under XLA the compiled step
is opaque, so what a span can honestly time is the HOST side: dispatch,
trace/compile, grad-guard verdict readbacks, async negotiation boundaries,
checkpoint save/restore, elastic rendezvous rounds, watchdog sections — the
exact phases a human (or the autotune-v2 scorer) needs to answer "what was
rank 3 doing when the watchdog fired?".

Design constraints, in order:

* **Never touches the device.**  ``trace_span`` records two
  ``time.monotonic()`` reads and a deque append — no jnp ops, no readbacks —
  so the compiled step program is IDENTICAL with tracing on or off
  (jaxpr-equality-pinned in ``tests/test_obs.py``).  Spans opened inside
  traced code (the per-bucket collective launches in the overlap scheduler)
  run at *trace time* and document the launch schedule, not per-step
  runtime.
* **Bounded.**  Spans land in a ring buffer (``BAGUA_OBS_RING``, default
  512); the oldest drop and the drop count is kept, so a long run can crash
  at step 10^6 and still leave a readable tail.  The few spans that happen
  seldom and explain much (:data:`RARE_SPANS`: the interpreter's pauses of
  ``obs/pauses.py``, a step's build, a stall record) are kept in a second
  deque of 128 that the steps' chatter cannot push out.
* **Import-light.**  No jax import: the launcher and the watchdog waiter
  thread open spans too.
* **On the profiler's clock when there is one.**  A span mirrors itself to
  ``jax.profiler.TraceAnnotation("bagua/<name>")`` when the process has
  already imported jax (looked up in ``sys.modules``, never imported
  here), so a ``BAGUA_PROFILE_DIR`` / ``jax.profiler`` capture shows the
  program's own spans on the host plane next to the device timeline.
  With no capture running the annotation is a ~0.4 us no-op.

``BAGUA_OBS=off`` turns every hook into a cheap early return (one module
flag read) — the default-compatible mode.
"""

from __future__ import annotations

import contextlib
import operator
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from .. import env as _env

__all__ = ["trace_span", "trace_step_span", "phase_scope", "area_of",
           "in_loop", "AREAS", "LOSS_TAIL_SCOPE", "ACCUM_SCOPE",
           "POS_EMBED_SCOPE", "EXIT_SCOPE", "LOOP_SCOPE",
           "DIFFUSION_INPUT_SCOPE",
           "recorder", "span_ring", "SpanRecorder", "enabled", "set_enabled",
           "set_current_step", "set_ledger_sink", "RARE_SPANS",
           "RARE_CAPACITY", "finished_span"]

#: prefix of every span's mirror on the profiler's host plane
ANNOTATION_PREFIX = "bagua/"
#: name of the per-step ``StepTraceAnnotation`` the root span opens
STEP_ANNOTATION = "bagua_train"

#: spans that happen seldom and explain much: the interpreter's pauses, a
#: step's build, a stall record.  A steady step opens 7 spans into a ring of
#: 512, so a pause at step 30 would be gone by step 110; these names are kept
#: in a second bounded deque beside the ring (:class:`SpanRecorder`)
RARE_SPANS = frozenset({"host/gc", "host/blocked", "step/build", "step/stall"})
#: how many rare spans are kept (oldest drop)
RARE_CAPACITY = 128

#: resolved master switch; None = not yet read from BAGUA_OBS
_ENABLED: Optional[bool] = None
_ENABLED_LOCK = threading.Lock()


def enabled() -> bool:
    """Whether the observability plane is on (``BAGUA_OBS``, default on).
    Cached after the first read — the check sits on the train-step hot
    path."""
    global _ENABLED
    if _ENABLED is None:
        with _ENABLED_LOCK:
            if _ENABLED is None:
                _ENABLED = _env.get_obs_mode() == "on"
    return _ENABLED


def set_enabled(value: Optional[bool]) -> None:
    """Override the cached switch (tests); ``None`` re-reads ``BAGUA_OBS``
    on the next :func:`enabled` call."""
    global _ENABLED
    with _ENABLED_LOCK:
        _ENABLED = value


def _cached_rank() -> int:
    global _RANK
    if _RANK is None:
        try:
            _RANK = int(_env.get_rank())
        except Exception:  # noqa: BLE001 - spans must never raise
            _RANK = 0
    return _RANK


_RANK: Optional[int] = None

#: the trainer's current step counter, stamped onto every span opened while
#: that step is being driven (threads like the watchdog waiter inherit it —
#: "which step was in flight" is exactly what a post-mortem wants to know)
_CURRENT_STEP: Optional[int] = None


def set_current_step(step: Optional[int]) -> None:
    global _CURRENT_STEP
    _CURRENT_STEP = step


#: goodput-ledger sink (``bagua_tpu.obs.ledger.install()`` sets it): spans
#: whose names map to a ledger class feed their wall seconds on close.
#: None (the default) keeps the enter/exit pair at its pre-ledger cost.
_LEDGER_SINK = None


def set_ledger_sink(sink) -> None:
    """Install (or clear, with None) the goodput-ledger span sink — an
    object with ``span_enter(name) -> cls|None`` and
    ``span_exit(cls, dur_s)``."""
    global _LEDGER_SINK
    _LEDGER_SINK = sink


class SpanRecorder:
    """Thread-safe bounded ring buffer of finished spans.

    One per process (:data:`recorder`), like the telemetry counters; the
    flight recorder snapshots it on failure, the exporter may sample it.
    Capacity comes from ``BAGUA_OBS_RING`` lazily (the module imports
    before test harnesses set their env).

    Spans named in :data:`RARE_SPANS` go to a second deque of
    :data:`RARE_CAPACITY` that the steps' chatter cannot push out;
    :meth:`snapshot` returns both, merged by ``t0``.  That deque is written
    and read WITHOUT the lock (:meth:`record_rare`): the collector's
    callback records into it, and a collection can start at any bytecode
    boundary of a thread that already holds the lock."""

    def __init__(self, capacity: Optional[int] = None):
        self._lock = threading.Lock()
        self._capacity = capacity
        self._spans: Optional[deque] = (
            deque(maxlen=capacity) if capacity else None
        )
        self._rare: deque = deque(maxlen=RARE_CAPACITY)
        self._dropped = 0
        self._local = threading.local()
        #: spans currently OPEN (entered, not yet exited), keyed by the
        #: span object: the flight recorder reports these as "what was in
        #: flight when the defense tripped" — a wedged watched section
        #: never reaches the ring, but it IS the post-mortem's headline
        self._open: Dict[int, Dict[str, Any]] = {}

    def _buf(self) -> deque:
        if self._spans is None:
            self._capacity = max(1, _env.get_obs_ring_size())
            self._spans = deque(maxlen=self._capacity)
        return self._spans

    def set_capacity(self, capacity: int) -> None:
        """Re-size the ring (tests); drops existing spans."""
        with self._lock:
            self._capacity = int(capacity)
            self._spans = deque(maxlen=self._capacity)
            self._dropped = 0

    # -- nesting bookkeeping (per thread, so depth and parent render
    # correctly even with the watchdog waiter recording concurrently) ------

    def _enter(self, name: str):
        """Push ``name``; returns (depth, enclosing span's name or None)."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        depth, parent = len(stack), stack[-1] if stack else None
        stack.append(name)
        return depth, parent

    def _exit(self) -> None:
        stack = getattr(self._local, "stack", None)
        if stack:
            stack.pop()

    def open_span(self, key: int, stub: Dict[str, Any]) -> None:
        with self._lock:
            self._open[key] = stub

    def close_span(self, key: int, span: Dict[str, Any]) -> None:
        """Pop the open stub and append the finished span — one lock
        acquisition for both."""
        with self._lock:
            self._open.pop(key, None)
            if span["name"] in RARE_SPANS:
                self._rare.append(span)
                return
            buf = self._buf()
            if len(buf) == buf.maxlen:
                self._dropped += 1
            buf.append(span)

    def record_rare(self, span: Dict[str, Any]) -> None:
        """Keep a finished span (:func:`finished_span`) that was timed by
        its recorder and not by a ``with`` block: a pause, a stall record.
        Takes no lock (a deque's append is atomic), so the collector's
        callback may call it."""
        self._rare.append(span)

    def snapshot(self) -> List[Dict[str, Any]]:
        """Copies of every retained (finished) span, the ring's and the
        rare ones, by start time."""
        with self._lock:
            # list() of a deque is one call: no callback appends under it
            kept = list(self._buf()) + list(self._rare)
        kept.sort(key=operator.itemgetter("t0"))
        return [dict(s) for s in kept]

    def active_snapshot(self) -> List[Dict[str, Any]]:
        """Copies of spans currently in flight (entered, not exited),
        oldest first — the sections a hang is pinning."""
        with self._lock:
            return sorted((dict(s) for s in self._open.values()),
                          key=lambda s: s["t0"])

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            if self._spans is not None:
                self._spans.clear()
            self._rare.clear()
            self._open.clear()
            self._dropped = 0


def finished_span(name: str, t0: float, t1: float,
                  step: Optional[int] = None, **attrs) -> Dict[str, Any]:
    """A span of the ring's shape from a clock pair its recorder took
    itself (``time.monotonic()``, the ring's clock): top level, on the
    calling thread, at the current step unless ``step`` is given."""
    span = {
        "name": name, "t0": t0, "t1": t1, "dur_s": t1 - t0,
        "rank": _cached_rank(),
        "step": _CURRENT_STEP if step is None else step,
        "depth": 0, "parent": None,
        "thread": threading.current_thread().name,
    }
    if attrs:
        span["attrs"] = attrs
    return span


#: process-wide span ring (one per process, like ``telemetry.counters``);
#: ``span_ring`` is the collision-free alias the package re-exports
#: (``obs.recorder`` is the flight-recorder MODULE)
recorder = SpanRecorder()
span_ring = recorder


def _open_annotations(name: str, step_num: Optional[int],
                      **metadata) -> tuple:
    """The span's mirror on the profiler's clock, entered: a
    ``TraceAnnotation("bagua/<name>")`` (inside a ``StepTraceAnnotation``
    for the root span of a train step), innermost last; ``metadata`` rides
    the annotation.  Empty in a process that has not imported jax — this
    module never does."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)  # None while jax is importing
    if profiler is None:
        return ()
    opened = ()
    if step_num is not None:
        # (an annotation's clock starts when it is constructed: the step
        # first, so that it encloses the span)
        step = profiler.StepTraceAnnotation(STEP_ANNOTATION,
                                            step_num=step_num)
        step.__enter__()
        opened = (step,)
    annotation = profiler.TraceAnnotation(ANNOTATION_PREFIX + name,
                                          **metadata)
    annotation.__enter__()
    return (annotation,) + opened


class _Span:
    """The context manager behind :func:`trace_span` — a plain class with
    ``__slots__`` instead of ``contextlib.contextmanager`` because the
    enter/exit pair sits on the train-step hot path (measured in
    ``tests/test_obs.py`` against the <2%-of-step-time budget)."""

    __slots__ = ("name", "attrs", "t0", "step", "ledger_cls", "depth",
                 "parent", "step_num", "annotations", "dur_s")

    def __init__(self, name: str, attrs: Dict[str, Any],
                 step_num: Optional[int] = None):
        self.name = name
        self.attrs = attrs
        self.step_num = step_num
        self.dur_s = None

    def __enter__(self):
        self.step = self.attrs.pop("step", _CURRENT_STEP)
        self.depth, self.parent = recorder._enter(self.name)
        self.annotations = _open_annotations(self.name, self.step_num)
        # ledger ownership resolves at open (outermost mapped span wins);
        # one global read when no sink is installed
        self.ledger_cls = (
            _LEDGER_SINK.span_enter(self.name) if _LEDGER_SINK else None
        )
        self.t0 = time.monotonic()
        recorder.open_span(id(self), {
            "name": self.name,
            "t0": self.t0,
            "rank": _cached_rank(),
            "step": self.step,
            "depth": self.depth,
            "parent": self.parent,
            "thread": threading.current_thread().name,
        })
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic()
        self.dur_s = t1 - self.t0
        for annotation in self.annotations:
            annotation.__exit__(exc_type, exc, tb)
        recorder._exit()
        if self.ledger_cls is not None and _LEDGER_SINK is not None:
            _LEDGER_SINK.span_exit(self.ledger_cls, t1 - self.t0)
        span = {
            "name": self.name,
            "t0": self.t0,
            "t1": t1,
            "dur_s": self.dur_s,
            "rank": _cached_rank(),
            "step": self.step,
            "depth": self.depth,
            "parent": self.parent,
            "thread": threading.current_thread().name,
        }
        if exc_type is not None:
            span["error"] = exc_type.__name__
        if self.attrs:
            span["attrs"] = self.attrs
        recorder.close_span(id(self), span)
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL = _NullSpan()


def trace_span(name: str, **attrs):
    """Open a structured span::

        with trace_span("step/bucket_collective", bucket=i, bytes=n):
            ...

    Records monotonic start/end, duration, rank, the trainer's current step
    (override with ``step=``), nesting depth, the enclosing span's name
    (``parent``), thread name, and the given key=value attrs into the
    process ring buffer, and mirrors itself to the profiler as
    ``bagua/<name>``.  ``with ... as span`` yields the span, whose
    ``dur_s`` is set on exit (None while ``BAGUA_OBS=off``: the shared null
    context yields None).  Attrs must be host values (ints/floats/strings)
    — never tracers."""
    if not enabled():
        return _NULL
    return _Span(name, attrs)


def trace_step_span(step_num: int):
    """The root span of one ``BaguaTrainer.train_step`` call,
    ``step/train_step``: a :func:`trace_span` that also opens the
    profiler's ``StepTraceAnnotation("bagua_train", step_num=...)``, so a
    capture groups host and device activity by training step."""
    if not enabled():
        return _NULL
    return _Span("step/train_step", {"step": step_num}, step_num)


@contextlib.contextmanager
def phase_scope(scope: str, span: Optional[str] = None, **attrs):
    """Name a phase of the compiled step, for code that runs while JAX
    TRACES the step: a ``jax.named_scope(scope)`` — metadata only, the
    ``op_name`` path of every instruction traced inside, so a device trace
    and the optimized HLO text can be read by phase (``bagua.loss``,
    ``bagua.layout``, ``bagua.comm/bucket_<i>``, ``bagua.optimizer``,
    ``bagua.guard``) and by area (``bagua.moe/<part>`` and the plain
    scopes of the table below, which no reader of the phases matches) —
    and, where ``span`` is given, the trace-time ring span of the same
    site (launch order and byte accounting of the schedule).  One
    construct for both, so the schedule and the program cannot name
    different things.  The scope is unconditional: the program is the
    same with ``BAGUA_OBS`` on or off."""
    import jax  # tracing code only: jax is imported by whoever traces

    with trace_span(span, **attrs) if span else _NULL, jax.named_scope(scope):
        yield


# ---- the model's areas in the compiled step ---------------------------------
#
# Beside the phases, a compiled instruction's ``op_name`` says which part of
# the model it is: flax writes the module path that ``models/transformer.py``
# fixes with ``name=`` (the parameter tree's names, which checkpoints hold
# stable), ``MoEMLP`` opens ``bagua.moe/<part>``, and three plain scopes name
# what no module does.  None of the three matches ``bagua\.\w+``: a reader of
# the phases (innermost ``bagua.*`` component) does not see them.

#: the cross-entropy and mean after the logits (``models.transformer.loss_tail``)
LOSS_TAIL_SCOPE = "loss_tail"
#: the micro-batch accumulation of ``accum_steps > 1``: the carry's zeros, the
#: micro-batch reshape and slices, the gradient adds, the final division
ACCUM_SCOPE = "grad_accum"
#: slice and add of ``TransformerLM``'s own learned position table
POS_EMBED_SCOPE = "pos_embed"
#: the exit distribution of a looped model over its passes, its entropy and
#: the weighting of the passes' cross-entropies (``looped_lm_loss_fn``)
EXIT_SCOPE = "exit_dist"
#: the plain scope around the blocks of a looped model's pass
#: (``TransformerConfig.n_passes``): a component of the path that names no
#: area (the modules inside it do) and that no reader of the phases matches.
#: The passes are one scanned body, so it names the body and not the pass
LOOP_SCOPE = "loop_body"
#: the assembly of a block-diffusion model's input ``[x ; x~]`` from the
#: batch's tokens and noise (``block_diffusion_loss_fn``): the mask id put
#: in, the two halves laid end to end
DIFFUSION_INPUT_SCOPE = "diffusion_input"
#: the scope whose NEXT component is the part of an expert layer
MOE_SCOPE = "bagua.moe"
MOE_PARTS = ("route", "dispatch", "experts", "combine", "shared")

#: component of an ``op_name`` path -> the area it names.  The contract
#: ``area_of`` reads; ``tests/test_step_scopes.py`` holds the compiled step
#: to it and ``docs/observability.md`` has it as a table.
AREA_COMPONENTS = {
    "embed": "embed", POS_EMBED_SCOPE: "embed",
    DIFFUSION_INPUT_SCOPE: "embed",
    "attn_norm": "attn", "attn": "attn", "attn_post_norm": "attn",
    "linear_attn_norm": "linattn", "linear_attn": "linattn",
    "linear_attn_post_norm": "linattn",
    "ssm_norm": "ssm", "ssm": "ssm",
    "mlp_norm": "mlp", "mlp": "mlp", "mlp_post_norm": "mlp",
    "final_norm": "head", "lm_head": "head", LOSS_TAIL_SCOPE: "head",
    "exit_gate": "exit", EXIT_SCOPE: "exit",
    ACCUM_SCOPE: "accum",
}
AREAS = ("embed", "attn", "linattn", "ssm", "mlp") + tuple(
    f"moe/{part}" for part in MOE_PARTS) + ("head", "exit", "accum")


def in_loop(op_name: Optional[str]) -> bool:
    """Whether an instruction's ``op_name`` path lies inside a looped
    model's pass (a ``loop_body`` component), forward, backward or replay;
    False for the heads, the exit gate and a model that is not looped.
    Pure, like :func:`area_of`."""
    return LOOP_SCOPE in (op_name or "").split("/")


def area_of(op_name: Optional[str]) -> Optional[str]:
    """The area of the model an instruction's ``op_name`` path names, one
    of :data:`AREAS`, or None where no component names one (the optimizer,
    the bucket layout, a residual add under a bare ``block_<i>``).  A
    ``bagua.moe`` component decides wherever it sits (an expert layer's
    router may read another module's input); otherwise the innermost
    component of :data:`AREA_COMPONENTS` does.  JAX's wrappers
    (``jvp(...)``, ``transpose(...)``, ``checkpoint/rematted_computation``)
    are components of their own, so forward, backward and replay of an
    area read alike.  Pure: works on a path copied out of a profile viewer."""
    if not op_name:
        return None
    parts = op_name.split("/")
    if MOE_SCOPE in parts:
        inner = len(parts) - parts[::-1].index(MOE_SCOPE)  # what follows it
        part = parts[inner] if inner < len(parts) else None
        return f"moe/{part}" if part in MOE_PARTS else None
    for part in reversed(parts):
        area = AREA_COMPONENTS.get(part)
        if area is not None:
            return area
    return None
