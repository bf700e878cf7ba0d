"""Telemetry producer: per-tensor gradient-readiness spans for the autotuner.

Counterpart of the reference's OpenTelemetry span pipeline: the Rust backend
opens a ``tensor_ready`` span per gradient as the backward pass marks it
(bagua-core-internal/src/lib.rs:305-308), a custom exporter POSTs the batch to
the autotune sidecar (bagua-opentelemetry/src/exporter/mod.rs:15-59), and the
service re-orders buckets by the observed readiness order
(service/autotune_service.py:274-294, autotune_task_manager.py:167-172).

Under XLA the backward pass is one fused program — there is no per-tensor
runtime event to hook.  What *is* observable, and is exactly the quantity the
consumer needs, is each tensor's position in the backward schedule: the cost
of backpropagating from the loss to that tensor alone.  Differentiating the
loss w.r.t. a single leaf compiles a program containing the full forward plus
the backward chain only as deep as that leaf, so its static cost (XLA's FLOP
count) grows monotonically with backward depth — tensors near the loss (ready
first) cost least.  We use that cost as the span timestamp: deterministic, no
timing noise, no instrumentation in the hot path.  Wall-clock execution time
is the fallback when the cost model is unavailable.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Union

logger = logging.getLogger(__name__)

# NOTE: jax is imported lazily inside the span functions — the launcher
# process consumes the counters below and must not pay (or depend on) a
# jax import just to count membership transitions.


class CounterSnapshot(dict):
    """A counters snapshot: a plain ``name -> value`` dict (so every
    existing consumer — JSON dumps, delta arithmetic — keeps working)
    carrying a monotonic ``collected_at`` stamp, so the metrics exporter
    and flight recorder can order/age snapshots without a second clock
    read racing the lock."""

    def __init__(self, values: Dict[str, Union[int, float]],
                 collected_at: float):
        super().__init__(values)
        self.collected_at = collected_at


class TelemetryCounters:
    """Process-wide named counters/gauges (thread-safe).

    The reference exports OTel metrics next to its spans; here the
    consumers are in-process (the elastic launcher's membership/resize
    accounting, the obs exporter, tests, the drill scripts' JSON
    artifacts), so a dict under a lock is the whole implementation.
    ``incr`` is for monotonic event counts (``elastic/resizes``),
    ``set_gauge`` for last-value readings (``elastic/world_nnodes``);
    every name is declared in
    :data:`bagua_tpu.obs.export.METRIC_REGISTRY` (bagua-lint's
    ``unregistered-counter`` rule enforces it)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._values: Dict[str, Union[int, float]] = {}

    def incr(self, name: str, n: int = 1) -> Union[int, float]:
        with self._lock:
            self._values[name] = self._values.get(name, 0) + n
            return self._values[name]

    def add_nowait(self, name: str, n: Union[int, float]) -> bool:
        """:meth:`incr` that never waits; False where the lock was taken
        and nothing was added.  For the collector's callback
        (``obs/pauses.py``): a collection starts at any bytecode boundary,
        also on a thread that is inside one of the methods here."""
        if not self._lock.acquire(blocking=False):
            return False
        try:
            self._values[name] = self._values.get(name, 0) + n
        finally:
            self._lock.release()
        return True

    def incr_many(self, updates: Dict[str, Union[int, float]]) -> None:
        """Batch increment under ONE lock acquisition — for writer loops
        (fault-plan arming, exporter self-accounting) that would otherwise
        take the lock once per metric."""
        with self._lock:
            for name, n in updates.items():
                self._values[name] = self._values.get(name, 0) + n

    def set_gauge(self, name: str, value: Union[int, float]) -> None:
        with self._lock:
            self._values[name] = value

    def get(self, name: str) -> Union[int, float]:
        with self._lock:
            return self._values.get(name, 0)

    def snapshot(self) -> CounterSnapshot:
        """Point-in-time copy with a monotonic ``collected_at`` stamp
        (still a plain dict to every old consumer)."""
        with self._lock:
            return CounterSnapshot(self._values, time.monotonic())

    def reset(self) -> None:
        with self._lock:
            self._values.clear()


#: process-wide registry (one per process, like the global watchdog)
counters = TelemetryCounters()


def _leaf_cost_flops(fn: Callable, leaf) -> Optional[float]:
    """Static FLOP count of ``jit(fn)(leaf)`` via XLA's cost model."""
    import jax

    try:
        compiled = jax.jit(fn).lower(leaf).compile()
        analysis = compiled.cost_analysis()
        flops = analysis.get("flops")
        return float(flops) if flops is not None else None
    except Exception as e:  # pragma: no cover - backend-dependent
        logger.debug("cost_analysis unavailable (%s)", e)
        return None


def _leaf_cost_walltime(fn: Callable, leaf, repeats: int = 3) -> float:
    import jax

    compiled = jax.jit(fn)
    jax.block_until_ready(compiled(leaf))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(leaf))
        best = min(best, time.perf_counter() - t0)
    return best


def _first_use_costs(loss_fn, params, batch) -> Optional[List[float]]:
    """Readiness cost per leaf from ONE jaxpr trace (no compiles).

    Reverse-mode autodiff produces gradients in roughly the reverse of
    forward execution order, and a parameter's forward position is the index
    of the first equation consuming it — so readiness rank = descending
    first-use index.  One trace regardless of model size (BERT-Large has
    ~400 leaves; per-leaf compilation would block the first step for hours).
    """
    import jax

    leaves, _ = jax.tree_util.tree_flatten(params)
    try:
        closed = jax.make_jaxpr(lambda p: loss_fn(p, batch))(params)
    except Exception as e:  # pragma: no cover - loss_fn may need real arrays
        logger.debug("telemetry: trace failed (%s)", e)
        return None
    jaxpr = closed.jaxpr
    invars = jaxpr.invars[: len(leaves)]  # flattened params come first
    first_use = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jax.extend.core.Var):
                continue
            if v not in first_use:
                first_use[v] = i
    n = len(jaxpr.eqns) + 1
    # used earlier in forward -> gradient ready LATER -> larger cost
    return [float(n - first_use.get(v, n)) for v in invars]


def profile_tensor_execution_order(
    loss_fn: Callable,
    params: Any,
    batch: Any,
    max_tensors: int = 512,
    mode: str = "static",
) -> List[Dict]:
    """Measure per-tensor gradient readiness order; returns spans (dicts with
    the reference's ``BaguaCoreTelemetrySpan`` shape) sorted by readiness.

    ``loss_fn(params, batch) -> scalar`` must be the training loss;
    ``params`` the user-shaped param pytree.  ``mode="static"`` (default)
    derives the order from one jaxpr trace — O(1) compiles, safe to run
    inline.  ``mode="flops"`` compiles a grad-to-leaf program per tensor and
    uses XLA's FLOP count (more precise, one compile per leaf — only for
    offline analysis of small models).
    """
    import jax

    from .tensor import _name_of_path

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    names_all = [_name_of_path(path) for path, _ in flat]

    if mode not in ("static", "flops"):
        raise ValueError(f"unknown telemetry mode {mode!r}")

    if mode == "static":
        costs = _first_use_costs(loss_fn, params, batch)
        names = names_all
        if costs is None:
            mode = "flops"  # trace failed; fall through to measurement

    if mode == "flops":
        if len(flat) > max_tensors:
            logger.warning(
                "telemetry: profiling only the %d largest of %d tensors",
                max_tensors, len(flat),
            )
            flat = sorted(flat, key=lambda kv: -kv[1].size)[:max_tensors]
        names = [_name_of_path(path) for path, _ in flat]

        def grad_fns():
            for path, leaf in flat:

                def grad_wrt_leaf(v, _path=path):
                    patched = _set_leaf(params, _path, v)
                    return loss_fn(patched, batch)

                yield jax.grad(grad_wrt_leaf), leaf

        # one consistent unit across ALL leaves: FLOPs when the cost model
        # answers for every leaf, else wall-time nanoseconds for every
        # leaf — mixing units would produce a garbage ordering
        costs = []
        for g, leaf in grad_fns():
            cost = _leaf_cost_flops(g, leaf)
            if cost is None:
                costs = []
                break
            costs.append(cost)
        if not costs:
            costs = [
                _leaf_cost_walltime(g, leaf) * 1e9  # ns: int() keeps order
                for g, leaf in grad_fns()
            ]

    spans = [
        {
            "trace_id": 0,
            "action": "tensor_ready",
            "tensor_name": name,
            "start_time": int(cost),
            "end_time": int(cost),
        }
        for name, cost in zip(names, costs)
    ]
    spans.sort(key=lambda s: s["start_time"])
    return spans


def _set_leaf(tree, target_path, value):
    """Replace the leaf at ``target_path`` with ``value`` (functional)."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [value if path == target_path else leaf for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)
