"""HBM memory accounting: does the bucket-flat layout fit the device?

Three complementary views, cheapest first:

* **static footprint** (:func:`static_footprint`) — the resident training
  state's per-device bytes computed from host metadata alone: the live
  ``TrainState`` leaves (params / optimizer state / algorithm state —
  per-device shard sizes, so stacked-gossip axes and sharded ZeRO chunks
  count once, not world-size times) plus the transient per-bucket gradient
  flats the compiled step materializes (:func:`plan_flat_bytes` over the
  ``BucketPlan``).  Exact and testable on cpu-sim — the number an operator
  sizes a config against before ever compiling.
* **compiled-step analysis** — XLA's ``compile().memory_analysis()``
  per step-cache entry, harvested alongside the cached cost analysis in
  ``BaguaTrainer.step_cost_analysis`` when the backend provides one
  (TPU does; cpu-sim reports null-with-rationale).
* **live peaks** (:func:`live_memory_stats`) — ``device.memory_stats()``
  polled off the hot path (the trainer's ~2 s beacon cadence): the
  high-water mark (:func:`peak_bytes` — NOT ``peak_bytes_in_use`` alone,
  which on a TPU never sees a running program's temporaries) and the
  headroom against ``bytes_limit``.  TPU runtimes expose it; cpu-sim
  returns null-with-rationale.

Footprint and headroom ride the per-rank obs summary → health beacon →
fleet snapshot as gauges.  Host-side only: nothing here touches the
compiled step.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional

import numpy as np

logger = logging.getLogger(__name__)

__all__ = [
    "plan_flat_bytes", "tree_device_bytes", "static_footprint",
    "compiled_memory_analysis", "live_memory_stats", "peak_bytes",
]


def plan_flat_bytes(plan) -> int:
    """Bytes of one full set of flat bucket buffers for a
    :class:`~bagua_tpu.bucket.BucketPlan` — padding included (the padded
    numel IS what the compiled step materializes per bucket)."""
    return int(sum(
        b.padded_numel * np.dtype(b.dtype).itemsize for b in plan.buckets
    ))


def tree_device_bytes(tree) -> int:
    """Per-device bytes of a pytree of arrays: each leaf counts its LOCAL
    shard (``addressable_shards[0]``), so a replicated leaf counts its
    full size, a stacked/sharded leaf its per-device slice — the HBM a
    single chip actually holds.  Host metadata only (shapes/dtypes), no
    readbacks."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, "nbytes"):
            continue
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            total += int(shards[0].data.nbytes)
        else:
            total += int(leaf.nbytes)
    return total


def static_footprint(trainer, state) -> Dict[str, Any]:
    """Per-device HBM bytes of a trainer's resident training state plus the
    step's transient gradient flats — the static fit estimate.

    Components (all per device):

    * ``params_bytes`` / ``opt_state_bytes`` / ``algo_state_bytes`` — the
      live :class:`TrainState` leaves' shard sizes.  Under the
      flat-resident layout the params/opt leaves ARE the bucket flats, so
      this matches the ``BucketPlan`` avals exactly — 1/world of a bucket
      whose update is sharded, parameter and moment alike (pinned in
      ``tests/test_ledger.py``).
    * ``grad_flats_bytes`` — one set of per-bucket gradient flats
      (:func:`plan_flat_bytes`): the dominant transient the compiled step
      materializes between backward and the collective.
    """
    plan = getattr(trainer, "_plan", None)
    record: Dict[str, Any] = {
        "params_bytes": tree_device_bytes(state.params),
        "opt_state_bytes": tree_device_bytes(state.opt_state),
        "algo_state_bytes": tree_device_bytes(
            getattr(state, "algo_state", None)),
        "grad_flats_bytes": plan_flat_bytes(plan) if plan is not None else 0,
        "bucket_count": len(plan.buckets) if plan is not None else 0,
        "flat_resident": bool(getattr(trainer, "_flat_resident", False)),
        "per_device": True,
    }
    record["total_bytes"] = (
        record["params_bytes"] + record["opt_state_bytes"]
        + record["algo_state_bytes"] + record["grad_flats_bytes"]
    )
    return record


#: attributes a jax ``CompiledExecutable.memory_analysis()`` result may
#: expose (backend-dependent; missing ones are simply absent)
_MEMORY_ANALYSIS_FIELDS = (
    "argument_size_in_bytes", "output_size_in_bytes",
    "temp_size_in_bytes", "alias_size_in_bytes",
    "generated_code_size_in_bytes", "host_argument_size_in_bytes",
    "host_output_size_in_bytes", "host_temp_size_in_bytes",
    "host_generated_code_size_in_bytes", "serialized_size_in_bytes",
)


def compiled_memory_analysis(compiled) -> Optional[Dict[str, int]]:
    """Extract the plain-int fields from a compiled executable's
    ``memory_analysis()`` (None when the backend offers none — cpu-sim's
    null-with-rationale case).  Adds ``peak_bytes`` = arguments + outputs +
    temps when all three are present: the executable's own HBM high-water
    estimate."""
    try:
        analysis = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001 - backend-dependent surface
        logger.debug("memory_analysis unavailable: %s", e)
        return None
    if analysis is None:
        return None
    out: Dict[str, int] = {}
    for field in _MEMORY_ANALYSIS_FIELDS:
        value = getattr(analysis, field, None)
        if isinstance(value, (int, np.integer)):
            out[field] = int(value)
    if not out:
        return None
    if all(k in out for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes")):
        out["peak_bytes"] = (out["argument_size_in_bytes"]
                             + out["output_size_in_bytes"]
                             + out["temp_size_in_bytes"])
    return out


def peak_bytes(stats: Dict[str, Any]) -> int:
    """High-water mark of one device from its ``memory_stats()`` dict: the
    larger of the buffers' own peak (``peak_bytes_in_use``) and buffers +
    the programs' reservation now (``bytes_in_use + bytes_reserved``).  On
    a TPU ``peak_bytes_in_use`` counts buffers only; a running program's
    temporaries sit in ``bytes_reserved``, sized to the largest program run
    so far (v5e, BERT-Large step, PR 23: the counter read 7.455 GB after
    365 steps while 5.55 GB of activations sat in the reservation —
    11.59 GB is the true mark).  The same arithmetic as the benchmark's
    ``perfbench/drivers/train.py::peak_memory_bytes``."""
    return int(max(stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0)
                   + stats.get("bytes_reserved", 0)))


def _device_memory_record(device) -> Dict[str, Any]:
    try:
        stats = device.memory_stats()
    except Exception as e:  # noqa: BLE001 - backend-dependent surface
        # transient: a runtime hiccup, not "this backend never has HBM
        # stats" — callers should keep polling (with a budget)
        return {"available": False, "transient": True,
                "rationale": f"memory_stats raised {type(e).__name__}: {e}"}
    if not stats:
        return {"available": False,
                "rationale": f"device {device.device_kind!r} reports no "
                             "memory_stats (cpu-sim has no HBM)"}
    record: Dict[str, Any] = {"available": True,
                              "device_kind": device.device_kind,
                              "device_id": device.id}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                "bytes_limit", "largest_alloc_size"):
        if key in stats:
            record[key] = int(stats[key])
    if "bytes_in_use" in stats or "peak_bytes_in_use" in stats:
        record["peak_bytes"] = peak_bytes(stats)
        if "bytes_limit" in record:
            record["headroom_bytes"] = (record["bytes_limit"]
                                        - record["peak_bytes"])
    return record


def live_memory_stats(device=None) -> Dict[str, Any]:
    """One poll of ``device.memory_stats()``: ``{"available": True,
    bytes_in_use, peak_bytes_in_use, bytes_reserved, peak_bytes
    (:func:`peak_bytes`), bytes_limit, headroom_bytes}`` on
    runtimes that expose it (TPU), else ``{"available": False,
    "rationale": ...}`` — null-with-rationale, so a fleet view can show
    *why* a rank has no live-memory column.

    Default: EVERY local device is polled and the record is the one with
    the least headroom (``device_id`` names it, ``devices_polled`` counts
    them) — one process drives all chips of a host, and the chip that runs
    out first is the one the capacity gauges must show (``init`` stages
    the whole state through chip 0, so the chips are not symmetric)."""
    import jax

    if device is not None:
        return _device_memory_record(device)
    records = [_device_memory_record(d) for d in jax.local_devices()]
    unavailable = [r for r in records if not r.get("available")]
    if unavailable:
        return unavailable[0]
    tightest = min(records,
                   key=lambda r: r.get("headroom_bytes", float("inf")))
    return {**tightest, "devices_polled": len(records)}
