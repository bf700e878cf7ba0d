"""Integrity-verified model loads for the serving plane.

A serving replica must never decode with silently corrupted weights, so
every load rides the PR 5 checkpoint integrity chain
(:class:`bagua_tpu.checkpoint.BaguaCheckpointManager`): the content digest
recorded at save time is verified on restore, a torn sidecar or digest
mismatch disqualifies that step with a loud warning, and (when no explicit
step was requested) the load falls back newest-first to the last step that
verifies — the exact policy training resumes use.

Layout awareness: training may have checkpointed the params as
**bucket-flat buffers** (the flat-resident layout, PR 4).  The layout
sidecar records the full bucket descriptor, so the loader rebuilds the
:class:`~bagua_tpu.bucket.BucketPlan` from the sidecar alone, restores the
flat buffers with their shapes derived from the descriptor (no trainer
required in the serving process), digest-verifies them, and unflattens to
the leaf params the decode program consumes — the flat→serving-layout
conversion.  Leaf-layout checkpoints restore directly.

:func:`save_serving_artifact` is the publishing half: flatten trained leaf
params under a plan, record the descriptor + digest, and ship a directory
any replica can :func:`load_serving_params` from.
"""

from __future__ import annotations

import logging
from typing import Any, Optional, Tuple

from ..bucket import BucketPlan
from ..checkpoint import BaguaCheckpointManager
from ..obs.spans import trace_span
from ..telemetry import counters

logger = logging.getLogger(__name__)

__all__ = ["save_serving_artifact", "load_serving_params"]


def save_serving_artifact(
    directory: str,
    params: Any,
    step: int = 0,
    bucket_bytes: Optional[int] = None,
) -> None:
    """Publish ``params`` as a serving artifact: bucket-flat buffers + the
    layout sidecar (bucket descriptor, content digest) under
    ``directory``.  The flat layout is deliberate — one contiguous buffer
    per bucket restores with large sequential reads, and the descriptor
    makes the artifact self-describing (a replica needs no trainer, no
    bucket plan, only the target model's param structure)."""
    from .. import env as _env
    from ..tensor import build_params

    named = build_params(params)
    plan = BucketPlan.build(
        named, bucket_bytes or _env.get_default_bucket_size(), alignment=1
    )
    flats = plan.flatten_tree(params)
    mgr = BaguaCheckpointManager(directory, async_save=False)
    try:
        meta = {
            "layout": "flat",
            "plan_dependent": True,
            "serving_artifact": True,
            "flat_layout": plan.layout_descriptor(),
        }
        mgr.save(int(step), {"flats": tuple(flats)}, metadata=meta)
    finally:
        mgr.close()


def _restore_with_layout(mgr: BaguaCheckpointManager, step: int,
                         params_like: Any) -> Tuple[int, Any]:
    """Restore one step into the serving (leaf) layout, converting via the
    sidecar when the on-disk layout is bucket-flat.  Raises
    ``CheckpointIntegrityError`` for corruption (the newest-first walk
    then falls back) and ``ValueError`` for genuine mismatches (a model
    whose params the artifact does not cover)."""
    import jax
    import numpy as np

    from ..tensor import leaves_by_name, tree_from_named

    sidecar = mgr.read_layout(step)  # torn sidecar -> integrity error
    if sidecar and "flat_layout" in sidecar:
        plan = BucketPlan.from_layout_descriptor(sidecar["flat_layout"])
        # in the shapes the artifact was written with (an older one holds
        # every buffer 1-D; unflatten_to_named takes either)
        flats_like = {
            "flats": tuple(
                jax.ShapeDtypeStruct(shape, np.dtype(b.dtype))
                for b, shape in zip(plan.buckets,
                                    BucketPlan.saved_buffer_shapes(
                                        sidecar["flat_layout"]))
            ),
        }
        # the expectation IS the sidecar's own constraint set (the flat
        # shapes come from its descriptor), so the plan-dependent-layout
        # warning path stays quiet — a genuine mismatch still raises
        expect = {k: v for k, v in sidecar.items()
                  if k not in ("flat_layout", "integrity")}
        got_step, restored = mgr.restore(flats_like, step=step,
                                         expect_metadata=expect)
        named = plan.unflatten_to_named(restored["flats"])
        want = leaves_by_name(params_like)
        missing = sorted(set(want) - set(named))
        if missing:
            raise ValueError(
                "serving artifact does not cover the model's params "
                f"(missing {missing[:3]}{'…' if len(missing) > 3 else ''}) "
                "— wrong checkpoint for this model config?"
            )
        mismatched = sorted(
            n for n in want
            if tuple(np.shape(want[n])) != tuple(np.shape(named[n]))
        )
        if mismatched:
            raise ValueError(
                "serving artifact param shapes do not match the model "
                f"({mismatched[:3]}{'…' if len(mismatched) > 3 else ''})"
            )
        return got_step, tree_from_named(params_like, named)
    return mgr.restore(params_like, step=step)


def load_serving_params(
    directory: str,
    params_like: Any,
    step: Optional[int] = None,
) -> Tuple[int, Any]:
    """Load serving params from ``directory`` with digest verification and
    newest-first integrity fallback.

    ``params_like`` provides the target leaf structure/shapes — pass the
    model's initialized params (or ``jax.eval_shape`` of the init).  The
    load is spanned as ``serve/weight_load``, which the goodput ledger
    books under the serving ``weight_load`` class.
    """
    from ..obs import ledger as obs_ledger
    from ..obs import spans as obs_spans

    if obs_spans.enabled():
        # the load may be the process's FIRST serving act — hook the
        # ledger sink up before the span opens so weight_load is booked
        obs_ledger.install()
    with trace_span("serve/weight_load", directory=str(directory)):
        mgr = BaguaCheckpointManager(directory, async_save=False)
        try:
            if step is not None:
                result = _restore_with_layout(mgr, int(step), params_like)
            else:
                result = mgr._restore_newest_verified(
                    lambda s: _restore_with_layout(mgr, s, params_like)
                )
        finally:
            mgr.close()
    counters.incr("serve/weight_loads")
    logger.info("serving params loaded from %s at step %d", directory,
                result[0])
    return result
