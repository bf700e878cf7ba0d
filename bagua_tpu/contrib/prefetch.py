"""Device prefetching: overlap host→device transfer with compute.

Additive input-pipeline piece (the reference leans on torch DataLoader's
worker processes + pinned-memory prefetch; on TPU the analogous win is
keeping the next batch's H2D transfer in flight while the current step
runs).  ``prefetch_to_device`` wraps any host batch iterator and keeps
``size`` batches resident on device, already laid out with the trainer's
batch sharding — so ``train_step`` never waits on the transfer and never
re-lays-out the input.

JAX dispatch is asynchronous: ``device_put`` returns immediately and the
transfer proceeds in the background, so a one-element lookahead buffer is
usually enough.
"""

from __future__ import annotations

import collections
from typing import Iterable, Iterator, Optional

from ..obs.spans import trace_span

__all__ = ["prefetch_to_device"]


def prefetch_to_device(
    iterable: Iterable,
    trainer=None,
    size: int = 2,
    mesh=None,
    spec=None,
) -> Iterator:
    """Yield batches from ``iterable`` with ``size`` batches pre-transferred.

    Args:
        iterable: host-side batch iterator (pytrees of arrays).
        trainer: a :class:`~bagua_tpu.core.backend.BaguaTrainer` — batches
            are placed with ``trainer.shard_batch`` (validates shard counts
            and uses the step's input sharding).  Mutually exclusive with
            ``mesh``/``spec``.
        size: lookahead depth (≥ 1).
        mesh / spec: explicit mesh + PartitionSpec placement, for use
            without a trainer.
    """
    # validate eagerly (a generator body would defer errors to first next())
    if size < 1:
        raise ValueError(f"size must be >= 1, got {size}")
    if trainer is not None and (mesh is not None or spec is not None):
        raise ValueError("pass trainer OR mesh/spec, not both")

    if trainer is not None:
        place = trainer.shard_batch
    elif mesh is not None and spec is not None:
        from ..parallel.mesh import make_global_array

        def place(batch):
            import jax

            return jax.tree.map(
                lambda x: make_global_array(mesh, spec, x), batch
            )
    else:
        raise ValueError("pass a trainer, or both mesh and spec")

    def gen():
        queue: collections.deque = collections.deque()
        it = iter(iterable)

        def fill():
            # both halves run on the consumer's thread inside its
            # ``next(batches)``: the spans divide that wait into the user's
            # iterator and the program's own placement
            while len(queue) < size:
                try:
                    with trace_span("input/source"):
                        batch = next(it)
                except StopIteration:
                    return
                with trace_span("input/place"):
                    queue.append(place(batch))

        fill()
        while queue:
            yield queue.popleft()
            fill()

    return gen()
