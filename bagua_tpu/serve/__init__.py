"""Serving plane: continuous-batching inference over trained artifacts
(docs/serving.md).

The framework's end-state story — train, observe, heal, and now *serve*:

* :mod:`~bagua_tpu.serve.cache` — the paged KV-cache's host bookkeeping:
  the page-pool allocator and the per-slot block tables over the
  per-layer page pools the transformer's paged decode mode keeps
  (``TransformerConfig(decode=True, page_size=…, num_pages=…)``).
* :mod:`~bagua_tpu.serve.engine` — the continuous-batching scheduler:
  one static-shape jitted tick, join-mid-batch / evict-on-finish without
  recompiling, chunked prefill that never stalls running decodes,
  queue-then-preempt backpressure on pool exhaustion, and greedy decode
  bit-identical to ``models.generate.generate()``.
* :mod:`~bagua_tpu.serve.loader` — integrity-verified weight loads
  through the checkpoint digest chain, with layout-sidecar-aware
  flat→serving-layout conversion.

Observability rides the existing planes: ``serve/*`` spans and counters,
and the goodput ledger's serving classes (``prefill``/``decode`` count as
serving goodput; ``batch_formation_idle``/``weight_load`` are badput with
a name).
"""

from .cache import PagePool, SlotTable  # noqa: F401
from .engine import (  # noqa: F401
    Request,
    ServeConfig,
    ServeEngine,
    ServeQueueFull,
    clear_serve_program_cache,
)
from .loader import load_serving_params, save_serving_artifact  # noqa: F401
