"""The exact-loss gate's fixed task: seven algorithm families, one seeded MLP
task, deterministic final losses.

Mirrors the reference's exact-loss CI gate
(/root/reference/.buildkite/scripts/benchmark_master.sh:98-108).  No timing
lives here.  ``tests/`` is on ``sys.path`` under pytest, so test files
``import golden``; the drills under ``scripts/`` add ``tests/`` to the path
they already extend with the repo root.
"""

import jax
import jax.numpy as jnp
import optax


def _algorithms():
    from bagua_tpu.algorithms.async_model_average import AsyncModelAverageAlgorithm
    from bagua_tpu.algorithms.bytegrad import ByteGradAlgorithm
    from bagua_tpu.algorithms.decentralized import (
        DecentralizedAlgorithm,
        LowPrecisionDecentralizedAlgorithm,
    )
    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.algorithms.q_adam import QAdamAlgorithm
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm

    return {
        "gradient_allreduce": lambda: GradientAllReduceAlgorithm(hierarchical=False),
        "bytegrad": lambda: ByteGradAlgorithm(hierarchical=False),
        "qadam": lambda: QAdamAlgorithm(warmup_steps=2, hierarchical=False),
        "decentralized": lambda: DecentralizedAlgorithm(
            hierarchical=False, peer_selection_mode="all"
        ),
        "low_precision_decentralized": lambda: LowPrecisionDecentralizedAlgorithm(
            hierarchical=False
        ),
        "async": lambda: AsyncModelAverageAlgorithm(sync_interval_ms=100),
        "zero": lambda: ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9)),
    }


def golden_task(batch_size: int = None):
    """The fixed seed/task of the exact-loss gate, shared with the elastic
    cross-topology resume gate (tests/test_elastic_resume.py) so a
    save/resize/restore run is measured against the SAME trajectory the
    goldens certify.  Returns ``(loss_fn, params, batch)``; the batch is
    the full global batch — identical under any dp split that divides it,
    which is what makes final losses comparable across world sizes."""
    from bagua_tpu.models.mlp import MLP

    if batch_size is None:
        batch_size = 8 * len(jax.devices())
    model = MLP(features=(32, 8))
    x = jax.random.normal(jax.random.PRNGKey(0), (batch_size, 4))
    y = jnp.argmax(x @ jax.random.normal(jax.random.PRNGKey(1), (4, 8)), -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    return loss_fn, params, {"x": x, "y": y}


def loss_goldens(n_steps: int = 30) -> dict:
    """Deterministic final losses per family on a fixed seed/task.
    Platform-specific (reduction orders differ CPU vs TPU); the test asserts
    them on the 8-device CPU mesh."""
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    n_dev = len(jax.devices())
    mesh = build_mesh({"dp": n_dev})
    loss_fn, params, batch = golden_task()

    out = {}
    for family, factory in _algorithms().items():
        algo = factory()
        trainer = BaguaTrainer(
            loss_fn,
            None if algo.owns_optimizer else optax.sgd(0.1),
            algo, mesh=mesh, autotune=False,
        )
        state = trainer.init(params)
        for _ in range(n_steps):
            state, loss = trainer.train_step(state, batch)
        if hasattr(algo, "abort"):
            algo.abort()
        out[family] = round(float(loss), 6)

    # staged (hierarchical) ZeRO needs a tiered mesh; its reduction order
    # differs from flat ZeRO (rs(intra)+allreduce(inter)), so it gets its
    # own exact golden
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm
    from bagua_tpu.parallel.mesh import hierarchical_mesh

    trainer = BaguaTrainer(
        loss_fn, None,
        ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9),
                               hierarchical=True),
        mesh=hierarchical_mesh(intra_size=max(1, n_dev // 2)),
        autotune=False,
    )
    state = trainer.init(params)
    for _ in range(n_steps):
        state, loss = trainer.train_step(state, batch)
    out["zero_hierarchical"] = round(float(loss), 6)
    return out
