"""Profiler integration (SURVEY.md §5.1: jax.profiler traces are the
TPU-native form of the reference's profiling role)."""

import glob
import os

import jax
import jax.numpy as jnp
import optax

from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.models.mlp import MLP
from bagua_tpu.profiling import StepProfiler, trace


def _trace_files(d):
    return glob.glob(os.path.join(d, "**", "*.trace.json*"), recursive=True) \
        + glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)


def test_trace_context_writes_files(tmp_path):
    with trace(str(tmp_path)):
        jnp.ones((64, 64)).sum().block_until_ready()
    assert _trace_files(str(tmp_path)), os.listdir(tmp_path)


def test_trainer_auto_capture(tmp_path, monkeypatch):
    monkeypatch.setenv("BAGUA_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("BAGUA_PROFILE_STEPS", "1:3")

    model = MLP(features=(8, 4))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jnp.zeros((16,), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    trainer = BaguaTrainer(loss_fn, optax.sgd(0.1),
                           GradientAllReduceAlgorithm(), autotune=False)
    assert isinstance(trainer._profiler, StepProfiler)
    state = trainer.init(params)
    batch = trainer.shard_batch({"x": x, "y": y})
    for _ in range(5):
        state, loss = trainer.train_step(state, batch)
    assert trainer._profiler._done
    assert _trace_files(str(tmp_path)), os.listdir(tmp_path)
