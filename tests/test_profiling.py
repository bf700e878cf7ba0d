"""Profiler integration (SURVEY.md §5.1: jax.profiler traces are the
TPU-native form of the reference's profiling role)."""

import glob
import os

import pytest

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


def test_parse_xplane_memory_traffic_synthetic(tmp_path):
    """Parser coverage without a TPU: synthesize an XSpace with a device
    plane carrying Steps + XLA Ops lines and per-op memory breakdowns
    (memory_space 1=HBM, 3=VMEM per op_metrics.proto)."""
    pytest.importorskip("tensorflow.tsl.profiler.protobuf")
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from xprof.protobuf import op_metrics_pb2

    from bagua_tpu.profiling import parse_xplane_memory_traffic

    xs = xplane_pb2.XSpace()
    plane = xs.planes.add(name="/device:TPU:0")
    # stat metadata
    sm = plane.stat_metadata
    sm[1].id = 1
    sm[1].name = "memory_access_breakdown"
    # event metadata: one op moving 2 GB HBM + 1 GB VMEM per occurrence
    em = plane.event_metadata
    em[10].id = 10
    em[10].name = "fusion.1"
    mab = op_metrics_pb2.MemoryAccessBreakdown()
    a = mab.memory_accessed.add()
    a.memory_space, a.bytes_accessed = 1, 2_000_000_000
    b = mab.memory_accessed.add()
    b.memory_space, b.bytes_accessed = 3, 1_000_000_000
    st = em[10].stats.add()
    st.metadata_id = 1
    st.bytes_value = mab.SerializeToString()

    steps = plane.lines.add(name="Steps")
    for i in range(2):
        ev = steps.events.add()
        ev.duration_ps = int(0.05e12)  # 50 ms per step
    ops = plane.lines.add(name="XLA Ops")
    for i in range(4):  # the op runs twice per step
        ev = ops.events.add()
        ev.metadata_id = 10
        ev.duration_ps = int(0.01e12)

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xs.SerializeToString())
    out = parse_xplane_memory_traffic(str(path))
    assert out["step_s"] == 0.05
    assert out["hbm_gb_per_step"] == 4.0   # 2 occurrences x 2 GB
    assert out["vmem_gb_per_step"] == 2.0
    assert out["hbm_gbps_measured"] == 80  # 4 GB / 50 ms


def test_newest_xplane_is_mtime_ordered(tmp_path):
    """The satellite fix: trace selection must follow mtime, not
    lexicographic filename order — jax names traces host+timestamp, and a
    directory holding two captures sorted the OLD one last."""
    from bagua_tpu.profiling import _newest_xplane

    sub = tmp_path / "plugins" / "profile"
    sub.mkdir(parents=True)
    # lexicographically LAST file is the OLDEST capture
    old = sub / "zzz_host.xplane.pb"
    new = tmp_path / "aaa_host.xplane.pb"
    old.write_bytes(b"old")
    new.write_bytes(b"new")
    past = os.path.getmtime(str(new)) - 100
    os.utime(str(old), (past, past))
    assert _newest_xplane(str(tmp_path)) == str(new)
    assert _newest_xplane(str(tmp_path / "plugins")) == str(old)
    assert _newest_xplane(str(tmp_path / "nope")) is None
