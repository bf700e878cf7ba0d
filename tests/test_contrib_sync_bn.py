"""SyncBatchNorm tests (reference tests/contrib/test_sync_bn.py pattern:
sync-BN over shards == local BN over the full batch; running-stat updates;
plugs into ResNet via norm_cls)."""

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from bagua_tpu.contrib import SyncBatchNorm
from bagua_tpu.parallel.mesh import build_mesh

N_DEVICES = 8


def test_sync_bn_matches_full_batch_bn():
    """BN over 8 shards with moment sync == plain BN over the whole batch."""
    mesh = build_mesh({"dp": N_DEVICES})
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 6, 5)) * 3.0 + 1.5

    sync_bn = SyncBatchNorm(use_running_average=False, axis_name="dp")
    local_bn = nn.BatchNorm(use_running_average=False)
    variables = sync_bn.init(jax.random.PRNGKey(1), x[:2])
    ref_vars = local_bn.init(jax.random.PRNGKey(1), x[:2])

    def shard_fn(v, xs):
        y, updated = sync_bn.apply(v, xs, mutable=["batch_stats"])
        return y, updated["batch_stats"]

    y_sync, stats_sync = jax.jit(
        shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P("dp")), out_specs=(P("dp"), P()),
            check_vma=False,
        )
    )(variables, x)
    y_ref, ref_updated = local_bn.apply(ref_vars, x, mutable=["batch_stats"])

    np.testing.assert_allclose(
        np.asarray(y_sync), np.asarray(y_ref), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(stats_sync["mean"]),
        np.asarray(ref_updated["batch_stats"]["mean"]),
        rtol=1e-5, atol=1e-6,
    )
    # biased batch variance is what both track (flax semantics)
    np.testing.assert_allclose(
        np.asarray(stats_sync["var"]),
        np.asarray(ref_updated["batch_stats"]["var"]),
        rtol=1e-4, atol=1e-5,
    )


def test_sync_bn_gradient_flows_through_pmean():
    """d(loss)/d(x) must include the cross-shard moment coupling — the part
    the reference implements as a hand-written backward allreduce."""
    mesh = build_mesh({"dp": N_DEVICES})
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    bn = SyncBatchNorm(use_running_average=False, axis_name="dp")
    variables = bn.init(jax.random.PRNGKey(1), x[:2])

    def loss_sharded(xs):
        def f(v, xb):
            y, _ = bn.apply(v, xb, mutable=["batch_stats"])
            return jnp.sum(y**2)

        per = shard_map(
            lambda v, xb: jax.lax.psum(f(v, xb), "dp"),
            mesh=mesh, in_specs=(P(), P("dp")), out_specs=P(),
            check_vma=False,
        )
        return per(variables, xs)

    def loss_full(xs):
        ref = nn.BatchNorm(use_running_average=False)
        y, _ = ref.apply(variables, xs, mutable=["batch_stats"])
        return jnp.sum(y**2)

    g_sync = jax.grad(loss_sharded)(x)
    g_ref = jax.grad(loss_full)(x)
    np.testing.assert_allclose(
        np.asarray(g_sync), np.asarray(g_ref), rtol=2e-4, atol=2e-5
    )


def test_running_average_mode_uses_stats():
    bn = SyncBatchNorm(use_running_average=True, axis_name=None)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 3))
    variables = bn.init(jax.random.PRNGKey(1), x)
    stats = {
        "mean": jnp.array([1.0, 2.0, 3.0]),
        "var": jnp.array([4.0, 4.0, 4.0]),
    }
    y = bn.apply({"params": variables["params"], "batch_stats": stats}, x)
    expected = (x - stats["mean"]) / jnp.sqrt(stats["var"] + bn.epsilon)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expected), rtol=1e-5,
                               atol=1e-5)


def test_unbound_axis_falls_back_to_local():
    """Outside shard_map the axis isn't bound: behaves as local BN
    (reference world-size-1 fallback, sync_batchnorm.py:83-85)."""
    bn = SyncBatchNorm(use_running_average=False, axis_name="dp")
    ref = nn.BatchNorm(use_running_average=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 3))
    v = bn.init(jax.random.PRNGKey(1), x)
    y, _ = bn.apply(v, x, mutable=["batch_stats"])
    y_ref, _ = ref.apply(v, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)


def test_plugs_into_resnet_norm_cls():
    from bagua_tpu.models.resnet import ResNet

    model = ResNet(
        stage_sizes=(1,), num_classes=4, num_filters=8,
        norm_cls=partial(SyncBatchNorm, axis_name="dp"),
    )
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    logits, _ = model.apply(variables, x, train=True, mutable=["batch_stats"])
    assert logits.shape == (2, 4)
