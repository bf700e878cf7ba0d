"""Overlap-aware bucket communication scheduler (ISSUE 2).

Pinned contracts:

* chunked ring allreduce / reduce-scatter / allgather match the fused
  ``psum`` / ``psum_scatter`` / ``all_gather`` primitives on the 8-device
  CPU mesh (numerically for the reductions, exactly for the gather);
* the overlap path trains the same trajectory as the serialized path —
  EXACTLY for gradient_allreduce and flat-resident ZeRO (the scan peel
  preserves sum order; re-bucketing is elementwise-lossless under psum),
  within quantization tolerance for bytegrad (readiness re-bucketing moves
  codec chunk boundaries);
* ``overlap="off"`` restores the exact serialized step construction (HLO
  text identical to the ``auto``-resolved accum=1 default, no ring
  collective-permute chains);
* the ``auto`` dispatch gate follows ``Algorithm.overlap_auto`` (set from
  a cpu-sim record, never measured on the chip: ROADMAP Queue 3 item 3) and
  the autotune recommendation path carries the overlap knobs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from bagua_tpu import BaguaTrainer
from bagua_tpu.algorithms import (
    ByteGradAlgorithm,
    GradientAllReduceAlgorithm,
    QAdamAlgorithm,
    ZeroOptimizerAlgorithm,
)
from bagua_tpu.communication import BaguaCommunicator, ReduceOp, ring_chunks_for
from jax import shard_map
from bagua_tpu.models import MLP
from bagua_tpu.parallel.mesh import build_mesh

N = 8
DIM = 12
NCLASS = 10
MODEL = MLP(features=(16, NCLASS))


def _loss_fn(params, batch):
    logits = MODEL.apply({"params": params}, batch["x"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["y"]
    ).mean()


# ---- chunked ring vs fused primitives ---------------------------------


def _run_sharded(mesh, fn, x):
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
                  check_vma=False)
    )(x)


@pytest.mark.parametrize("num_chunks", [1, 2, 4])
def test_ring_allreduce_matches_psum(num_chunks):
    mesh = build_mesh({"dp": N})
    comm = BaguaCommunicator("dp", mesh)
    x = np.random.default_rng(0).normal(size=(N, 64)).astype(np.float32)
    for op in (ReduceOp.AVG, ReduceOp.SUM):
        fused = _run_sharded(
            mesh, lambda v, op=op: comm.allreduce(v[0], op)[None], x
        )
        ring = _run_sharded(
            mesh,
            lambda v, op=op: comm.ring_allreduce(
                v[0], op, num_chunks=num_chunks
            )[None],
            x,
        )
        np.testing.assert_allclose(
            np.asarray(ring), np.asarray(fused), rtol=1e-6, atol=1e-6
        )


@pytest.mark.parametrize("num_chunks", [1, 2, 4])
def test_ring_reduce_scatter_matches_psum_scatter(num_chunks):
    mesh = build_mesh({"dp": N})
    comm = BaguaCommunicator("dp", mesh)
    x = np.random.default_rng(1).normal(size=(N, 64)).astype(np.float32)
    fused = _run_sharded(
        mesh, lambda v: comm.reduce_scatter(v[0], ReduceOp.AVG)[None], x
    )
    ring = _run_sharded(
        mesh,
        lambda v: comm.ring_reduce_scatter(
            v[0], ReduceOp.AVG, num_chunks=num_chunks
        )[None],
        x,
    )
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(fused), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("num_chunks", [1, 2, 4])
def test_ring_allgather_matches_all_gather(num_chunks):
    mesh = build_mesh({"dp": N})
    comm = BaguaCommunicator("dp", mesh)
    x = np.random.default_rng(2).normal(size=(N, 8)).astype(np.float32)
    fused = _run_sharded(
        mesh, lambda v: comm.allgather(v[0], tiled=True)[None], x
    )
    ring = _run_sharded(
        mesh, lambda v: comm.ring_allgather(v[0], num_chunks=num_chunks)[None],
        x,
    )
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(fused))


def test_ring_scatter_gather_pair_is_layout_symmetric():
    """reduce_scatter then allgather round-trips to the psum average — the
    invariant ZeRO's chunk-resident optimizer state depends on."""
    mesh = build_mesh({"dp": N})
    comm = BaguaCommunicator("dp", mesh)
    x = np.random.default_rng(3).normal(size=(N, 64)).astype(np.float32)

    def pair(v):
        chunk = comm.ring_reduce_scatter(v[0], ReduceOp.AVG, num_chunks=4)
        return comm.ring_allgather(chunk, num_chunks=4)[None]

    out = _run_sharded(mesh, pair, x)
    np.testing.assert_allclose(
        np.asarray(out)[0], x.mean(axis=0), rtol=1e-6, atol=1e-6
    )


def test_ring_chunks_for_sizing():
    # 8 ranks, 1024 f32 elems -> 128 elems (512 B) per rank
    assert ring_chunks_for(1024, 4, 8, None) == 1
    assert ring_chunks_for(1024, 4, 8, 0) == 1
    assert ring_chunks_for(1024, 4, 8, 512) == 1
    assert ring_chunks_for(1024, 4, 8, 128) == 4
    # chunk count always divides the per-rank block
    k = ring_chunks_for(1024, 4, 8, 100)
    assert 128 % k == 0 and k > 1
    # indivisible buffers size against the ring's internal zero-padding
    assert ring_chunks_for(1023, 4, 8, 64) == 8
    # compile-size guard: a tiny chunk size against a 10 MiB bucket must
    # not unroll thousands of ring chains
    from bagua_tpu.communication import MAX_RING_CHUNKS

    assert ring_chunks_for(800_000, 4, 8, 16) <= MAX_RING_CHUNKS


def test_ring_allreduce_pads_indivisible_buffers():
    mesh = build_mesh({"dp": N})
    comm = BaguaCommunicator("dp", mesh)
    # 50 elements: not a multiple of 8, nor of 8*num_chunks
    x = np.random.default_rng(4).normal(size=(N, 50)).astype(np.float32)
    fused = _run_sharded(
        mesh, lambda v: comm.allreduce(v[0], ReduceOp.AVG)[None], x
    )
    for k in (1, 2):
        ring = _run_sharded(
            mesh,
            lambda v, k=k: comm.ring_allreduce(
                v[0], ReduceOp.AVG, num_chunks=k
            )[None],
            x,
        )
        np.testing.assert_allclose(
            np.asarray(ring), np.asarray(fused), rtol=1e-6, atol=1e-6
        )


# ---- overlap vs serialized training equivalence -----------------------


def _train(algo_factory, optimizer, accum, overlap, chunk=0, steps=4):
    trainer = BaguaTrainer(
        _loss_fn, optimizer, algo_factory(), bucket_bytes=256,
        accum_steps=accum, overlap=overlap, overlap_chunk_bytes=chunk,
    )
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    state = trainer.init(params)
    rng = np.random.default_rng(7)
    losses = []
    for _ in range(steps):
        batch = {
            "x": rng.normal(size=(N * 2 * accum, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2 * accum,)).astype(
                np.int32
            ),
        }
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    return np.array(losses), state, trainer


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize(
    "algo_factory,optimizer,exact",
    [
        (GradientAllReduceAlgorithm, optax.sgd(0.1), True),
        (lambda: ZeroOptimizerAlgorithm(optax.adam(1e-2)), None, True),
        # readiness re-bucketing moves the codec's chunk boundaries, so the
        # 8-bit quantization levels differ slightly between the paths
        (ByteGradAlgorithm, optax.sgd(0.1), False),
    ],
    ids=["gradient_allreduce", "zero", "bytegrad"],
)
def test_overlap_matches_serialized(algo_factory, optimizer, exact, accum):
    l_off, st_off, tr_off = _train(algo_factory, optimizer, accum, "off")
    l_on, st_on, tr_on = _train(algo_factory, optimizer, accum, "on")
    assert tr_on._overlap_active()
    if exact:
        np.testing.assert_array_equal(l_on, l_off)
        # leaf views: the overlap trainer re-buckets by readiness, so its
        # flat-RESIDENT raw state is laid out under a different plan
        for a, b in zip(jax.tree.leaves(tr_on.unstack_params(st_on)),
                        jax.tree.leaves(tr_off.unstack_params(st_off))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        np.testing.assert_allclose(l_on, l_off, rtol=0.05, atol=0.02)


@pytest.mark.parametrize(
    "algo_factory,optimizer",
    [
        (GradientAllReduceAlgorithm, optax.sgd(0.1)),
        (lambda: ZeroOptimizerAlgorithm(optax.adam(1e-2)), None),
    ],
    ids=["gradient_allreduce", "zero"],
)
def test_chunked_ring_end_to_end(algo_factory, optimizer):
    """overlap=on with an explicit ring chunk size trains the serialized
    trajectory within float tolerance (ring reduction order differs)."""
    l_off, _, _ = _train(algo_factory, optimizer, 4, "off")
    l_chunk, _, tr = _train(algo_factory, optimizer, 4, "on", chunk=64)
    assert tr._overlap_active()
    np.testing.assert_allclose(l_chunk, l_off, rtol=1e-5, atol=1e-6)


# ---- step construction and dispatch gate ------------------------------


def _step_hlo(overlap, accum=1, chunk=0):
    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        bucket_bytes=256, accum_steps=accum, overlap=overlap,
        overlap_chunk_bytes=chunk,
    )
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    state = trainer.init(params)
    rng = np.random.default_rng(0)
    batch = trainer.shard_batch({
        "x": rng.normal(size=(N * 2 * accum, DIM)).astype(np.float32),
        "y": rng.integers(0, NCLASS, size=(N * 2 * accum,)).astype(np.int32),
    })
    return trainer._get_step_fn().lower(state, batch).as_text()


def test_overlap_off_restores_serialized_construction():
    """``overlap="off"`` and the auto-resolved accum=1 default lower to the
    IDENTICAL program; the serialized construction never contains the ring's
    collective-permute chains."""
    off = _step_hlo("off")
    auto = _step_hlo("auto")
    assert off == auto
    assert "collective_permute" not in off
    # explicit chunking swaps the fused all-reduce for ppermute rings
    # (16 B per rank per sub-collective on these tiny test buckets)
    ringed = _step_hlo("on", chunk=16)
    assert "collective_permute" in ringed


def test_auto_gate_follows_measurement():
    def trainer_for(algo, accum, **kw):
        t = BaguaTrainer(
            _loss_fn,
            None if algo.owns_optimizer else optax.sgd(0.1),
            algo, bucket_bytes=256, accum_steps=accum, **kw,
        )
        params = MODEL.init(
            jax.random.PRNGKey(0), jnp.zeros((1, DIM))
        )["params"]
        t.init(params)
        return t

    # the gate's setting: serialized at accum==1; overlap at accum>1
    assert not trainer_for(GradientAllReduceAlgorithm(), 1)._overlap_active()
    assert trainer_for(GradientAllReduceAlgorithm(), 4)._overlap_active()
    # zero and bytegrad set overlap_auto=False (from a cpu-sim record):
    # auto stays serialized, explicit on still wins
    assert not trainer_for(ZeroOptimizerAlgorithm(optax.adam(1e-2)),
                           4)._overlap_active()
    assert trainer_for(ZeroOptimizerAlgorithm(optax.adam(1e-2)), 4,
                       overlap="on")._overlap_active()
    assert not trainer_for(ByteGradAlgorithm(), 4)._overlap_active()
    assert trainer_for(ByteGradAlgorithm(), 4,
                       overlap="on")._overlap_active()
    # families outside the contract never overlap
    assert not trainer_for(QAdamAlgorithm(warmup_steps=2), 4,
                           overlap="on")._overlap_active()
    # explicit chunking opts accum==1 into the ring path
    assert trainer_for(GradientAllReduceAlgorithm(), 1,
                       overlap_chunk_bytes=4096)._overlap_active()


def test_overlap_readiness_rebucket_covers_all_tensors():
    _, _, trainer = _train(GradientAllReduceAlgorithm, optax.sgd(0.1), 4,
                           "on", steps=1)
    assert trainer._overlap_ordered
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    from bagua_tpu.tensor import build_params

    expected = {p.name for p in build_params(params)}
    assert set(trainer._plan.tensor_names) == expected


def test_recommendation_path_carries_overlap_knobs():
    from bagua_tpu.define import BaguaHyperparameter
    from bagua_tpu.service.autotune_task_manager import AutotuneTaskManager

    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        bucket_bytes=256, overlap="off",
    )
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    trainer.init(params)
    trainer._apply_recommendation(
        BaguaHyperparameter(overlap="on", overlap_chunk_bytes=4096)
    )
    assert trainer.overlap == "on"
    assert trainer.overlap_chunk_bytes == 4096
    # "" / 0 keep the current values
    trainer._apply_recommendation(BaguaHyperparameter())
    assert trainer.overlap == "on"
    assert trainer.overlap_chunk_bytes == 4096
    # the trainer reports its knobs, and the service's next materialized
    # recommendation carries them through re-bucketing
    hp = trainer._current_hyperparameters()
    assert hp.overlap == "on" and hp.overlap_chunk_bytes == 4096
    mgr = AutotuneTaskManager("t", is_output_autotune_log=False)
    decls = [t.declaration() for b in trainer._plan.buckets
             for t in b.tensors]
    nxt = mgr.ask_hyperparameters(100, decls, hp, 1.0)
    assert nxt.overlap == "on" and nxt.overlap_chunk_bytes == 4096
