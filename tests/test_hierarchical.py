"""DCN-aware hierarchical bucket collectives (ISSUE 11).

Pinned contracts:

* the two-level decomposition (slice-local reduce-scatter -> cross-slice
  allreduce on the 1/intra shard -> slice-local allgather) matches the flat
  fused allreduce numerically on the (2-slice x 4-chip) cpu-sim mesh — the
  only difference is sum association order, so the comparison is
  tight-tolerance, while END-TO-END loss trajectories are BIT-equal for the
  sgd-family (allreduce, zero) on this pinned workload/horizon (the
  last-ulp gradient drift stays below f32 loss resolution for these 5
  steps — deterministic here, but heavier workloads accumulate an ulp:
  the drive script pins <=1e-5 relative over 40 steps) and within
  quantization tolerance for bytegrad;
* the DCN tier carries ~1/intra_size of the flat path's bytes (jaxpr byte
  accounting — exact on any platform);
* per-tier ring chunking is layout-symmetric with the fused primitives and
  with itself across the scatter/gather pair;
* overlap-vs-serialized stays bit-identical under the hierarchical path;
* ``overlap="off"`` + non-hierarchical construction contains no tiered
  collectives (HLO pin);
* the per-tier chunk knobs ride the env registry, the autotune
  recommendation path, and the step-cache key;
* ``get_backend`` invalidates its cache when the global mesh changes
  (elastic resize / ``set_global_mesh``);
* ``ring_chunks_for`` handles prime/pathological per-rank blocks in
  O(sqrt(m)) via the direct largest-divisor computation.
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
    ZeroOptimizerAlgorithm,
)
from bagua_tpu.algorithms.base import AlgorithmContext
from bagua_tpu.communication import (
    LINK_DCN,
    LINK_ICI,
    MAX_RING_CHUNKS,
    BaguaCommunicator,
    ReduceOp,
    collapse_trivial_axes,
    largest_divisor_leq,
    ring_chunks_for,
)
from jax import shard_map
from bagua_tpu.models import MLP
from bagua_tpu.parallel.mesh import build_mesh

N = 8
INTRA = 4
INTER = 2
DIM = 12
NCLASS = 10
MODEL = MLP(features=(16, NCLASS))


def _loss_fn(params, batch):
    logits = MODEL.apply({"params": params}, batch["x"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["y"]
    ).mean()


def _hier_mesh():
    return build_mesh({"inter": INTER, "intra": INTRA})


def _ctx(mesh, **kw):
    class _EmptyPlan:
        buckets = []

    comm = BaguaCommunicator(
        collapse_trivial_axes(mesh, ("inter", "intra")), mesh
    )
    return AlgorithmContext(
        comm=comm,
        internode=BaguaCommunicator("inter", mesh),
        intranode=BaguaCommunicator("intra", mesh),
        plan=kw.pop("plan", _EmptyPlan()),
        world_size=N,
        **kw,
    )


def _run(mesh, fn, x):
    spec = P(("inter", "intra"))
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                  check_vma=False)
    )(x)


# ---- divisor search (satellite: O(sqrt(m)) largest divisor) ------------


def test_largest_divisor_leq():
    assert largest_divisor_leq(12, 12) == 12
    assert largest_divisor_leq(12, 100) == 12
    assert largest_divisor_leq(12, 5) == 4
    assert largest_divisor_leq(128, 10) == 8
    # primes: the only divisor <= k < m is 1
    assert largest_divisor_leq(127, 126) == 1
    assert largest_divisor_leq(104729, 104728) == 1
    assert largest_divisor_leq(1, 5) == 1
    # perfect square (the i*i == m edge of the enumeration)
    assert largest_divisor_leq(49, 7) == 7
    assert largest_divisor_leq(49, 6) == 1
    # semiprime with a large factor
    assert largest_divisor_leq(2 * 104729, 104729) == 104729
    assert largest_divisor_leq(2 * 104729, 104728) == 2


def test_ring_chunks_for_prime_and_pathological_sizes():
    # prime per-rank block: the old O(m) `k -= 1` scan walked every
    # candidate; the divisor computation answers directly (and the answer
    # for any k < m is 1 — a prime block cannot be split evenly)
    assert ring_chunks_for(8 * 104729, 4, 8, 4) == 1
    assert ring_chunks_for(1016, 4, 8, 4) == 1          # m = 127, prime
    # highly composite block still sizes normally
    assert ring_chunks_for(1024, 4, 8, 128) == 4
    assert ring_chunks_for(1024, 4, 8, 512) == 1
    # indivisible buffers size against the ring's internal zero-padding
    assert ring_chunks_for(1023, 4, 8, 64) == 8
    # the compile-size cap still binds
    assert ring_chunks_for(800_000, 4, 8, 16) <= MAX_RING_CHUNKS
    # every answer divides the (padded) per-rank block
    for numel in (1016, 1023, 997 * 8, 123456):
        for chunk in (4, 64, 1000):
            k = ring_chunks_for(numel, 4, 8, chunk)
            m = -(-numel // 8)
            assert m % k == 0


def test_ring_chunks_for_link_class_mapping():
    # a mapping chunk target resolves per link class; ints apply anywhere
    targets = {LINK_ICI: 128, LINK_DCN: 512}
    assert ring_chunks_for(1024, 4, 8, targets, LINK_ICI) == 4
    assert ring_chunks_for(1024, 4, 8, targets, LINK_DCN) == 1
    assert ring_chunks_for(1024, 4, 8, targets, "unknown") == 1
    assert ring_chunks_for(1024, 4, 8, 128, LINK_DCN) == 4


def test_ctx_chunk_bytes_per_tier_fallback():
    mesh = _hier_mesh()
    ctx = _ctx(mesh, overlap=True, overlap_chunk_bytes=64,
               intra_chunk_bytes=32, inter_chunk_bytes=256)
    assert ctx.chunk_bytes_for(LINK_ICI) == 32
    assert ctx.chunk_bytes_for(LINK_DCN) == 256
    # unset tier knobs fall back to the link-agnostic target
    ctx2 = _ctx(mesh, overlap=True, overlap_chunk_bytes=64)
    assert ctx2.chunk_bytes_for(LINK_ICI) == 64
    assert ctx2.chunk_bytes_for(LINK_DCN) == 64


# ---- two-level decomposition vs the flat fused allreduce ---------------


@pytest.mark.parametrize("size", [64, 50, 7])
@pytest.mark.parametrize("op", [ReduceOp.AVG, ReduceOp.SUM])
def test_two_level_allreduce_matches_flat(op, size):
    """The decomposition computes the same reduction as the flat psum —
    tight tolerance: the tiers change only the sum association order
    (indivisible sizes exercise the internal zero-padding)."""
    mesh = _hier_mesh()
    ctx = _ctx(mesh)
    assert ctx.two_tier()
    x = np.random.default_rng(0).normal(size=(N, size)).astype(np.float32)
    flat = _run(mesh, lambda v: ctx.comm.allreduce(v[0], op)[None], x)
    two = _run(
        mesh, lambda v: ctx.hierarchical_allreduce(v[0], op, True)[None], x
    )
    assert np.asarray(two).shape == np.asarray(flat).shape
    np.testing.assert_allclose(
        np.asarray(two), np.asarray(flat), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("intra_chunk,inter_chunk",
                         [(32, 0), (0, 16), (32, 16)])
def test_two_level_per_tier_ring_matches_fused(intra_chunk, inter_chunk):
    """Per-tier ring chunking (either tier, or both) reproduces the fused
    two-level result — the ring-vs-psum layout symmetry per tier."""
    mesh = _hier_mesh()
    fused = _ctx(mesh)
    ringed = _ctx(mesh, overlap=True,
                  intra_chunk_bytes=intra_chunk or None,
                  inter_chunk_bytes=inter_chunk or None)
    x = np.random.default_rng(1).normal(size=(N, 64)).astype(np.float32)
    a = _run(mesh, lambda v: fused.hierarchical_allreduce(
        v[0], ReduceOp.AVG, True)[None], x)
    b = _run(mesh, lambda v: ringed.hierarchical_allreduce(
        v[0], ReduceOp.AVG, True)[None], x)
    np.testing.assert_allclose(
        np.asarray(b), np.asarray(a), rtol=1e-6, atol=1e-6
    )


def test_tier_scatter_gather_pair_is_layout_symmetric():
    """tier_reduce_scatter -> tier_allgather round-trips to the intra
    psum average under ring chunking, and the chunked tier_allgather is
    EXACTLY the fused all_gather (pure data movement)."""
    mesh = _hier_mesh()
    ctx = _ctx(mesh, overlap=True, intra_chunk_bytes=32)
    fused = _ctx(mesh)
    x = np.random.default_rng(2).normal(size=(N, 64)).astype(np.float32)

    def pair(v):
        chunk = ctx.tier_reduce_scatter(v[0], ReduceOp.AVG)
        return ctx.tier_allgather(chunk)[None]

    out = _run(mesh, pair, x)
    # each slice row averages ITS slice's 4 rows (intra average)
    want = x.reshape(INTER, INTRA, 64).mean(axis=1, keepdims=True)
    want = np.broadcast_to(want, (INTER, INTRA, 64)).reshape(N, 64)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6, atol=1e-6)

    # gather stage alone: chunked ring == fused all_gather, bit-exact
    y = np.random.default_rng(3).normal(size=(N, 16)).astype(np.float32)
    ringed = _run(mesh, lambda v: ctx.tier_allgather(v[0])[None], y)
    plain = _run(mesh, lambda v: fused.tier_allgather(v[0])[None], y)
    np.testing.assert_array_equal(np.asarray(ringed), np.asarray(plain))


# ---- end-to-end: two-tier vs flat training equivalence -----------------


def _train(algo_factory, optimizer, accum, hierarchical, overlap="off",
           steps=5, **kw):
    trainer = BaguaTrainer(
        _loss_fn, optimizer, algo_factory(hierarchical), mesh=_hier_mesh(),
        bucket_bytes=256, accum_steps=accum, overlap=overlap,
        autotune=False, **kw,
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
        (lambda h: GradientAllReduceAlgorithm(hierarchical=h),
         optax.sgd(0.1), True),
        (lambda h: ZeroOptimizerAlgorithm(optax.adam(1e-2), hierarchical=h),
         None, True),
        # the DCN-stage codec quantizes the 1/intra shard instead of the
        # whole bucket, so the 8-bit levels differ from the flat path's
        (lambda h: ByteGradAlgorithm(hierarchical=h), optax.sgd(0.1), False),
    ],
    ids=["gradient_allreduce", "zero", "bytegrad"],
)
def test_two_tier_matches_flat_trajectory(algo_factory, optimizer, exact,
                                          accum):
    l_flat, st_flat, tr_flat = _train(algo_factory, optimizer, accum, False)
    l_two, st_two, tr_two = _train(algo_factory, optimizer, accum, True)
    if exact:
        # two different programs: the two-level decomposition sums within
        # a slice, then across slices, the flat collective over all ranks
        # at once, so the same gradients associate in another order —
        # params and the losses read off them agree to a few float32 ulp
        np.testing.assert_allclose(l_two, l_flat, rtol=1e-6, atol=0)
        for a, b in zip(jax.tree.leaves(tr_two.unstack_params(st_two)),
                        jax.tree.leaves(tr_flat.unstack_params(st_flat))):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
    else:
        np.testing.assert_allclose(l_two, l_flat, rtol=0.05, atol=0.02)


@pytest.mark.parametrize(
    "algo_factory,optimizer",
    [
        (lambda h: GradientAllReduceAlgorithm(hierarchical=h),
         optax.sgd(0.1)),
        (lambda h: ZeroOptimizerAlgorithm(optax.adam(1e-2), hierarchical=h),
         None),
    ],
    ids=["gradient_allreduce", "zero"],
)
def test_hierarchical_overlap_matches_serialized(algo_factory, optimizer):
    """Overlap-vs-serialized stays BIT-identical under the hierarchical
    path (one reduce_bucket_grad implementation, launch reordering never
    changes the per-bucket math)."""
    l_off, _, _ = _train(algo_factory, optimizer, 4, True, overlap="off")
    l_on, _, tr_on = _train(algo_factory, optimizer, 4, True, overlap="on")
    assert tr_on._overlap_active()
    np.testing.assert_array_equal(l_on, l_off)


def test_hierarchical_per_tier_chunked_end_to_end():
    """Per-tier ring chunking trains the fused two-level trajectory within
    float tolerance (ring reduction order differs per tier)."""
    l_fused, _, _ = _train(
        lambda h: GradientAllReduceAlgorithm(hierarchical=h),
        optax.sgd(0.1), 4, True, overlap="on",
    )
    l_ring, _, tr = _train(
        lambda h: GradientAllReduceAlgorithm(hierarchical=h),
        optax.sgd(0.1), 4, True, overlap="on",
        overlap_chunk_bytes_intra=64, overlap_chunk_bytes_inter=32,
    )
    assert tr._overlap_active()
    np.testing.assert_allclose(l_ring, l_fused, rtol=1e-5, atol=1e-6)


# ---- DCN byte accounting (the decomposition's reason to exist) ---------


def _tier_wire_bytes(trainer, state, batch):
    """(dcn_bytes, ici_bytes) of one traced step: jaxpr collective
    operands classified by axis — anything spanning ``inter`` crosses the
    slice boundary."""
    from bagua_tpu.analysis.jaxpr_check import iter_collectives

    jaxpr = trainer.trace_step(state, batch)
    dcn = ici = 0
    for c in iter_collectives(jaxpr):
        if "inter" in c.axes:
            dcn += c.nbytes
        else:
            ici += c.nbytes
    return dcn, ici


@pytest.mark.parametrize(
    "algo_factory,optimizer,dcn_share",
    [
        # the pure shard: exactly 1/intra of the flat path's bytes
        (lambda h: GradientAllReduceAlgorithm(hierarchical=h),
         optax.sgd(0.1), 1.01 / INTRA),
        # below the shard: the flat path's gather legs all cross the boundary
        (lambda h: ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9),
                                          hierarchical=h), None, 0.5),
        # the codec's per-rank min/max scales do not shrink with the shard,
        # yet the slow link's bytes must still halve
        (lambda h: ByteGradAlgorithm(hierarchical=h), optax.sgd(0.1), 0.5),
    ],
    ids=["gradient_allreduce", "zero", "bytegrad"],
)
def test_dcn_bytes_reduced_to_shard(algo_factory, optimizer, dcn_share):
    """The flat path moves every bucket's FULL bytes across the slice
    boundary; the two-level path moves the 1/intra_size shard (+ the
    4-byte loss reduction) — the acceptance ratio of ISSUE 11."""
    def build(hierarchical):
        trainer = BaguaTrainer(
            _loss_fn, optimizer, algo_factory(hierarchical),
            mesh=_hier_mesh(), bucket_bytes=256, autotune=False,
            overlap="off",
        )
        params = MODEL.init(
            jax.random.PRNGKey(0), jnp.zeros((1, DIM))
        )["params"]
        state = trainer.init(params)
        rng = np.random.default_rng(0)
        batch = trainer.shard_batch({
            "x": rng.normal(size=(N * 2, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2,)).astype(np.int32),
        })
        return trainer, state, batch

    dcn_flat, _ = _tier_wire_bytes(*build(False))
    dcn_two, ici_two = _tier_wire_bytes(*build(True))
    loss_scalar_bytes = 4
    assert dcn_two - loss_scalar_bytes <= (
        (dcn_flat - loss_scalar_bytes) * dcn_share
    ) + 8  # +8: per-bucket intra-padding slack
    # and the ICI tiers took over the heavy lifting
    assert ici_two > dcn_two


def test_non_hierarchical_off_construction_has_no_tiered_collectives():
    """HLO pin: the non-hierarchical ``overlap="off"`` construction is
    untouched by the tier machinery — no reduce-scatter/all-gather stages
    appear (one fused all-reduce per bucket), and setting the per-tier
    knobs without overlap changes nothing (they are nulled outside the
    overlap scheduler, same as the link-agnostic knob)."""
    def hlo(**kw):
        trainer = BaguaTrainer(
            _loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
            mesh=_hier_mesh(), bucket_bytes=256, overlap="off",
            autotune=False, **kw,
        )
        params = MODEL.init(
            jax.random.PRNGKey(0), jnp.zeros((1, DIM))
        )["params"]
        state = trainer.init(params)
        rng = np.random.default_rng(0)
        batch = trainer.shard_batch({
            "x": rng.normal(size=(N * 2, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2,)).astype(np.int32),
        })
        return trainer._get_step_fn().lower(state, batch).as_text()

    plain = hlo()
    assert "reduce-scatter" not in plain
    assert "collective-permute" not in plain
    knobbed = hlo(overlap_chunk_bytes_intra=64, overlap_chunk_bytes_inter=32)
    assert knobbed == plain


# ---- bandwidth-tier-aware overlap scheduling ---------------------------


def test_bucket_launch_order_streams_dcn_dominant_first():
    from bagua_tpu.bucket import BucketPlan
    from bagua_tpu.tensor import build_params

    params = {
        "a": jnp.zeros((8,), jnp.float32),
        "b": jnp.zeros((256,), jnp.float32),
        "c": jnp.zeros((64,), jnp.float32),
    }
    named = build_params(params)
    plan = BucketPlan.from_declaration_buckets(
        [[p.declaration()] for p in named], named, alignment=1
    )
    mesh = _hier_mesh()
    ctx = _ctx(mesh, plan=plan, overlap=True)
    sizes = [b.padded_numel for b in plan.buckets]
    want = sorted(range(len(sizes)), key=lambda i: -sizes[i])
    assert ctx.bucket_launch_order(True) == want
    # plan (readiness) order everywhere else: serialized, non-hierarchical
    assert ctx.bucket_launch_order(False) == list(range(len(sizes)))
    serialized = _ctx(mesh, plan=plan, overlap=False)
    assert serialized.bucket_launch_order(True) == list(range(len(sizes)))
    # tier byte estimates: the DCN stage carries the 1/intra shard
    tiers = ctx.bucket_tier_bytes(want[0], True)
    assert tiers["tier"] == "two_level"
    assert tiers["dcn_bytes"] <= tiers["bytes"] // INTRA
    flat_tiers = ctx.bucket_tier_bytes(want[0], False)
    assert flat_tiers["tier"] == "flat"
    assert flat_tiers["dcn_bytes"] > tiers["dcn_bytes"]


# ---- knobs: env/step-cache/autotune plumbing ---------------------------


def test_step_key_includes_tier_knobs_only_under_overlap():
    _, _, tr = _train(lambda h: GradientAllReduceAlgorithm(hierarchical=h),
                      optax.sgd(0.1), 4, True, overlap="on", steps=1)
    key_before = tr._step_key()
    tr.overlap_chunk_bytes_inter = 12345
    assert tr._step_key() != key_before
    _, _, tr_off = _train(
        lambda h: GradientAllReduceAlgorithm(hierarchical=h),
        optax.sgd(0.1), 1, True, overlap="off", steps=1,
    )
    key_off = tr_off._step_key()
    tr_off.overlap_chunk_bytes_inter = 12345
    assert tr_off._step_key() == key_off


def test_recommendation_path_carries_tier_knobs():
    from bagua_tpu.define import BaguaHyperparameter
    from bagua_tpu.service.autotune_task_manager import AutotuneTaskManager

    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        mesh=_hier_mesh(), bucket_bytes=256, overlap="off", autotune=False,
    )
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    trainer.init(params)
    trainer._apply_recommendation(BaguaHyperparameter(
        overlap="on", overlap_chunk_bytes_intra=4096,
        overlap_chunk_bytes_inter=1 << 20, is_hierarchical_reduce=True,
    ))
    assert trainer.overlap_chunk_bytes_intra == 4096
    assert trainer.overlap_chunk_bytes_inter == 1 << 20
    assert trainer.algorithm.hierarchical is True
    # 0 keeps the current values
    trainer._apply_recommendation(
        BaguaHyperparameter(is_hierarchical_reduce=True)
    )
    assert trainer.overlap_chunk_bytes_intra == 4096
    assert trainer.overlap_chunk_bytes_inter == 1 << 20
    hp = trainer._current_hyperparameters()
    assert hp.overlap_chunk_bytes_intra == 4096
    assert hp.overlap_chunk_bytes_inter == 1 << 20
    assert hp.is_hierarchical_reduce is True
    # the service's next materialized recommendation carries them through
    mgr = AutotuneTaskManager("t", is_output_autotune_log=False)
    decls = [t.declaration() for b in trainer._plan.buckets
             for t in b.tensors]
    nxt = mgr.ask_hyperparameters(100, decls, hp, 1.0)
    assert nxt.overlap_chunk_bytes_intra == 4096
    assert nxt.overlap_chunk_bytes_inter == 1 << 20


def test_tier_knobs_opt_into_overlap_and_env_registry():
    from bagua_tpu import env as env_mod

    for var in ("BAGUA_OVERLAP_CHUNK_BYTES_INTRA",
                "BAGUA_OVERLAP_CHUNK_BYTES_INTER"):
        assert var in env_mod.ENV_REGISTRY
    # a per-tier knob is an explicit opt-in to the ring path at accum==1,
    # like the link-agnostic knob
    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1),
        GradientAllReduceAlgorithm(hierarchical=True), mesh=_hier_mesh(),
        bucket_bytes=256, overlap_chunk_bytes_inter=4096, autotune=False,
    )
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    trainer.init(params)
    assert trainer._overlap_active()


# ---- get_backend cache invalidation (satellite) ------------------------


def test_get_backend_invalidated_on_mesh_change():
    from bagua_tpu import communication
    from bagua_tpu.parallel.mesh import set_global_mesh

    mesh_a = _hier_mesh()
    set_global_mesh(mesh_a)
    be_a = communication.get_backend("m")
    assert be_a.mesh is mesh_a
    # same registered mesh: the cache holds (no rebuild per call)
    assert communication.get_backend("m") is be_a
    # an elastic resize / set_global_mesh re-registers a NEW mesh object:
    # the cached backend spans the dead topology and must be rebuilt
    mesh_b = build_mesh({"dp": N})
    set_global_mesh(mesh_b)
    be_b = communication.get_backend("m")
    assert be_b is not be_a
    assert be_b.mesh is mesh_b
    assert be_b.global_communicator.mesh is mesh_b
