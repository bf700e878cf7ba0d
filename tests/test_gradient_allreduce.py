"""Golden-model equivalence for GradientAllReduce.

Reference pattern (SURVEY.md §4): run the algorithm distributed, then a pure
single-worker reimplementation on the same data, and compare weights
elementwise.  DP with averaged grads over the full batch must equal
single-worker training on the concatenated batch.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from tests.internal.jaxpr_walk import equations

from bagua_tpu import BaguaTrainer
from bagua_tpu.algorithms import GradientAllReduceAlgorithm
from bagua_tpu.models import MLP

N = 8
BATCH_PER_RANK = 4
DIM = 12
NCLASS = 10


def _data(steps=5, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(steps, N * BATCH_PER_RANK, DIM)).astype(np.float32)
    ys = rng.integers(0, NCLASS, size=(steps, N * BATCH_PER_RANK)).astype(np.int32)
    return xs, ys


def _loss_fn(model):
    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]
        ).mean()

    return loss_fn


@pytest.mark.parametrize("hierarchical", [False, True])
def test_matches_single_worker_sgd(hierarchical):
    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    opt = optax.sgd(0.1)
    loss_fn = _loss_fn(model)

    trainer = BaguaTrainer(
        loss_fn, opt, GradientAllReduceAlgorithm(hierarchical=hierarchical),
        bucket_bytes=256,
    )
    state = trainer.init(params)

    xs, ys = _data()
    for s in range(xs.shape[0]):
        state, loss = trainer.train_step(state, {"x": xs[s], "y": ys[s]})

    # golden: plain full-batch SGD (mean loss over the whole global batch ==
    # mean of per-rank means since shards are equal size)
    gp = params
    gopt = opt.init(gp)
    g_step = jax.jit(
        lambda p, o, b: (lambda g: (optax.apply_updates(p, opt.update(g, o, p)[0]), opt.update(g, o, p)[1]))(
            jax.grad(loss_fn)(p, b)
        )
    )
    for s in range(xs.shape[0]):
        gp, gopt = g_step(gp, gopt, {"x": xs[s], "y": ys[s]})

    # leaf view: flat-resident raw state holds bucket flats, not leaves
    flat_a = jax.tree.leaves(trainer.unstack_params(state))
    flat_b = jax.tree.leaves(gp)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_bf16_comm_dtype_close_to_full_precision():
    """comm_dtype=bfloat16 halves wire bytes; the result must track the
    full-precision allreduce within bf16 rounding (bf16 keeps f32's
    exponent range, so no scale factor is involved)."""
    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    loss_fn = _loss_fn(model)
    xs, ys = _data(steps=3, seed=5)

    outs = {}
    for dtype in (None, jnp.bfloat16):
        trainer = BaguaTrainer(
            loss_fn, optax.sgd(0.1),
            GradientAllReduceAlgorithm(comm_dtype=dtype), bucket_bytes=256,
        )
        st = trainer.init(params)
        for s in range(xs.shape[0]):
            st, _ = trainer.train_step(st, {"x": xs[s], "y": ys[s]})
        outs[dtype] = st.params

    for a, b in zip(jax.tree.leaves(outs[jnp.bfloat16]), jax.tree.leaves(outs[None])):
        a, b = np.asarray(a), np.asarray(b)
        # bf16 has ~3 decimal digits; after 3 SGD steps the drift stays
        # within a few bf16 ulps of the weight scale
        np.testing.assert_allclose(a, b, rtol=0, atol=3e-2)


def test_bf16_comm_dtype_hierarchical():
    """comm_dtype composes with the hierarchical (intra -> inter) path:
    both allreduce stages run on the cast buffer, result tracks full
    precision within bf16 rounding."""

    from bagua_tpu.parallel.mesh import hierarchical_mesh

    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    loss_fn = _loss_fn(model)
    xs, ys = _data(steps=3, seed=11)

    outs = {}
    for dtype in (None, jnp.bfloat16):
        trainer = BaguaTrainer(
            loss_fn, optax.sgd(0.1),
            GradientAllReduceAlgorithm(hierarchical=True, comm_dtype=dtype),
            mesh=hierarchical_mesh(intra_size=4), bucket_bytes=256,
        )
        st = trainer.init(params)
        for s in range(xs.shape[0]):
            st, _ = trainer.train_step(st, {"x": xs[s], "y": ys[s]})
        outs[dtype] = st.params

    # anchor the nontrivial 2x4 hierarchical topology to a flat-mesh golden
    # (avg-of-avg over equal groups == global avg); the bf16 run is then
    # compared against the anchored full-precision run
    flat = BaguaTrainer(
        loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        bucket_bytes=256,
    )
    st = flat.init(params)
    for s in range(xs.shape[0]):
        st, _ = flat.train_step(st, {"x": xs[s], "y": ys[s]})
    for a, b in zip(jax.tree.leaves(outs[None]), jax.tree.leaves(st.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)

    for a, b in zip(jax.tree.leaves(outs[jnp.bfloat16]), jax.tree.leaves(outs[None])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=3e-2)


def test_sum_vs_avg_scales_update():
    model = MLP(features=(8, NCLASS))
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, DIM)))["params"]
    loss_fn = _loss_fn(model)
    xs, ys = _data(steps=1, seed=3)
    batch = {"x": xs[0], "y": ys[0]}

    outs = {}
    for avg in (True, False):
        trainer = BaguaTrainer(
            loss_fn, optax.sgd(0.05), GradientAllReduceAlgorithm(average=avg)
        )
        st = trainer.init(params)
        st, _ = trainer.train_step(st, batch)
        outs[avg] = trainer.unstack_params(st)

    # delta with SUM should be N times delta with AVG
    d_avg = jax.tree.map(lambda a, b: np.asarray(a - b), outs[True], params)
    d_sum = jax.tree.map(lambda a, b: np.asarray(a - b), outs[False], params)
    for a, b in zip(jax.tree.leaves(d_avg), jax.tree.leaves(d_sum)):
        np.testing.assert_allclose(b, N * a, rtol=1e-4, atol=1e-5)


# ---- the sharded update: reduce-scatter -> update of the owned chunk ->
# all-gather (gradient_allreduce.py's header) --------------------------------
#
# Nothing selects it, so a test that wants the replicated update over the
# same 8 ranks steers what the trainer observes: the elementwise probe.


def _keep_replicated(monkeypatch):
    from bagua_tpu.core import backend

    monkeypatch.setattr(backend, "is_elementwise", lambda optimizer: False)


def _mlp_task(features=(16, NCLASS), seed=0):
    model = MLP(features=features)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, DIM)))["params"]
    return _loss_fn(model), params


def _run(trainer, params, xs, ys):
    state = trainer.init(params)
    losses = []
    for s in range(xs.shape[0]):
        state, loss = trainer.train_step(state, {"x": xs[s], "y": ys[s]})
        losses.append(float(loss))
    return state, losses


_OPTIMIZERS = {
    "adamw": lambda: optax.adamw(1e-2),
    "sgd_momentum": lambda: optax.sgd(0.1, momentum=0.9),
}


@pytest.mark.parametrize("accum_steps", [1, 4])
@pytest.mark.parametrize("comm_dtype", [None, jnp.float32, jnp.bfloat16],
                         ids=["wire_as_is", "float32_wire", "bf16_wire"])
@pytest.mark.parametrize("optimizer", sorted(_OPTIMIZERS))
def test_sharded_update_trajectory_is_the_replicated_one(
        optimizer, comm_dtype, accum_steps, monkeypatch):
    """Every rank steps the chunk it owns and gathers the rest: the
    parameters after 6 steps are the ones the all-reduce and 8 replicated
    updates give.  bucket_bytes=600 leaves one packed 1-D flat beside the
    shaped buckets, so both kinds of chunk are driven.  A wire narrower
    than the parameters keeps the all-reduce (the gather would carry the
    parameters' float32: more bytes than the bfloat16 all-reduce moves),
    so that case is the same program twice."""
    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=6, seed=3)

    def make():
        return BaguaTrainer(
            loss_fn, _OPTIMIZERS[optimizer](),
            GradientAllReduceAlgorithm(comm_dtype=comm_dtype),
            bucket_bytes=600, accum_steps=accum_steps)

    sharded = make()
    st_a, losses_a = _run(sharded, params, xs, ys)
    assert sharded._update_sharded() == (comm_dtype is not jnp.bfloat16)
    assert sharded._overlap_active() == (accum_steps > 1)
    _keep_replicated(monkeypatch)
    replicated = make()
    st_b, losses_b = _run(replicated, params, xs, ys)
    assert not replicated._update_sharded()

    # the same reduction and the same elementwise arithmetic; XLA:CPU is
    # free to fuse the two programs differently
    np.testing.assert_allclose(losses_a, losses_b, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(sharded.unstack_params(st_a)),
                    jax.tree.leaves(replicated.unstack_params(st_b))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    # the moments are the replicated layout's buffers, cut over the ranks
    for a, b in zip(jax.tree.leaves(st_a.opt_state),
                    jax.tree.leaves(st_b.opt_state)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_sum_reduction_rides_the_sharded_update(monkeypatch):
    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=3, seed=4)

    def make():
        return BaguaTrainer(loss_fn, optax.sgd(0.01),
                            GradientAllReduceAlgorithm(average=False),
                            bucket_bytes=600)

    sharded = make()
    st_a, _ = _run(sharded, params, xs, ys)
    assert sharded._update_sharded()
    _keep_replicated(monkeypatch)
    replicated = make()
    st_b, _ = _run(replicated, params, xs, ys)
    for a, b in zip(jax.tree.leaves(sharded.unstack_params(st_a)),
                    jax.tree.leaves(replicated.unstack_params(st_b))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_chunks_are_rows_of_the_bucket_and_nothing_is_raveled():
    """A shaped bucket is scattered over its leading axis, its owned rows
    are a chunk of that axis, the gather puts rows together: no reshape of
    a bucket's buffer to 1-D anywhere in the step."""
    loss_fn, params = _mlp_task()
    trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                           GradientAllReduceAlgorithm(), bucket_bytes=256)
    state = trainer.init(params)
    plan = trainer._plan
    shapes = {b.buffer_shape for b in plan.buckets}
    assert all(b.shaped for b in plan.buckets) and (16, NCLASS) in shapes
    xs, ys = _data(steps=1)
    eqns = list(equations(trainer.trace_step(
        state, trainer.shard_batch({"x": xs[0], "y": ys[0]})).jaxpr))

    ctx = trainer._ctx(plan)
    taken = [b.buffer_shape for i, b in enumerate(plan.buckets)
             if ctx.update_sharded(i)]
    assert (16, NCLASS) in taken and (DIM, 16) not in taken  # 12 rows / 8
    scatters = [e for e in eqns if e.primitive.name == "reduce_scatter"]
    gathers = [e for e in eqns if e.primitive.name == "all_gather"]
    assert sorted(e.invars[0].aval.shape for e in scatters) == sorted(taken)
    assert sorted(e.outvars[0].aval.shape for e in gathers) == sorted(taken)
    assert all(e.params["scatter_dimension"] == 0 and e.params["tiled"]
               for e in scatters)
    assert all(e.params["all_gather_dimension"] == 0 and e.params["tiled"]
               for e in gathers)
    # the buckets left to the all-reduce are the ones that do not divide
    psums = [e.invars[0].aval.shape for e in eqns
             if e.primitive.name == "psum" and e.invars[0].aval.ndim]
    assert sorted(psums) == sorted(shapes - set(taken))
    # owned rows: what the step is handed (the parameters rest as chunks of
    # the leading axis), so the gathers take rows and nothing slices a
    # taken buffer
    rows = sorted((shape[0] // N,) + shape[1:] for shape in taken)
    assert sorted(e.invars[0].aval.shape for e in gathers) == rows
    assert not any(e.primitive.name == "dynamic_slice"
                   and e.invars[0].aval.shape in taken for e in eqns)
    for e in eqns:
        if e.primitive.name == "reshape":
            src, dst = e.invars[0].aval.shape, e.outvars[0].aval.shape
            assert not (len(src) > 1 and len(dst) == 1
                        and src in shapes), (src, dst)


def test_a_rank_stores_a_world_th_of_the_moments_and_the_parameters(
        monkeypatch):
    from bagua_tpu.obs.memory import tree_device_bytes

    # 2,336 parameters in one packed flat: 8 ranks divide it
    loss_fn, params = _mlp_task(features=(16, 64, 16))

    def make():
        trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                               GradientAllReduceAlgorithm(),
                               bucket_bytes=1 << 20)
        return trainer, trainer.init(params)

    sharded, st_a = make()
    _keep_replicated(monkeypatch)
    replicated, st_b = make()
    # the plan is the same at every world size and under either update
    # (nothing is padded to the world: an elastic resume restores it as it
    # is); every byte of this one is sharded
    assert sharded._plan.signature() == replicated._plan.signature()
    assert [(b.shaped, b.padding) for b in sharded._plan.buckets] == [
        (False, 0)]
    assert sharded._plan.buckets[0].padded_numel % N == 0
    count = 4  # adamw's step count, replicated
    assert tree_device_bytes(st_a.opt_state) - count == pytest.approx(
        (tree_device_bytes(st_b.opt_state) - count) / N, rel=0.01)
    # ... and of the parameters, which rest as the moments do
    assert tree_device_bytes(st_a.params) == pytest.approx(
        tree_device_bytes(st_b.params) / N, rel=0.01)


def _one_rank_mesh():
    from bagua_tpu.parallel.mesh import build_mesh

    return build_mesh({"dp": 1}, jax.devices()[:1])


_FALLBACKS = {
    # global-norm clipping couples every element: a chunk's norm is not the
    # gradient's
    "not_elementwise": dict(
        optimizer=lambda: optax.chain(optax.clip_by_global_norm(0.1),
                                      optax.adam(1e-2))),
    # (the two-level exchange and the codec's ring scatter and gather on
    # their own account: the update behind them is whole all the same)
    "hierarchical": dict(algorithm=dict(hierarchical=True), scatters=True),
    # the gather would carry float32 where the all-reduce carries bfloat16
    "wire_narrower_than_parameters": dict(
        algorithm=dict(comm_dtype=jnp.bfloat16)),
    # an error-feedback residual rides whole buckets
    "error_feedback_codec": dict(trainer=dict(compress_intra="onebit_ef"),
                                 scatters=True),
    "one_rank": dict(trainer=dict(mesh=_one_rank_mesh)),
}


@pytest.mark.parametrize("case", sorted(_FALLBACKS))
def test_falls_back_to_the_allreduce_and_the_replicated_update(case):
    from bagua_tpu.telemetry import counters

    spec = _FALLBACKS[case]
    loss_fn, params = _mlp_task()
    kwargs = {k: (v() if callable(v) else v)
              for k, v in spec.get("trainer", {}).items()}
    trainer = BaguaTrainer(
        loss_fn, spec.get("optimizer", lambda: optax.adam(1e-2))(),
        GradientAllReduceAlgorithm(**spec.get("algorithm", {})),
        bucket_bytes=256, **kwargs)
    state = trainer.init(params)
    assert not trainer._update_sharded()
    assert all(b.alignment == 1 for b in trainer._plan.buckets)
    xs, ys = _data(steps=1)
    batch = trainer.shard_batch({"x": xs[0][:4 * trainer.world_size],
                                 "y": ys[0][:4 * trainer.world_size]})
    names = {e.primitive.name for e in equations(
        trainer.trace_step(state, batch).jaxpr)}
    assert spec.get("scatters") or not names & {"reduce_scatter",
                                                "all_gather"}
    for leaf in jax.tree.leaves(state.opt_state):
        assert leaf.sharding.is_fully_replicated
    state, loss = trainer.train_step(state, batch)
    assert np.isfinite(float(loss))
    assert counters.get("comm/sharded_update_share") == 0


def test_one_rank_traces_the_step_it_always_did(monkeypatch):
    """World 1 is seven of the benchmark's nine cells: with the mechanism
    in the tree its step is, equation for equation, the step of a trainer
    that cannot shard at all, and holds no collective of the new kind."""
    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=1)

    def trace(**kw):
        trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                               GradientAllReduceAlgorithm(),
                               mesh=_one_rank_mesh(), bucket_bytes=600, **kw)
        state = trainer.init(params)
        batch = trainer.shard_batch({"x": xs[0][:4], "y": ys[0][:4]})
        return trainer, str(trainer.trace_step(state, batch))

    # sha256 of the parent commit's (PR 48, f67b1a2) jaxpr text for the same
    # three constructions, taken from its checkout: a change that moves them
    # moves seven cells' step programs — say so in the PR, then re-pin
    parents = ("fe5b76f9524ce8de", "0de039d80ff17d96", "32305221dff90c27")
    for kw, parent in zip(({}, {"accum_steps": 4}, {"grad_guard": "skip"}),
                          parents):
        trainer, ours = trace(**kw)
        assert hashlib.sha256(ours.encode()).hexdigest()[:16] == parent
        assert not trainer._update_sharded()
        assert trainer._elementwise_probed[0] is None  # (not even probed)
        with monkeypatch.context() as m:
            m.setattr(GradientAllReduceAlgorithm, "supports_sharded_update",
                      False)
            _, never = trace(**kw)
        assert ours == never
        assert "reduce_scatter" not in ours and "all_gather" not in ours


def test_gauge_reads_the_plans_sharded_share():
    from bagua_tpu.obs import export
    from bagua_tpu.telemetry import counters

    assert export.is_registered("comm/sharded_update_share")
    loss_fn, params = _mlp_task()
    trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                           GradientAllReduceAlgorithm(), bucket_bytes=256)
    state = trainer.init(params)
    xs, ys = _data(steps=1)
    trainer.train_step(state, {"x": xs[0], "y": ys[0]})
    nbytes = {b.buffer_shape: b.padded_numel * 4
              for b in trainer._plan.buckets}
    # 8 ranks divide neither the 12 rows of the first kernel nor the 10
    # logits' bias
    assert set(nbytes) == {(DIM, 16), (16,), (16, NCLASS), (NCLASS,)}
    want = (nbytes[(16,)] + nbytes[(16, NCLASS)]) / sum(nbytes.values())
    assert counters.get("comm/sharded_update_share") == pytest.approx(want)
    assert 0.4 < want < 1


# ---- what the sharded state layout touches ---------------------------------


def _sharded_leaves(trainer, state):
    """(sharded, replicated) counts of the optimizer state's array leaves."""
    leaves = [x for x in jax.tree.leaves(state.opt_state) if x.ndim]
    cut = sum(not x.sharding.is_fully_replicated for x in leaves)
    return cut, len(leaves) - cut


def test_a_rebucket_keeps_the_moments_sharded_and_the_trajectory(monkeypatch):
    """Autotune stays on for the default family (``BaguaTrainer`` switches
    it off only for ZeRO's per-chunk states): a rebucket moves the sharded
    moments as it moves replicated ones — globally they are the same
    buffers — and the step is handed them cut over the ranks again."""
    from bagua_tpu.bucket import split_bucket_by_bucket_size
    from bagua_tpu.obs.memory import tree_device_bytes

    loss_fn, params = _mlp_task(features=(16, 64, 16))
    xs, ys = _data(steps=6, seed=8)

    def run(rebucket_at):
        trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                               GradientAllReduceAlgorithm(), bucket_bytes=600,
                               autotune=False)
        state = trainer.init(params)
        held = tree_device_bytes(state.opt_state)
        for s in range(xs.shape[0]):
            if s == rebucket_at:
                decls = [t.declaration() for b in trainer._plan.buckets
                         for t in b.tensors]
                before = trainer._plan.signature()
                trainer.rebucket(split_bucket_by_bucket_size(decls, 1 << 20))
                assert trainer._plan.signature() != before
            state, _ = trainer.train_step(state, {"x": xs[s], "y": ys[s]})
        assert trainer._update_sharded()
        return trainer, state, held

    plain, st_a, _ = run(None)
    moved, st_b, held = run(3)
    for a, b in zip(jax.tree.leaves(plain.unstack_params(st_a)),
                    jax.tree.leaves(moved.unstack_params(st_b))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    # one packed flat now (2,336 elements), every moment of it a 1/8 a rank
    assert _sharded_leaves(moved, st_b) == (2, 0)
    assert tree_device_bytes(st_b.opt_state) <= held


def test_autotune_is_not_switched_off_for_the_sharded_update():
    from bagua_tpu.algorithms import ZeroOptimizerAlgorithm

    loss_fn, _ = _mlp_task()
    keeps = BaguaTrainer(loss_fn, optax.adam(1e-2),
                         GradientAllReduceAlgorithm(), autotune=True)
    drops = BaguaTrainer(loss_fn, None,
                         ZeroOptimizerAlgorithm(optax.adam(1e-2)),
                         autotune=True)
    assert keeps.autotune and not drops.autotune


def test_switch_to_a_family_that_owns_its_optimizer_and_back():
    """gradient_allreduce (sharded moments) -> qadam (its own, replicated)
    -> gradient_allreduce: the stashed optax state comes back and is cut
    over the ranks again."""
    from bagua_tpu.algorithms.q_adam import QAdamOptState
    from bagua_tpu.define import BaguaHyperparameter

    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=1, seed=2)
    batch = {"x": xs[0], "y": ys[0]}
    trainer = BaguaTrainer(loss_fn, optax.adam(1e-2),
                           GradientAllReduceAlgorithm(), bucket_bytes=600,
                           autotune=False)
    state = trainer.init(params)
    layout = _sharded_leaves(trainer, state)
    assert layout[0] > 0
    losses = []

    def steps(state, n):
        for _ in range(n):
            state, loss = trainer.train_step(state, batch)
            losses.append(float(loss))
        return state

    def switch(family):
        trainer._maybe_switch_algorithm(BaguaHyperparameter(
            algorithm=family, is_hierarchical_reduce=False))
        assert trainer.algorithm.name == family

    state = steps(state, 3)
    switch("qadam")
    assert not trainer._update_sharded()
    state = steps(state, 3)  # (the queued migration runs in train_step)
    assert isinstance(state.opt_state, QAdamOptState)
    switch("gradient_allreduce")
    assert trainer._update_sharded()
    state = steps(state, 3)
    assert _sharded_leaves(trainer, state) == layout
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_unstack_params_hands_back_whole_leaves():
    loss_fn, params = _mlp_task()
    trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                           GradientAllReduceAlgorithm(), bucket_bytes=600)
    state = trainer.init(params)
    assert trainer._update_sharded()
    for a, b in zip(jax.tree.leaves(trainer.unstack_params(state)),
                    jax.tree.leaves(params)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("guard", ["skip", "warn"])
def test_the_guards_verdict_is_rank_uniform_under_a_poisoned_gradient(guard):
    """The reduced gradient a rank holds is its own chunk alone, so the
    verdict is read off the gathered parameters, as ZeRO's is: one row,
    the same on every rank; ``skip`` rewinds every rank alike."""
    from bagua_tpu.faults.inject import FaultSpec, fault_scope

    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=1, seed=6)
    batch = {"x": xs[0], "y": ys[0]}
    with fault_scope(FaultSpec("grad.poison", step=1)):
        trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                               GradientAllReduceAlgorithm(), bucket_bytes=600,
                               grad_guard=guard)
        state = trainer.init(params)
        assert trainer._update_sharded()
        state, _ = trainer.train_step(state, batch)
        assert float(trainer.step_metrics["grad_healthy"]) == 1.0
        before = jax.tree.map(np.asarray, trainer.unstack_params(state))
        moments = jax.tree.map(np.asarray, state.opt_state)
        state, _ = trainer.train_step(state, batch)
        health = trainer.step_metrics["grad_health_buckets"]
        assert float(trainer.step_metrics["grad_healthy"]) == 0.0
        assert health.sharding.is_fully_replicated
        trainer.flush_grad_health()
    after = jax.tree.map(np.asarray, trainer.unstack_params(state))
    if guard == "skip":
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree.leaves(moments),
                        jax.tree.leaves(state.opt_state)):
            np.testing.assert_array_equal(a, np.asarray(b))
    else:
        assert not all(np.isfinite(x).all() for x in jax.tree.leaves(after))


# ---- the parameters rest as the moments do (PR 57) ---------------------------
#
# Wherever a bucket's update is sharded its parameter buffer rests between
# steps as a chunk a rank, like its moments; the step gathers it at its top.
# The parent's order — parameters replicated, a rank slices its chunk out,
# steps it and gathers every chunk at the END of the step — lives on here as
# the reference the new order is held to, bit for bit.


def _dp_mesh(dp):
    from bagua_tpu.parallel.mesh import build_mesh

    return build_mesh({"dp": dp}, jax.devices()[:dp])


def _gather_at_the_end(trainer):
    """The parent's step (PR 49's ``update_owned``) over the trainer's own
    plan, exchange and optimizer: ``(whole parameters, moments, batch) ->
    (whole parameters, moments, loss)``."""
    from jax.sharding import PartitionSpec as P

    from bagua_tpu.communication import ReduceOp

    plan = trainer._plan
    ctx = trainer._ctx(plan)
    taken = [i for i in range(len(plan.buckets)) if ctx.update_sharded(i)]

    def loss_on(zp, batch):
        return trainer.loss_fn(trainer._flat_leaf_view(zp), batch)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_on)(params, batch)
        grads, _ = trainer.algorithm.process_grads(ctx, grads, params, None, 0)
        owned = list(params["flats"])
        for i in taken:
            owned[i] = ctx.owned_chunk(owned[i])
        owned = {"flats": tuple(owned), "local": params["local"]}
        updates, opt_state = trainer._opt.update(grads, opt_state, owned)
        owned = optax.apply_updates(owned, updates)
        flats = list(owned["flats"])
        for i in taken:
            flats[i] = ctx.bucket_allgather(flats[i])
        return ({"flats": tuple(flats), "local": owned["local"]}, opt_state,
                ctx.comm.allreduce(loss, ReduceOp.AVG))

    moments = trainer._opt_state_specs(plan)
    return jax.jit(jax.shard_map(
        step, mesh=trainer.mesh, in_specs=(P(), moments, trainer._batch_spec()),
        out_specs=(P(), moments, P()), check_vma=False))


def _whole(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("dp", [4, 8])
@pytest.mark.parametrize("optimizer", sorted(_OPTIMIZERS))
def test_gather_at_the_top_is_bitwise_the_gather_at_the_end(optimizer, dp):
    """After 5 steps the gathered parameters, the moments and every loss are
    the bits the parent's order gives: the same exchange, the same update of
    the same rows, the gather moved from the end of one step to the top of
    the next."""
    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=5, seed=11)
    trainer = BaguaTrainer(loss_fn, _OPTIMIZERS[optimizer](),
                           GradientAllReduceAlgorithm(), mesh=_dp_mesh(dp),
                           bucket_bytes=600)
    state = trainer.init(params)
    assert trainer._update_sharded()
    parent = _gather_at_the_end(trainer)
    # (copies: the trainer's step donates its state)
    whole = jax.tree.map(np.asarray, state.params)
    moments = jax.tree.map(jnp.copy, state.opt_state)
    for s in range(xs.shape[0]):
        batch = trainer.shard_batch({"x": xs[s][:4 * dp], "y": ys[s][:4 * dp]})
        whole, moments, want = parent(whole, moments, batch)
        state, loss = trainer.train_step(state, batch)
        assert float(loss) == float(want)
    for a, b in zip(_whole(state.params) + _whole(state.opt_state),
                    _whole(whole) + _whole(moments)):
        np.testing.assert_array_equal(a, b)


def _flats_of(opt_state):
    """The ``flats`` tuple of every parameter-shaped part of an optax
    state."""
    is_zp = BaguaTrainer._is_flat_container
    found = []
    jax.tree.map(lambda x: found.append(x["flats"]) if is_zp(x) else None,
                 opt_state, is_leaf=is_zp)
    return found


def _rests_as_chunks(trainer, state):
    """Each taken bucket of ``state`` is placed over the comm axes along its
    leading axis and every other is replicated, parameter and moments alike,
    in the plan's global shapes -> how many are taken."""
    from jax.sharding import PartitionSpec as P

    plan, ctx = trainer._plan, trainer._ctx(trainer._plan)
    taken = [ctx.update_sharded(i) for i in range(len(plan.buckets))]
    for flats in [state.params["flats"]] + _flats_of(state.opt_state):
        for held, bucket, cut in zip(flats, plan.buckets, taken):
            assert held.shape == bucket.buffer_shape
            if cut:
                assert held.sharding.spec == P(trainer.comm_axes)
            else:
                assert held.sharding.is_fully_replicated
    return sum(taken)


@pytest.mark.parametrize("comm_dtype", [None, jnp.float32],
                         ids=["wire_as_is", "float32_wire"])
def test_the_parameters_rest_as_their_moments_do(comm_dtype):
    """With a wire as wide as the parameters every taken bucket of
    ``state.params`` is placed over the comm axes, as ``init`` builds it and
    as the step returns it, and ``comm/params_sharded_share`` reads the
    plan's share: 1.0 where the world divides every bucket (four ranks, 12
    and 16 and 32 rows)."""
    from bagua_tpu.obs import export
    from bagua_tpu.telemetry import counters

    assert export.is_registered("comm/params_sharded_share")
    loss_fn, params = _mlp_task(features=(16, 32))
    xs, ys = _data(steps=1)
    trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                           GradientAllReduceAlgorithm(comm_dtype=comm_dtype),
                           mesh=_dp_mesh(4), bucket_bytes=600)
    state = trainer.init(params)
    assert _rests_as_chunks(trainer, state) == len(trainer._plan.buckets)
    state, _ = trainer.train_step(state, {"x": xs[0][:16], "y": ys[0][:16]})
    assert _rests_as_chunks(trainer, state) == len(trainer._plan.buckets)
    assert counters.get("comm/params_sharded_share") == 1.0
    assert counters.get("comm/sharded_update_share") == 1.0


@pytest.mark.parametrize("construction", ["plain", "accum4", "guard_skip"])
def test_a_bfloat16_wire_traces_the_parents_step(construction):
    """``comm_dtype=bfloat16`` over float32 parameters: the predicate says
    no, the parameters stay replicated, the gauge reads 0.0 and the step
    over 8 ranks is the parent's, digest for digest (sha256 of the jaxpr
    text from the parent's checkout, 530cdab, for the three constructions PR
    49 pinned at world 1): ``bert-large.squad384-bf16comm-dp4`` runs the
    program it ran."""
    from bagua_tpu.telemetry import counters

    kw, parent = {
        "plain": ({}, "0d89312b7bbbf84d"),
        "accum4": ({"accum_steps": 4}, "3c7af3b57365c3bd"),
        "guard_skip": ({"grad_guard": "skip"}, "d9c31a7a9f265296"),
    }[construction]
    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=1)
    trainer = BaguaTrainer(
        loss_fn, optax.adamw(1e-2),
        GradientAllReduceAlgorithm(comm_dtype=jnp.bfloat16),
        bucket_bytes=600, **kw)
    state = trainer.init(params)
    batch = trainer.shard_batch({"x": xs[0], "y": ys[0]})
    ours = str(trainer.trace_step(state, batch))
    assert hashlib.sha256(ours.encode()).hexdigest()[:16] == parent
    state, _ = trainer.train_step(state, batch)
    assert not trainer._update_sharded()
    assert _rests_as_chunks(trainer, state) == 0
    assert counters.get("comm/params_sharded_share") == 0.0


def test_a_bucket_the_world_does_not_divide_stays_replicated():
    """8 ranks divide neither the 12 rows of the first kernel nor the 10
    logits' bias: those two rest whole on every rank beside the two that
    rest as chunks, and only the latter are gathered."""
    from bagua_tpu.telemetry import counters

    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=1)
    trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                           GradientAllReduceAlgorithm(), bucket_bytes=256)
    state = trainer.init(params)
    batch = trainer.shard_batch({"x": xs[0], "y": ys[0]})
    gathered = sorted(
        e.outvars[0].aval.shape
        for e in equations(trainer.trace_step(state, batch).jaxpr)
        if e.primitive.name == "all_gather")
    assert gathered == [(16,), (16, NCLASS)]
    state, _ = trainer.train_step(state, batch)
    assert _rests_as_chunks(trainer, state) == 2
    replicated = sorted(f.shape for f in state.params["flats"]
                        if f.sharding.is_fully_replicated)
    assert replicated == [(NCLASS,), (DIM, 16)]
    assert 0.4 < counters.get("comm/params_sharded_share") < 1


@pytest.mark.parametrize("overlap", ["auto", "off"])
@pytest.mark.parametrize("accum_steps", [2, 4])
def test_accumulation_gathers_a_bucket_once_a_step(accum_steps, overlap):
    """The gather stands at the top of the step, outside the micro-batch
    loop: one ``all_gather`` a taken bucket in the whole jaxpr, none in a
    scan's body (nor in the peeled tail the overlap scheduler leaves)."""
    loss_fn, params = _mlp_task()
    xs, ys = _data(steps=1)
    trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                           GradientAllReduceAlgorithm(), bucket_bytes=256,
                           accum_steps=accum_steps, overlap=overlap)
    state = trainer.init(params)
    batch = trainer.shard_batch({"x": xs[0], "y": ys[0]})
    jaxpr = trainer.trace_step(state, batch).jaxpr
    assert trainer._overlap_active() == (overlap == "auto")
    taken = _rests_as_chunks(trainer, state)
    everywhere = [e for e in equations(jaxpr)]
    assert sum(e.primitive.name == "all_gather" for e in everywhere) == taken
    scans = [e for e in everywhere if e.primitive.name == "scan"]
    assert scans
    for scan in scans:
        assert not any(e.primitive.name == "all_gather"
                       for e in equations(scan.params["jaxpr"].jaxpr))


# ---- ... and everything that reads them outside the step -------------------


def _sharded_and_replicated(monkeypatch, optimizer=None, **task):
    """Two trainers over the same 8 ranks and the same plan, the parameters
    of the first resting as chunks, of the second (the parent's layout for
    every reader outside the step) replicated."""
    loss_fn, params = _mlp_task(**task)

    def make():
        trainer = BaguaTrainer(loss_fn, (optimizer or optax.adamw)(1e-2),
                               GradientAllReduceAlgorithm(), bucket_bytes=600,
                               autotune=False)
        return trainer, trainer.init(params)

    sharded = make()
    with monkeypatch.context() as m:
        _keep_replicated(m)
        replicated = make()
        assert not replicated[0]._update_sharded()
    assert sharded[0]._update_sharded()
    return sharded, replicated


def _two_steps_then_the_same_arrays_whole(monkeypatch):
    """The pair after two steps of the first, the second handed the same
    global parameter arrays placed whole on every rank; a third batch."""
    (ta, sa), (tb, sb) = _sharded_and_replicated(monkeypatch)
    xs, ys = _data(steps=3, seed=5)
    for s in range(2):
        sa, _ = ta.train_step(sa, {"x": xs[s], "y": ys[s]})
    sb = sb._replace(params=jax.device_put(
        jax.tree.map(np.asarray, sa.params), sb.params["flats"][0].sharding))
    return (ta, sa), (tb, sb), {"x": xs[2], "y": ys[2]}


def test_eval_step_gathers_what_it_reads(monkeypatch):
    (ta, sa), (tb, sb), batch = _two_steps_then_the_same_arrays_whole(
        monkeypatch)
    got = ta.eval_step(sa, ta.shard_batch(batch))
    assert float(got) == float(tb.eval_step(sb, tb.shard_batch(batch)))
    assert _rests_as_chunks(ta, sa)  # (untouched: nothing is donated)


def test_unstack_params_reads_chunks_as_whole_leaves(monkeypatch):
    (ta, sa), (tb, sb), _ = _two_steps_then_the_same_arrays_whole(monkeypatch)
    for a, b, like in zip(jax.tree.leaves(ta.unstack_params(sa)),
                          jax.tree.leaves(tb.unstack_params(sb)),
                          jax.tree.leaves(_mlp_task()[1])):
        assert a.shape == like.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _switch(trainer, family):
    from bagua_tpu.define import BaguaHyperparameter

    trainer._maybe_switch_algorithm(BaguaHyperparameter(
        algorithm=family, is_hierarchical_reduce=False))
    assert trainer.algorithm.name == family


def _rebucket(trainer):
    from bagua_tpu.bucket import split_bucket_by_bucket_size

    decls = [t.declaration() for b in trainer._plan.buckets
             for t in b.tensors]
    trainer.rebucket(split_bucket_by_bucket_size(decls, 1 << 20))


@pytest.mark.parametrize("event", ["family_switch", "rebucket"])
def test_a_migration_ends_in_the_placement_the_step_takes(event, monkeypatch):
    """A switch to a family that keeps whole parameters (qadam) and back,
    and an autotune rebucket: the trajectory is the one a trainer with
    replicated parameters walks through the same events, and behind each
    the parameters rest as chunks again."""
    # (2,336 parameters: 8 ranks divide the one packed flat of the rebucket)
    (ta, sa), (tb, sb) = _sharded_and_replicated(monkeypatch, optax.adam,
                                                 features=(16, 64, 16))
    xs, ys = _data(steps=9, seed=7)
    losses = ([], [])

    def steps(span):
        nonlocal sa, sb
        for s in span:
            batch = {"x": xs[s], "y": ys[s]}
            sa, la = ta.train_step(sa, batch)
            sb, lb = tb.train_step(sb, batch)
            losses[0].append(float(la))
            losses[1].append(float(lb))

    steps(range(3))
    with monkeypatch.context() as m:
        for trainer in (ta, tb):
            if trainer is tb:
                _keep_replicated(m)
            if event == "rebucket":
                _rebucket(trainer)
            else:
                _switch(trainer, "qadam")
        steps(range(3, 6))
        if event == "family_switch":
            assert not ta._update_sharded()
            assert all(f.sharding.is_fully_replicated
                       for f in sa.params["flats"])
            for trainer in (ta, tb):
                _switch(trainer, "gradient_allreduce")
        steps(range(6, 9))
        assert ta._update_sharded() and not tb._update_sharded()
    assert _rests_as_chunks(ta, sa)
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ta.unstack_params(sa)),
                    jax.tree.leaves(tb.unstack_params(sb))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("dp_restore", [2, 4, 8])
def test_a_checkpoint_holds_whole_buffers_at_every_world(tmp_path,
                                                         dp_restore):
    """Saved at dp 4 with the parameters resting as chunks, restored at dp
    2, 4 and 8: the file holds the global arrays, the restored state is
    placed as the new world's step takes it, and reads back the numbers
    that were saved."""
    from bagua_tpu.checkpoint import BaguaCheckpointManager

    loss_fn, params = _mlp_task(features=(16, 32))
    xs, ys = _data(steps=4, seed=9)

    def make(dp):
        trainer = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                               GradientAllReduceAlgorithm(),
                               mesh=_dp_mesh(dp), bucket_bytes=600,
                               autotune=False)
        return trainer, trainer.init(params)

    t4, state = make(4)
    for s in range(3):
        state, _ = t4.train_step(state, {"x": xs[s], "y": ys[s]})
    saved = jax.tree.map(np.asarray, (t4.unstack_params(state),
                                      state.opt_state))
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert t4.save_checkpoint(mgr, 3, state)
    mgr.wait()
    state, want = t4.train_step(state, {"x": xs[3], "y": ys[3]})

    trainer, like = make(dp_restore)
    assert trainer._plan.signature() == t4._plan.signature()
    step, restored = trainer.restore_checkpoint(mgr, like)
    assert step == 3
    # (8 ranks do not divide the 12 rows of the first kernel)
    assert _rests_as_chunks(trainer, restored) == sum(
        b.buffer_shape[0] % dp_restore == 0 for b in trainer._plan.buckets)
    for a, b in zip(_whole((trainer.unstack_params(restored),
                            restored.opt_state)), jax.tree.leaves(saved)):
        np.testing.assert_array_equal(a, b)
    _, got = trainer.train_step(restored, {"x": xs[3], "y": ys[3]})
    # (reduction orders differ between dp extents)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    mgr.close()
