"""ZeRO-1 optimizer-state sharding (additive; no reference counterpart —
SURVEY.md §2.3 lists ZeRO/FSDP as absent from the reference).

Golden-model equivalence: reduce_scatter + shard-local adam + all_gather must
equal replicated adam over allreduce-averaged gradients (an allreduce IS
reduce-scatter + all-gather), so ZeRO training == plain DP training
elementwise.  Plus layout checks: each rank must hold only 1/world_size of
the optimizer state.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from tests.internal.jaxpr_walk import equations

from bagua_tpu import BaguaTrainer
from bagua_tpu.algorithms import GradientAllReduceAlgorithm, ZeroOptimizerAlgorithm
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


def _train(trainer, params, xs, ys):
    state = trainer.init(params)
    for s in range(xs.shape[0]):
        state, loss = trainer.train_step(state, {"x": xs[s], "y": ys[s]})
    return state, float(loss)


def test_matches_replicated_adam():
    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    loss_fn = _loss_fn(model)
    xs, ys = _data()

    zero = BaguaTrainer(
        loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
        bucket_bytes=256,
    )
    st_zero, _ = _train(zero, params, xs, ys)

    plain = BaguaTrainer(
        loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
        bucket_bytes=256,
    )
    st_plain, _ = _train(plain, params, xs, ys)

    # flat-resident layout: leaf views materialize via unstack_params
    z_leaves = jax.tree.leaves(zero.unstack_params(st_zero))
    for a, b in zip(z_leaves, jax.tree.leaves(plain.unstack_params(st_plain))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)


def test_optimizer_state_is_sharded():
    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    trainer = BaguaTrainer(
        _loss_fn(model), None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
        bucket_bytes=256,
    )
    state = trainer.init(params)

    total_padded = sum(b.padded_numel for b in trainer._plan.buckets)
    # adam: exp_avg (mu) + exp_avg_sq (nu) per bucket chunk; the stacked
    # global view is [N, *chunk] so each rank materializes chunk = padded/N:
    # rows of a shaped bucket whose leading axis N divides, a 1-D run
    # otherwise (base.chunk_form)
    buckets = state.opt_state["buckets"]
    for b, bucket_state in zip(trainer._plan.buckets, buckets):
        adam_state = bucket_state[0]  # ScaleByAdamState
        by_rows = b.buffer_shape[0] % N == 0
        assert adam_state.mu.shape[1:] == (
            (b.buffer_shape[0] // N,) + b.buffer_shape[1:] if by_rows
            else (b.padded_numel // N,))
    assert any(b.shaped and b.buffer_shape[0] % N == 0
               and len(b.buffer_shape) > 1 for b in trainer._plan.buckets)
    chunk_elems = sum(bs[0].mu[0].size for bs in buckets)
    assert chunk_elems == total_padded // N

    # each per-rank shard holds only its chunk
    for bs in buckets:
        shard_shapes = {s.data.shape for s in bs[0].mu.addressable_shards}
        assert all(s[0] == 1 for s in shard_shapes)


def test_clip_global_norm_matches_optax():
    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, DIM)))["params"]
    loss_fn = _loss_fn(model)
    xs, ys = _data(steps=4, seed=7)
    clip = 0.05  # small enough that clipping actually engages

    zero = BaguaTrainer(
        loss_fn, None,
        ZeroOptimizerAlgorithm(optax.adam(1e-2), clip_global_norm=clip),
        bucket_bytes=256,
    )
    st_zero, _ = _train(zero, params, xs, ys)

    # golden: full-batch chained clip->adam (same averaged gradient)
    opt = optax.chain(optax.clip_by_global_norm(clip), optax.adam(1e-2))
    gp, gopt = params, opt.init(params)

    @jax.jit
    def g_step(p, o, b):
        g = jax.grad(loss_fn)(p, b)
        u, o = opt.update(g, o, p)
        return optax.apply_updates(p, u), o

    for s in range(xs.shape[0]):
        gp, gopt = g_step(gp, gopt, {"x": xs[s], "y": ys[s]})

    z_leaves = jax.tree.leaves(zero.unstack_params(st_zero))
    for a, b in zip(z_leaves, jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)


def test_zero_with_tp_matches_replicated_adam():
    """ZeRO composed with tensor parallelism (dp=4 x tp=2): dense buckets
    take the reduce_scatter/all_gather path over dp, tp slices get the
    shard-local update with leaf-sharded state — must equal plain DP+TP
    adam elementwise."""
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn, tp_param_dim,
    )
    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.parallel.tensor_parallel import globalize_tp_params

    TPd = 2
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32,
                            tp_axis="tp", tp_size=TPd)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 9), 0, 64)
    params = globalize_tp_params(
        model.init(jax.random.PRNGKey(1), tokens[:2, :-1])["params"],
        jax.random.PRNGKey(2), TPd, tp_param_dim,
    )
    mesh = build_mesh({"dp": 4, "tp": TPd})

    def train(trainer):
        st = trainer.init(params)
        batch = trainer.shard_batch({"tokens": tokens})
        for _ in range(4):
            st, loss = trainer.train_step(st, batch)
        return st, float(loss)

    st_zero, loss_zero = train(BaguaTrainer(
        lm_loss_fn(model), None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
        mesh=mesh, tp_axis="tp", autotune=False,
    ))
    st_plain, loss_plain = train(BaguaTrainer(
        lm_loss_fn(model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=mesh, tp_axis="tp", autotune=False,
    ))

    np.testing.assert_allclose(loss_zero, loss_plain, atol=1e-5)
    flat_z = jax.tree_util.tree_leaves_with_path(st_zero.params)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(st_plain.params))
    for path, leaf in flat_z:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_p[path]), rtol=2e-5, atol=2e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_zero_with_3d_matches_replicated_adam():
    """ZeRO under the full dp x pp x tp mesh must equal the plain
    GradientAllReduce + adam trainer elementwise — guarding the ZeRO
    interaction with the pp prescale (dense buckets reduce-scatter over
    dp + pp AFTER the prescale turns the average into the cross-stage
    sum)."""
    from bagua_tpu.models.transformer import TransformerConfig
    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.parallel.pipeline import (
        PipelinedTransformerLM, globalize_pp_params, pp_lm_loss_fn,
    )

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=4,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32,
                            tp_axis="tp", tp_size=2)
    model = PipelinedTransformerLM(cfg, pp_size=2, n_microbatches=2)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (8, 9), 0, 64)
    params = globalize_pp_params(
        model.init(jax.random.PRNGKey(5), tokens[:2])["params"],
        jax.random.PRNGKey(6), 2, tp_size=2,
    )
    mesh = build_mesh({"dp": 2, "pp": 2, "tp": 2})

    def train(trainer):
        st = trainer.init(params)
        batch = trainer.shard_batch({"tokens": tokens})
        losses = []
        for _ in range(6):
            st, loss = trainer.train_step(st, batch)
            losses.append(float(loss))
        return st, losses

    st_zero, l_zero = train(BaguaTrainer(
        pp_lm_loss_fn(model), None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
        mesh=mesh, pp_axis="pp", tp_axis="tp", autotune=False,
    ))
    st_plain, l_plain = train(BaguaTrainer(
        pp_lm_loss_fn(model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=mesh, pp_axis="pp", tp_axis="tp", autotune=False,
    ))

    assert l_zero[-1] < l_zero[0], l_zero
    np.testing.assert_allclose(l_zero, l_plain, rtol=1e-5, atol=1e-6)
    flat_z = jax.tree_util.tree_leaves_with_path(st_zero.params)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(st_plain.params))
    for path, leaf in flat_z:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_p[path]), rtol=2e-5, atol=2e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_zero_clip_rejects_model_parallel():
    """clip_global_norm only sees the dp-sharded chunks, so combining it
    with tp/pp leaves must fail loudly, not silently misclip."""
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn, tp_param_dim,
    )
    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.parallel.tensor_parallel import globalize_tp_params

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32,
                            tp_axis="tp", tp_size=2)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 9), 0, 64)
    params = globalize_tp_params(
        model.init(jax.random.PRNGKey(1), tokens[:2, :-1])["params"],
        jax.random.PRNGKey(2), 2, tp_param_dim,
    )
    trainer = BaguaTrainer(
        lm_loss_fn(model), None,
        ZeroOptimizerAlgorithm(optax.adam(1e-2), clip_global_norm=1.0),
        mesh=build_mesh({"dp": 4, "tp": 2}), tp_axis="tp", autotune=False,
    )
    state = trainer.init(params)
    with pytest.raises(NotImplementedError, match="clip_global_norm"):
        trainer.train_step(state, trainer.shard_batch({"tokens": tokens}))


def _moe_setup(ep=2, key=20):
    from bagua_tpu.model_parallel.moe import MoEMLP, moe_lm_loss_fn
    from bagua_tpu.model_parallel.moe.layer import globalize_expert_params
    from bagua_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32)
    model = TransformerLM(
        cfg,
        mlp_factory=lambda i: (
            lambda: MoEMLP(n_experts=2 * ep, d_ff=cfg.d_ff, ep_size=ep,
                           dtype=jnp.float32)
        ) if i == 1 else None,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(key), (8, 9), 0, 64)
    params = globalize_expert_params(
        model.init(jax.random.PRNGKey(key + 1), tokens[:2, :-1])["params"],
        jax.random.PRNGKey(key + 2), ep_size=ep,
    )
    return model, moe_lm_loss_fn(model), tokens, params


def test_zero_with_ep_matches_plain_moe():
    """ZeRO composed with expert parallelism (dp=4 x ep=2): dense buckets
    chunk over dp x ep, expert leaves get shard-local states placed P(ep)
    — must equal the plain stacked-layout GradientAllReduce + adam run
    elementwise."""
    from bagua_tpu.parallel.mesh import build_mesh

    model, loss_fn, tokens, params = _moe_setup()
    mesh = build_mesh({"dp": 4, "ep": 2})

    def train(trainer):
        st = trainer.init(params)
        batch = trainer.shard_batch({"tokens": tokens})
        for _ in range(4):
            st, loss = trainer.train_step(st, batch)
        return trainer.unstack_params(st), float(loss)

    p_zero, loss_zero = train(BaguaTrainer(
        loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
        mesh=mesh, expert_axis="ep", autotune=False,
    ))
    p_plain, loss_plain = train(BaguaTrainer(
        loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=mesh, expert_axis="ep", autotune=False,
    ))

    np.testing.assert_allclose(loss_zero, loss_plain, atol=1e-5)
    flat_z = jax.tree_util.tree_leaves_with_path(p_zero)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(p_plain))
    for path, leaf in flat_z:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_p[path]), rtol=2e-5, atol=2e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_rejects_norm_coupled_optimizer():
    """A norm-coupled transform (global-norm clipping) would silently train
    on per-rank-chunk norms; construction must fail loudly."""
    with pytest.raises(ValueError, match="ELEMENTWISE"):
        ZeroOptimizerAlgorithm(
            optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-3))
        )
    # plain elementwise transforms pass the probe
    ZeroOptimizerAlgorithm(optax.adamw(1e-3))
    ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9))


def test_hierarchical_constructs():
    """hierarchical= gained a real staged implementation in r5 (the old
    construction-time NotImplementedError is gone); the staged layout's
    behavior is pinned by the tests below."""
    algo = ZeroOptimizerAlgorithm(optax.adam(1e-3), hierarchical=True)
    assert algo.hierarchical


def test_hierarchical_matches_flat_and_replicated():
    """Staged (hierarchical) ZeRO on an (inter=2, intra=4) mesh: the
    rs(intra) -> allreduce(inter) -> update -> ag(intra) dance must train
    identically (up to fp reassociation) to flat ZeRO and to replicated
    adam — avg-of-avgs over equal intra rows is the exact global average."""
    from bagua_tpu.parallel.mesh import hierarchical_mesh

    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    loss_fn = _loss_fn(model)
    xs, ys = _data(steps=5, seed=3)
    mesh = hierarchical_mesh(intra_size=4)

    staged = BaguaTrainer(
        loss_fn, None,
        ZeroOptimizerAlgorithm(optax.adam(1e-2), hierarchical=True),
        mesh=mesh, bucket_bytes=256,
    )
    st_staged, _ = _train(staged, params, xs, ys)

    flat = BaguaTrainer(
        loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
        mesh=mesh, bucket_bytes=256,
    )
    st_flat, _ = _train(flat, params, xs, ys)

    plain = BaguaTrainer(
        loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
        bucket_bytes=256,
    )
    st_plain, _ = _train(plain, params, xs, ys)

    s_leaves = jax.tree.leaves(staged.unstack_params(st_staged))
    f_leaves = jax.tree.leaves(flat.unstack_params(st_flat))
    p_leaves = jax.tree.leaves(plain.unstack_params(st_plain))
    for a, b, c in zip(s_leaves, f_leaves, p_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-5, atol=2e-6)


def test_hierarchical_opt_state_sharded_intra_only():
    """Staged layout: chunk states stack over INTRA (dim 4, not world 8) and
    replicate across inter — 1/intra optimizer memory per chip, and the
    inter tier carries only 1/intra of the flat bytes."""
    from bagua_tpu.parallel.mesh import hierarchical_mesh

    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    mesh = hierarchical_mesh(intra_size=4)
    trainer = BaguaTrainer(
        _loss_fn(model), None,
        ZeroOptimizerAlgorithm(optax.adam(1e-2), hierarchical=True),
        mesh=mesh, bucket_bytes=256,
    )
    state = trainer.init(params)
    total_padded = sum(b.padded_numel for b in trainer._plan.buckets)
    buckets = state.opt_state["buckets"]
    chunk_elems = sum(bs[0].mu[0].size for bs in buckets)
    assert chunk_elems == total_padded // 4  # intra, not world=8
    for bs in buckets:
        assert bs[0].mu.shape[0] == 4
    # metadata records the shard count so a restart at a different intra
    # size fails actionably
    meta = trainer.checkpoint_layout_metadata()
    assert meta["opt_shards"] == 4

    # one training step keeps the cross-inter replication intact
    xs, ys = _data(steps=2, seed=9)
    state, loss = trainer.train_step(state, {"x": xs[0], "y": ys[0]})
    assert np.isfinite(float(loss))


def test_hierarchical_flag_falls_back_on_flat_mesh():
    """Like the other families' hierarchical flag: on a mesh without
    inter/intra tiers the staged layout degrades to the flat (world-
    sharded) path.  An EXPLICIT {'dp': 8} mesh — the default mesh for a
    hierarchical algorithm is itself tiered, which would silently test the
    staged path instead."""
    from bagua_tpu.parallel.mesh import build_mesh

    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    loss_fn = _loss_fn(model)
    xs, ys = _data(steps=3, seed=5)
    trainer = BaguaTrainer(
        loss_fn, None,
        ZeroOptimizerAlgorithm(optax.adam(1e-2), hierarchical=True),
        mesh=build_mesh({"dp": N}), bucket_bytes=256,
    )
    assert not trainer._zero_staged()
    state, loss = _train(trainer, params, xs, ys)
    assert np.isfinite(loss)
    assert state.opt_state["buckets"][0][0].mu.shape[0] == N  # world-sharded


def test_hierarchical_with_sp_falls_back_to_flat():
    """Staged ZeRO must NOT activate when sequence parallelism folds sp
    into the comm world: the staged collectives span exactly inter x intra
    and would silently skip the sp partial-grad reduction (r5 review
    finding).  The predicate falls back to the flat path, which spans sp."""
    from bagua_tpu.parallel.mesh import build_mesh

    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    mesh = build_mesh({"inter": 2, "intra": 2, "sp": 2})
    trainer = BaguaTrainer(
        _loss_fn(model), None,
        ZeroOptimizerAlgorithm(optax.adam(1e-2), hierarchical=True),
        mesh=mesh, seq_axis="sp", bucket_bytes=256,
    )
    assert not trainer._zero_staged()


def test_hierarchical_rejects_model_parallel():
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn,
    )
    from bagua_tpu.parallel.mesh import build_mesh

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=32, max_seq_len=8, dtype=jnp.float32,
                            tp_axis="tp", tp_size=2)
    model = TransformerLM(cfg)
    mesh = build_mesh({"inter": 2, "intra": 2, "tp": 2})
    with pytest.raises(NotImplementedError, match="flat-resident"):
        trainer = BaguaTrainer(
            lm_loss_fn(model), None,
            ZeroOptimizerAlgorithm(optax.adam(1e-2), hierarchical=True),
            mesh=mesh, tp_axis="tp",
        )
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 9), 0, 32)
        trainer.init(model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"])


# ---- chunks are rows, not runs (one implementation with the exact family's
# sharded update: base.chunk_form / AlgorithmContext.owned_chunk) -----------


@pytest.mark.parametrize("flat_resident", ["auto", "off"])
def test_zero_cuts_a_shaped_bucket_by_rows(flat_resident):
    """A shaped bucket whose leading axis the shard count divides is
    scattered, sliced and gathered over that axis — no ravel of it, in the
    flat-resident layout and in the leaf one; a bucket whose rows do not
    divide (numel does: the plan pads it) is still cut as a 1-D run."""
    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    trainer = BaguaTrainer(
        _loss_fn(model), None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
        bucket_bytes=256, flat_resident=flat_resident,
    )
    state = trainer.init(params)
    by_rows = {b.buffer_shape for b in trainer._plan.buckets
               if b.shaped and b.buffer_shape[0] % N == 0}
    by_runs = {b.buffer_shape for b in trainer._plan.buckets} - by_rows
    assert (16, NCLASS) in by_rows and (DIM, 16) in by_runs
    xs, ys = _data(steps=1)
    eqns = list(equations(trainer.trace_step(
        state, trainer.shard_batch({"x": xs[0], "y": ys[0]})).jaxpr))
    scattered = {e.invars[0].aval.shape for e in eqns
                 if e.primitive.name == "reduce_scatter"}
    assert by_rows <= scattered
    assert (DIM * 16,) in scattered and (DIM, 16) not in scattered
    raveled = {e.invars[0].aval.shape for e in eqns
               if e.primitive.name == "reshape"
               and e.outvars[0].aval.ndim == 1}
    assert not raveled & by_rows


def test_zero_row_chunks_train_like_the_replicated_update():
    """The trajectory over a plan with row-cut and run-cut buckets side by
    side is plain data parallelism's (float tolerance: XLA:CPU fuses the two
    programs differently)."""
    model = MLP(features=(16, NCLASS))
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, DIM)))["params"]
    loss_fn = _loss_fn(model)
    xs, ys = _data(steps=6, seed=12)
    zero = BaguaTrainer(loss_fn, None,
                        ZeroOptimizerAlgorithm(optax.adamw(1e-2)),
                        bucket_bytes=256)
    st_zero, _ = _train(zero, params, xs, ys)
    plain = BaguaTrainer(loss_fn, optax.adamw(1e-2),
                         GradientAllReduceAlgorithm(hierarchical=True),
                         bucket_bytes=256)
    st_plain, _ = _train(plain, params, xs, ys)
    assert not plain._update_sharded()
    for a, b in zip(jax.tree.leaves(zero.unstack_params(st_zero)),
                    jax.tree.leaves(plain.unstack_params(st_plain))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
