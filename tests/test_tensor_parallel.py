"""Tensor parallelism: TP-sharded transformer vs single-device equality
(golden-model pattern, SURVEY.md §4).  TP is additive — the reference has
none (SURVEY.md §2.3) — so the golden is our own dense model."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    lm_loss_fn,
    tp_param_dim,
)
from bagua_tpu.parallel.mesh import build_mesh

TP = 4


def _cfgs():
    kw = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
              max_seq_len=8, dtype=jnp.float32)
    plain = TransformerConfig(**kw)
    tp = TransformerConfig(tp_axis="tp", tp_size=TP, **kw)
    return plain, tp


def _spec_tree(params):
    def leaf_spec(path, leaf):
        name = jax.tree_util.keystr(path)
        import re

        name = re.sub(r"[\[\]'\.]+", ".", name).strip(".")
        dim = tp_param_dim(name)
        if dim is None:
            return P()
        return P(*([None] * dim + ["tp"]))

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def test_tp_forward_matches_single_device():
    plain_cfg, tp_cfg = _cfgs()
    plain, tpm = TransformerLM(plain_cfg), TransformerLM(tp_cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 8), 0, 64)
    params = plain.init(jax.random.PRNGKey(1), tokens)["params"]
    ref = plain.apply({"params": params}, tokens)

    mesh = build_mesh({"tp": TP}, jax.devices()[:TP])
    out = jax.jit(shard_map(
        lambda p, t: tpm.apply({"params": p}, t),
        mesh=mesh, in_specs=(_spec_tree(params), P()), out_specs=P(),
        check_vma=False,
    ))(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_tp_one_step_matches_single_device():
    """One SGD step: dp=1 x tp=4 must produce the same updated weights as
    the dense single-device run (validates the conjugate collectives, the
    tp-leaf bucket exclusion, and the spec trees)."""
    plain_cfg, tp_cfg = _cfgs()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 9), 0, 64)
    params = TransformerLM(plain_cfg).init(
        jax.random.PRNGKey(3), tokens[:, :-1]
    )["params"]

    t1 = BaguaTrainer(
        lm_loss_fn(TransformerLM(plain_cfg)), optax.sgd(0.1),
        GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 1}, jax.devices()[:1]), autotune=False,
    )
    s1 = t1.init(params)
    s1, loss1 = t1.train_step(s1, t1.shard_batch({"tokens": tokens}))

    ttp = BaguaTrainer(
        lm_loss_fn(TransformerLM(tp_cfg)), optax.sgd(0.1),
        GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 1, "tp": TP}, jax.devices()[:TP]),
        tp_axis="tp", autotune=False,
    )
    stp = ttp.init(params)
    stp, losstp = ttp.train_step(stp, ttp.shard_batch({"tokens": tokens}))

    np.testing.assert_allclose(float(loss1), float(losstp), atol=1e-5)
    flat1 = jax.tree_util.tree_leaves_with_path(t1.unstack_params(s1))
    flattp = dict(jax.tree_util.tree_leaves_with_path(ttp.unstack_params(stp)))
    for path, leaf in flat1:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flattp[path]), atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_tp_dp_trains():
    _, tp_cfg = _cfgs()
    model = TransformerLM(tp_cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (8, 9), 0, 64)
    from bagua_tpu.parallel.tensor_parallel import globalize_tp_params

    trainer = BaguaTrainer(
        lm_loss_fn(model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 2, "tp": TP}), tp_axis="tp", autotune=False,
    )
    # init outside the mesh yields symmetric LOCAL tp slices; the trainer
    # expects GLOBAL tp arrays — redraw the sharded dims at global size
    params = globalize_tp_params(
        model.init(jax.random.PRNGKey(7), tokens[:2, :-1])["params"],
        jax.random.PRNGKey(8), TP, tp_param_dim,
    )
    state = trainer.init(params)
    batch = trainer.shard_batch({"tokens": tokens})
    losses = []
    for _ in range(10):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_tp_only_mesh_matches_single_device():
    """A mesh with ONLY a tp axis must not shard the batch over tp
    (regression: the dp_axes fallback used to grab the tp axis and mix
    different samples' partial sums)."""
    plain_cfg, tp_cfg = _cfgs()
    tokens = jax.random.randint(jax.random.PRNGKey(9), (4, 9), 0, 64)
    params = TransformerLM(plain_cfg).init(
        jax.random.PRNGKey(10), tokens[:, :-1]
    )["params"]

    t1 = BaguaTrainer(
        lm_loss_fn(TransformerLM(plain_cfg)), optax.sgd(0.1),
        GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 1}, jax.devices()[:1]), autotune=False,
    )
    s1 = t1.init(params)
    s1, loss1 = t1.train_step(s1, t1.shard_batch({"tokens": tokens}))

    ttp = BaguaTrainer(
        lm_loss_fn(TransformerLM(tp_cfg)), optax.sgd(0.1),
        GradientAllReduceAlgorithm(),
        mesh=build_mesh({"tp": TP}, jax.devices()[:TP]),
        tp_axis="tp", autotune=False,
    )
    assert ttp.dp_axes == (), ttp.dp_axes
    stp = ttp.init(params)
    stp, losstp = ttp.train_step(stp, ttp.shard_batch({"tokens": tokens}))
    np.testing.assert_allclose(float(loss1), float(losstp), atol=1e-5)


def test_globalize_tp_params_variance():
    """Redrawn global tp leaves must match the model's own init scale."""
    from bagua_tpu.parallel.tensor_parallel import globalize_tp_params

    _, tp_cfg = _cfgs()
    tokens = jnp.zeros((2, 8), jnp.int32)
    # dense (tp_size=1) model init = the scale golden
    plain_cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=8, dtype=jnp.float32,
    )
    golden = TransformerLM(plain_cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    local = TransformerLM(tp_cfg).init(jax.random.PRNGKey(1), tokens)["params"]
    redrawn = globalize_tp_params(local, jax.random.PRNGKey(2), TP,
                                  tp_param_dim)
    for name in ("q", "o"):
        want = float(jnp.std(golden["block_0"]["attn"][name]["kernel"]))
        got = float(jnp.std(redrawn["block_0"]["attn"][name]["kernel"]))
        assert abs(got - want) / want < 0.15, (name, want, got)
        assert (redrawn["block_0"]["attn"][name]["kernel"].shape
                == golden["block_0"]["attn"][name]["kernel"].shape)


def test_tp_qadam_trains_through_phase_switch():
    """QAdam (stateful, owns its optimizer) under tp: momentum/second-moment
    trees get per-leaf tp specs via suffix matching; the compressed phase
    communicates only the dense bucket plan while tp momenta stay local."""
    from bagua_tpu.algorithms.q_adam import QAdamAlgorithm

    _, tp_cfg = _cfgs()
    model = TransformerLM(tp_cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (8, 9), 0, 64)
    from bagua_tpu.parallel.tensor_parallel import globalize_tp_params

    trainer = BaguaTrainer(
        lm_loss_fn(model), None, QAdamAlgorithm(warmup_steps=3, lr=3e-3),
        mesh=build_mesh({"dp": 2, "tp": TP}), tp_axis="tp", autotune=False,
    )
    params = globalize_tp_params(
        model.init(jax.random.PRNGKey(12), tokens[:2, :-1])["params"],
        jax.random.PRNGKey(13), TP, tp_param_dim,
    )
    state = trainer.init(params)
    batch = trainer.shard_batch({"tokens": tokens})
    losses = []
    for _ in range(8):  # crosses the warmup->compressed boundary at 3
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_tp_qadam_warmup_step_matches_single_device():
    from bagua_tpu.algorithms.q_adam import QAdamAlgorithm

    plain_cfg, tp_cfg = _cfgs()
    tokens = jax.random.randint(jax.random.PRNGKey(14), (4, 9), 0, 64)
    params = TransformerLM(plain_cfg).init(
        jax.random.PRNGKey(15), tokens[:, :-1]
    )["params"]

    t1 = BaguaTrainer(
        lm_loss_fn(TransformerLM(plain_cfg)), None,
        QAdamAlgorithm(warmup_steps=100, lr=1e-2),
        mesh=build_mesh({"dp": 1}, jax.devices()[:1]), autotune=False,
    )
    s1 = t1.init(params)
    s1, loss1 = t1.train_step(s1, t1.shard_batch({"tokens": tokens}))

    ttp = BaguaTrainer(
        lm_loss_fn(TransformerLM(tp_cfg)), None,
        QAdamAlgorithm(warmup_steps=100, lr=1e-2),
        mesh=build_mesh({"dp": 1, "tp": TP}, jax.devices()[:TP]),
        tp_axis="tp", autotune=False,
    )
    stp = ttp.init(params)
    stp, losstp = ttp.train_step(stp, ttp.shard_batch({"tokens": tokens}))

    np.testing.assert_allclose(float(loss1), float(losstp), atol=1e-5)
    flat1 = jax.tree_util.tree_leaves_with_path(t1.unstack_params(s1))
    flattp = dict(jax.tree_util.tree_leaves_with_path(ttp.unstack_params(stp)))
    for path, leaf in flat1:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flattp[path]), atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )
