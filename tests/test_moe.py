"""MoE expert-parallelism tests (reference: MoE CI benchmark with exact loss,
benchmark_master.sh:126-153, and sharded_moe gating math)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.model_parallel.moe import MoEMLP, moe_lm_loss_fn, top1_gating, top2_gating
from bagua_tpu.model_parallel.moe.layer import globalize_expert_params
from bagua_tpu.models.transformer import TransformerConfig, TransformerLM
from bagua_tpu.parallel.mesh import build_mesh

N_DEVICES = 8


# ---- gating ---------------------------------------------------------------


def test_top1_gating_capacity_and_shapes():
    key = jax.random.PRNGKey(0)
    T, E, C = 32, 4, 4
    logits = jax.random.normal(key, (T, E))
    dispatch, combine, l_aux = top1_gating(logits, C)
    assert dispatch.shape == (T, E, C)
    # each slot holds at most one token
    assert float(dispatch.sum(axis=0).max()) <= 1.0
    # each token goes to at most 1 slot
    assert float(dispatch.sum(axis=(1, 2)).max()) <= 1.0
    # kept tokens carry their full top-1 prob
    probs = jax.nn.softmax(logits, axis=-1)
    kept = dispatch.sum(axis=(1, 2)) > 0
    np.testing.assert_allclose(
        np.asarray(combine.sum(axis=(1, 2)))[np.asarray(kept)],
        np.asarray(probs.max(axis=-1))[np.asarray(kept)],
        rtol=1e-5,
    )
    assert float(l_aux) > 0


def test_top2_gating_two_experts_and_normalized():
    key = jax.random.PRNGKey(1)
    T, E = 16, 8
    C = T  # no drops
    logits = jax.random.normal(key, (T, E))
    dispatch, combine, l_aux = top2_gating(logits, C)
    # every token dispatched to exactly 2 experts when capacity is ample
    np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(1, 2))), 2.0)
    # combine weights normalized over the two winners
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))), 1.0,
                               rtol=1e-5)


def test_gating_capacity_drops():
    # all tokens prefer expert 0 -> only `capacity` survive
    logits = jnp.tile(jnp.array([[10.0, 0.0, 0.0, 0.0]]), (12, 1))
    C = 3
    dispatch, combine, _ = top1_gating(logits, C)
    assert float(dispatch[:, 0].sum()) == C


# ---- layer ----------------------------------------------------------------


def moe_model(ep_size, n_experts=4, k=2):
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=8, dtype=jnp.float32,
    )
    factory = lambda i: (
        (lambda: MoEMLP(n_experts=n_experts, d_ff=cfg.d_ff, ep_size=ep_size,
                        k=k, capacity_factor=2.0, dtype=jnp.float32))
        if i % 2 == 1 else None
    )
    return TransformerLM(cfg, mlp_factory=factory), cfg


def test_moe_single_device_trains():
    model, cfg = moe_model(ep_size=1)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, cfg.max_seq_len + 1),
                                0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens[:2, :-1])["params"]
    loss_fn = moe_lm_loss_fn(model)
    opt = optax.adam(1e-2)
    opt_state = jax.jit(opt.init)(params)

    @jax.jit
    def step(p, o, batch):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        updates, o = opt.update(g, o, p)
        return optax.apply_updates(p, updates), o, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, {"tokens": tokens})
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_moe_ep_matches_single_device_forward():
    """With ample capacity, expert-parallel forward == single-device forward."""
    E, ep = 8, 4
    d_model, d_ff, seq = 16, 32, 8

    single = MoEMLP(n_experts=E, d_ff=d_ff, ep_size=1, k=2,
                    capacity_factor=float(E), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, seq, d_model))
    params = single.init(jax.random.PRNGKey(1), x[:2])["params"]
    ref = single.apply({"params": params}, x)

    sharded = MoEMLP(n_experts=E, d_ff=d_ff, ep_size=ep, k=2,
                     capacity_factor=float(E), dtype=jnp.float32)
    mesh = build_mesh({"ep": ep}, jax.devices()[:ep])

    def fwd(p, xs):
        return sharded.apply({"params": p}, xs)

    pspec = jax.tree_util.tree_map_with_path(
        lambda path, leaf: P("ep") if "expert" in jax.tree_util.keystr(path) else P(),
        params,
    )
    out = jax.jit(shard_map(
        fwd, mesh=mesh, in_specs=(pspec, P("ep")), out_specs=P("ep"),
        check_vma=False,
    ))(params, x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_expert_grads_match_single_device():
    """One SGD step with ep=4 must produce the SAME updated weights as the
    single-device run (regression: expert grads were ep x too large because
    the all_to_all backward already sums cross-shard contributions)."""
    E, ep, d_model, d_ff, seq, b = 8, 4, 16, 32, 4, 8
    lr = 0.1

    single = MoEMLP(n_experts=E, d_ff=d_ff, ep_size=1, k=2,
                    capacity_factor=float(E), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, seq, d_model))
    y = jax.random.normal(jax.random.PRNGKey(1), (b, seq, d_model))
    params = single.init(jax.random.PRNGKey(2), x[:2])["params"]

    def ref_loss(p, batch):
        out = single.apply({"params": p}, batch["x"])
        return jnp.mean((out - batch["y"]) ** 2)

    g = jax.grad(ref_loss)(params, {"x": x, "y": y})
    ref_updated = jax.tree.map(lambda p_, g_: p_ - lr * g_, params, g)

    sharded = MoEMLP(n_experts=E, d_ff=d_ff, ep_size=ep, k=2,
                     capacity_factor=float(E), dtype=jnp.float32)

    def sp_loss(p, batch):
        out = sharded.apply({"params": p}, batch["x"])
        return jnp.mean((out - batch["y"]) ** 2)

    mesh = build_mesh({"ep": ep}, jax.devices()[:ep])
    trainer = BaguaTrainer(
        sp_loss, optax.sgd(lr), GradientAllReduceAlgorithm(), mesh=mesh,
        expert_axis="ep",
    )
    state = trainer.init(params)
    state, _ = trainer.train_step(state, {"x": x, "y": y})
    updated = trainer.unstack_params(state)
    for (path, a), (_, r) in zip(
        jax.tree_util.tree_flatten_with_path(updated)[0],
        jax.tree_util.tree_flatten_with_path(ref_updated)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_moe_expert_parallel_trains_e2e():
    """Full trainer path: mesh ('dp','ep'), experts sharded, loss decreases,
    experts stay distinct across ep shards."""
    model, cfg = moe_model(ep_size=4, n_experts=8)
    mesh = build_mesh({"dp": 2, "ep": 4})
    tokens = jax.random.randint(jax.random.PRNGKey(0), (16, cfg.max_seq_len + 1),
                                0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens[:2, :-1])["params"]
    params = globalize_expert_params(params, jax.random.PRNGKey(2), ep_size=4)

    trainer = BaguaTrainer(
        moe_lm_loss_fn(model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=mesh, expert_axis="ep",
    )
    # expert tensors excluded from the DP bucket plan
    state = trainer.init(params)
    assert all("expert" not in n for n in trainer._plan.tensor_names)

    losses = []
    for _ in range(10):
        state, loss = trainer.train_step(state, {"tokens": tokens})
        losses.append(float(loss))
    assert losses[-1] < losses[0]

    # expert weights differ across ep shards; dense weights stay in lockstep
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        arr = np.asarray(leaf)
        if "expert_wi" in name:
            assert not np.allclose(arr[0, 0], arr[0, 1])
        if name.endswith("['embed']['embedding']"):
            for r in range(1, arr.shape[0]):
                np.testing.assert_allclose(arr[0], arr[r], atol=1e-6)


def test_expert_param_marking_is_exact_not_substring():
    """An unrelated param containing "expert" as a substring must stay in the
    DP plan; only MoEMLP's own params (exact segment names) are excluded."""
    from bagua_tpu.model_parallel.moe.layer import is_expert_param

    assert is_expert_param("layers_1.mlp.expert_wi")
    assert is_expert_param("['layers_1']['mlp']['expert_wo']")
    assert not is_expert_param("encoder.expertise_head.kernel")
    assert not is_expert_param("my_expert_wi_extra.kernel")
    assert not is_expert_param("dense.kernel")


def test_trainer_rejects_unknown_expert_axis():
    import optax
    import pytest

    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"dp": 8})
    with pytest.raises(ValueError, match="expert_axis"):
        BaguaTrainer(lambda p, b: 0.0, optax.sgd(0.1),
                     GradientAllReduceAlgorithm(), mesh=mesh,
                     expert_axis="not_an_axis")
    with pytest.raises(ValueError, match="seq_axis"):
        BaguaTrainer(lambda p, b: 0.0, optax.sgd(0.1),
                     GradientAllReduceAlgorithm(), mesh=mesh,
                     seq_axis="sq")


def test_trainer_accepts_explicit_expert_params_collection():
    import optax

    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"dp": 4, "ep": 2})
    t = BaguaTrainer(lambda p, b: 0.0, optax.sgd(0.1),
                     GradientAllReduceAlgorithm(), mesh=mesh,
                     expert_axis="ep",
                     expert_params=["blk.moe.expert_wi", "blk.moe.expert_wo"])
    assert t._is_expert_name("blk.moe.expert_wi")
    assert not t._is_expert_name("blk.attn.kernel")
    # callable form
    t2 = BaguaTrainer(lambda p, b: 0.0, optax.sgd(0.1),
                      GradientAllReduceAlgorithm(), mesh=mesh,
                      expert_axis="ep",
                      expert_params=lambda n: n.endswith("_moe"))
    assert t2._is_expert_name("w_moe")
