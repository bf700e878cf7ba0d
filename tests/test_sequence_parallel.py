"""Sequence/context parallelism tests: ring attention and Ulysses vs full
attention, and end-to-end SP training through the trainer."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    causal_attention,
    sp_lm_loss_fn,
)
from bagua_tpu.parallel.mesh import build_mesh
from bagua_tpu.parallel.ring_attention import make_ring_attention
from bagua_tpu.parallel.ulysses import make_ulysses_attention

N_DEVICES = 8


def _sp_reference_and_inputs(key, b=2, s_global=32, h=4, d=8):
    qkv = jax.random.normal(key, (3, b, s_global, h, d), jnp.float32)
    q, k, v = qkv
    ref = causal_attention(q, k, v, jnp.float32)
    return q, k, v, ref


def _run_sharded(attn_factory, q, k, v, sp):
    mesh = build_mesh({"sp": sp}, jax.devices()[:sp])
    attn = attn_factory(sp)

    def fn(q, k, v):
        return attn(q, k, v, jnp.float32)

    spec = P(None, "sp")  # shard the sequence axis
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    ))(q, k, v)


def test_ring_attention_matches_full():
    q, k, v, ref = _sp_reference_and_inputs(jax.random.PRNGKey(0))
    out = _run_sharded(lambda sp: make_ring_attention(sp), q, k, v, sp=8)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)


def test_ring_attention_sp4():
    q, k, v, ref = _sp_reference_and_inputs(jax.random.PRNGKey(1))
    out = _run_sharded(lambda sp: make_ring_attention(sp), q, k, v, sp=4)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)


def test_ulysses_matches_full():
    q, k, v, ref = _sp_reference_and_inputs(jax.random.PRNGKey(2))
    out = _run_sharded(lambda sp: make_ulysses_attention(sp), q, k, v, sp=4)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)


def test_ulysses_rejects_indivisible_heads():
    q, k, v, _ = _sp_reference_and_inputs(jax.random.PRNGKey(3), h=6)
    import pytest

    with pytest.raises(Exception, match="divisible"):
        _run_sharded(lambda sp: make_ulysses_attention(sp), q, k, v, sp=4)


def _sp_model(sp, attn_kind):
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, sp_axis="sp",
    )
    attn = (make_ring_attention(sp) if attn_kind == "ring"
            else make_ulysses_attention(sp))
    return TransformerLM(cfg, attn_fn=attn), cfg


def test_sp_training_e2e_ring():
    _sp_train("ring")


def test_sp_training_e2e_ulysses():
    _sp_train("ulysses")


def _sp_train(kind):
    sp, dp = 4, 2
    model, cfg = _sp_model(sp, kind)
    mesh = build_mesh({"dp": dp, "sp": sp})
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (2 * dp, cfg.max_seq_len + 1), 0, cfg.vocab_size
    )
    # init outside the mesh with a local-sized chunk (sp_axis unbound -> no
    # offset); param shapes don't depend on seq length
    params = model.init(
        jax.random.PRNGKey(1), tokens[:2, : cfg.max_seq_len // sp]
    )["params"]
    trainer = BaguaTrainer(
        sp_lm_loss_fn(model, sp_size=sp), optax.adam(1e-2),
        GradientAllReduceAlgorithm(), mesh=mesh, seq_axis="sp",
    )
    state = trainer.init(params)
    losses = []
    for _ in range(15):
        state, loss = trainer.train_step(state, {"tokens": tokens})
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], losses


def test_sp_loss_matches_single_device():
    """One SP step's loss == the plain full-sequence loss (same params)."""
    sp = 4
    model_sp, cfg = _sp_model(sp, "ring")
    cfg_full = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    model_full = TransformerLM(cfg_full)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, cfg.max_seq_len + 1),
                                0, cfg.vocab_size)
    params = model_full.init(jax.random.PRNGKey(6), tokens[:, :-1])["params"]

    from bagua_tpu.models.transformer import lm_loss_fn

    ref_loss = lm_loss_fn(model_full)(params, {"tokens": tokens})

    mesh = build_mesh({"sp": sp}, jax.devices()[:sp])
    loss_fn = sp_lm_loss_fn(model_sp, sp_size=sp)

    def fn(p, batch):
        local = loss_fn(p, batch)
        return jax.lax.pmean(local, "sp")

    sp_loss = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    ))(params, {"tokens": tokens})
    np.testing.assert_allclose(float(ref_loss), float(sp_loss), rtol=1e-5)


def test_ring_flash_matches_full():
    # flash-kernel ring (interpret mode): s_local = 512/4 = 128 blocks
    q, k, v, ref = _sp_reference_and_inputs(
        jax.random.PRNGKey(7), b=1, s_global=512, h=2, d=64
    )
    out = _run_sharded(
        lambda sp: make_ring_attention(sp, use_flash="always", interpret=True), q, k, v, sp=4
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_ring_flash_grads_match_full():
    q, k, v, _ = _sp_reference_and_inputs(
        jax.random.PRNGKey(8), b=1, s_global=512, h=2, d=64
    )
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    want = jax.grad(
        lambda q, k, v: (causal_attention(q, k, v, jnp.float32) * g).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)

    sp = 4
    mesh = build_mesh({"sp": sp}, jax.devices()[:sp])
    attn = make_ring_attention(sp, use_flash="always", interpret=True)

    def loss(q, k, v, g):
        out = attn(q, k, v, jnp.float32)
        # total = sum over shards of the local partial; tp_reduce (psum fwd,
        # identity bwd) gives each shard's local term cotangent 1 — a raw
        # psum would transpose to psum and scale cotangents by sp
        from bagua_tpu.parallel.tensor_parallel import tp_reduce

        return tp_reduce((out * g).sum(), "sp")

    spec = P(None, "sp")
    got = jax.jit(shard_map(
        jax.grad(loss, argnums=(0, 1, 2)),
        mesh=mesh, in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec), check_vma=False,
    ))(q, k, v, g)
    for w, o, name in zip(want, got, "qkv"):
        np.testing.assert_allclose(np.asarray(o), np.asarray(w), atol=5e-5,
                                   err_msg=f"d{name}")


def test_sp_tp_composition_one_step_matches_dense():
    """SP (ring attention) x TP x DP composed in one trainer step must equal
    the dense single-device run on the same global params — pins the
    composition: tp grads average over dp x sp, dense grads bucket over
    dp x sp, ring attention equals full attention."""
    from bagua_tpu.models.transformer import lm_loss_fn, tp_param_dim
    from bagua_tpu.parallel.tensor_parallel import globalize_tp_params

    sp, tp = 2, 2
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=16, dtype=jnp.float32, sp_axis="sp", tp_axis="tp",
        tp_size=tp,
    )
    model = TransformerLM(cfg, attn_fn=make_ring_attention(sp))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, 17), 0, 64)
    params = globalize_tp_params(
        model.init(jax.random.PRNGKey(8), tokens[:2, :8])["params"],
        jax.random.PRNGKey(9), tp, tp_param_dim,
    )

    # golden: dense model, full attention, single device
    dense_cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=16, dtype=jnp.float32,
    )
    dense = TransformerLM(dense_cfg)
    t_ref = BaguaTrainer(
        lm_loss_fn(dense), optax.sgd(0.1), GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 1}, jax.devices()[:1]), autotune=False,
    )
    s_ref = t_ref.init(params)
    s_ref, loss_ref = t_ref.train_step(s_ref, t_ref.shard_batch({"tokens": tokens}))

    t_sp = BaguaTrainer(
        sp_lm_loss_fn(model, sp_size=sp), optax.sgd(0.1),
        GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 2, "sp": sp, "tp": tp}),
        seq_axis="sp", tp_axis="tp", autotune=False,
    )
    s_sp = t_sp.init(params)
    s_sp, loss_sp = t_sp.train_step(s_sp, t_sp.shard_batch({"tokens": tokens}))

    np.testing.assert_allclose(float(loss_ref), float(loss_sp), atol=1e-5)
    flat_ref = jax.tree_util.tree_leaves_with_path(t_ref.unstack_params(s_ref))
    flat_sp = dict(jax.tree_util.tree_leaves_with_path(t_sp.unstack_params(s_sp)))
    for path, leaf in flat_ref:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_sp[path]), atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )
