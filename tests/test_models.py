"""Model smoke + training tests (tiny shapes, 8-device CPU mesh)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.models.resnet import ResNet, classification_loss_fn
from bagua_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    lm_loss_fn,
)
from bagua_tpu.parallel.mesh import build_mesh

N_DEVICES = 8


def tiny_lm():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=16, dtype=jnp.float32,
    )
    return TransformerLM(cfg), cfg


def test_transformer_forward_shape():
    model, cfg = tiny_lm()
    tokens = jnp.zeros((2, cfg.max_seq_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, cfg.max_seq_len, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_transformer_causality():
    """Changing a future token must not change past logits."""
    model, cfg = tiny_lm()
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (1, cfg.max_seq_len), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    tokens2 = tokens.at[0, -1].set((tokens[0, -1] + 1) % cfg.vocab_size)
    logits2 = model.apply({"params": params}, tokens2)
    assert jnp.allclose(logits[0, :-1], logits2[0, :-1], atol=1e-5)


def test_transformer_trains_dp():
    model, cfg = tiny_lm()
    mesh = build_mesh({"dp": N_DEVICES})
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (2 * N_DEVICES, cfg.max_seq_len + 1), 0,
        cfg.vocab_size,
    )
    params = model.init(jax.random.PRNGKey(0), tokens[:2, :-1])["params"]
    trainer = BaguaTrainer(
        lm_loss_fn(model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=mesh,
    )
    state = trainer.init(params)
    losses = []
    for _ in range(10):
        state, loss = trainer.train_step(state, {"tokens": tokens})
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_resnet_trains():
    model = ResNet(stage_sizes=(1, 1), num_classes=4, num_filters=8,
                   dtype=jnp.float32)
    mesh = build_mesh({"dp": N_DEVICES})
    images = jax.random.normal(jax.random.PRNGKey(0), (N_DEVICES * 2, 32, 32, 3))
    labels = jax.random.randint(jax.random.PRNGKey(1), (N_DEVICES * 2,), 0, 4)
    variables = model.init(jax.random.PRNGKey(2), images[:2], train=True)
    params = variables["params"]
    trainer = BaguaTrainer(
        classification_loss_fn(model, batch_stats=variables["batch_stats"]),
        optax.sgd(0.05), GradientAllReduceAlgorithm(), mesh=mesh,
    )
    state = trainer.init(params)
    losses = []
    for _ in range(5):
        state, loss = trainer.train_step(state, {"images": images,
                                                 "labels": labels})
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_vgg_trains():
    from bagua_tpu.models.vgg import VGG, vgg_loss_fn

    # tiny VGG: two conv stages, small head
    model = VGG(cfg=(8, "M", 16, "M"), num_classes=4, hidden=32,
                dtype=jnp.float32)
    mesh = build_mesh({"dp": N_DEVICES})
    images = jax.random.normal(jax.random.PRNGKey(0), (N_DEVICES * 2, 16, 16, 3))
    labels = jax.random.randint(jax.random.PRNGKey(1), (N_DEVICES * 2,), 0, 4)
    params = model.init(jax.random.PRNGKey(2), images[:2])["params"]
    trainer = BaguaTrainer(
        vgg_loss_fn(model), optax.sgd(0.05), GradientAllReduceAlgorithm(),
        mesh=mesh,
    )
    state = trainer.init(params)
    losses = []
    for _ in range(5):
        state, loss = trainer.train_step(state, {"images": images,
                                                 "labels": labels})
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def _forced_flash(q, k, v, dtype):
    """The Pallas kernels in interpret mode: on the CPU the model's own
    dispatch takes the plain path."""
    from bagua_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, dtype, causal=True, interpret=True,
                           force=True)


#: attention -> (attn_fn, d_model, n_heads, seq): the kernel wants a
#: 128-multiple sequence and a 64-wide head
_REMAT_SHAPES = {"plain": (None, 64, 4, 32),
                 "flash": (_forced_flash, 128, 2, 128)}


@pytest.mark.parametrize("policy", [None, "dots", "dots_no_batch"])
@pytest.mark.parametrize("attention", ["plain", "flash"])
def test_remat_policies_preserve_gradients(attention, policy):
    """remat_policy changes WHAT is saved for the backward, never the math:
    loss and gradients must match the no-remat run bitwise-closely for
    every policy — also where the policy keeps the flash kernel's ``o`` and
    ``lse`` and rebuilds the out-projection from them."""
    import dataclasses

    attn_fn, d_model, n_heads, seq = _REMAT_SHAPES[attention]
    cfg0 = TransformerConfig(vocab_size=97, d_model=d_model, n_heads=n_heads,
                             n_layers=2, d_ff=2 * d_model, max_seq_len=seq)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, seq + 1), 0, 97)

    def loss_and_grads(cfg):
        model = TransformerLM(cfg, attn_fn=attn_fn)
        params = TransformerLM(cfg0).init(
            jax.random.PRNGKey(1), tokens[:, :-1]
        )["params"]

        def loss_fn(p):
            logits = model.apply({"params": p}, tokens[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens[:, 1:]
            ).mean()

        return jax.jit(jax.value_and_grad(loss_fn))(params)

    l0, g0 = loss_and_grads(cfg0)
    l1, g1 = loss_and_grads(
        dataclasses.replace(cfg0, remat=True, remat_policy=policy))
    # bf16 compute: rematerialization reorders fusions, so tiny numeric
    # drift is expected — the check is "same math", not bit-equality
    assert abs(float(l0) - float(l1)) < 1e-4, (policy, float(l0), float(l1))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-3),
        g0, g1,
    )


@pytest.mark.parametrize("out_features", [None, 96])
def test_heads_dense_is_dense_general_as_one_matmul(out_features):
    """``HeadsDense`` (what ``Attention`` projects with where the flash
    kernels run) is ``nn.DenseGeneral`` for both directions: the same
    parameter tree from the same key, bit for bit, and the same product."""
    from bagua_tpu.models.transformer import HeadsDense

    h, d = 4, 32
    if out_features is None:
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 96))
        general = nn.DenseGeneral((h, d), axis=-1, use_bias=False,
                                  dtype=jnp.float32)
    else:
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, h, d))
        general = nn.DenseGeneral(out_features, axis=(-2, -1), use_bias=False,
                                  dtype=jnp.float32)
    merged = HeadsDense(h, d, out_features, dtype=jnp.float32)
    want = general.init(jax.random.PRNGKey(1), x)
    got = merged.init(jax.random.PRNGKey(1), x)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    np.testing.assert_array_equal(got["params"]["kernel"],
                                  want["params"]["kernel"])
    np.testing.assert_allclose(merged.apply(got, x), general.apply(want, x),
                               atol=1e-5, rtol=1e-5)


def test_attention_projects_with_one_matmul_where_the_kernels_run(
        monkeypatch):
    """Where ``flash_supported`` says the kernels run, the four projections
    are 2-D matmuls (no ``dot_general`` result or operand with a separate
    heads axis); elsewhere they are ``DenseGeneral``'s, as they were; the
    parameters are the same tree either way."""
    import importlib

    from bagua_tpu.models.transformer import Attention

    fa = importlib.import_module("bagua_tpu.ops.flash_attention")
    cfg = TransformerConfig(vocab_size=97, d_model=128, n_heads=2,
                            n_layers=1, d_ff=256, max_seq_len=128)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128), cfg.dtype)
    attn = Attention(cfg, _forced_flash)

    def dot_ranks(supported):
        monkeypatch.setattr(fa, "flash_supported", lambda *a, **k: supported)
        params = jax.eval_shape(attn.init, jax.random.PRNGKey(1), x)
        jaxpr = jax.make_jaxpr(attn.apply)(params, x)
        ranks = {len(v.aval.shape) for eqn in jaxpr.eqns
                 if eqn.primitive.name == "dot_general"
                 for v in (*eqn.invars, *eqn.outvars)}
        return jax.tree.map(lambda p: p.shape, params), ranks

    plain_params, plain_ranks = dot_ranks(False)
    merged_params, merged_ranks = dot_ranks(True)
    assert merged_params == plain_params
    assert max(merged_ranks) == 3 and max(plain_ranks) == 4


class _ForeignMLP(nn.Module):
    """An MLP from outside the model file: it tags nothing."""

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(384, use_bias=False, dtype=x.dtype, name="wi")(x)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=x.dtype,
                        name="wo")(nn.gelu(h))


@pytest.mark.parametrize("policy,mlp,keeps_out_projection", [
    ("dots_no_batch", None, False),
    ("dots_no_batch", _ForeignMLP, True),
    ("dots", None, True),
])
def test_what_a_rematted_block_keeps(policy, mlp, keeps_out_projection,
                                     monkeypatch):
    """The kept set of one block against the bare dots rule's (what the
    block kept before the kernel's outputs were tagged): ``o`` as
    ``[b, s, h * d]`` and ``lse`` as the head rows ``[b * h / g, g, s]`` f32
    (never the kernel's 8-sublane stripe) come on top; a stock block under
    ``"dots_no_batch"`` gives up the equally large out-projection output
    for ``o`` and so grows by the ``lse`` row alone."""
    from jax._src.ad_checkpoint import saved_residuals  # 0.9.0 exports only
    # the printer, print_saved_residuals

    import importlib

    from bagua_tpu.models.transformer import (
        KEPT_FFN_IN, KEPT_QKV, Block)
    from bagua_tpu.utils import remat_wrap

    # the block as it is where the kernels run: projections as one matmul
    monkeypatch.setattr(importlib.import_module(
        "bagua_tpu.ops.flash_attention"), "flash_supported",
        lambda *a, **k: True)

    b, s, h, d = 2, 128, 2, 64
    cfg = TransformerConfig(vocab_size=97, d_model=h * d, n_heads=h,
                            n_layers=1, d_ff=384, max_seq_len=s)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, h * d), cfg.dtype)
    rules = {"dots": jax.checkpoint_policies.dots_saveable,
             "dots_no_batch":
                 jax.checkpoint_policies.dots_with_no_batch_dims_saveable}

    def kept(block_cls):
        block = block_cls(cfg, _forced_flash, mlp)
        params = block.init(jax.random.PRNGKey(1), x)
        out = [(tuple(aval.shape), aval.dtype)
               for aval, why in saved_residuals(block.apply, params, x)
               if "from the argument" not in why]
        return out, sum(int(np.prod(shape)) * dtype.itemsize
                        for shape, dtype in out)

    before, bytes_before = kept(nn.checkpoint(Block, policy=rules[policy]))
    after, bytes_after = kept(remat_wrap(
        Block, policy,
        matmul_names=(KEPT_QKV, KEPT_FFN_IN) if mlp is None else ()))

    # two heads of 64 share a 128-lane block of the kernels: ``o`` has the
    # shape the four projections write, the head rows are [b * h / 2, 2, s]
    flat = ((b, s, h * d), jnp.bfloat16)
    lse = ((b * h // 2, 2, s), jnp.float32)
    # the bare rule keeps q, k, v and the out-projection as matmuls write
    # them; a stock block keeps q / k / v under their tag's [b, s, h, d]
    assert before.count(flat) == 4 and lse not in before
    tagged_qkv = after.count(((b, s, h, d), jnp.bfloat16))
    assert tagged_qkv == (3 if mlp is None else 0)
    assert after.count(flat) + tagged_qkv == 3 + 1 + keeps_out_projection
    assert after.count(lse) == 1
    assert ((b * h // 2, 8, s), jnp.float32) not in after
    o_bytes, lse_bytes = b * h * s * d * 2, b * h * s * 4
    assert bytes_after - bytes_before == lse_bytes + (
        o_bytes if keeps_out_projection else 0)
