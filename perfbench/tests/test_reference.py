"""perfbench/reference/transformer_lm.py against the program's TransformerLM at
tiny widths on the CPU, perfbench/flops.py against a hand count, and what
the reference's tolerance would and would not let through."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss_fn,
)
from perfbench import cells, flops

ref = cells.load_plugin("reference", "transformer_lm")

TINY = dict(vocab_size=256, d_model=64, n_heads=2, n_layers=2, d_ff=128,
            max_seq_len=32)
OPTIMIZER = {"name": "adamw", "kwargs": {"learning_rate": 1e-3}}


def program(dtype=jnp.float32, **overrides):
    model = TransformerLM(TransformerConfig(dtype=dtype, **{**TINY, **overrides}))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 250, size=(4, 33), dtype=np.int32)


def program_losses(model, params, tokens, steps, optimizer):
    """The program's own loss under optax, full batch, no trainer."""
    loss_fn = jax.jit(jax.value_and_grad(lm_loss_fn(model)))
    state = optimizer.init(params)
    losses = []
    for _ in range(steps):
        loss, grads = loss_fn(params, {"tokens": tokens})
        updates, state = optimizer.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    return losses


def test_loss_and_gradients_match_the_program_in_float32(tokens):
    model, params = program()
    want_loss, want_grads = jax.value_and_grad(lm_loss_fn(model))(
        params, {"tokens": tokens})
    got_loss, got_grads = jax.value_and_grad(ref.loss_fn)(
        ref.stack_blocks(params), jnp.asarray(tokens))
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-5)
    want_stacked = ref.stack_blocks(want_grads)
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree.leaves(want_stacked)
    assert len(flat_got) == len(flat_want)
    for (path, got), want in zip(flat_got, flat_want):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_micro_batched_replay_equals_full_batch_optax(tokens):
    model, params = program()
    want = program_losses(model, params, tokens, 3, optax.adamw(1e-3))
    got = ref.replay_losses(params, tokens, 3, OPTIMIZER, micro_batch=1)
    assert got == pytest.approx(want, abs=2e-5)
    # the loss must move, or three equal numbers would prove nothing
    assert want[0] - want[2] > 0.05


def test_adamw_arguments_that_are_not_written_out_are_refused():
    with pytest.raises(NotImplementedError):
        ref.adamw_hyperparameters({"name": "sgd", "kwargs": {"learning_rate": 1.0}})
    with pytest.raises(NotImplementedError):
        ref.adamw_hyperparameters(
            {"name": "adamw", "kwargs": {"learning_rate": 1.0, "nesterov": True}})


def test_agree_needs_every_step_finite_and_within_tolerance():
    assert ref.agree([10.0, 9.0], [10.0 + ref.LOSS_TOLERANCE / 2, 9.0])
    assert not ref.agree([10.0, 9.0], [10.0, 9.0 + 2 * ref.LOSS_TOLERANCE])
    assert not ref.agree([10.0, float("nan")], [10.0, float("nan")])
    assert not ref.agree([10.0], [10.0, 9.0])
    assert not ref.agree([], [])


def adamw_without_bias_correction(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, wd=1e-4):
    """adamw with the division by (1 - b^t) left out."""

    def init(p):
        zeros = jax.tree.map(jnp.zeros_like, p)
        return zeros, zeros

    def update(g, state, p):
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, state[0], g)
        nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * x * x, state[1], g)
        step = jax.tree.map(
            lambda m, n, w: -lr * (m / (jnp.sqrt(n) + eps) + wd * w), mu, nu, p)
        return step, (mu, nu)

    return optax.GradientTransformation(init, update)


@pytest.mark.parametrize("fault", [
    "no position table", "no bias correction", "plain sgd"])
def test_the_tolerance_sees_a_dropped_term(tokens, fault):
    """Faults in the program's mathematics move one of three losses by more
    than LOSS_TOLERANCE against the reference.  (Weight decay is not among
    them: over three steps it moves the loss by parts in 10^8, and no
    tolerance above float32 rounding would see it.)"""
    model, params = program()
    good = ref.replay_losses(params, tokens, 3, OPTIMIZER, micro_batch=2)
    optimizer = optax.adamw(1e-3)
    if fault == "no position table":
        params = dict(params, pos_embed=jnp.zeros_like(params["pos_embed"]))
    elif fault == "no bias correction":
        optimizer = adamw_without_bias_correction()
    else:
        optimizer = optax.sgd(1e-3)
    bad = program_losses(model, params, tokens, 3, optimizer)
    assert not ref.agree(bad, good), (fault, bad, good)


def test_bfloat16_compute_as_configured_stays_inside_the_tolerance(tokens):
    """The configurations state bfloat16 matrix products with float32
    weights: that must pass.  Weights themselves in bfloat16 is a different
    (lower) precision than stated; at these widths it is the size of the
    difference that is recorded here, not a verdict."""
    model, params = program(dtype=jnp.bfloat16)
    as_configured = program_losses(model, params, tokens, 3, optax.adamw(1e-3))
    good = ref.replay_losses(params, tokens, 3, OPTIMIZER, micro_batch=2)
    assert ref.agree(as_configured, good), (as_configured, good)


# ---------------------------------------------------------------------------
# flops.py against a hand count
# ---------------------------------------------------------------------------


def config(name):
    with open(cells.BENCH_DIR / "configs" / f"{name}.json", encoding="utf-8") as f:
        return json.load(f)


def test_parameter_count_matches_the_model_as_built():
    model = TransformerLM(TransformerConfig(**TINY))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    built = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    tiny = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 2,
            "intermediate_size": 128, "vocab_size": 256,
            "max_position_embeddings": 32}
    assert flops.transformer_lm_params(tiny) == built


@pytest.mark.parametrize("name, params, millions", [
    # 30528*1024*2 (table + head) + 512*1024 + 24*(4*1024^2 + 3*1024*4096
    # + 2*1024) + 1024; PR 22 built 465.6 M with a 384-row position table
    ("bert-large", 465_748_992, 465.7),
    # 50304*1024*2 + 1024*1024 + the same 24 blocks + 1024
    ("gpt2-medium", 506_774_528, 506.8),
])
def test_parameters_by_hand(name, params, millions):
    cfg = config(name)
    assert flops.transformer_lm_params(cfg) == params == cfg["parameters_as_built"]
    assert round(params / 1e6, 1) == millions


def test_bert_large_step_flops_by_hand():
    # per token, forward MAC: 24 blocks x (4*1024^2 + 3*1024*4096 + 2*384*1024)
    # + head 1024*30528; x2 FLOP/MAC, x3 for forward + backward
    blocks = 24 * (4 * 1024 ** 2 + 3 * 1024 * 4096 + 2 * 384 * 1024)
    by_hand = 6 * (blocks + 1024 * 30528)
    per_token = flops.transformer_lm_flops_per_token(config("bert-large"), 384)
    assert per_token == by_hand
    # XLA's cost model said 8.39e12 for the 8 x 384 tokens of PR 22's step
    assert per_token * 8 * 384 == pytest.approx(8.39e12, rel=0.02)


def test_gpt2_medium_step_flops_by_hand():
    blocks = 24 * (4 * 1024 ** 2 + 3 * 1024 * 4096 + 2 * 1024 * 1024)
    per_token = flops.transformer_lm_flops_per_token(config("gpt2-medium"), 1024)
    assert per_token == 6 * (blocks + 1024 * 50304)
    assert per_token == pytest.approx(3.03e9, rel=0.01)


def test_sizes_read_either_sources_key_names():
    bert = flops.transformer_lm_sizes(config("bert-large"))
    gpt2 = flops.transformer_lm_sizes(config("gpt2-medium"))
    for key in ("d_model", "n_layers", "n_heads", "d_ff"):
        assert bert[key] == gpt2[key]
    assert (bert["vocab_size"], bert["padded_vocab_size"]) == (30522, 30528)
    assert (gpt2["vocab_size"], gpt2["padded_vocab_size"]) == (50257, 50304)
    assert (bert["max_positions"], gpt2["max_positions"]) == (512, 1024)
    assert gpt2["d_ff"] == 4096   # n_inner unset = 4 x n_embd
