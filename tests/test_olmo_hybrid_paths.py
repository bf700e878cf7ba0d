"""Olmo-Hybrid's paths and its trainer step (``tests/test_olmo_hybrid.py``
has the model against the plain reference): which shapes the delta rule's
kernels and row passes take, the whole layer forced onto them (interpreted)
at 96 / 192-lane heads against the ``jax.numpy`` layer, a ``dp = 4`` step of
``BaguaTrainer`` on four CPU devices — the update sharded over them —
against the ``dp = 1`` step on the same global batch, the output norms'
areas and the gauges.  Small widths that keep the shape of the problem, two
periods, seeded, CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.models.transformer import Block, lm_loss_fn
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.ops import gated_delta as gd
from bagua_tpu.ops import gated_delta_rows as rows
from bagua_tpu.parallel.mesh import build_mesh
from bagua_tpu.telemetry import counters

# the model and its seeded weights
from test_olmo_hybrid import _LEAVES, flat, olmo_hybrid, seeded


# ---------------------------------------------------------------------------
# which path runs where
# ---------------------------------------------------------------------------


def test_the_kernels_take_heads_in_blocks_of_four(monkeypatch):
    assert gd.heads_per_block(128, 128, 2) == 1
    assert gd.heads_per_block(96, 192, 1) == 4
    assert gd.heads_per_block(64, 128, 1) == 2
    assert not gd.gated_delta_supported(30, 30, 96, 192)      # the CPU
    monkeypatch.setattr(gd.jax, "default_backend", lambda: "tpu")
    assert gd.gated_delta_supported(30, 30, 96, 192)
    assert gd.gated_delta_supported(6, 6, 96, 192, jnp.float32)
    assert gd.gated_delta_supported(16, 32, 128, 128)
    assert not gd.gated_delta_supported(30, 30, 48, 192)      # eight a block
    assert not gd.gated_delta_supported(30, 30, 100, 192)
    dims = (30, 30, 96, 192)
    assert rows._merged(dims) and not rows._merged((16, 32, 128, 128))
    assert rows.rows_supported(8192, dims, 4)
    assert not rows.rows_supported(8192 + 64, dims, 4)
    # 5 heads of 96: q | k together are no whole number of 384-lane blocks
    assert not rows.rows_supported(8192, (5, 5, 96, 192), 4)
    model = olmo_hybrid()
    from bagua_tpu.models.linear_attention import rows_by_kernel

    assert rows_by_kernel(model.cfg, 256) and not rows_by_kernel(model.cfg, 80)


def test_the_whole_layer_on_the_row_passes_is_the_jnp_layer(monkeypatch):
    """The layer forced onto the passes and the kernels (interpreted) at 96 /
    192-lane heads, six of them, against the ``jax.numpy`` layer: value and
    every gradient."""
    model = olmo_hybrid()
    params, _ = seeded(model)
    p = params["block_0"]
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(9), (2, 128, 64))
    layer = Block(model.cfg, layer=0)
    loss = lambda p, x: jnp.sum(jnp.sin(layer.apply({"params": p}, x)))
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
        monkeypatch.setattr(rows, "_on_tpu", lambda: True)
        forced = rows.gated_delta_rows
        monkeypatch.setattr(
            rows, "gated_delta_rows",
            lambda *a, **kw: forced(*a, **kw, interpret=True))
        text = str(jax.make_jaxpr(loss)(p, x))
        assert all(name in text for name in ("gdn_mix", "gdn_fwd", "gdn_gate"))
        got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=2e-4 * scale, rtol=0)


# ---------------------------------------------------------------------------
# through the trainer: dp = 4 against dp = 1
# ---------------------------------------------------------------------------


def _steps(dp: int, params, tokens, steps=2):
    mesh = build_mesh({"dp": dp}, jax.devices()[:dp])
    bagua_tpu.init_process_group(mesh=mesh)
    trainer = bagua_tpu.BaguaTrainer(
        lm_loss_fn(olmo_hybrid()), optax.adamw(1e-4),
        GradientAllReduceAlgorithm(hierarchical=False), mesh=mesh,
        autotune=False)
    state = trainer.init(jax.tree.map(jnp.copy, params))
    sharded = trainer._update_sharded()
    batch = trainer.shard_batch({"tokens": tokens})
    losses = []
    for _ in range(steps):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))       # fenced: the CPU mesh's rendezvous
    return losses, flat(jax.device_get(trainer.unstack_params(state))), sharded


@pytest.fixture(scope="module")
def dp_steps():
    params, tokens = seeded(olmo_hybrid())
    with jax.default_matmul_precision("highest"):
        return _steps(1, params, tokens), _steps(4, params, tokens), flat(
            jax.device_get(params))


def test_dp4_shards_the_update_and_dp1_does_not(dp_steps):
    (_, _, one), (_, _, four), _ = dp_steps
    assert four and not one


def test_the_dp4_losses_are_the_dp1_losses(dp_steps):
    (one, _, _), (four, _, _), _ = dp_steps
    assert one[1] < one[0]
    np.testing.assert_allclose(four, one, atol=2e-5, rtol=0)


@pytest.mark.parametrize("leaf", _LEAVES)
def test_the_dp4_update_is_the_dp1_update(dp_steps, leaf):
    """The same global batch over four chips, the moments sharded over
    them: every leaf's change over two updates is the one-chip step's (an
    entry whose gradient is zero but for rounding steps either way under
    AdamW: the distance is over the leaf)."""
    (_, one, _), (_, four, _), start = dp_steps
    change_one, change_four = one[leaf] - start[leaf], four[leaf] - start[leaf]
    norm = float(np.linalg.norm(change_one))
    assert norm > 0
    assert float(np.linalg.norm(change_four - change_one)) < 0.05 * norm


# ---------------------------------------------------------------------------
# spans and gauges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path,area", [
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/"
     "linear_attn_post_norm/mul", "linattn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_3/"
     "attn_post_norm/mul", "attn"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_3/mlp_post_norm/"
     "rsqrt", "mlp"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_1/linear_attn/"
     "jit(_mix_part)/gdn_mix/pallas_call", "linattn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_1/"
     "linear_attn/jit(_kernel_bwd)/gdn_bwd/pallas_call", "linattn"),
])
def test_area_of_reads_the_output_norms(path, area):
    assert obs_spans.area_of(path) == area


def test_a_traced_step_sets_the_gauges():
    model = olmo_hybrid()
    params, tokens = seeded(model)
    jax.jit(lm_loss_fn(model)).lower(params, {"tokens": jnp.asarray(tokens)})
    gauges = counters.snapshot()
    assert (gauges["linattn/layers"], gauges["linattn/key_heads"],
            gauges["linattn/value_heads"], gauges["linattn/key_dim"],
            gauges["linattn/value_dim"], gauges["linattn/neg_eigval"]) == (
        6, 6, 6, 96, 192, 1)
    assert gauges["linattn/row_kernel_layers"] == 0       # the CPU
    assert gauges["attn/rope_kernel_layers"] == 0
