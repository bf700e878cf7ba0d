"""Dropless (capacity-free) MoE vs dense per-expert reference and vs the
capacity path at infinite capacity — golden-model pattern (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.model_parallel.moe.layer import MoEMLP


def _dense_reference(params, x, k, dtype=jnp.float32):
    """Every token through its top-k experts, computed expert-by-expert."""
    from bagua_tpu.model_parallel.moe.gating import topk_routing

    b, s, d = x.shape
    xt = x.reshape(-1, d)
    router = params["router"]["kernel"]
    logits = xt.astype(jnp.float32) @ router
    eidx, gates, _ = topk_routing(logits, k)
    wi, wo = params["expert_wi"], params["expert_wo"]

    def expert(e, t):
        h = jax.nn.silu(xt[t] @ wi[e])
        return h @ wo[e]

    out = jnp.zeros_like(xt)
    for t in range(xt.shape[0]):
        for j in range(k):
            e = int(eidx[t, j])
            out = out.at[t].add(gates[t, j] * expert(e, t))
    return out.reshape(b, s, d)


def test_dropless_matches_dense_reference():
    layer = MoEMLP(n_experts=4, d_ff=32, k=2, dropless=True,
                   dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    got = layer.apply({"params": params}, x)
    want = _dense_reference(params, x, k=2)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("k", [1, 2])
def test_dropless_equals_capacity_path_at_infinite_capacity(k):
    # with capacity >= tokens nothing is dropped, so both paths compute the
    # same math (same gate conventions by design)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 16))
    tokens = x.shape[0] * x.shape[1]
    drop = MoEMLP(n_experts=4, d_ff=32, k=k, dropless=True,
                  dtype=jnp.float32)
    cap = MoEMLP(n_experts=4, d_ff=32, k=k, dropless=False,
                 capacity_factor=float(tokens), dtype=jnp.float32)
    params = drop.init(jax.random.PRNGKey(3), x)["params"]
    got = drop.apply({"params": params}, x)
    want = cap.apply({"params": params}, x)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_dropless_never_drops_under_skew():
    # route everything to one expert: capacity path drops, dropless doesn't
    layer = MoEMLP(n_experts=4, d_ff=32, k=1, dropless=True,
                   dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 16))
    params = layer.init(jax.random.PRNGKey(5), x)["params"]
    # bias the router so expert 2 wins for every token
    router = jnp.zeros_like(params["router"]["kernel"]).at[:, 2].set(10.0)
    params = {**params, "router": {"kernel": router}}
    out = layer.apply({"params": params}, x)
    want = _dense_reference(params, x, k=1)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)
    assert float(jnp.abs(out).sum()) > 0


def test_dropless_trains():
    layer = MoEMLP(n_experts=4, d_ff=32, k=2, dropless=True,
                   dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 8, 16))
    y = jax.random.normal(jax.random.PRNGKey(7), (4, 8, 16))
    params = layer.init(jax.random.PRNGKey(8), x)["params"]
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            out, mut = MoEMLP(
                n_experts=4, d_ff=32, k=2, dropless=True, dtype=jnp.float32
            ).apply({"params": p}, x, mutable=["intermediates"])
            aux = sum(jax.tree.leaves(mut["intermediates"]))
            return ((out - y) ** 2).mean() + 0.01 * aux.sum()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0], losses[:3] + losses[-3:]


# ---------------------------------------------------------------------------
# expert-parallel dropless: ragged all-to-all dispatch
# ---------------------------------------------------------------------------


def test_dropless_ep_matches_single_shard_forward():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from bagua_tpu.parallel.mesh import build_mesh

    E, ep, d_model, d_ff, seq = 8, 4, 16, 32, 8
    single = MoEMLP(n_experts=E, d_ff=d_ff, ep_size=1, k=2, dropless=True,
                    dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, seq, d_model))
    params = single.init(jax.random.PRNGKey(1), x[:2])["params"]
    ref = single.apply({"params": params}, x)

    sharded = MoEMLP(n_experts=E, d_ff=d_ff, ep_size=ep, k=2, dropless=True,
                     dtype=jnp.float32)
    mesh = build_mesh({"ep": ep}, jax.devices()[:ep])
    pspec = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (
            P("ep") if "expert" in jax.tree_util.keystr(path) else P()
        ),
        params,
    )
    out = jax.jit(shard_map(
        lambda p, xs: sharded.apply({"params": p}, xs),
        mesh=mesh, in_specs=(pspec, P("ep")), out_specs=P("ep"),
        check_vma=False,
    ))(params, x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_dropless_ep_one_step_matches_single_shard():
    """One SGD step through the trainer: ep=4 must yield the same updated
    weights as single-shard dropless (validates the ragged-exchange grads
    and the trainer's 1/ep expert-grad rescale)."""
    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.model_parallel.moe import moe_lm_loss_fn
    from bagua_tpu.models.transformer import TransformerConfig, TransformerLM
    from bagua_tpu.parallel.mesh import build_mesh

    E, ep, lr = 4, 4, 0.1
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32)

    def make_model(ep_size):
        return TransformerLM(cfg, mlp_factory=lambda i: (
            lambda: MoEMLP(n_experts=E, d_ff=64, k=2, ep_size=ep_size,
                           dropless=True, dtype=jnp.float32)
        ) if i == 1 else None)

    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, cfg.max_seq_len + 1),
                                0, cfg.vocab_size)
    params = make_model(1).init(jax.random.PRNGKey(1), tokens[:2, :-1])["params"]

    # aux_loss_weight=0: the load-balancing aux is nonlinear in the
    # batch, so sharding the batch over ep legitimately changes it —
    # this test isolates the routing/compute/grad path
    t1 = BaguaTrainer(moe_lm_loss_fn(make_model(1), aux_loss_weight=0.0),
                      optax.sgd(lr),
                      GradientAllReduceAlgorithm(),
                      mesh=build_mesh({"dp": 1}, jax.devices()[:1]),
                      autotune=False)
    s1 = t1.init(params)
    s1, loss1 = t1.train_step(s1, t1.shard_batch({"tokens": tokens}))

    tep = BaguaTrainer(moe_lm_loss_fn(make_model(ep), aux_loss_weight=0.0),
                       optax.sgd(lr),
                       GradientAllReduceAlgorithm(),
                       mesh=build_mesh({"dp": 1, "ep": ep},
                                       jax.devices()[:ep]),
                       expert_axis="ep", autotune=False)
    sep = tep.init(params)
    sep, lossep = tep.train_step(sep, tep.shard_batch({"tokens": tokens}))

    np.testing.assert_allclose(float(loss1), float(lossep), atol=1e-5)
    w1 = t1.unstack_params(s1)
    wep = tep.unstack_params(sep)
    flat1 = jax.tree_util.tree_leaves_with_path(w1)
    flatep = dict(jax.tree_util.tree_leaves_with_path(wep))
    for path, leaf in flat1:
        got = flatep[path]
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(got), atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )


# ---------------------------------------------------------------------------
# the row kernels (ops/moe_rows.py): the layer on them is the fallback's layer
# ---------------------------------------------------------------------------

from internal.row_kernels import (  # noqa: E402
    QUANTITIES, assert_the_same_layer, both_paths,
)


@pytest.fixture(scope="module")
def row_kernel_paths():
    """An ungated top-2 layer of 8 experts at the kernels' lane width."""
    return both_paths(MoEMLP(n_experts=8, d_ff=128, k=2, dropless=True,
                             dtype=jnp.float32))


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_the_layer_on_the_row_kernels_is_the_fallback_layer(
        row_kernel_paths, quantity):
    assert_the_same_layer(*row_kernel_paths, quantity)
