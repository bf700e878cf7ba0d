"""OLMoE on the normal path: ``TransformerLM`` (RoPE, QK-norm, the
configuration's RMSNorm epsilon) + ``MoEMLP`` (gated experts, dropless
top-k, raw winners' probabilities, balance loss over all assignments) +
``moe_lm_loss_fn``, against the benchmark's plain float32 reference
(``perfbench/reference/olmoe.py``, which imports nothing of ``bagua_tpu``)
and against hand-rolled forms of each new piece.  Tiny widths, seeded, CPU.
"""

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu.model_parallel.moe.gating import topk_routing
from bagua_tpu.model_parallel.moe.layer import (
    EXPERT_PARAM_NAMES, MoEMLP, is_expert_param, moe_lm_loss_fn,
)
from bagua_tpu.models.transformer import (
    Attention, RMSNorm, TransformerConfig, TransformerLM, rope_rotate,
)
from bagua_tpu.ops.gmm import (
    _padded_rows, gmm, gmm_reference, kernel_layout, padded_layout,
)


def _reference():
    path = (Path(__file__).resolve().parents[1] / "perfbench" / "reference"
            / "olmoe.py")
    spec = importlib.util.spec_from_file_location("olmoe_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()

#: (experts, experts per token): a small router and OLMoE's own 8 of 64
ROUTERS = [(8, 4), (64, 8)]
AUX = 0.01
#: float32 against float32 on the CPU, both with exact products: what is
#: left is the order of summation (a sort and a scatter-add in the program,
#: a masked loop in the reference).  A missing piece (no rotation, no q/k
#: norm, renormalised winners, top-1 balance loss, a dropped expert) moves
#: logits by 1e-2 to 1 and fails every one of these.
LOGIT_ATOL = 2e-5
LOSS_ATOL = 2e-6
GRAD_RTOL = 2e-5


def olmoe(n_experts, k, *, layers=2, dtype=jnp.float32, **cfg_kw):
    cfg = TransformerConfig(
        vocab_size=97, d_model=64, n_heads=4, n_layers=layers, d_ff=32,
        max_seq_len=32, dtype=dtype, rope_theta=10000.0, qk_norm=True,
        norm_eps=1e-5, **cfg_kw)
    moe = lambda: MoEMLP(
        n_experts=n_experts, d_ff=32, k=k, dropless=True, gated=True,
        norm_topk_prob=False, balance_over_topk=True, dtype=dtype, name="mlp")
    model = TransformerLM(cfg, mlp_factory=lambda _i: moe)
    hyper = {"layers": layers, "experts_per_token": k,
             "norm_topk_prob": False, "rope_theta": 10000.0,
             "rms_norm_eps": 1e-5, "aux_coef": AUX}
    return model, hyper


def seeded(model, seed=0, batch=4, seq=16):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1),
                                0, model.cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(seed + 1), tokens[:1, :8])["params"]
    # norm scales off their all-ones init, so that a norm applied in the
    # wrong place or with the wrong scale shows
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if "scale" in jax.tree_util.keystr(path) else leaf
        for (path, leaf), key in zip(leaves, keys)])
    return params, tokens


# ---------------------------------------------------------------------------
# system against the plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=ROUTERS, ids=lambda r: f"{r[1]}of{r[0]}")
def both(request):
    """Logits, loss and gradients of system and reference, computed once."""
    model, hyper = olmoe(*request.param)
    params, tokens = seeded(model)
    with jax.default_matmul_precision("highest"):
        sys_logits = model.apply({"params": params}, tokens[:, :-1])
        ref_logits = ref.logits_fn(params, tokens[:, :-1], hyper)
        sys_loss, sys_grads = jax.value_and_grad(moe_lm_loss_fn(model, AUX))(
            params, {"tokens": tokens})
        ref_loss, ref_grads = jax.value_and_grad(ref.loss_fn)(
            params, tokens, hyper)
    return {"logits": (sys_logits, ref_logits), "loss": (sys_loss, ref_loss),
            "grads": (sys_grads, ref_grads), "params": params}


def test_the_model_has_no_position_table_and_three_expert_leaves(both):
    params = both["params"]
    assert "pos_embed" not in params
    assert set(params["block_0"]["mlp"]) == {
        "router", "expert_wi", "expert_wg", "expert_wo"}
    assert set(params["block_0"]["attn"]) == {
        "q", "k", "v", "o", "q_norm", "k_norm"}
    assert params["block_0"]["attn"]["q_norm"]["scale"].shape == (64,)


def test_logits_agree_with_the_reference(both):
    got, want = both["logits"]
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)


def test_loss_agrees_with_the_reference(both):
    got, want = both["loss"]
    assert abs(float(got) - float(want)) <= LOSS_ATOL


_LEAVES = [jax.tree_util.keystr(path) for path, _ in
           jax.tree_util.tree_flatten_with_path(jax.eval_shape(
               lambda: seeded(olmoe(8, 4)[0])[0]))[0]]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_agrees_with_the_reference(both, leaf):
    flat = lambda tree: {jax.tree_util.keystr(p): v for p, v in
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(both["grads"][0])[leaf], flat(both["grads"][1])[leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a gradient that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=GRAD_RTOL * scale, rtol=0)


@pytest.mark.parametrize("fault", ["bf16_weights", "dropped_expert",
                                   "renormalised", "no_rotation"])
def test_the_comparison_sees_a_fault(fault):
    """What the tolerances are for: each of these moves the loss by far
    more than ``LOSS_ATOL``."""
    model, hyper = olmoe(8, 4)
    params, tokens = seeded(model)
    loss = moe_lm_loss_fn(model, AUX)
    if fault == "bf16_weights":
        params_sys = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    else:
        params_sys = params
    if fault == "dropped_expert":
        hyper = {**hyper, "experts_per_token": 3}
    if fault == "renormalised":
        hyper = {**hyper, "norm_topk_prob": True}
    if fault == "no_rotation":
        hyper = {**hyper, "rope_theta": 1e30}   # every angle ~ 0
    with jax.default_matmul_precision("highest"):
        got = float(loss(params_sys, {"tokens": tokens}))
        want = float(ref.loss_fn(params, tokens, hyper))
    assert abs(got - want) > 50 * LOSS_ATOL


# ---------------------------------------------------------------------------
# the pieces, each against a hand-rolled form
# ---------------------------------------------------------------------------


def test_rope_is_a_rotation_of_each_pair_by_position_times_frequency():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 3, 8))
    got = np.asarray(rope_rotate(x, 10000.0))
    half = 4
    for pos in range(6):
        for i in range(half):
            angle = pos * 10000.0 ** (-2 * i / 8)
            a, b = np.asarray(x[:, pos, :, i]), np.asarray(x[:, pos, :, i + half])
            np.testing.assert_allclose(
                got[:, pos, :, i], a * math.cos(angle) - b * math.sin(angle),
                atol=1e-5)
            np.testing.assert_allclose(
                got[:, pos, :, i + half],
                a * math.sin(angle) + b * math.cos(angle), atol=1e-5)


@pytest.mark.parametrize("shift", [1, 7, 300])
def test_rope_scores_depend_on_relative_position_only(shift):
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 2, 16))
    scores = lambda start: jnp.einsum(
        "bqhd,bkhd->bhqk", rope_rotate(q, 10000.0, start),
        rope_rotate(k, 10000.0, start))
    np.testing.assert_allclose(scores(0), scores(shift), atol=2e-4)
    # and a chunk at an offset is that slice of the whole sequence's
    # rotation (the sp_axis offset)
    long = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 2, 16))
    np.testing.assert_allclose(
        rope_rotate(long[:, 3:], 10000.0, 3), rope_rotate(long, 10000.0)[:, 3:],
        atol=1e-5)


def test_rope_keeps_the_dtype_and_the_norm():
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 4, 2, 8), jnp.bfloat16)
    y = rope_rotate(x, 10000.0)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        jnp.linalg.norm(y.astype(jnp.float32), axis=-1),
        jnp.linalg.norm(x.astype(jnp.float32), axis=-1), rtol=2e-2)


def test_qk_norm_is_an_rmsnorm_over_all_heads_before_the_split():
    cfg = TransformerConfig(d_model=32, n_heads=4, dtype=jnp.float32,
                            qk_norm=True, norm_eps=1e-5, max_seq_len=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    seen = {}

    def spy(q, k, v, dtype):
        seen["q"], seen["k"], seen["v"] = q, k, v
        return v

    attn = Attention(cfg, attn_fn=spy)
    params = attn.init(jax.random.PRNGKey(1), x)["params"]
    params["q_norm"]["scale"] = jnp.linspace(0.5, 1.5, 32)
    attn.apply({"params": params}, x)

    def by_hand(name):
        flat = jnp.einsum("bsd,dhk->bshk", x, params[name]["kernel"]).reshape(
            2, 8, 32)
        rms = jnp.sqrt(jnp.mean(flat ** 2, axis=-1, keepdims=True) + 1e-5)
        return (flat / rms * params[f"{name}_norm"]["scale"]).reshape(
            2, 8, 4, 8)

    np.testing.assert_allclose(seen["q"], by_hand("q"), atol=1e-5)
    np.testing.assert_allclose(seen["k"], by_hand("k"), atol=1e-5)
    assert "v_norm" not in params


@pytest.mark.parametrize("eps", [1e-6, 1e-5, 1e-2])
def test_rmsnorm_takes_its_epsilon_from_the_configuration(eps):
    x = 1e-2 * jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    norm = RMSNorm(jnp.float32, jnp.float32, eps)
    got = norm.apply(norm.init(jax.random.PRNGKey(1), x), x)
    want = x / jnp.sqrt(jnp.mean(x ** 2, axis=-1, keepdims=True) + eps)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert RMSNorm().eps == 1e-6 == TransformerConfig().norm_eps


@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_the_router_keeps_raw_probabilities_when_not_renormalised(k):
    logits = jax.random.normal(jax.random.PRNGKey(k), (12, 16))
    probs = jax.nn.softmax(logits, axis=-1)
    eidx, gates, _ = topk_routing(logits, k, renormalize=False)
    np.testing.assert_allclose(
        gates, jnp.take_along_axis(probs, eidx, axis=-1), rtol=1e-6)
    assert eidx.shape == gates.shape == (12, k)
    # the winners are the k largest, in order
    np.testing.assert_array_equal(eidx, jnp.argsort(-probs, axis=-1)[:, :k])
    _, renormalised, _ = topk_routing(logits, k)
    if k > 1:
        np.testing.assert_allclose(renormalised.sum(-1), 1.0, rtol=1e-5)
        assert float(gates.sum(-1).max()) < 1.0
    else:
        np.testing.assert_allclose(renormalised, gates)


@pytest.mark.parametrize("n_experts, k", ROUTERS)
def test_the_balance_loss_is_the_hand_count_over_all_assignments(n_experts, k):
    tokens = 24
    logits = jax.random.normal(jax.random.PRNGKey(5), (tokens, n_experts))
    eidx, _, l_aux = topk_routing(logits, k, balance_over_topk=True)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    counts = np.zeros(n_experts)
    for t in range(tokens):
        for j in range(k):
            counts[int(eidx[t, j])] += 1
    assert counts.sum() == tokens * k
    want = n_experts * sum(
        counts[e] / tokens * probs[:, e].mean() for e in range(n_experts))
    assert float(l_aux) == pytest.approx(want, rel=1e-5)
    # the default is GShard's: the top-1 assignment alone
    _, _, top1 = topk_routing(logits, k)
    first = np.bincount(np.asarray(eidx[:, 0]), minlength=n_experts)
    assert float(top1) == pytest.approx(n_experts * sum(
        first[e] / tokens * probs[:, e].mean() for e in range(n_experts)),
        rel=1e-5)


def _gated(mm, rows, w_gate, w_up, w_down, sizes):
    return mm(jax.nn.silu(mm(rows, w_gate, sizes)) * mm(rows, w_up, sizes),
              w_down, sizes)


@pytest.mark.parametrize("sizes", [(100, 60, 0, 96), (256, 0, 0, 0),
                                   (1, 127, 127, 1)])
def test_the_gated_expert_runs_through_the_gmm_kernel(sizes):
    """Three grouped matmuls and the gate through the Pallas kernels
    (interpret mode) against the dense one-hot form, values and gradients."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    rows = jax.random.normal(keys[0], (256, 128))
    w_gate, w_up = (0.1 * jax.random.normal(k, (4, 128, 128)) for k in keys[1:3])
    w_down = 0.1 * jax.random.normal(keys[3], (4, 128, 128))
    sizes = jnp.array(sizes, jnp.int32)
    kernel = lambda l, r, s: gmm(l, r, s, interpret=True, force=True)
    args = (rows, w_gate, w_up, w_down)
    np.testing.assert_allclose(_gated(kernel, *args, sizes),
                               _gated(gmm_reference, *args, sizes), atol=1e-4)
    loss = lambda mm: lambda *a: jnp.sum(_gated(mm, *a, sizes) ** 2)
    got = jax.grad(loss(kernel), argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(loss(gmm_reference), argnums=(0, 1, 2, 3))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-3 * float(jnp.abs(w).max()))


def test_kernel_rows_is_the_padded_layout_of_the_cell():
    # 2 x 4096 tokens x 8 experts in 64 groups of 128-row blocks
    assert _padded_rows(65536, 64, 128) == 73728
    assert _padded_rows(256, 4, 128) == 768
    sizes = jnp.full((64,), 1024, jnp.int32)
    assert padded_layout(sizes, 65536).src.shape == (73728,)
    # off the TPU the dense fallback multiplies the routed rows only
    assert kernel_layout(sizes, 65536, 2048, 1024).src.shape == (65536,)


def test_dropless_equals_the_capacity_path_at_infinite_capacity():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 16))
    common = dict(n_experts=4, d_ff=32, k=2, dtype=jnp.float32)
    drop = MoEMLP(dropless=True, **common)
    cap = MoEMLP(dropless=False, capacity_factor=16.0, **common)
    params = drop.init(jax.random.PRNGKey(3), x)["params"]
    assert "expert_wg" not in params
    np.testing.assert_allclose(drop.apply({"params": params}, x),
                               cap.apply({"params": params}, x),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kwargs, message", [
    (dict(k=8), "top-1 and top-2 only"),
    (dict(k=2, gated=True), "dropless"),
    (dict(k=2, norm_topk_prob=False), "dropless"),
    (dict(k=2, balance_over_topk=True), "dropless"),
])
def test_the_capacity_path_refuses_what_only_dropless_routes(kwargs, message):
    layer = MoEMLP(n_experts=16, d_ff=32, dtype=jnp.float32, **kwargs)
    x = jnp.zeros((1, 8, 16))
    with pytest.raises(ValueError, match=message):
        layer.init(jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("ep", [1, 4])
def test_the_moe_gauges_are_set_on_one_shard_and_under_ep(ep):
    """``moe/*`` describe the layer last traced, on this rank: the routed
    rows, and the rows the grouped matmuls get (under ``ep`` the worst-case
    receive buffer).  Off the TPU the dense fallback pads nothing."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.telemetry import counters

    layer = MoEMLP(n_experts=8, d_ff=32, ep_size=ep, k=2, dropless=True,
                   gated=True, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16))
    params = MoEMLP(n_experts=8, d_ff=32, k=2, dropless=True, gated=True,
                    dtype=jnp.float32).init(jax.random.PRNGKey(1), x)["params"]
    for name in ("moe/experts", "moe/rows_per_step",
                 "moe/padded_rows_per_step"):
        counters.set_gauge(name, -1)
    if ep == 1:
        layer.apply({"params": params}, x)
    else:
        mesh = build_mesh({"ep": ep}, jax.devices()[:ep])
        pspec = jax.tree_util.tree_map_with_path(
            lambda path, leaf: P("ep") if is_expert_param(
                jax.tree_util.keystr(path)) else P(), params)
        jax.jit(shard_map(
            lambda p, xs: layer.apply({"params": p}, xs), mesh=mesh,
            in_specs=(pspec, P("ep")), out_specs=P("ep"), check_vma=False,
        ))(params, x)
    gauges = counters.snapshot()
    rows = 4 * 8 * 2 // ep
    assert gauges["moe/experts"] == 8 // ep
    assert gauges["moe/rows_per_step"] == rows
    # every peer may route all its rows here
    assert gauges["moe/padded_rows_per_step"] == rows * ep


def test_every_expert_leaf_is_an_expert_param():
    assert EXPERT_PARAM_NAMES == {"expert_wi", "expert_wo", "expert_wg"}
    model, _ = olmoe(8, 4)
    params, _ = seeded(model)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    experts = [n for n in names if is_expert_param(n)]
    assert len(experts) == 3 * 2            # three leaves a layer, two layers
    assert all(params_leaf.ndim == 3 for n, params_leaf in zip(
        names, jax.tree.leaves(params)) if n in experts)
    assert not any(is_expert_param(n) for n in names if "router" in n)


# ---------------------------------------------------------------------------
# defaults and refusals
# ---------------------------------------------------------------------------


def test_the_defaults_are_the_learned_position_table_model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32)
    assert (cfg.rope_theta, cfg.qk_norm, cfg.norm_eps) == (None, False, 1e-6)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    assert params["pos_embed"].shape == (8, 32)
    assert set(params["block_0"]["attn"]) == {"q", "k", "v", "o"}


@pytest.mark.parametrize("page_size", [0, 4])
def test_rope_refuses_the_decode_paths(page_size):
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32,
                            rope_theta=10000.0, decode=True,
                            page_size=page_size, num_pages=8)
    with pytest.raises(NotImplementedError, match="decode"):
        TransformerLM(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 1), jnp.int32))


def test_rope_positions_follow_the_sequence_parallel_chunk():
    """Inside a bound ``sp_axis`` each shard rotates by its own chunk's
    positions, as the learned table is offset today."""
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=64, max_seq_len=16, dtype=jnp.float32,
                            rope_theta=10000.0, sp_axis="sp")
    seen = []

    def spy(q, k, v, dtype):
        seen.append(q)
        return v

    attn = Attention(cfg, attn_fn=spy)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    params = attn.init(jax.random.PRNGKey(1), x)["params"]
    attn.apply({"params": params}, x)
    whole = seen.pop()

    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))

    def shard(x_chunk):
        attn.apply({"params": params}, x_chunk)
        return seen.pop()

    chunks = jax.shard_map(shard, mesh=mesh, in_specs=P(None, "sp"),
                           out_specs=P(None, "sp"))(x)
    np.testing.assert_allclose(chunks, whole, atol=1e-5)


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------


def one_chip_trainer(model, **kwargs):
    """World 1, as the cell: the balance loss is not linear in the batch, so
    a rank's loss is the reference's only where the rank holds all of it."""
    from bagua_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    bagua_tpu.init_process_group(mesh=mesh)
    return bagua_tpu.BaguaTrainer(
        moe_lm_loss_fn(model, AUX), optax.adamw(1e-3),
        bagua_tpu.algorithms.gradient_allreduce.GradientAllReduceAlgorithm(),
        mesh=mesh, autotune=False, **kwargs)


@pytest.mark.parametrize("flat_resident", ["on", "off"])
def test_a_trainer_step_on_the_tiny_model(flat_resident):
    model, hyper = olmoe(8, 4, layers=1)
    params, tokens = seeded(model, batch=8)
    trainer = one_chip_trainer(model, flat_resident=flat_resident)
    # the trainer's default expert filter is the layer's own
    assert {n for n in ("a.expert_wi", "a.expert_wg", "a.expert_wo", "a.router")
            if trainer._expert_filter(n)} == {
        "a.expert_wi", "a.expert_wg", "a.expert_wo"}
    state = trainer.init(params)
    batch = trainer.shard_batch({"tokens": np.asarray(tokens)})
    losses = []
    for _ in range(4):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # the first loss is the reference's on the same weights and batch
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss_fn(params, jnp.asarray(tokens), hyper))
    assert losses[0] == pytest.approx(want, abs=1e-4)
    # and the expert leaves moved
    after = trainer.unstack_params(state)["block_0"]["mlp"]
    for name in ("expert_wi", "expert_wg", "expert_wo"):
        assert float(jnp.abs(after[name] - params["block_0"]["mlp"][name]).max()) > 0


def test_the_replayed_losses_agree_with_the_reference_through_adamw():
    """Three steps on one batch, trainer against the reference's written-out
    AdamW: the comparison the benchmark's ``correct`` makes, at float32."""
    model, hyper = olmoe(8, 4, layers=1)
    params, tokens = seeded(model, batch=8)
    trainer = one_chip_trainer(model)
    state = trainer.init(params)
    batch = trainer.shard_batch({"tokens": np.asarray(tokens)})
    got = []
    for _ in range(3):
        state, loss = trainer.train_step(state, batch)
        got.append(float(loss))
    want = ref.replay_losses(
        params, np.asarray(tokens), 3,
        {"name": "adamw", "kwargs": {"learning_rate": 1e-3}}, 8, hyper)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert ref.agree(got, want)
    # the limits are by step: what the third step allows the first does not
    assert not ref.agree(got, [w + 0.02 for w in want])
    assert ref.agree([1.0, 1.0, 1.02], [1.0, 1.0, 1.0])
    assert not ref.agree([1.02, 1.0, 1.0], [1.0, 1.0, 1.0])
    assert not ref.agree([1.0, float("nan"), 1.0], [1.0, 1.0, 1.0])
    assert not ref.agree([1.0, 1.0], [1.0, 1.0, 1.0])


# ---------------------------------------------------------------------------
# the padded-resident path: rows enter the kernels' layout once, leave once
# ---------------------------------------------------------------------------


def _force_kernels(patch):
    """The kernels' path on the CPU: ``_use_kernel`` says yes and every
    ``pallas_call`` runs in interpret mode — steered here, in the test, not
    by an option of the program."""
    import bagua_tpu.ops.gmm as gmm_mod

    real = gmm_mod.pl.pallas_call
    patch.setattr(gmm_mod, "_use_kernel", lambda *a: True)
    patch.setattr(gmm_mod.pl, "pallas_call",
                  lambda *a, **kw: real(*a, **{**kw, "interpret": True}))


@pytest.fixture
def forced_kernels(monkeypatch):
    _force_kernels(monkeypatch)


def resident_layer(gated, k, ep_size=1):
    """128 tokens of width 128 (the kernels' lane width): ``k = 8`` of 16
    experts routes 1,024 rows into 3,072 padded ones."""
    return MoEMLP(n_experts=8 if k == 2 else 16, d_ff=128, k=k,
                  ep_size=ep_size, dropless=True, gated=gated,
                  norm_topk_prob=False, balance_over_topk=True,
                  dtype=jnp.float32)


def layer_loss(layer, g):
    def loss(params, x):
        out, mutated = layer.apply({"params": params}, x,
                                   mutable=["intermediates"])
        l_aux = sum(jnp.sum(l) for l in jax.tree.leaves(mutated))
        return jnp.sum(out * g) + l_aux, (out, l_aux)
    return loss


RESIDENT = [(True, 2), (True, 8), (False, 2), (False, 8)]
QUANTITIES = ["out", "l_aux", "d_xt", "router", "expert_wi", "expert_wo",
              "expert_wg"]


@pytest.fixture(scope="module", params=RESIDENT,
                ids=lambda c: f"{'gated' if c[0] else 'ungated'}-k{c[1]}")
def resident_and_fallback(request):
    """Output, balance loss and every gradient of one layer, computed once
    on the padded-resident path (forced, interpret mode) and once on the
    fallback."""
    from bagua_tpu.telemetry import counters

    gated, k = request.param
    layer = resident_layer(gated, k)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 128))
    g = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 128))
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    grad = jax.value_and_grad(layer_loss(layer, g), argnums=(0, 1),
                              has_aux=True)

    def quantities():
        (_, (out, l_aux)), (d_params, d_x) = grad(params, x)
        found = {"out": out, "l_aux": l_aux, "d_xt": d_x,
                 "router": d_params["router"]["kernel"],
                 **{name: d_params[name] for name in d_params
                    if name.startswith("expert_")}}
        return found, counters.get("moe/padded_resident_layers")

    fallback, engaged_fallback = quantities()
    with pytest.MonkeyPatch.context() as patch:
        _force_kernels(patch)
        resident, engaged_resident = quantities()
    assert (engaged_fallback, engaged_resident) == (0, 1)
    return resident, fallback


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_the_padded_resident_layer_is_the_fallback_layer(
        resident_and_fallback, quantity):
    resident, fallback = resident_and_fallback
    if quantity not in fallback:
        assert quantity == "expert_wg"      # an ungated layer has no gate
        assert set(resident) == set(fallback)
        return
    got, want = resident[quantity], fallback[quantity]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a quantity that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_padded_resident_step_moves_rows_by_gathers_alone(
        forced_kernels, gated):
    """Value-and-grad of the forced layer at ``T * k`` = 1,024 rows: no
    scatter or scatter-add touches an operand of the row width, and the
    kernels are called 3 + 3 and 3 times (2 + 2 and 2 ungated) — the hidden
    rows' rebuild in the backward pass replays no product — and none of the
    d_lhs calls is fed a transposed copy of an expert stack."""
    from bagua_tpu.telemetry import counters
    from tests.internal.jaxpr_walk import primitives

    layer = resident_layer(gated, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 128))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    counters.set_gauge("moe/padded_resident_layers", -1)
    ops = primitives(jax.value_and_grad(
        layer_loss(layer, jnp.ones_like(x)), argnums=(0, 1), has_aux=True),
        params, x)
    assert counters.get("moe/padded_resident_layers") == 1
    transposed = [shapes for name, shapes in ops if name == "transpose"
                  and any(len(s) == 3 and s[0] == 16 for s in shapes)]
    assert len(transposed) == 0
    assert counters.get("moe/rows_per_step") == 1024
    assert counters.get("moe/padded_rows_per_step") == 3072
    names = [name for name, _ in ops]
    products = 3 if gated else 2
    assert names.count("gmm_fwd") == 2 * products
    assert names.count("gmm_bwd_drhs") == products
    scatters = [(name, shapes) for name, shapes in ops
                if name.startswith("scatter")]
    # what is left: the sort's inverse, the group sizes, the router's
    # top-k transpose over [T, E] — index vectors and per-expert tables
    assert scatters, "the inverse permutation is a scatter over int32"
    for name, shapes in scatters:
        assert all(shape[-1:] != (128,) for shape in shapes if shape), (
            name, shapes)
        assert max(int(np.prod(shape)) for shape in shapes) <= 128 * 16


def test_the_fallback_layer_reports_no_padded_resident_layer():
    from bagua_tpu.telemetry import counters

    layer = resident_layer(True, 2)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 128))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    counters.set_gauge("moe/padded_resident_layers", -1)
    layer.apply({"params": params}, x)
    assert counters.get("moe/padded_resident_layers") == 0
    assert counters.get("moe/padded_rows_per_step") == 256


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_expert_parallel_pads_once_from_its_receive_buffer(
        forced_kernels, gated):
    """``ep`` 4 on the virtual mesh with the kernels forced: each shard
    pads its worst-case receive buffer (empty slots and all) into the
    layout once and unpads once — output and every gradient against the
    single shard's."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.telemetry import counters

    ep = 4
    single, sharded = resident_layer(gated, 2), resident_layer(gated, 2, ep)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 128))
    g = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 128))
    params = single.init(jax.random.PRNGKey(2), x[:2])["params"]
    mesh = build_mesh({"ep": ep}, jax.devices()[:ep])
    pspec = jax.tree_util.tree_map_with_path(
        lambda path, leaf: P("ep") if is_expert_param(
            jax.tree_util.keystr(path)) else P(), params)

    def on_mesh(p, xs):
        return jax.jit(shard_map(
            lambda p, xs: sharded.apply({"params": p}, xs), mesh=mesh,
            in_specs=(pspec, P("ep")), out_specs=P("ep"), check_vma=False,
        ))(p, xs)

    loss = lambda fn: lambda p, xs: jnp.sum(fn(p, xs) * g)
    want_out = single.apply({"params": params}, x)
    want = jax.grad(loss(lambda p, xs: single.apply({"params": p}, xs)),
                    argnums=(0, 1))(params, x)
    got_out = on_mesh(params, x)
    # 2 x 16 tokens x 2 a shard: a receive buffer of 4 x 64 rows, padded
    assert counters.get("moe/padded_rows_per_step") == 512
    assert counters.get("moe/padded_resident_layers") == 1
    np.testing.assert_allclose(got_out, want_out, atol=2e-5)
    got = jax.grad(loss(on_mesh), argnums=(0, 1))(params, x)
    flat = lambda tree: {jax.tree_util.keystr(p): v for p, v in
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    for name, w in flat(want).items():
        np.testing.assert_allclose(
            flat(got)[name], w, atol=2e-5 * float(jnp.abs(w).max()),
            err_msg=name)
