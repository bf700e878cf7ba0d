"""SmallThinker on the normal path: ``TransformerLM`` (grouped key / value
heads at an explicit head width, a causal window with RoPE on three layers
of four and full attention without positions on the fourth, the router
reading the block's input) + ``MoEMLP`` (ReLU-gated experts, dropless top-k
with softmax over the winners, one expert-parallel rank's share) +
``lm_loss_fn``, against the benchmark's plain float32 reference
(``perfbench/reference/smallthinker.py``, which imports nothing of
``bagua_tpu``) and against hand-rolled forms of each new piece.  Tiny
widths, seeded, CPU.
"""

import importlib.util
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.model_parallel.moe.layer import MoEMLP
from internal import row_kernels
from bagua_tpu.models.transformer import (
    Attention, Block, TransformerConfig, TransformerLM, lm_loss_fn,
    rope_rotate,
)


def _reference():
    path = (Path(__file__).resolve().parents[1] / "perfbench" / "reference"
            / "smallthinker.py")
    spec = importlib.util.spec_from_file_location("smallthinker_reference",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()

D, HEADS, KV_HEADS, HEAD_DIM, FF = 48, 4, 2, 16, 24
EXPERTS, K, WINDOW, THETA, EPS = 8, 3, 5, 1.5e6, 1e-6
PATTERN = (0, 1, 1, 1)
#: float32 against float32 on the CPU, both with exact products: what is
#: left is the order of summation.  A missing piece (a rotated full layer,
#: an unwindowed window layer, the router on the wrong input, SiLU for
#: ReLU, a dropped winner, a wrong key / value head) moves logits by 1e-2
#: to 1 and fails every one of these.
LOGIT_ATOL = 3e-5
LOSS_ATOL = 3e-6
GRAD_RTOL = 3e-5


def smallthinker(ep_size=1, ep_rank=0, *, layers=4, dtype=jnp.float32):
    cfg = TransformerConfig(
        vocab_size=97, d_model=D, n_heads=HEADS, n_kv_heads=KV_HEADS,
        d_head=HEAD_DIM, n_layers=layers, d_ff=FF, max_seq_len=32,
        dtype=dtype, rope_theta=THETA, rope_layers=PATTERN, window=WINDOW,
        window_layers=PATTERN, route_before_attention=True, norm_eps=EPS)
    moe = lambda: MoEMLP(
        n_experts=EXPERTS, d_ff=FF, k=K, ep_size=ep_size, ep_rank=ep_rank,
        dropless=True, gated=True, activation="relu", dtype=dtype, name="mlp")
    model = TransformerLM(cfg, mlp_factory=lambda _i: moe)
    hyper = {"layers": layers, "experts_per_token": K,
             "first_expert": ep_rank * (EXPERTS // ep_size),
             "rope_theta": THETA, "rope_layout": PATTERN,
             "window_layout": PATTERN, "window": WINDOW,
             "rms_norm_eps": EPS, "activation": jax.nn.relu}
    return model, hyper


def seeded(model, seed=0, batch=3, seq=16):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1),
                                0, model.cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(seed + 1), tokens[:1, :8])["params"]
    # norm scales off their all-ones init, so that a norm applied in the
    # wrong place shows
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if "scale" in jax.tree_util.keystr(path) else leaf
        for (path, leaf), key in zip(leaves, keys)])
    return params, tokens


# ---------------------------------------------------------------------------
# system against the plain reference: the whole model, and a rank's share
# ---------------------------------------------------------------------------

#: (expert-parallel degree, rank): all experts here, and ranks of four
SHARES = [(1, 0), (4, 0), (4, 3)]


@pytest.fixture(scope="module", params=SHARES,
                ids=lambda s: f"rank{s[1]}of{s[0]}")
def both(request):
    """Logits, loss and gradients of system and reference, computed once."""
    model, hyper = smallthinker(*request.param)
    params, tokens = seeded(model)
    with jax.default_matmul_precision("highest"):
        sys_logits = model.apply({"params": params}, tokens[:, :-1])
        ref_logits = ref.logits_fn(params, tokens[:, :-1], hyper)
        sys_loss, sys_grads = jax.value_and_grad(lm_loss_fn(model))(
            params, {"tokens": tokens})
        ref_loss, ref_grads = jax.value_and_grad(ref.loss_fn)(
            params, tokens, hyper)
    return {"logits": (sys_logits, ref_logits), "loss": (sys_loss, ref_loss),
            "grads": (sys_grads, ref_grads), "params": params,
            "share": request.param}


def test_the_parameter_tree_is_the_architectures(both):
    params, (ep_size, _) = both["params"], both["share"]
    assert "pos_embed" not in params
    attn = params["block_0"]["attn"]
    assert set(attn) == {"q", "k", "v", "o"}
    assert attn["q"]["kernel"].shape == (D, HEADS, HEAD_DIM)
    assert attn["k"]["kernel"].shape == (D, KV_HEADS, HEAD_DIM)
    assert attn["v"]["kernel"].shape == (D, KV_HEADS, HEAD_DIM)
    assert attn["o"]["kernel"].shape == (HEADS, HEAD_DIM, D)
    mlp = params["block_0"]["mlp"]
    assert mlp["router"]["kernel"].shape == (D, EXPERTS)   # all of them
    assert mlp["expert_wg"].shape == (EXPERTS // ep_size, D, FF)


def test_logits_agree_with_the_reference(both):
    got, want = both["logits"]
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)


def test_loss_agrees_with_the_reference(both):
    got, want = both["loss"]
    assert abs(float(got) - float(want)) <= LOSS_ATOL


_LEAVES = [jax.tree_util.keystr(path) for path, _ in
           jax.tree_util.tree_flatten_with_path(jax.eval_shape(
               lambda: seeded(smallthinker()[0])[0]))[0]
           if "block_2" not in jax.tree_util.keystr(path)
           and "block_3" not in jax.tree_util.keystr(path)]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_agrees_with_the_reference(both, leaf):
    """Both kinds of layer (``block_0`` full, ``block_1`` windowed) and the
    leaves around them, leaf by leaf."""
    flat = lambda tree: {jax.tree_util.keystr(p): v for p, v in
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(both["grads"][0])[leaf], flat(both["grads"][1])[leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a gradient that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=GRAD_RTOL * scale, rtol=0)


@pytest.mark.parametrize("fault", ["five_of_six", "full_on_a_window_layer",
                                   "rope_on_the_full_layer", "silu_for_relu",
                                   "router_after_attention"])
def test_the_reference_tells_each_mechanism_from_its_absence(fault):
    """What the chip's loss comparison must refuse, at tiny widths: each
    departure moves the reference's own logits by far more than the
    system's distance from it."""
    model, hyper = smallthinker()
    params, tokens = seeded(model)
    wrong = dict(hyper)
    if fault == "five_of_six":
        wrong["experts_per_token"] = K - 1
    elif fault == "full_on_a_window_layer":
        wrong["window_layout"] = (0, 0, 1, 1)
    elif fault == "rope_on_the_full_layer":
        wrong["rope_layout"] = (1, 1, 1, 1)
    elif fault == "silu_for_relu":
        wrong["activation"] = jax.nn.silu
    with jax.default_matmul_precision("highest"):
        want = ref.logits_fn(params, tokens[:, :-1], hyper)
        if fault == "router_after_attention":
            after = TransformerLM(
                model.cfg.__class__(**{**model.cfg.__dict__,
                                       "route_before_attention": False}),
                mlp_factory=model.mlp_factory)
            got = after.apply({"params": params}, tokens[:, :-1])
        else:
            got = ref.logits_fn(params, tokens[:, :-1], wrong)
    assert float(jnp.abs(got - want).max()) > 100 * LOGIT_ATOL


# ---------------------------------------------------------------------------
# the benchmark's second comparison: the first gradient, leaf by leaf
# ---------------------------------------------------------------------------

_WRONG = {
    "none": {},
    "five_of_six": {"experts_per_token": K - 1},
    "full_on_a_window_layer": {"window_layout": (0, 0, 1, 1)},
    "rope_on_the_full_layer": {"rope_layout": (1, 1, 1, 1)},
    "silu_for_relu": {"activation": jax.nn.silu},
}


@pytest.fixture(scope="module")
def system_gradient():
    model, hyper = smallthinker()
    params, tokens = seeded(model)
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(lm_loss_fn(model)))(params,
                                                     {"tokens": tokens})
    return params, tokens, hyper, ref.watched(grads)


@pytest.mark.parametrize("fault", list(_WRONG))
def test_the_first_gradient_tells_each_mechanism_from_its_absence(
        system_gradient, fault):
    """``correct``'s second comparison at tiny widths and float32: the
    system's gradient is the sound reference's to rounding, and a reference
    with one mechanism left out is far from it on the leaves it watches —
    the two attention faults too, which three replayed losses let pass."""
    params, tokens, hyper, got = system_gradient
    wrong = {**hyper, **_WRONG[fault]}
    with jax.default_matmul_precision("highest"):
        want = ref.watched(jax.jit(
            lambda p, t: jax.grad(ref.loss_fn)(p, t, wrong))(params, tokens))
    distance = {name: float(d) for name, d in
                ref.gradient_distance(got, want).items()}
    assert set(distance) == set(got)
    if fault == "none":
        assert max(distance.values()) < 1e-4
        assert ref.gradients_agree(distance, 1e-4)
    else:
        assert max(distance.values()) > 0.1
        assert not ref.gradients_agree(distance, 0.1)
    if fault == "full_on_a_window_layer":   # layer 1 is the one unwindowed
        assert distance["block_1/attn/q/kernel"] > 0.1
    if fault == "rope_on_the_full_layer":
        assert distance["block_0/attn/k/kernel"] > 0.1


def test_the_watched_leaves_are_the_attention_matrices(system_gradient):
    names = set(system_gradient[3])
    assert names == {f"block_{i}/attn/{leaf}/kernel" for i in range(4)
                     for leaf in "qkvo"}


@pytest.mark.parametrize("distances,agrees", [
    ({"a": 0.01, "b": 0.05}, True), ({"a": 0.01, "b": 0.0501}, False),
    ({"a": float("nan")}, False), ({"a": float("inf")}, False), ({}, False)])
def test_gradients_agree_within_the_limit(distances, agrees):
    assert ref.gradients_agree(distances, 0.05) is agrees


def test_the_replay_hands_over_its_first_gradient(system_gradient):
    """The mean over the micro-batches, the watched leaves only, once."""
    params, tokens, hyper, _ = system_gradient
    with jax.default_matmul_precision("highest"):
        want = ref.watched(jax.jit(
            lambda p, t: jax.grad(ref.loss_fn)(p, t, hyper))(params, tokens))
    seen = []
    losses = ref.replay_losses(
        jax.tree.map(jnp.copy, params), tokens, 2,
        {"name": "adamw", "kwargs": {"learning_rate": 1e-3}}, 1, hyper,
        first_gradient=seen.append)
    assert len(losses) == 2 and losses[1] < losses[0]
    (got,) = seen
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=0,
                                   atol=1e-5 * float(jnp.abs(want[name]).max()))


# ---------------------------------------------------------------------------
# the share: the ranks' parts of one layer add up to the whole layer
# ---------------------------------------------------------------------------


def _whole_layer(seed=3, tokens=40):
    """One expert layer's inputs and GLOBAL tables."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "m": jax.random.normal(keys[0], (2, tokens // 2, D)),
        "route_x": jax.random.normal(keys[1], (2, tokens // 2, D)),
        "router": jax.random.normal(keys[2], (D, EXPERTS)) / math.sqrt(D),
        "wi": jax.random.normal(keys[3], (EXPERTS, D, FF)) / math.sqrt(D),
        "wg": jax.random.normal(keys[4], (EXPERTS, D, FF)) / math.sqrt(D),
        "wo": jax.random.normal(keys[5], (EXPERTS, FF, D)) / math.sqrt(FF),
    }


def _sdar_reference():
    path = (Path(__file__).resolve().parents[1] / "perfbench" / "reference"
            / "sdar.py")
    spec = importlib.util.spec_from_file_location("sdar_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


#: the expert settings of the two architectures that run one rank's share:
#: ``MoEMLP``'s options, whether the router reads another input than the
#: experts, and the plain reference's expert layer over ALL the experts
EXPERT_SETTINGS = {
    # ReLU-gated, softmax over the winners, the router on the block's input
    "smallthinker": dict(
        options=dict(activation="relu"), routes_apart=True,
        whole=lambda layer, tables: ref.moe(
            layer["m"].reshape(-1, D), layer["route_x"].reshape(-1, D),
            tables, {"experts_per_token": K, "first_expert": 0,
                     "activation": jax.nn.relu})),
    # SiLU-gated, softmax over all experts then the winners renormalised
    "sdar": dict(
        options=dict(activation="silu", norm_topk_prob=True),
        routes_apart=False,
        whole=lambda layer, tables: _sdar_reference().moe(
            layer["m"].reshape(-1, D), tables,
            {"experts_per_token": K, "first_expert": 0})),
}


def _share_of(layer, ep_size, rank, settings):
    """Rank ``rank``'s part of the layer's result, by ``MoEMLP`` holding
    its slice of the tables."""
    n_local = EXPERTS // ep_size
    held = slice(rank * n_local, (rank + 1) * n_local)
    moe = MoEMLP(n_experts=EXPERTS, d_ff=FF, k=K, ep_size=ep_size,
                 ep_rank=rank, dropless=True, gated=True,
                 dtype=jnp.float32, **settings["options"])
    params = {"router": {"kernel": layer["router"]},
              "expert_wi": layer["wi"][held], "expert_wg": layer["wg"][held],
              "expert_wo": layer["wo"][held]}
    route = {"route_x": layer["route_x"]} if settings["routes_apart"] else {}
    return moe.apply({"params": params}, layer["m"], **route)


@pytest.mark.parametrize("family", list(EXPERT_SETTINGS))
@pytest.mark.parametrize("ep_size", [2, 4, 8])
def test_the_ranks_shares_add_up_to_the_uncut_reference(ep_size, family):
    """Guide section 4: the parts of the result that all the shares give add
    up to what the uncut reference gives for the whole layer."""
    layer, settings = _whole_layer(), EXPERT_SETTINGS[family]
    flat = lambda t: t.reshape(-1, D)
    with jax.default_matmul_precision("highest"):
        whole = settings["whole"](layer, {
            "router": {"kernel": layer["router"]}, "expert_wi": layer["wi"],
            "expert_wg": layer["wg"], "expert_wo": layer["wo"]})
        shares = [flat(_share_of(layer, ep_size, r, settings))
                  for r in range(ep_size)]
    assert float(jnp.abs(whole).max()) > 0.1
    # no share is the whole, and none is nothing
    for share in shares:
        assert 1e-3 < float(jnp.abs(share).max())
        assert float(jnp.abs(share - whole).max()) > 1e-3
    np.testing.assert_allclose(sum(shares), whole, atol=2e-5, rtol=0)


@pytest.fixture(scope="module")
def row_kernel_paths():
    """Rank 1 of four's share of a ReLU-gated layer, six experts a token,
    at the kernels' lane width: three quarters of a token's pairs enter no
    group, as in the cell."""
    return row_kernels.both_paths(MoEMLP(n_experts=16, d_ff=128, k=6, ep_size=4,
                             ep_rank=1, dropless=True, gated=True,
                             activation="relu", dtype=jnp.float32))


@pytest.mark.parametrize("quantity", row_kernels.QUANTITIES)
def test_a_share_on_the_row_kernels_is_the_fallbacks_share(
        row_kernel_paths, quantity):
    row_kernels.assert_the_same_layer(*row_kernel_paths, quantity)


def test_a_share_outside_the_axis_is_what_init_sees():
    """``model.init`` outside any mesh runs rank 0's share: the local
    tables' shapes, and no fold of foreign expert ids onto them."""
    moe = MoEMLP(n_experts=EXPERTS, d_ff=FF, k=K, ep_size=4, dropless=True,
                 gated=True, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, D))
    params = moe.init(jax.random.PRNGKey(1), x)["params"]
    assert params["expert_wi"].shape == (EXPERTS // 4, D, FF)
    assert params["router"]["kernel"].shape == (D, EXPERTS)


@pytest.mark.parametrize("option", ["activation", "ep_rank"])
def test_the_capacity_path_refuses_the_dropless_options(option):
    kw = {"activation": {"activation": "relu"},
          "ep_rank": {"ep_size": 2, "ep_rank": 1}}[option]
    moe = MoEMLP(n_experts=4, d_ff=FF, k=2, **kw)
    with pytest.raises(ValueError, match="dropless"):
        moe.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, D)))


# ---------------------------------------------------------------------------
# each new piece against a hand-rolled form
# ---------------------------------------------------------------------------


def test_relu_gated_experts_routed_from_another_input_by_hand():
    """``down(relu(gate m) * up m)`` weighted by the softmax over the
    winners of ``route_x``'s logits, written out with loops."""
    layer = _whole_layer(seed=5, tokens=12)
    got = _share_of(layer, 1, 0, EXPERT_SETTINGS["smallthinker"]).reshape(-1, D)
    m = np.asarray(layer["m"], np.float64).reshape(-1, D)
    r = np.asarray(layer["route_x"], np.float64).reshape(-1, D)
    logits = r @ np.asarray(layer["router"], np.float64)
    want = np.zeros_like(m)
    for t in range(m.shape[0]):
        winners = np.argsort(-logits[t])[:K]
        w = np.exp(logits[t, winners] - logits[t, winners].max())
        w /= w.sum()
        for e, weight in zip(winners, w):
            gate = np.maximum(m[t] @ np.asarray(layer["wg"][e], np.float64), 0)
            up = m[t] @ np.asarray(layer["wi"][e], np.float64)
            want[t] += weight * ((gate * up)
                                 @ np.asarray(layer["wo"][e], np.float64))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # routed from the experts' own input instead, the result is another
    other = MoEMLP(n_experts=EXPERTS, d_ff=FF, k=K, dropless=True, gated=True,
                   activation="relu", dtype=jnp.float32).apply(
        {"params": {"router": {"kernel": layer["router"]},
                    "expert_wi": layer["wi"], "expert_wg": layer["wg"],
                    "expert_wo": layer["wo"]}}, layer["m"]).reshape(-1, D)
    assert float(jnp.abs(other - want).max()) > 1e-2


def test_the_block_hands_the_router_its_own_input():
    """``route_before_attention``: the router's logits are of the block's
    input, whatever attention adds to the stream."""
    model, _ = smallthinker(layers=1)
    params, tokens = seeded(model)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, D))
    block = Block(model.cfg, None, model.mlp_factory(0), 0)
    p = params["block_0"]
    seen = {}

    class Spy(MoEMLP):
        def __call__(self, m, route_x=None):
            seen["route_x"], seen["m"] = route_x, m
            return super().__call__(m, route_x=route_x)

    spy = Block(model.cfg, None, lambda: Spy(
        n_experts=EXPERTS, d_ff=FF, k=K, dropless=True, gated=True,
        activation="relu", dtype=jnp.float32, name="mlp"), 0)
    np.testing.assert_array_equal(spy.apply({"params": p}, x),
                                  block.apply({"params": p}, x))
    np.testing.assert_array_equal(seen["route_x"], x)
    assert float(jnp.abs(seen["m"] - x).max()) > 0.1
    with pytest.raises(ValueError, match="route_before_attention"):
        Block(model.cfg).init(jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("layer,window,rotary", [
    (0, None, False), (1, WINDOW, True), (3, WINDOW, True), (4, None, False),
    (6, WINDOW, True)])
def test_the_layer_pattern_names_each_layers_kind(layer, window, rotary):
    cfg = smallthinker()[0].cfg
    assert cfg.layer_window(layer) == window
    assert cfg.layer_rotary(layer) is rotary
    # without patterns every layer is of the one kind the model has
    plain = TransformerConfig(rope_theta=1e4, window=7)
    assert plain.layer_window(layer) == 7 and plain.layer_rotary(layer)
    assert TransformerConfig().layer_window(layer) is None
    assert not TransformerConfig().layer_rotary(layer)


def test_the_pipelined_stack_refuses_layers_of_several_kinds():
    """It scans ONE block over its layers: with a pattern every layer would
    silently be the pattern's entry 0."""
    from bagua_tpu.parallel.pipeline import PipelinedTransformerLM

    tokens = jnp.zeros((2, 9), jnp.int32)
    dense = dict(vocab_size=97, d_model=D, n_heads=HEADS, n_layers=4,
                 d_ff=FF, max_seq_len=32, dtype=jnp.float32)
    mixed = TransformerConfig(**dense, rope_theta=THETA, rope_layers=PATTERN,
                              window=WINDOW, window_layers=PATTERN)
    with pytest.raises(NotImplementedError, match="2 kinds"):
        PipelinedTransformerLM(mixed, pp_size=1).init(
            jax.random.PRNGKey(0), tokens)
    # one kind for every layer is what it has always built
    uniform = TransformerConfig(**dense, rope_theta=THETA, window=WINDOW,
                                window_layers=(1,), rope_layers=(1,))
    PipelinedTransformerLM(uniform, pp_size=1).init(jax.random.PRNGKey(0),
                                                    tokens)


@pytest.mark.parametrize("drop_in", ["ring", "ulysses"])
def test_the_sequence_parallel_drop_ins_refuse_grouped_heads(drop_in):
    """They index k / v by the query's head: fewer key / value heads must
    not reach them unseen."""
    from bagua_tpu.parallel.ring_attention import make_ring_attention
    from bagua_tpu.parallel.ulysses import make_ulysses_attention

    attn_fn = {"ring": make_ring_attention,
               "ulysses": make_ulysses_attention}[drop_in](2)
    q = jnp.zeros((1, 8, HEADS, HEAD_DIM))
    kv = jnp.zeros((1, 8, KV_HEADS, HEAD_DIM))
    with pytest.raises(NotImplementedError, match="n_kv_heads"):
        attn_fn(q, kv, kv, jnp.float32)
    assert attn_fn(q, q, q, jnp.float32).shape == q.shape


def _attention_by_hand(x, p, window, rotary):
    """Grouped-head causal attention with loops over heads and queries."""
    b, s, _ = x.shape
    project = lambda n: jnp.einsum("bsd,dhe->bshe", x, p[n]["kernel"])
    q, k, v = project("q"), project("k"), project("v")
    if rotary:
        q, k = rope_rotate(q, THETA), rope_rotate(k, THETA)
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    o = np.zeros((b, s, HEADS, HEAD_DIM))
    group = HEADS // KV_HEADS
    for h in range(HEADS):
        for i in range(s):
            first = 0 if window is None else max(0, i - window + 1)
            keys = k[:, first:i + 1, h // group]              # [b, n, e]
            scores = np.einsum("be,bne->bn", q[:, i, h], keys) / math.sqrt(
                HEAD_DIM)
            w = np.exp(scores - scores.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            o[:, i, h] = np.einsum("bn,bne->be", w,
                                   v[:, first:i + 1, h // group])
    return np.einsum("bshe,hed->bsd", o, np.asarray(p["o"]["kernel"],
                                                    np.float64))


@pytest.mark.parametrize("window,rotary", [(None, False), (WINDOW, True),
                                           (None, True), (1, False),
                                           (16, True), (40, False)])
def test_attention_of_each_kind_by_hand(window, rotary):
    """Key / value head ``i // group``, the window counting the query's own
    position (1: itself alone; as long as the sequence or longer: plain
    causal), rotation only where the layer rotates."""
    cfg = smallthinker()[0].cfg
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, D))
    attn = Attention(cfg, None, window, rotary)
    params = attn.init(jax.random.PRNGKey(5), x)["params"]
    with jax.default_matmul_precision("highest"):
        got = attn.apply({"params": params}, x)
    np.testing.assert_allclose(got, _attention_by_hand(x, params, window,
                                                       rotary),
                               atol=2e-5, rtol=0)


# ---------------------------------------------------------------------------
# the defaults are the model of before
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["bert", "gpt2_remat", "olmoe"])
def test_defaults_leave_the_older_models_as_they_were(family):
    """The new fields at their defaults, and the same fields spelled out at
    the values the defaults stand for, build one model: the same parameter
    tree, the same jaxpr, the same bits out."""
    kw = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
              max_seq_len=16, dtype=jnp.float32)
    mlp_factory = None
    if family == "gpt2_remat":
        kw.update(remat=True, remat_policy="dots_no_batch")
    if family == "olmoe":
        kw.update(rope_theta=1e4, qk_norm=True, norm_eps=1e-5)
        mlp_factory = lambda _i: (lambda: MoEMLP(
            n_experts=4, d_ff=16, k=2, dropless=True, gated=True,
            norm_topk_prob=False, balance_over_topk=True, dtype=jnp.float32,
            name="mlp"))
    spelled = dict(n_kv_heads=4, d_head=8, window=None, window_layers=None,
                   rope_layers=None, route_before_attention=False)
    default = TransformerLM(TransformerConfig(**kw), mlp_factory=mlp_factory)
    explicit = TransformerLM(TransformerConfig(**kw, **spelled),
                             mlp_factory=mlp_factory)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    params = default.init(jax.random.PRNGKey(1), tokens)["params"]
    other = explicit.init(jax.random.PRNGKey(1), tokens)["params"]
    assert (jax.tree.structure(params) == jax.tree.structure(other))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(other)):
        np.testing.assert_array_equal(a, b)
    expected = {"q", "k", "v", "o"} | ({"q_norm", "k_norm"}
                                      if family == "olmoe" else set())
    assert set(params["block_0"]["attn"]) == expected
    assert params["block_0"]["attn"]["k"]["kernel"].shape == (32, 4, 8)
    assert ("pos_embed" in params) == (family != "olmoe")
    # (a remat policy prints as a function object at its address)
    run = lambda m: re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
        lambda p, t: m.apply({"params": p}, t))(params, tokens)))
    assert run(default) == run(explicit)
    np.testing.assert_array_equal(
        default.apply({"params": params}, tokens),
        explicit.apply({"params": params}, tokens))
