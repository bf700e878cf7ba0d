"""Olmo-Hybrid on the normal path: ``TransformerLM`` (linear attention by the
gated delta rule on three layers of four at heads that are no whole lane
tile — 96-lane keys under 192-lane values, a head count that is no multiple
of four —, a write strength of ``2 sigmoid(b)``; softmax attention under a
whole-width q / k norm and no rotation on the fourth; a dense gated MLP; a
block that norms each sub-layer's OUTPUT and nothing in front of it) +
``lm_loss_fn``, against the benchmark's plain float32 reference
(``perfbench/reference/olmo_hybrid.py``, which imports nothing of
``bagua_tpu``); the comparison's refusal of each wrong mechanism; the
rule's own probe; and a ``dp = 4`` step of ``BaguaTrainer`` on four CPU
devices against the ``dp = 1`` step on the same global batch.  Small
widths that keep the shape of the problem, two periods, seeded, CPU.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models.transformer import (
    Block, TransformerConfig, TransformerLM, lm_loss_fn,
)
from bagua_tpu.ops import gated_delta as gd

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from perfbench import cells  # noqa: E402 - the benchmark's loader by file name

ref = cells.load_plugin("reference", "olmo_hybrid")
builder = cells.load_plugin("builders", "olmo_hybrid")

#: the published shapes' kind at a small size: six heads of 96 / 192 (a block
#: of four and a ragged one of two; the k columns start half a 384-lane
#: block in), two periods of the pattern
TINY = {
    "builder": "olmo_hybrid", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]
    + ["linear_attention"] * 3 + ["full_attention"],
    "num_hidden_layers": 8, "linear_num_key_heads": 6,
    "linear_num_value_heads": 6, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6, "rope_theta": None, "vocab_size": 200,
}
#: float32 against float32 on the CPU, both with exact products: what is
#: left is the order of summation.  A missing piece moves logits by 1e-2 to
#: 1 and fails every one of these.
LOGIT_ATOL = 2e-4
LOSS_ATOL = 1e-5
GRAD_RTOL = 1e-3


def olmo_hybrid(**model):
    return builder.make_model(TINY, {"model": {"dtype": "float32", **model}})


def seeded(model, seed=0, batch=4, seq=80):
    """Weights and tokens; 80 positions are a chunk of 64 and a quarter.
    Every scale off its init (ones), so that a norm applied in the wrong
    place shows."""
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, TINY["vocab_size"]))
    params = builder.make_params(model, seed + 1)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    moved = ("scale", "norm", "dt_bias")
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if any(m in jax.tree_util.keystr(path) for m in moved) else leaf
        for (path, leaf), key in zip(leaves, keys)]), tokens


def flat(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def both():
    """Logits, loss and gradients of system and reference, computed once."""
    model = olmo_hybrid()
    hyper = ref.hyperparameters(TINY)
    params, tokens = seeded(model)
    with jax.default_matmul_precision("highest"):
        sys_logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, tokens[:, :-1])
        ref_logits = jax.jit(lambda p, t: ref.logits_fn(p, t, hyper))(
            params, tokens[:, :-1])
        sys_loss, sys_grads = jax.jit(jax.value_and_grad(lm_loss_fn(model)))(
            params, {"tokens": jnp.asarray(tokens)})
        # the reference's own blocks: a sequence a device (two here, each
        # twice), summed
        ref_loss, ref_grads = ref.loss_and_grads(params, tokens, hyper,
                                                 jax.devices()[:2])
    return {"logits": (sys_logits, ref_logits), "loss": (sys_loss, ref_loss),
            "grads": (flat(sys_grads), flat(ref_grads)), "params": params,
            "tokens": tokens, "hyper": hyper}


def test_the_parameter_tree_is_the_architectures(both):
    params = both["params"]
    assert set(params) == {"embed", "final_norm", "lm_head"} | {
        f"block_{i}" for i in range(8)}                # no table of positions
    linear, full = params["block_0"], params["block_3"]
    # one norm BEHIND each sub-layer and none in front
    assert set(linear) == {"linear_attn", "linear_attn_post_norm", "mlp",
                           "mlp_post_norm"}
    assert set(full) == {"attn", "attn_post_norm", "mlp", "mlp_post_norm"}
    mixer = linear["linear_attn"]
    assert mixer["in_proj_qkvz"]["kernel"].shape == (64, 2 * 576 + 2 * 1152)
    assert mixer["in_proj_ba"]["kernel"].shape == (64, 12)
    assert mixer["conv"].shape == (4, 2 * 576 + 1152)
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == (6,)
    assert mixer["norm"].shape == (192,)
    assert mixer["out_proj"]["kernel"].shape == (1152, 64)
    # whole-width q / k norms
    assert full["attn"]["q_norm"]["scale"].shape == (64,)
    assert set(full["mlp"]) == {"wi_gate", "wi_up", "wo"}
    held = sum(x.size for x in jax.tree.leaves(params))
    assert held == builder.parameters(TINY)


def test_logits_agree_with_the_reference(both):
    got, want = both["logits"]
    assert float(jnp.abs(want).max()) > 1
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)


def test_loss_agrees_with_the_reference(both):
    got, want = both["loss"]
    assert abs(float(got) - float(want)) < LOSS_ATOL
    # the blocks of the reference add up to its plain form
    with jax.default_matmul_precision("highest"):
        whole = ref.loss_fn(both["params"], both["tokens"], both["hyper"])
    assert abs(float(whole) - float(want)) < LOSS_ATOL


_LEAVES = sorted(flat(jax.eval_shape(
    lambda: builder.make_params(olmo_hybrid(), 0))))


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_agrees_with_the_reference(both, leaf):
    got, want = both["grads"][0][leaf], both["grads"][1][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a gradient that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=GRAD_RTOL * scale, rtol=0)


# ---------------------------------------------------------------------------
# the comparison refuses a system that lacks a mechanism
# ---------------------------------------------------------------------------

_WRONG = {
    "none": {},
    "beta_is_sigmoid": {"neg_eigval": False},
    "norm_in_front": {"output_norm": False},
    "alpha_is_one": {"decay": False},
    "no_l2_norm": {"l2_norm": False},
    "no_qk_norm": {"qk_norm": False},
    "rotation": {"rope_theta": 500000.0},
}


@pytest.mark.parametrize("fault", list(_WRONG))
def test_the_comparison_tells_each_mechanism_from_its_absence(both, fault):
    """``correct``'s second comparison at small widths and float32: the
    system's first gradient is the sound reference's to rounding, and a
    reference with one mechanism wrong is far from it by the cell's own
    limit on the watched leaves."""
    got = {name: g for name, g in both["grads"][0].items()
           if name.endswith(ref.WATCHED_ENDS)}
    wrong = {**both["hyper"], **_WRONG[fault]}
    with jax.default_matmul_precision("highest"):
        _, grads = ref.loss_and_grads(both["params"], both["tokens"], wrong,
                                      jax.devices()[:1])
    distance = ref.gradient_distance(got, ref.watched(grads))
    assert set(distance) == set(got) and len(distance) == 6 * 10 + 2 * 9
    if fault == "none":
        assert ref.gradients_agree(distance, 2e-3, ())  # every leaf held
        return
    assert not ref.gradients_agree(distance)
    where = {
        "beta_is_sigmoid": "block_0/linear_attn/in_proj_ba/kernel",
        "norm_in_front": "block_0/linear_attn_post_norm/scale",
        "alpha_is_one": "block_0/linear_attn/A_log",
        "no_l2_norm": "block_0/linear_attn/in_proj_qkvz/kernel",
        "no_qk_norm": "block_3/attn/q_norm/scale",
        "rotation": "block_3/attn/k/kernel",
    }[fault]
    # (not finite counts: keys that are not unit vectors under a write
    # strength of 2 blow the state up)
    assert not distance[where] <= ref.GRADIENT_TOLERANCE, (where,
                                                           distance[where])


def test_the_model_with_a_wrong_mechanism_is_refused_too(both):
    """The other way round: the SYSTEM with ``sigmoid(b)`` for ``2
    sigmoid(b)`` against the sound reference; a pre-normed block is another
    tree altogether."""
    sound = olmo_hybrid()
    want = {name: g for name, g in both["grads"][1].items()
            if name.endswith(ref.WATCHED_ENDS)}
    wrong = TransformerLM(dataclasses.replace(sound.cfg,
                                              linear_neg_eigval=False))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lm_loss_fn(wrong)))(
            both["params"], {"tokens": jnp.asarray(both["tokens"])})
    assert not ref.gradients_agree(ref.gradient_distance(ref.watched(got),
                                                         want))
    pre = TransformerLM(dataclasses.replace(sound.cfg, pre_norms=True,
                                            post_norms=False))
    names = set(jax.eval_shape(
        lambda: builder.make_params(pre, 0))["block_0"])
    assert names == {"linear_attn_norm", "linear_attn", "mlp_norm", "mlp"}


def test_the_decay_vectors_are_held_by_the_median_over_the_layers():
    """``A_log`` and ``dt_bias`` (one entry a head: the first block's is its
    one slowest head's sum over the positions, which all but cancels, and
    reads 0.13 to 0.82 on the chip by the seed) are held by the median over
    the layers and each to have a distance; every other leaf by itself; the
    change comparison bounds whatever it does not skip."""
    decay = {f"block_{i}/linear_attn/A_log": d
             for i, d in enumerate((0.8, 0.14, 0.07))}
    read = {**decay, "block_0/linear_attn/conv": 0.2}
    assert ref.gradients_agree(read)
    assert not ref.gradients_agree(read, median_of=())   # each by itself
    assert not ref.gradients_agree(
        {**read, "block_0/linear_attn/A_log": float("inf")})
    # two layers of three past the limit: the median is
    assert not ref.gradients_agree(
        {**read, "block_1/linear_attn/A_log": 0.6})
    assert not ref.gradients_agree({**read, "block_0/linear_attn/conv": 0.6})
    assert not ref.gradients_agree({})
    # one layer alone is its own median
    assert not ref.gradients_agree({"block_0/linear_attn/dt_bias": 0.8})
    assert ref.changes_agree({"block_0/linear_attn/A_log": 0.9,
                              "block_0/mlp/wi_gate/kernel": 0.4})
    assert not ref.changes_agree({"block_0/mlp/wi_gate/kernel": 0.8})


def test_the_gates_projection_is_compared_by_halves_too(both):
    """The ``a`` columns of ``in_proj_ba`` carry the log decay's cotangent a
    position (what ``A_log``'s entry sums): compared by themselves, and far
    from a reference without the decay."""
    name = "block_0/linear_attn/in_proj_ba/kernel"
    got = {name: both["grads"][0][name]}
    halves = ref.with_gate_halves(got)
    assert set(halves) == {name, name + "[b]", name + "[a]"}
    heads = TINY["linear_num_value_heads"]
    np.testing.assert_array_equal(halves[name + "[a]"], got[name][:, heads:])
    want = ref.with_gate_halves({name: both["grads"][1][name]})
    distance = ref.gradient_distance(halves, want)
    assert max(distance.values()) < 2e-3
    with jax.default_matmul_precision("highest"):
        _, grads = ref.loss_and_grads(
            both["params"], both["tokens"],
            {**both["hyper"], "decay": False}, jax.devices()[:1])
    wrong = ref.gradient_distance(halves, ref.with_gate_halves(
        {name: flat(grads)[name]}))
    assert wrong[name + "[a]"] > 0.99       # no decay: no gradient there


def test_a_block_needs_a_norm():
    cfg = TransformerConfig(vocab_size=50, d_model=32, n_heads=2, n_layers=1,
                            d_ff=64, pre_norms=False, post_norms=False)
    with pytest.raises(ValueError, match="pre_norms, post_norms or both"):
        Block(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))


def test_the_output_norm_block_by_hand():
    """``x + N(Mixer(x))``, ``h + N(MLP(h))``: the mixer reads the block's
    input as it comes."""
    model = olmo_hybrid()
    params, _ = seeded(model)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 64))
    p = params["block_3"]
    hyper = ref.hyperparameters(TINY)
    with jax.default_matmul_precision("highest"):
        got = Block(model.cfg, layer=3).apply({"params": p}, x)
        mixed = ref.full_attention(x, p["attn"], hyper)
        h = x + ref.rms_norm(mixed, p["attn_post_norm"]["scale"], 1e-6)
        want = h + ref.rms_norm(ref.mlp(h, p["mlp"]),
                                p["mlp_post_norm"]["scale"], 1e-6)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# the rule by itself: its state's and its solve's precision
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def probe():
    hyper = ref.hyperparameters(TINY)
    args = ref.rule_probe(2 ** 31 + 7, 1024, hyper)
    return hyper, args, ref.rule_by_scan(*args)


@pytest.mark.parametrize("path", ["jnp", "kernel"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_rule_holds_the_probe(probe, path, dtype):
    """Keys that are nearly one vector, hardly any decay, write strengths
    near 2 on the even heads and near 0.002 on the odd ones: the chunked
    rule and its chunked VJP — ``jax.numpy`` chunks and the kernels,
    interpreted, at 96 / 192-lane heads in a ragged block — stay within the
    cell's limits of the per-position scan and the scan's VJP, operands in
    float32 and in the model's bfloat16."""
    _, (q, k, v, g, beta, do), want = probe
    assert float(beta[..., ::2].max()) > 1.99
    assert float(jnp.median(beta[..., ::2])) > 1.9
    assert float(beta[..., 1::2].max()) < 0.02
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    got = ref.rule_with_cotangents(
        lambda *a: gd.gated_delta_rule(*a, force=path == "kernel",
                                       interpret=True), q, k, v, g, beta, do)
    distance = ref.rule_distance(got, want)
    assert set(distance) == {f"{name}/{kind}" for name in ref.RULE_QUANTITIES
                             for kind in ("weak", "strong")}
    assert ref.rule_agrees(distance), distance
    if dtype == "float32":
        assert max(distance.values()) < 1e-4, distance
    else:
        # the limits' room: three times and more over the readings
        assert distance["o/weak"] < ref.RULE_TOLERANCE["o/weak"] / 3
        assert distance["o/strong"] < ref.RULE_TOLERANCE["o/strong"] / 1.5


def test_the_probe_refuses_a_bfloat16_state(probe):
    """What the three comparisons of the step cannot see at a fresh model's
    decays: the scan with its state and decay kept in bfloat16 drops the
    weak heads' increments and is past their limit, five times and more, on
    the output; the backward pass reads the rounded states, and the weak
    heads' cotangents are past theirs."""
    _, args, want = probe
    rounded = ref.rule_by_scan(*args, scan_dtype="bfloat16")
    distance = ref.rule_distance(rounded, want)
    assert not ref.rule_agrees(distance), distance
    assert distance["o/weak"] > 5 * ref.RULE_TOLERANCE["o/weak"]
    backward = {k: v for k, v in ref.RULE_TOLERANCE.items()
                if not k.startswith("o/")}
    assert backward and not ref.rule_agrees(distance, backward), distance


def test_the_builders_fourth_comparison_reads_the_systems_rule(probe):
    hyper, args, want = probe
    got = builder.system_rule(ref, 2 ** 31 + 7, 1024, hyper, jnp.bfloat16)
    assert set(got) == set(ref.RULE_QUANTITIES)
    assert got["o"].dtype == jnp.bfloat16 and got["o"].shape == want["o"].shape
    assert got["dg"].dtype == jnp.float32
    assert ref.rule_agrees(ref.rule_distance(got, want))
