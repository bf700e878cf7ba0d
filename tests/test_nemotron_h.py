"""Nemotron-H on the normal path: ``TransformerLM`` with ONE sub-layer a block
under a pattern of kinds (``layer_kinds``: Mamba-2 state-space mixers,
softmax attention without positions, expert layers) + ``MoEMLP`` (a sigmoid
router whose bias enters the choice alone, the winners renormalised and
scaled, ungated ReLU^2 experts, one expert-parallel rank's share, an ungated
shared expert) + ``lm_loss_fn``, against the benchmark's plain float32
reference (``perfbench/reference/nemotron_h.py``, which imports nothing of
``bagua_tpu``), the chunked ``ssd_scan`` (the kernels in interpret mode and
the ``jax.numpy`` chunks) against the per-token scan, and each new piece
against a hand-rolled form.  Tiny widths, seeded, CPU.
"""

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.model_parallel.moe.gating import topk_routing
from bagua_tpu.model_parallel.moe.layer import MoEMLP
from bagua_tpu.models.state_space import (
    Mamba2, STEP_FLOOR, STEP_RANGE, conv_bias_silu, gated_group_norm,
)
from bagua_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss_fn,
)
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.ops import ssd
from bagua_tpu.parallel.mesh import build_mesh
from bagua_tpu.telemetry import counters

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from perfbench import cells  # noqa: E402 - the benchmark's loader by file name

ref = cells.load_plugin("reference", "nemotron_h")

D, HEADS, KV_HEADS, HEAD_DIM = 48, 4, 2, 16
SSM_HEADS, SSM_DIM, GROUPS, STATE, TAPS, CHUNK = 4, 8, 2, 16, 4, 16
FF, SHARED_FF, EXPERTS, K, SCALE, EPS, THETA = 24, 40, 16, 3, 2.5, 1e-5, 1e4
PATTERN = "MEM*E"
#: float32 against float32 on the CPU, both with exact products: what is
#: left is the order of summation (the chunked form sums a chunk's 16
#: positions at once where the scan takes them one by one).  A missing
#: piece moves logits by 1e-2 to 1 and fails every one of these.
LOGIT_ATOL = 2e-4
LOSS_ATOL = 1e-5
GRAD_RTOL = 5e-4


def nemotron(ep_size=1, ep_rank=0, *, pattern=PATTERN, dtype=jnp.float32,
             moe_overrides=None, **overrides):
    """The model and the reference's settings for it: five blocks, every
    kind, both orders of neighbours."""
    kinds = tuple(ref.KINDS[letter] for letter in pattern)
    cfg = TransformerConfig(**{**dict(
        vocab_size=97, d_model=D, n_heads=HEADS, n_kv_heads=KV_HEADS,
        d_head=HEAD_DIM, n_layers=len(kinds), d_ff=FF, max_seq_len=128,
        dtype=dtype, rope_theta=THETA, rope_layers=(0,), norm_eps=EPS,
        layer_kinds=kinds, ssm_heads=SSM_HEADS, ssm_head_dim=SSM_DIM,
        ssm_groups=GROUPS, ssm_state=STATE, ssm_conv=TAPS, ssm_chunk=CHUNK),
        **overrides})
    moe = lambda: MoEMLP(**{**dict(
        n_experts=EXPERTS, d_ff=FF, k=K, ep_size=ep_size, ep_rank=ep_rank,
        dropless=True, gated=False, activation="relu2", norm_topk_prob=True,
        router_score="sigmoid", score_bias=True, score_bias_std=0.05,
        routed_scale=SCALE, shared_d_ff=SHARED_FF, dtype=dtype, name="mlp"),
        **(moe_overrides or {})})
    model = TransformerLM(cfg, mlp_factory=lambda _i: moe)
    hyper = {
        "pattern": pattern, "ssm_heads": SSM_HEADS, "ssm_head_dim": SSM_DIM,
        "ssm_groups": GROUPS, "ssm_state": STATE, "norm_groups": GROUPS,
        "experts_per_token": K,
        "first_expert": ep_rank * (EXPERTS // ep_size),
        "routed_scale": SCALE, "renormalise": True, "activation": "relu2",
        "norm_eps": EPS, "rope_theta": THETA, "router_score": "sigmoid",
        "bias_in_weights": False, "softplus": True, "decay": True,
        "skip": True, "gate_first": True, "head_group": "blocked",
        "conv_bias": True, "rotate": False, "shared": True, "sublayers": 1,
        "scan_dtype": "float32"}
    return model, hyper


def seeded(model, seed=0, batch=2, seq=40):
    """Weights and tokens; 40 positions are two chunks of 16 and a half."""
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1),
                                0, model.cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(seed + 1), tokens[:1, :8])["params"]
    # every scale and skip off its init (ones), so that a norm or a gate
    # applied in the wrong place, or over the wrong lanes, shows; the step
    # sizes up from the family's 1e-3 .. 0.1, so that 40 positions decay
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    moved = ("scale", "norm", "['D']")

    def move(path, leaf, key):
        name = jax.tree_util.keystr(path)
        if "dt_bias" in name:
            return leaf + 4.0
        if any(m in name for m in moved):
            return leaf + 0.2 * jax.random.normal(key, leaf.shape)
        return leaf

    return jax.tree_util.tree_unflatten(tree, [
        move(path, leaf, key) for (path, leaf), key in zip(leaves, keys)
    ]), tokens


def flat(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


# ---------------------------------------------------------------------------
# system against the plain reference: the whole model, and a rank's share
# ---------------------------------------------------------------------------

SHARES = [(1, 0), (4, 1)]


@pytest.fixture(scope="module", params=SHARES,
                ids=lambda s: f"rank{s[1]}of{s[0]}")
def both(request):
    """Logits, loss and gradients of system and reference, computed once."""
    model, hyper = nemotron(*request.param)
    params, tokens = seeded(model)
    with jax.default_matmul_precision("highest"):
        sys_logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, tokens[:, :-1])
        ref_logits = jax.jit(lambda p, t: ref.logits_fn(p, t, hyper))(
            params, tokens[:, :-1])
        sys_loss, sys_grads = jax.jit(jax.value_and_grad(lm_loss_fn(model)))(
            params, {"tokens": tokens})
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, t: ref.loss_fn(p, t, hyper)))(params, tokens)
    return {"logits": (sys_logits, ref_logits), "loss": (sys_loss, ref_loss),
            "grads": (sys_grads, ref_grads), "params": params,
            "share": request.param}


def test_the_parameter_tree_is_the_architectures(both):
    params, (ep_size, _) = both["params"], both["share"]
    assert "pos_embed" not in params
    # ONE norm and ONE sub-layer a block
    assert set(params["block_0"]) == {"ssm_norm", "ssm"}
    assert set(params["block_1"]) == {"mlp_norm", "mlp"}
    assert set(params["block_3"]) == {"attn_norm", "attn"}
    mixer = params["block_0"]["ssm"]
    inner, maps = SSM_HEADS * SSM_DIM, GROUPS * STATE
    assert mixer["in_proj"].shape == (D, 2 * inner + 2 * maps + SSM_HEADS)
    assert mixer["conv"].shape == (TAPS, inner + 2 * maps)
    assert mixer["conv_bias"].shape == (inner + 2 * maps,)
    assert (mixer["A_log"].shape == mixer["dt_bias"].shape
            == mixer["D"].shape == (SSM_HEADS,))
    assert mixer["norm"].shape == (inner,)
    assert mixer["out_proj"]["kernel"].shape == (inner, D)
    attn = params["block_3"]["attn"]
    assert set(attn) == {"q", "k", "v", "o"}        # no q / k norm, no gate
    assert attn["q"]["kernel"].shape == (D, HEADS, HEAD_DIM)
    assert attn["k"]["kernel"].shape == (D, KV_HEADS, HEAD_DIM)
    mlp = params["block_1"]["mlp"]
    assert set(mlp) == {"router", "score_bias", "expert_wi", "expert_wo",
                        "shared_wi", "shared_wo"}   # no gate matrix anywhere
    assert mlp["router"]["kernel"].shape == (D, EXPERTS)   # all of them
    assert mlp["score_bias"].shape == (EXPERTS,)
    assert mlp["expert_wi"].shape == (EXPERTS // ep_size, D, FF)
    assert mlp["shared_wi"]["kernel"].shape == (D, SHARED_FF)


def test_the_leaves_start_where_the_family_starts_them():
    model, _ = nemotron()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    mixer = params["block_0"]["ssm"]
    np.testing.assert_allclose(np.exp(mixer["A_log"]),
                               np.arange(1, SSM_HEADS + 1), rtol=1e-6)
    assert np.all(np.asarray(mixer["D"]) == 1)
    assert np.all(np.asarray(mixer["norm"]) == 1)
    step = np.log1p(np.exp(np.asarray(mixer["dt_bias"], np.float64)))
    assert np.all((step >= STEP_RANGE[0] * 0.999) & (step <= STEP_RANGE[1]
                                                     * 1.001))
    assert STEP_FLOOR < STEP_RANGE[0]
    for name in ("conv", "conv_bias"):
        leaf = np.asarray(mixer[name])
        assert np.abs(leaf).max() <= 0.5 and np.abs(leaf).mean() > 0.1
    bias = np.asarray(params["block_1"]["mlp"]["score_bias"])
    assert 0.01 < bias.std() < 0.1
    for name, leaf in flat(params).items():
        if name.endswith("scale"):
            assert np.all(np.asarray(leaf) == 1), name


def test_logits_agree_with_the_reference(both):
    got, want = both["logits"]
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)


def test_loss_agrees_with_the_reference(both):
    got, want = both["loss"]
    assert abs(float(got) - float(want)) <= LOSS_ATOL


_LEAVES = [name for name in flat(jax.eval_shape(
    lambda: seeded(nemotron()[0])[0])) if "block_2" not in name]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_agrees_with_the_reference(both, leaf):
    """Every kind of block and the leaves around them, leaf by leaf; the
    score bias's gradient is exactly zero, here and there."""
    got, want = flat(both["grads"][0])[leaf], flat(both["grads"][1])[leaf]
    if leaf.endswith("score_bias"):
        assert not np.asarray(got).any() and not np.asarray(want).any()
        return
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a gradient that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=GRAD_RTOL * scale, rtol=0)


def test_three_adamw_steps_through_the_trainer_are_the_references():
    """``BaguaTrainer``'s own step (flat-resident state, ``train_step``) on
    one batch three times, against the reference's AdamW written out: the
    losses and the change of every watched leaf."""
    model, hyper = nemotron(pattern="ME*")
    params, tokens = seeded(model)
    optimizer = {"name": "adamw", "kwargs": {"learning_rate": 1e-4}}
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    bagua_tpu.init_process_group(mesh=mesh)
    trainer = bagua_tpu.BaguaTrainer(
        lm_loss_fn(model), optax.adamw(1e-4), GradientAllReduceAlgorithm(),
        mesh=mesh, autotune=False)
    start = ref.watched_copy(params)
    with jax.default_matmul_precision("highest"):
        state = trainer.init(jax.tree.map(jnp.copy, params))
        batch = trainer.shard_batch({"tokens": np.asarray(tokens)})
        losses = []
        for _ in range(3):
            state, loss = trainer.train_step(state, batch)
            losses.append(float(loss))
        after = trainer.unstack_params(state)
        got = ref.parameter_change(start, ref.watched(after, ref.CHANGE_ALSO))
        seen = {}
        want_losses = ref.replay_losses(
            jax.tree.map(jnp.copy, params), {"tokens": tokens}, 3, optimizer,
            hyper, first_gradient=lambda g: seen.update(gradient=g),
            last_change=lambda c: seen.update(change=c))
    assert want_losses[2] < want_losses[1] < want_losses[0]
    # Adam's first step moves every component by the learning rate along
    # its gradient's SIGN (tests/test_qwen3_next.py has why the third loss
    # is held more loosely)
    np.testing.assert_allclose(losses[:2], want_losses[:2], atol=1e-5, rtol=0)
    assert abs(losses[2] - want_losses[2]) < 2e-3
    distance = {name: float(d) for name, d in
                ref.gradient_distance(got, seen["change"]).items()}
    assert set(distance) == set(seen["change"]) and len(distance) > 15
    # by the cell's own limit, with room: a state left as it was reads 1
    assert ref.changes_agree(distance, 0.5 * ref.CHANGE_TOLERANCE), distance
    assert set(seen["gradient"]) == set(ref.watched(params))
    # the score bias has no gradient: only AdamW's decay touches it
    bias = after["block_1"]["mlp"]["score_bias"]
    before = params["block_1"]["mlp"]["score_bias"]
    np.testing.assert_allclose(bias, before * (1 - 1e-4 * 1e-4) ** 3,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# the comparison refuses a system that lacks a mechanism
# ---------------------------------------------------------------------------

#: a fault of the reference's (its ``hyper``) or, where the reference has no
#: leaf for it, of the system's (``MoEMLP`` options)
_WRONG = {
    "none": {},
    "no_softplus": {"softplus": False},
    "decay_is_one": {"decay": False},
    "no_skip": {"skip": False},
    "norm_before_the_gate": {"gate_first": False},
    "one_norm_over_all_lanes": {"norm_groups": 1},
    "head_reads_group_h_mod_g": {"head_group": "strided"},
    "convolution_without_its_bias": {"conv_bias": False},
    "softmax_router": {"router_score": "softmax"},
    "bias_in_the_weights_too": {"bias_in_weights": True},
    "no_routed_scale": {"routed_scale": 1.0},
    "no_renormalisation": {"renormalise": False},
    "plain_relu": {"activation": "relu"},
    "rotated_attention": {"rotate": True},
    "two_sub_layers_a_block": {"sublayers": 2},
    "a_gated_shared_expert": {"system": {"shared_gate": True}},
    "bfloat16_state_in_the_scan": {"scan_dtype": "bfloat16"},
}


@pytest.fixture(scope="module")
def system_logits():
    model, hyper = nemotron(pattern="ME*")
    params, tokens = seeded(model)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, tokens[:, :-1])
    return params, tokens, hyper, logits


@pytest.mark.parametrize("fault", list(_WRONG))
def test_the_comparison_tells_each_mechanism_from_its_absence(
        system_logits, fault):
    """At tiny widths and float32 the system's logits are the sound
    reference's to rounding, and with one mechanism left out or put in the
    wrong place they are far from them."""
    params, tokens, hyper, logits = system_logits
    wrong = dict(_WRONG[fault])
    system = wrong.pop("system", None)
    if system is not None:
        # the system with an option the architecture does not have; its
        # extra leaf is drawn, every other leaf is the sound one's
        model = nemotron(pattern="ME*", moe_overrides=system)[0]
        drawn = model.init(jax.random.PRNGKey(5), tokens[:1, :8])["params"]
        extra = jax.tree.map(lambda x: x, params)
        extra["block_1"]["mlp"] = {**drawn["block_1"]["mlp"],
                                   **params["block_1"]["mlp"]}
        with jax.default_matmul_precision("highest"):
            logits = model.apply({"params": extra}, tokens[:, :-1])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.logits_fn(
            p, t, {**hyper, **wrong}))(params, tokens[:, :-1])
    gap = float(jnp.abs(logits - want).max())
    if fault == "none":
        assert gap <= LOGIT_ATOL
    elif fault == "bfloat16_state_in_the_scan":
        assert gap > 5 * LOGIT_ATOL      # a precision, not a mechanism
    else:
        assert not gap <= 100 * LOGIT_ATOL, gap     # far, or not a number


def test_the_bias_moves_the_choice_of_a_tenth_of_the_tokens(system_logits):
    """The biased and the unbiased choice differ for at least a tenth of the
    test's tokens: a system that dropped the bias, or added it to the
    weights, is seen."""
    params, tokens, _, _ = system_logits
    m = params["embed"]["embedding"][tokens[:, :-1]].reshape(-1, D)
    p = params["block_1"]["mlp"]
    logits = m @ p["router"]["kernel"]
    biased, weights, _ = topk_routing(logits, K, score="sigmoid",
                                      choice_bias=p["score_bias"], scale=SCALE)
    plain, _, _ = topk_routing(logits, K, score="sigmoid")
    moved = np.mean([set(a) != set(b) for a, b in
                     zip(np.asarray(biased), np.asarray(plain))])
    assert moved >= 0.1, moved
    # the weights are the UNBIASED scores at the winners, renormalised, x 2.5
    scores = np.asarray(jax.nn.sigmoid(logits), np.float64)
    picked = np.take_along_axis(scores, np.asarray(biased), axis=-1)
    np.testing.assert_allclose(
        weights, SCALE * picked / picked.sum(-1, keepdims=True), rtol=1e-5)


def test_a_bias_that_leaks_into_the_weights_reads_on_the_bias_itself(
        system_logits):
    """The cell's second comparison: the score bias's gradient is exactly
    zero in the system and in the sound reference (distance 0, not 0 / 0),
    and a reference whose bias enters the weights too has one (distance 1
    against the system's none) — whatever the other leaves read."""
    params, tokens, hyper, _ = system_logits
    model = nemotron(pattern="ME*")[0]
    with jax.default_matmul_precision("highest"):
        got = ref.watched(jax.jit(jax.grad(lm_loss_fn(model)))(
            params, {"tokens": tokens}))
        sound, leaky = (ref.watched(jax.jit(jax.grad(
            lambda p, t, h=h: ref.loss_fn(p, t, h)))(params, tokens))
            for h in (hyper, {**hyper, "bias_in_weights": True}))
    bias = "block_1/mlp/score_bias"
    clean = {n: float(d) for n, d in ref.gradient_distance(got, sound).items()}
    assert clean[bias] == 0.0 and max(clean.values()) < 1e-3
    assert ref.gradients_agree(clean, 1e-3, 1e-3)
    wrong = {n: float(d) for n, d in ref.gradient_distance(got, leaky).items()}
    assert wrong[bias] == pytest.approx(1.0)
    assert not ref.gradients_agree(wrong)
    # and the other way round: a SYSTEM that leaked reads its whole gradient
    assert float(ref.gradient_distance(leaky, sound)[bias]) > 1e-4


def test_the_watched_leaves_are_what_the_architecture_adds(system_logits):
    names = set(ref.watched(system_logits[0]))
    mixer = {"A_log", "dt_bias", "D", "conv", "conv_bias", "norm",
             "in_proj[z]", "in_proj[xBC]", "in_proj[dt]", "out_proj/kernel"}
    attn = {"q/kernel", "k/kernel", "v/kernel", "o/kernel"}
    moe = {"router/kernel", "shared_wi/kernel", "shared_wo/kernel",
           "score_bias"}
    assert names == ({f"block_0/ssm/{leaf}" for leaf in mixer}
                     | {f"block_1/mlp/{leaf}" for leaf in moe}
                     | {f"block_2/attn/{leaf}" for leaf in attn})
    parts = ref.watched(system_logits[0])
    assert parts["block_0/ssm/in_proj[dt]"].shape == (D, SSM_HEADS)
    assert parts["block_0/ssm/in_proj[z]"].shape == (D, SSM_HEADS * SSM_DIM)


# ---------------------------------------------------------------------------
# the chunked scan against the per-token scan
# ---------------------------------------------------------------------------


def scan_inputs(seed, seq, heads, width, groups, state, step, rate,
                dtype=jnp.float32):
    """x, the step sizes around ``step`` (a factor of e either way), A
    around ``-rate``, B, C, D and a cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (2, seq, heads, width))
    dt = step * jnp.exp(jax.random.uniform(keys[1], (2, seq, heads),
                                           minval=-1.0, maxval=1.0))
    a = -rate * jnp.exp(0.2 * jax.random.normal(keys[2], (heads,)))
    b, c = (jax.random.normal(k, (2, seq, groups, state)) / math.sqrt(state)
            for k in keys[3:5])
    skip = 1.0 + 0.3 * jax.random.normal(keys[5], (heads,))
    dy = jax.random.normal(keys[6], (2, seq, heads, width))
    return (x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype),
            skip), dy


def value_and_cotangents(fn, args, dy):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(dy.astype(out.dtype))


#: (sequence, heads, width, groups, state, step size, decay rate): whole
#: chunks at the published head width (two heads a lane tile, two groups);
#: a ragged length of more than one block of eight chunks; both ends of the
#: family's step sizes against both ends of its ``A`` (1e-4 x 1: a chunk
#: decays by 1 %; 0.1 x 64: a position forgets e^-6.4); heads of a whole
#: lane tile; and, at width 8, a shape the kernels' grid does not cover
SCAN_CASES = {
    "whole_chunks": (64, 4, 64, 2, 128, 0.01, 8.0),
    "ragged_blocks": (300, 2, 64, 1, 128, 0.03, 2.0),
    "hardly_decays": (50, 2, 64, 1, 128, 1e-4, 1.0),
    "forgets_at_once": (50, 2, 64, 1, 128, 0.1, 64.0),
    "wide_heads": (40, 2, 128, 2, 128, 0.01, 8.0),
    "narrow_heads": (40, 4, 8, 2, 16, 0.01, 8.0),
}
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


@pytest.fixture(scope="module", params=list(SCAN_CASES))
def scan_case(request):
    *shape, step, rate = SCAN_CASES[request.param]
    args, dy = scan_inputs(3, *shape, step, rate)
    with jax.default_matmul_precision("highest"):
        want = value_and_cotangents(ssd.reference_ssd_scan, args, dy)
        by_jnp = value_and_cotangents(
            lambda *a: ssd.ssd_scan(*a, chunk=32), args, dy)
        by_kernel = value_and_cotangents(
            lambda *a: ssd.ssd_scan(*a, chunk=32, force=True,
                                    interpret=True), args, dy)
    return dict(zip(NAMES, want)), dict(zip(NAMES, by_jnp)), dict(
        zip(NAMES, by_kernel))


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_the_chunked_scan_is_the_per_token_scan(scan_case, path, quantity):
    """Forward and every cotangent of the chunked form — the ``jax.numpy``
    chunks and the kernels, interpreted — against the recurrence taken one
    position at a time (float32 both: what differs is the order of sums)."""
    want, by_jnp, by_kernel = scan_case
    got = (by_jnp if path == "jnp" else by_kernel)[quantity]
    scale = float(jnp.abs(want[quantity]).max())
    assert scale > 0
    np.testing.assert_allclose(got, want[quantity], atol=2e-4 * scale, rtol=0)


def test_the_kernels_are_what_the_forced_call_runs():
    args, _ = scan_inputs(0, 32, 2, 64, 1, 128, 0.01, 8.0)
    text = str(jax.make_jaxpr(lambda *a: ssd.ssd_scan(
        *a, chunk=32, force=True, interpret=True))(*args))
    assert "ssd_fwd" in text
    plain = str(jax.make_jaxpr(lambda *a: ssd.ssd_scan(*a))(*args))
    assert "pallas_call" not in plain           # the CPU takes the jnp chunks
    narrow, _ = scan_inputs(0, 32, 4, 8, 2, 16, 0.01, 8.0)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: ssd.ssd_scan(*a, force=True, interpret=True))(*narrow))


def test_bfloat16_operands_stay_near_the_scan():
    """The models' dtype: bfloat16 x / B / C into the products, the state
    and the decays float32."""
    args, dy = scan_inputs(7, 256, 2, 64, 1, 128, 0.02, 4.0,
                           dtype=jnp.bfloat16)
    want = value_and_cotangents(ssd.reference_ssd_scan, args, dy)
    got = value_and_cotangents(lambda *a: ssd.ssd_scan(
        *a, force=True, interpret=True), args, dy)
    assert got[0].dtype == got[1].dtype == got[4].dtype == jnp.bfloat16
    assert got[2].dtype == got[3].dtype == got[6].dtype == jnp.float32
    for name, a, b in zip(NAMES, got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 0.03, name


def test_the_kernels_take_whole_lane_tiles_on_a_tpu(monkeypatch):
    assert not ssd.ssd_supported(64, 64, 8, 128)              # the CPU
    monkeypatch.setattr(ssd.jax, "default_backend", lambda: "tpu")
    assert ssd.ssd_supported(64, 64, 8, 128)
    assert ssd.ssd_supported(4, 128, 4, 256, jnp.float32)
    assert not ssd.ssd_supported(64, 64, 64, 128)     # a group half a tile
    assert not ssd.ssd_supported(64, 64, 8, 64)       # the state half a tile
    assert not ssd.ssd_supported(64, 64, 7, 128)
    assert not ssd.ssd_supported(64, 64, 8, 128, jnp.float16)


# ---------------------------------------------------------------------------
# the share: the ranks' parts of one layer add up to the whole layer
# ---------------------------------------------------------------------------


def _whole_layer(experts, seed=3, tokens=48):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    matrix = lambda key, *shape: (jax.random.normal(key, shape)
                                  / math.sqrt(shape[-2]))
    return {
        "m": jax.random.normal(keys[0], (2, tokens // 2, D)),
        "router": {"kernel": matrix(keys[1], D, experts)},
        "score_bias": 0.05 * jax.random.normal(keys[2], (experts,)),
        "expert_wi": matrix(keys[3], experts, D, FF),
        "expert_wo": matrix(keys[4], experts, FF, D),
        "shared_wi": {"kernel": matrix(keys[5], D, SHARED_FF)},
        "shared_wo": {"kernel": matrix(keys[6], SHARED_FF, D)},
    }


def _share_of(layer, experts, ep_size, rank, shared=True):
    """Rank ``rank``'s part of the layer's result, by ``MoEMLP`` holding
    its slice of the routed tables (and the whole shared expert)."""
    n_local = experts // ep_size
    held = slice(rank * n_local, (rank + 1) * n_local)
    moe = MoEMLP(n_experts=experts, d_ff=FF, k=6, ep_size=ep_size,
                 ep_rank=rank, dropless=True, gated=False,
                 activation="relu2", norm_topk_prob=True,
                 router_score="sigmoid", score_bias=True, routed_scale=SCALE,
                 shared_d_ff=SHARED_FF if shared else 0, dtype=jnp.float32)
    params = {name: (leaf[held] if name.startswith("expert_") else leaf)
              for name, leaf in layer.items()
              if name != "m" and (shared or not name.startswith("shared_"))}
    return moe.apply({"params": params}, layer["m"]).reshape(-1, D)


def test_the_sixteen_ranks_shares_add_up_with_the_shared_expert_counted_once():
    """Guide section 4: the parts of the result that all the shares give,
    with what every chip computes alike — the shared expert — counted once,
    add up to what the uncut reference gives for the whole layer."""
    experts, ep_size = 32, 16
    layer = _whole_layer(experts)
    tables = {name: leaf for name, leaf in layer.items() if name != "m"}
    hyper = {**nemotron()[1], "experts_per_token": 6, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(layer["m"].reshape(-1, D), tables, hyper)
        routed_whole = ref.moe(layer["m"].reshape(-1, D), tables,
                               {**hyper, "shared": False})
        shares = [_share_of(layer, experts, ep_size, r)
                  for r in range(ep_size)]
        routed = [_share_of(layer, experts, ep_size, r, shared=False)
                  for r in range(ep_size)]
    shared = whole - routed_whole
    assert float(jnp.abs(shared).max()) > 0.05
    # every rank computes the same shared expert beside its own routed part
    for share, part in zip(shares, routed):
        np.testing.assert_allclose(share - part, shared, atol=2e-5, rtol=0)
        assert float(jnp.abs(part - routed_whole).max()) > 1e-3
    np.testing.assert_allclose(sum(routed) + shared, whole, atol=5e-5, rtol=0)
    np.testing.assert_allclose(sum(shares) - (ep_size - 1) * shared, whole,
                               atol=5e-5 * ep_size, rtol=0)


def test_the_exchange_inside_an_axis_takes_the_new_router():
    """``_dropless_exchange`` (timed by no cell) under the sigmoid router,
    the bias, the scale and ReLU^2: four ranks inside a bound ``ep`` axis
    give the uncut layer."""
    from jax.sharding import PartitionSpec as P

    experts, ep = 8, 4
    layer = _whole_layer(experts, seed=5, tokens=4 * 16)
    tables = {name: leaf for name, leaf in layer.items() if name != "m"}
    moe = MoEMLP(n_experts=experts, d_ff=FF, k=3, ep_size=ep, dropless=True,
                 gated=False, activation="relu2", router_score="sigmoid",
                 score_bias=True, routed_scale=SCALE, shared_d_ff=SHARED_FF,
                 dtype=jnp.float32)
    mesh = build_mesh({"ep": ep}, jax.devices()[:ep])
    rows = layer["m"].reshape(ep, -1, D)             # a slice of rows a rank
    stacks = {name: leaf.reshape(ep, experts // ep, *leaf.shape[1:])
              for name, leaf in tables.items() if name.startswith("expert_")}
    rest = {name: leaf for name, leaf in tables.items()
            if not name.startswith("expert_")}

    def rank(rows, stacks):
        params = {**rest, **{name: leaf[0] for name, leaf in stacks.items()}}
        return moe.apply({"params": params}, rows)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.shard_map(
            rank, mesh=mesh, in_specs=(P("ep"), P("ep")), out_specs=P("ep"),
            check_vma=False))(rows, stacks)
        hyper = {**nemotron()[1], "first_expert": 0}
        want = ref.moe(layer["m"].reshape(-1, D), tables, hyper)
    np.testing.assert_allclose(got.reshape(-1, D), want, atol=5e-5, rtol=0)


# ---------------------------------------------------------------------------
# each new piece against a hand-rolled form
# ---------------------------------------------------------------------------


def test_the_convolution_with_its_bias_by_hand():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    got = conv_bias_silu(x, taps, bias)
    pre = np.zeros(x.shape) + np.asarray(bias)
    for t in range(9):
        for j in range(4):
            if t - (3 - j) >= 0:
                pre[:, t] += np.asarray(taps[j]) * np.asarray(x[:, t - (3 - j)])
    np.testing.assert_allclose(got, pre / (1 + np.exp(-pre)), atol=1e-6,
                               rtol=0)
    # causal: position 4's output does not see position 5
    moved = conv_bias_silu(x.at[:, 5].add(1.0), taps, bias)
    np.testing.assert_array_equal(moved[:, :5], got[:, :5])
    # its written-out VJP is autodiff's of the plain form
    plain = lambda x, taps, bias: jax.nn.silu(ref.causal_conv(x, taps, bias))
    dy = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    for a, b in zip(jax.vjp(conv_bias_silu, x, taps, bias)[1](dy),
                    jax.vjp(plain, x, taps, bias)[1](dy)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)


def test_the_gate_comes_first_and_the_norm_is_a_groups():
    y = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 32))
    w = 1 + 0.2 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    got = gated_group_norm(y, z, w, 4, EPS)
    g = np.asarray(y, np.float64) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    per = g.reshape(3, 5, 4, 8)
    want = (per / np.sqrt(np.mean(per * per, -1, keepdims=True) + EPS)
            ).reshape(3, 5, 32) * np.asarray(w)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert float(jnp.abs(gated_group_norm(y, z, w, 1, EPS) - got).max()) > 0.05


def test_the_state_space_layer_by_hand():
    """One Mamba-2 layer against the recurrence written with loops,
    float64."""
    cfg = nemotron()[0].cfg
    layer = Mamba2(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 20, D))
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    p = {**p, "dt_bias": p["dt_bias"] + 4.0,
         "D": p["D"] + 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                               p["D"].shape),
         "norm": p["norm"] + 0.2 * jax.random.normal(jax.random.PRNGKey(3),
                                                     p["norm"].shape)}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.apply({"params": p}, x))[0]
    f = lambda a: np.asarray(a, np.float64)
    silu = lambda a: a / (1 + np.exp(-a))
    inner, maps = SSM_HEADS * SSM_DIM, GROUPS * STATE
    proj = f(x[0]) @ f(p["in_proj"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * maps],
                  proj[:, 2 * inner + 2 * maps:])
    mixed = np.zeros_like(xbc) + f(p["conv_bias"])
    for t in range(20):
        for j in range(TAPS):
            if t - (TAPS - 1 - j) >= 0:
                mixed[t] += f(p["conv"][j]) * xbc[t - (TAPS - 1 - j)]
    mixed = silu(mixed)
    xs = mixed[:, :inner].reshape(20, SSM_HEADS, SSM_DIM)
    b = mixed[:, inner:inner + maps].reshape(20, GROUPS, STATE)
    c = mixed[:, inner + maps:].reshape(20, GROUPS, STATE)
    delta = np.log1p(np.exp(dt + f(p["dt_bias"])))
    y = np.zeros((20, SSM_HEADS, SSM_DIM))
    for h in range(SSM_HEADS):
        state, g = np.zeros((SSM_DIM, STATE)), h // (SSM_HEADS // GROUPS)
        for t in range(20):
            state = (math.exp(-math.exp(f(p["A_log"])[h]) * delta[t, h])
                     * state + delta[t, h] * np.outer(xs[t, h], b[t, g]))
            y[t, h] = state @ c[t, g] + f(p["D"])[h] * xs[t, h]
    gated = (y.reshape(20, inner) * silu(z)).reshape(20, GROUPS, -1)
    normed = gated / np.sqrt((gated * gated).mean(-1, keepdims=True) + EPS)
    want = (normed.reshape(20, inner) * f(p["norm"])) @ f(
        p["out_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_the_expert_layer_by_hand():
    """Sigmoid scores, the bias in the choice alone, renormalised and
    scaled weights, ReLU^2 experts without a gate matrix, the shared expert
    added without a gate: loops, float64."""
    layer = _whole_layer(8, seed=9, tokens=12)
    got = _share_of(layer, 8, 1, 0)
    f = lambda a: np.asarray(a, np.float64)
    m = f(layer["m"]).reshape(-1, D)
    relu2 = lambda a: np.maximum(a, 0) ** 2
    scores = 1 / (1 + np.exp(-(m @ f(layer["router"]["kernel"]))))
    want = relu2(m @ f(layer["shared_wi"]["kernel"])) @ f(
        layer["shared_wo"]["kernel"])
    for t in range(m.shape[0]):
        winners = np.argsort(-(scores[t] + f(layer["score_bias"])))[:6]
        total = scores[t, winners].sum() + 1e-20
        for e in winners:
            want[t] += (SCALE * scores[t, e] / total) * (
                relu2(m[t] @ f(layer["expert_wi"][e]))
                @ f(layer["expert_wo"][e]))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)


def test_a_hidden_width_of_half_a_tile_is_padded_for_the_kernels(monkeypatch):
    """1,856 is 14.5 lane tiles: where the grouped-matmul kernels run the
    expert stacks are handed over with zero columns up to 1,920, and the
    layer's output and gradients are the unpadded layer's (here 192 -> 256,
    the kernels interpreted; the fallback multiplies the stacks as they
    are)."""
    from bagua_tpu.model_parallel.moe import layer as moe_layer
    from internal.row_kernels import both_paths

    assert moe_layer._whole_tiles(1856) == 1920
    assert moe_layer._whole_tiles(1024) == 1024
    layer = MoEMLP(n_experts=8, d_ff=192, k=2, dropless=True, gated=False,
                   activation="relu2", router_score="sigmoid",
                   routed_scale=SCALE, dtype=jnp.float32)
    by_kernels, fallback, sites = both_paths(layer)
    assert sites[0] > 0 and sites[1] == 0
    assert by_kernels["expert_wi"].shape == (8, 128, 192)
    for name, want in fallback.items():
        scale = float(jnp.abs(want).max())
        assert scale > 0
        np.testing.assert_allclose(by_kernels[name], want, atol=2e-5 * scale,
                                   rtol=0, err_msg=name)


# ---------------------------------------------------------------------------
# the paths that cannot take the new options refuse them by name
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overrides,error,match", [
    ({"decode": True}, NotImplementedError, "layer_kinds.*decode"),
    ({"sp_axis": "sp"}, NotImplementedError, "layer_kinds.*sp_axis"),
    ({"tp_axis": "tp", "tp_size": 2}, NotImplementedError,
     "layer_kinds.*tensor-parallel"),
    ({"n_passes": 2}, NotImplementedError, "layer_kinds.*looped"),
    ({"attention": "block_diffusion", "diffusion_block": 4, "rope_layers":
      None}, NotImplementedError, "layer_kinds.*block_diffusion"),
    ({"mixer_layers": (1, 0)}, ValueError, "layer_kinds replaces"),
    ({"layer_kinds": ("ssm", "moe")}, ValueError, "layer_kinds names"),
    ({"layer_kinds": ("ssm", "mlp", "ssm", "attn", "moe")}, ValueError,
     "layer_kinds names"),
    ({"ssm_groups": 3}, ValueError, "ssm_heads"),
])
def test_the_other_paths_refuse_the_new_options(overrides, error, match):
    model = nemotron(**overrides)[0]
    with pytest.raises(error, match=match):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_pipeline_stages_refuse_a_block_of_one_sub_layer():
    from bagua_tpu.parallel.pipeline import PipelinedTransformerLM

    cfg = nemotron()[0].cfg
    with pytest.raises(NotImplementedError, match="layer_kinds"):
        PipelinedTransformerLM(cfg, pp_size=5).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 9), jnp.int32))


def test_an_expert_block_needs_an_expert_layer():
    cfg = nemotron()[0].cfg
    with pytest.raises(ValueError, match="mlp_factory"):
        TransformerLM(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("options", [
    {"router_score": "sigmoid"}, {"score_bias": True},
    {"routed_scale": 2.5}, {"activation": "relu2"}])
def test_the_capacity_path_refuses_the_new_options_by_name(options):
    x = jnp.zeros((1, 8, D))
    with pytest.raises(ValueError, match="dropless"):
        MoEMLP(n_experts=4, d_ff=FF, k=2, dtype=jnp.float32,
               **options).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="router score"):
        topk_routing(jnp.zeros((4, 8)), 2, score="tanh")


# ---------------------------------------------------------------------------
# tracing: areas, scopes, gauges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path,area", [
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/ssm/"
     "dot_general", "ssm"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_2/"
     "ssm/jit(_kernel_bwd)/ssd_bwd/pallas_call", "ssm"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_4/ssm_norm/mul",
     "ssm"),
    # the row passes of ops/ssd_rows.py sit inside the layer's module
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/ssm/"
     "jit(_mix_part)/ssd_mix/pallas_call", "ssm"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_7/"
     "ssm/jit(gate_bwd)/ssd_gate_bwd/pallas_call", "ssm"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_5/attn/q/"
     "dot_general", "attn"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_1/mlp/"
     "bagua.moe/shared/shared_wi/dot_general", "moe/shared"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_1/mlp_norm/mul",
     "mlp"),
])
def test_area_of_reads_the_new_modules(path, area):
    assert obs_spans.area_of(path) == area
    assert area in obs_spans.AREAS


def test_a_traced_step_carries_the_scopes_and_sets_the_gauges():
    model, _ = nemotron()
    params, tokens = seeded(model)
    text = jax.jit(jax.grad(lm_loss_fn(model))).lower(
        params, {"tokens": tokens}).as_text(debug_info=True)
    assert "block_0/ssm" in text and "bagua.moe/shared" in text
    assert counters.get("ssm/layers") == 2
    # the CPU: the rows between the projections are the jax.numpy form
    assert counters.get("ssm/row_kernel_layers") == 0
    assert counters.get("ssm/chunk") == CHUNK
    assert (counters.get("ssm/heads"), counters.get("ssm/head_dim"),
            counters.get("ssm/groups"), counters.get("ssm/state")) == (
        SSM_HEADS, SSM_DIM, GROUPS, STATE)
    assert counters.get("moe/routed_scale") == SCALE
    assert counters.get("moe/score_bias") == 1
    assert counters.get("moe/shared_width") == SHARED_FF
    # one attention layer, full, unrotated; no linear-attention gauge
    assert counters.get("attn/full_layers") == 1
    assert counters.get("attn/kv_heads") == KV_HEADS
    assert counters.get("attn/rope_kernel_layers") == 0


def test_the_gauge_counts_the_cells_four_layers_on_the_row_passes(
        monkeypatch):
    """At the cell's widths and pattern (``MEMEM*EME``: four Mamba-2 blocks
    of nine) with the predicate forced, every state-space layer runs the
    passes of ``ops/ssd_rows.py``: ``ssm/row_kernel_layers`` 4 beside
    ``ssm/layers`` 4; off the TPU, or at a length no row block divides, 0."""
    from bagua_tpu.ops import ssd_rows

    model, _ = nemotron(
        pattern="MEMEM*EME", dtype=jnp.bfloat16, max_seq_len=256,
        ssm_heads=64, ssm_head_dim=64, ssm_groups=8, ssm_state=128,
        ssm_chunk=128)
    tokens = jnp.zeros((1, 257), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens[:, :-1])["params"]
    # a fresh function a trace: ``eval_shape`` keeps the traces it made
    trace = lambda tokens: jax.eval_shape(
        lambda params, batch: lm_loss_fn(model)(params, batch), params,
        {"tokens": tokens})
    trace(tokens)
    assert (counters.get("ssm/layers"),
            counters.get("ssm/row_kernel_layers")) == (4, 0)
    monkeypatch.setattr(ssd_rows, "_on_tpu", lambda: True)
    trace(tokens)
    assert (counters.get("ssm/layers"),
            counters.get("ssm/row_kernel_layers")) == (4, 4)
    trace(tokens[:, :201])
    assert counters.get("ssm/row_kernel_layers") == 0
