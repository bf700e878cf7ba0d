"""Qwen3-Next on the normal path: ``TransformerLM`` (linear attention by the
gated delta rule on three layers of four, output-gated softmax attention
with a rotation over a part of the head on the fourth, every norm's scale
``1 + w``) + ``MoEMLP`` (dropless top-k of all experts renormalised, one
expert-parallel rank's share, a shared expert under its own sigmoid gate) +
``lm_loss_fn``, against the benchmark's plain float32 reference
(``perfbench/reference/qwen3_next.py``, which imports nothing of
``bagua_tpu``), the chunked ``gated_delta_rule`` (the kernels in interpret
mode and the ``jax.numpy`` chunks) against the per-token scan, and each new
piece against a hand-rolled form.  Tiny widths, seeded, CPU.
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
from bagua_tpu.model_parallel.moe.layer import MoEMLP
from bagua_tpu.models.linear_attention import (
    GatedDeltaNet, causal_depthwise_conv,
)
from bagua_tpu.models.transformer import (
    Attention, RMSNorm, TransformerConfig, TransformerLM, lm_loss_fn,
    rope_rotate,
)
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.ops import gated_delta as gd
from bagua_tpu.parallel.mesh import build_mesh
from bagua_tpu.telemetry import counters

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from perfbench import cells  # noqa: E402 - the benchmark's loader by file name

ref = cells.load_plugin("reference", "qwen3_next")

D, HEADS, KV_HEADS, HEAD_DIM, ROTARY = 64, 4, 2, 32, 8
LIN_K, LIN_V, LIN_DIM, TAPS = 2, 4, 16, 4
FF, EXPERTS, K, THETA, EPS = 24, 16, 3, 1e7, 1e-6
PATTERN = (1, 1, 1, 0)
#: float32 against float32 on the CPU, both with exact products: what is
#: left is the order of summation (the chunked form sums a chunk's 64
#: positions at once and solves for all of their deltas together where the
#: scan takes them one by one).  A missing piece moves logits by 1e-2 to 1
#: and fails every one of these.
LOGIT_ATOL = 2e-4
LOSS_ATOL = 1e-5
GRAD_RTOL = 5e-4


def qwen3_next(ep_size=1, ep_rank=0, *, layers=4, dtype=jnp.float32,
               **overrides):
    """The model and the reference's settings for it: four layers in the
    published pattern, or (``layers=2``) one linear and one full layer, a
    period of two."""
    pattern = PATTERN if layers == 4 else (1, 0)
    cfg = TransformerConfig(**{**dict(
        vocab_size=97, d_model=D, n_heads=HEADS, n_kv_heads=KV_HEADS,
        d_head=HEAD_DIM, n_layers=layers, d_ff=FF, max_seq_len=128,
        dtype=dtype, rope_theta=THETA, rotary_dim=ROTARY, qk_norm="head",
        attn_gate=True, norm_zero_centered=True, norm_eps=EPS,
        mixer_layers=pattern,
        linear_key_heads=LIN_K, linear_value_heads=LIN_V,
        linear_key_dim=LIN_DIM, linear_value_dim=LIN_DIM, linear_conv=TAPS),
        **overrides})
    moe = lambda: MoEMLP(
        n_experts=EXPERTS, d_ff=FF, k=K, ep_size=ep_size, ep_rank=ep_rank,
        dropless=True, gated=True, norm_topk_prob=True, shared_d_ff=FF,
        shared_gate=True, dtype=dtype, name="mlp")
    model = TransformerLM(cfg, mlp_factory=lambda _i: moe)
    hyper = {
        "layers": layers, "full_attention_interval": len(pattern),
        "linear_key_heads": LIN_K, "linear_value_heads": LIN_V,
        "linear_key_dim": LIN_DIM, "linear_value_dim": LIN_DIM,
        "experts_per_token": K,
        "first_expert": ep_rank * (EXPERTS // ep_size), "rope_theta": THETA,
        "rotary_dim": ROTARY, "rms_norm_eps": EPS, "decay": True,
        "write_strength": True, "l2_norm": True, "attn_gate": True,
        "shared": True, "shared_gate": True, "zero_centered": True,
        "scan_dtype": "float32"}
    return model, hyper


def seeded(model, seed=0, batch=2, seq=80):
    """Weights and tokens; 80 positions are a chunk of 64 and a quarter."""
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1),
                                0, model.cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(seed + 1), tokens[:1, :8])["params"]
    # every scale and gate parameter off its init (zeros, ones), so that a
    # norm or gate applied in the wrong place shows
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    moved = ("scale", "norm", "dt_bias")
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if any(m in jax.tree_util.keystr(path) for m in moved) else leaf
        for (path, leaf), key in zip(leaves, keys)])
    return params, tokens


def flat(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


# ---------------------------------------------------------------------------
# system against the plain reference: the whole model, and a rank's share
# ---------------------------------------------------------------------------

SHARES = [(1, 0), (4, 3)]


@pytest.fixture(scope="module", params=SHARES,
                ids=lambda s: f"rank{s[1]}of{s[0]}")
def both(request):
    """Logits, loss and gradients of system and reference, computed once."""
    model, hyper = qwen3_next(*request.param)
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
    linear = params["block_0"]
    assert set(linear) == {"linear_attn_norm", "linear_attn", "mlp_norm",
                           "mlp"}
    mixer = linear["linear_attn"]
    key_w, value_w = LIN_K * LIN_DIM, LIN_V * LIN_DIM
    assert mixer["in_proj_qkvz"]["kernel"].shape == (
        D, 2 * key_w + 2 * value_w)
    assert mixer["in_proj_ba"]["kernel"].shape == (D, 2 * LIN_V)
    assert mixer["conv"].shape == (TAPS, 2 * key_w + value_w)
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == (LIN_V,)
    assert mixer["norm"].shape == (LIN_DIM,)
    assert mixer["out_proj"]["kernel"].shape == (value_w, D)
    full = params["block_3"]
    assert set(full) == {"attn_norm", "attn", "mlp_norm", "mlp"}
    attn = full["attn"]
    assert attn["q"]["kernel"].shape == (D, HEADS, 2 * HEAD_DIM)  # q | gate
    assert attn["k"]["kernel"].shape == (D, KV_HEADS, HEAD_DIM)
    assert attn["q_norm"]["scale"].shape == (HEAD_DIM,)
    mlp = full["mlp"]
    assert mlp["router"]["kernel"].shape == (D, EXPERTS)   # all of them
    assert mlp["expert_wg"].shape == (EXPERTS // ep_size, D, FF)
    assert mlp["shared_wi"]["kernel"].shape == (D, FF)
    assert mlp["shared_gate"]["kernel"].shape == (D, 1)


def test_the_zero_centred_scales_start_at_zero_and_the_gated_norm_at_one():
    model, _ = qwen3_next()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    for name, leaf in flat(params).items():
        if name.endswith("scale"):
            assert not np.asarray(leaf).any(), name
    assert np.all(np.asarray(params["block_0"]["linear_attn"]["norm"]) == 1)
    assert np.all(np.asarray(params["block_0"]["linear_attn"]["dt_bias"]) == 1)
    a = np.exp(np.asarray(params["block_0"]["linear_attn"]["A_log"]))
    assert np.all((a > 0) & (a <= 16))


def test_logits_agree_with_the_reference(both):
    got, want = both["logits"]
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)


def test_loss_agrees_with_the_reference(both):
    got, want = both["loss"]
    assert abs(float(got) - float(want)) <= LOSS_ATOL


_LEAVES = [name for name in flat(jax.eval_shape(
    lambda: seeded(qwen3_next()[0])[0]))
    if "block_1" not in name and "block_2" not in name]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_agrees_with_the_reference(both, leaf):
    """Both kinds of layer (``block_0`` linear, ``block_3`` full) and the
    leaves around them, leaf by leaf."""
    got, want = flat(both["grads"][0])[leaf], flat(both["grads"][1])[leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a gradient that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=GRAD_RTOL * scale, rtol=0)


def test_three_adamw_steps_through_the_trainer_are_the_references():
    """``BaguaTrainer``'s own step (flat-resident state, ``train_step``) on
    one batch three times, against the reference's AdamW written out: the
    losses and the change of every watched leaf."""
    model, hyper = qwen3_next(layers=2)
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
        got = ref.parameter_change(start, ref.watched(
            trainer.unstack_params(state), ref.CHANGE_ALSO))
        seen = {}
        want_losses = ref.replay_losses(
            jax.tree.map(jnp.copy, params), {"tokens": tokens}, 3, optimizer,
            hyper, first_gradient=lambda g: seen.update(gradient=g),
            last_change=lambda c: seen.update(change=c))
    assert want_losses[2] < want_losses[1] < want_losses[0]
    # Adam's first step moves every component by the learning rate along
    # its gradient's SIGN: a component that is zero but for rounding goes
    # one way here and the other way there, the second gradient then
    # differs in the third digit and the third loss in the fourth; the two
    # losses in front of it are the reference's to rounding
    np.testing.assert_allclose(losses[:2], want_losses[:2], atol=1e-5, rtol=0)
    assert abs(losses[2] - want_losses[2]) < 2e-3
    distance = {name: float(d) for name, d in
                ref.gradient_distance(got, seen["change"]).items()}
    assert set(distance) == set(seen["change"]) and len(distance) > 15
    # by the cell's own limit, with room: a state left as it was reads 1
    assert max(distance.values()) < 0.5 * ref.CHANGE_TOLERANCE, distance
    assert ref.changes_agree(distance, 0.5 * ref.CHANGE_TOLERANCE)
    assert set(seen["gradient"]) == set(ref.watched(params))


# ---------------------------------------------------------------------------
# the comparison refuses a system that lacks a mechanism
# ---------------------------------------------------------------------------

_WRONG = {
    "none": {},
    "alpha_is_one": {"decay": False},
    "beta_is_one": {"write_strength": False},
    "no_l2_norm": {"l2_norm": False},
    "whole_head_rotation": {"rotary_dim": None},
    "no_output_gate": {"attn_gate": False},
    "no_shared_expert_gate": {"shared_gate": False},
    "plain_norm_scale": {"zero_centered": False},
    "bfloat16_state_in_the_scan": {"scan_dtype": "bfloat16"},
}


@pytest.fixture(scope="module")
def system_gradient():
    model, hyper = qwen3_next(layers=2)
    params, tokens = seeded(model)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, tokens[:, :-1])
        grads = jax.jit(jax.grad(lm_loss_fn(model)))(params,
                                                     {"tokens": tokens})
    return params, tokens, hyper, logits, ref.watched(grads)


@pytest.mark.parametrize("fault", list(_WRONG))
def test_the_comparison_tells_each_mechanism_from_its_absence(
        system_gradient, fault):
    """``correct``'s comparisons at tiny widths and float32: the system's
    logits and first gradient are the sound reference's to rounding, and a
    reference with one mechanism left out is far from them — by the cell's
    own limit on the watched leaves of the gradient."""
    params, tokens, hyper, logits, got = system_gradient
    wrong = {**hyper, **_WRONG[fault]}
    with jax.default_matmul_precision("highest"):
        want_logits, want = jax.jit(lambda p, t: (
            ref.logits_fn(p, t[:, :-1], wrong),
            ref.watched(jax.grad(ref.loss_fn)(p, t, wrong))))(params, tokens)
    distance = {name: float(d) for name, d in
                ref.gradient_distance(got, want).items()}
    assert set(distance) == set(got)
    if fault == "none":
        assert max(distance.values()) < 1e-3
        assert ref.gradients_agree(distance, 1e-3)
        return
    assert not ref.gradients_agree(distance, ref.GRADIENT_TOLERANCE)
    if fault != "bfloat16_state_in_the_scan":
        assert float(jnp.abs(logits - want_logits).max()) > 100 * LOGIT_ATOL
    where = {
        "alpha_is_one": "block_0/linear_attn/A_log",
        "beta_is_one": "block_0/linear_attn/in_proj_ba/kernel",
        "no_l2_norm": "block_0/linear_attn/in_proj_qkvz/kernel",
        "whole_head_rotation": "block_1/attn/k/kernel",
        "no_output_gate": "block_1/attn/q/kernel[gate]",
        "no_shared_expert_gate": "block_1/mlp/shared_gate/kernel",
        "plain_norm_scale": "block_0/linear_attn/in_proj_qkvz/kernel",
        # at 80 positions and 16 lanes a bfloat16 state is off by less than
        # at the cell's size: which leaf shows it most is the seed's
        "bfloat16_state_in_the_scan": max(distance, key=distance.get),
    }[fault]
    assert distance[where] > ref.GRADIENT_TOLERANCE, (where, distance[where])


def test_the_watched_leaves_are_the_gates_and_what_feeds_them(
        system_gradient):
    names = set(system_gradient[4])
    linear = {"A_log", "dt_bias", "conv", "norm", "in_proj_qkvz/kernel",
              "in_proj_ba/kernel", "out_proj/kernel"}
    full = {"q/kernel[query]", "q/kernel[gate]", "k/kernel", "v/kernel",
            "o/kernel"}
    moe = {"shared_gate/kernel", "router/kernel", "shared_wi/kernel"}
    want = ({f"block_0/linear_attn/{leaf}" for leaf in linear}
            | {f"block_1/attn/{leaf}" for leaf in full}
            | {f"block_{i}/mlp/{leaf}" for i in range(2) for leaf in moe})
    assert names == want


# ---------------------------------------------------------------------------
# the chunked gated delta rule against the per-token scan
# ---------------------------------------------------------------------------


def l2_normalize(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_inputs(seed, seq, key_heads, value_heads, dim, log_decay,
                 dtype=jnp.float32, correlated=False, strength=1.0):
    """q, k L2-normalised (k with a common component: the keys of a
    SiLU-activated projection are correlated), v, g with a median of
    ``-exp(log_decay)``, beta (``strength`` times a sigmoid: 2 is
    Olmo-Hybrid's), and a cotangent.  ``dim``: a head's lanes, or ``(d_k,
    d_v)`` where keys and values differ."""
    d_k, d_v = dim if isinstance(dim, tuple) else (dim, dim)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2_normalize(jax.random.normal(keys[0], (2, seq, key_heads, d_k)))
    k = jax.random.normal(keys[1], (2, seq, key_heads, d_k))
    k = l2_normalize(k * (0.05 if correlated else 1.0) + 0.5)
    v = jax.random.normal(keys[2], (2, seq, value_heads, d_v))
    g = -jnp.exp(jax.random.normal(keys[3], (2, seq, value_heads))
                 + log_decay)
    beta = strength * jax.nn.sigmoid(
        2 * jax.random.normal(keys[4], (2, seq, value_heads))
        + (4.0 if correlated else 0.0))
    do = jax.random.normal(keys[5], (2, seq, value_heads, d_v))
    return ((q / math.sqrt(d_k)).astype(dtype), k.astype(dtype),
            v.astype(dtype), g, beta), do


def value_and_cotangents(fn, args, do):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(do.astype(out.dtype))


#: (sequence, key heads, value heads, head width, log of the median decay
#: rate): whole chunks; a ragged length of more than one block of eight
#: chunks; ragged lengths with decays near 1 (exp(-7): alpha = 0.999) and
#: near 0 (exp(3): alpha = e^-20); at width 16, a shape the kernels' grid
#: does not cover (eight heads to a lane tile); and heads that are no whole lane tile (Olmo-Hybrid's
#: 96-lane keys under 192-lane values, write strengths up to 2): six heads,
#: a block of four and a ragged one of two, over a ragged length of more
#: than one block of chunks — at decays of a position or two, and at decays
#: near 1, the slow heads whose log decay's cotangent is a long sum (the
#: leaf the Olmo-Hybrid cell's first gradient reads furthest from float32)
DELTA_CASES = {
    "whole_chunks": (128, 1, 2, 128, -1.0),
    "ragged_blocks": (600, 1, 1, 128, 0.0),
    "hardly_decays": (100, 2, 2, 128, -7.0),
    "forgets_at_once": (100, 1, 2, 128, 3.0),
    "narrow_heads": (80, 2, 4, 16, -1.0),
    "heads_96_192_ragged_blocks": (600, 6, 6, (96, 192), -1.0),
    "heads_96_192_hardly_decay": (600, 6, 6, (96, 192), -7.0),
}
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


@pytest.fixture(scope="module", params=list(DELTA_CASES))
def delta_case(request):
    seq, hk, hv, dim, log_decay = DELTA_CASES[request.param]
    args, do = delta_inputs(3, seq, hk, hv, dim, log_decay,
                            strength=2.0 if isinstance(dim, tuple) else 1.0)
    with jax.default_matmul_precision("highest"):
        want = value_and_cotangents(gd.reference_gated_delta_rule, args, do)
        by_jnp = value_and_cotangents(
            lambda *a: gd.gated_delta_rule(*a, chunk=64), args, do)
        by_kernel = value_and_cotangents(
            lambda *a: gd.gated_delta_rule(*a, chunk=64, force=True,
                                           interpret=True), args, do)
    return dict(zip(NAMES, want)), dict(zip(NAMES, by_jnp)), dict(
        zip(NAMES, by_kernel))


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_the_chunked_rule_is_the_per_token_scan(delta_case, path, quantity):
    """Forward and every cotangent of the chunked form — the ``jax.numpy``
    chunks and the kernels, interpreted — against the recurrence taken one
    position at a time (float32 both: what differs is the order of sums)."""
    want, by_jnp, by_kernel = delta_case
    got = (by_jnp if path == "jnp" else by_kernel)[quantity]
    scale = float(jnp.abs(want[quantity]).max())
    assert scale > 0
    np.testing.assert_allclose(got, want[quantity], atol=2e-4 * scale, rtol=0)


def test_the_kernels_are_what_the_forced_call_runs():
    args, _ = delta_inputs(0, 64, 1, 2, 128, -1.0)
    text = str(jax.make_jaxpr(lambda *a: gd.gated_delta_rule(
        *a, force=True, interpret=True))(*args))
    assert "gdn_fwd" in text
    plain = str(jax.make_jaxpr(lambda *a: gd.gated_delta_rule(*a))(*args))
    assert "pallas_call" not in plain           # the CPU takes the jnp chunks
    narrow, _ = delta_inputs(0, 64, 1, 2, 16, -1.0)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: gd.gated_delta_rule(*a, force=True, interpret=True))(
            *narrow))


@pytest.mark.parametrize("path,heads,dim,strength", [
    ("jnp", 1, 128, 1.0), ("jnp", 1, (96, 192), 2.0),
    ("kernel", 1, 128, 2.0), ("kernel", 6, (96, 192), 2.0)])
def test_the_solve_survives_keys_that_are_nearly_one_vector(path, heads, dim,
                                                            strength):
    """Keys all but equal, beta near 1, hardly any decay: ``I + A`` is close
    to the lower-triangular matrix of ones, whose powers grow binomially (a
    Neumann series for the inverse loses every digit) while the inverse
    stays bidiagonal.  Forward substitution holds the scan's numbers — also
    at write strengths near 2 (Olmo-Hybrid's), where ``A``'s entries double
    and the inverse's alternate in sign at size 2 all over the chunk, in
    the ``jax.numpy`` chunks and in the kernels, interpreted."""
    args, do = delta_inputs(5, 128, heads, heads, dim, -9.0, correlated=True,
                            strength=strength)
    assert float(jnp.median(args[4])) > 0.95 * strength
    with jax.default_matmul_precision("highest"):
        want = value_and_cotangents(gd.reference_gated_delta_rule, args, do)
        got = value_and_cotangents(
            lambda *a: gd.gated_delta_rule(*a, chunk=64,
                                           force=path == "kernel",
                                           interpret=True), args, do)
    for name, a, b in zip(NAMES, got, want):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=1e-3 * scale, rtol=0,
                                   err_msg=name)


@pytest.mark.parametrize("heads,dim,strength", [
    ((1, 2), 128, 1.0), ((6, 6), (96, 192), 2.0)])
def test_bfloat16_operands_stay_near_the_scan(heads, dim, strength):
    """The models' dtype: bfloat16 q / k / v into the products, the state
    and the decays float32."""
    args, do = delta_inputs(7, 256, *heads, dim, -2.0, dtype=jnp.bfloat16,
                            strength=strength)
    want = value_and_cotangents(gd.reference_gated_delta_rule, args, do)
    got = value_and_cotangents(lambda *a: gd.gated_delta_rule(
        *a, force=True, interpret=True), args, do)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.bfloat16
    assert got[4].dtype == jnp.float32
    for name, a, b in zip(NAMES, got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 0.03, name


@pytest.mark.parametrize("seq,chunk,chunks", [
    (64, 64, 1), (65, 64, 2), (512, 64, 8), (513, 64, 16), (4096, 64, 64),
    (100, 32, 4), (300, 32, 16)])
def test_the_rows_are_padded_to_whole_blocks_of_chunks(seq, chunk, chunks):
    assert gd._padded_chunks(seq, chunk) == chunks


def test_the_kernels_take_whole_lane_tiles_on_a_tpu(monkeypatch):
    assert not gd.gated_delta_supported(16, 32, 128, 128)     # the CPU
    monkeypatch.setattr(gd.jax, "default_backend", lambda: "tpu")
    assert gd.gated_delta_supported(16, 32, 128, 128)
    assert gd.gated_delta_supported(2, 2, 128, 256, jnp.float32)
    # no whole tile a head, whole tiles four heads together; any head count
    assert gd.gated_delta_supported(30, 30, 96, 192)
    assert gd.gated_delta_supported(6, 6, 64, 128)     # two heads to a block
    # several heads to a block only where key and value heads are as many
    assert not gd.gated_delta_supported(16, 32, 64, 128)
    assert not gd.gated_delta_supported(16, 32, 16, 128)
    assert not gd.gated_delta_supported(8, 8, 16, 128)  # eight to a tile
    assert not gd.gated_delta_supported(16, 24, 128, 128)
    assert not gd.gated_delta_supported(16, 32, 128, 128, jnp.float16)


# ---------------------------------------------------------------------------
# the share: the ranks' parts of one layer add up to the whole layer
# ---------------------------------------------------------------------------


def _whole_layer(experts, seed=3, tokens=48):
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    matrix = lambda key, *shape: (jax.random.normal(key, shape)
                                  / math.sqrt(shape[-2]))
    return {
        "m": jax.random.normal(keys[0], (2, tokens // 2, D)),
        "router": {"kernel": matrix(keys[1], D, experts)},
        "expert_wi": matrix(keys[2], experts, D, FF),
        "expert_wg": matrix(keys[3], experts, D, FF),
        "expert_wo": matrix(keys[4], experts, FF, D),
        "shared_wi": {"kernel": matrix(keys[5], D, FF)},
        "shared_wg": {"kernel": matrix(keys[6], D, FF)},
        "shared_wo": {"kernel": matrix(keys[7], FF, D)},
        "shared_gate": {"kernel": matrix(keys[8], D, 1)},
    }


def _share_of(layer, experts, ep_size, rank, shared=True):
    """Rank ``rank``'s part of the layer's result, by ``MoEMLP`` holding
    its slice of the routed tables (and the whole shared expert)."""
    n_local = experts // ep_size
    held = slice(rank * n_local, (rank + 1) * n_local)
    moe = MoEMLP(n_experts=experts, d_ff=FF, k=4, ep_size=ep_size,
                 ep_rank=rank, dropless=True, gated=True, norm_topk_prob=True,
                 shared_d_ff=FF if shared else 0, shared_gate=shared,
                 dtype=jnp.float32)
    params = {name: (leaf[held] if name.startswith("expert_") else leaf)
              for name, leaf in layer.items()
              if name != "m" and (shared or not name.startswith("shared_"))}
    return moe.apply({"params": params}, layer["m"]).reshape(-1, D)


@pytest.mark.parametrize("ep_size", [2, 4, 16])
def test_the_ranks_shares_add_up_with_the_shared_expert_counted_once(ep_size):
    """Guide section 4: the parts of the result that all the shares give,
    with what every chip computes alike — the shared expert — counted once,
    add up to what the uncut reference gives for the whole layer."""
    experts = 32
    layer = _whole_layer(experts)
    tables = {name: leaf for name, leaf in layer.items() if name != "m"}
    hyper = {"experts_per_token": 4, "first_expert": 0, "shared": True,
             "shared_gate": True}
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
    np.testing.assert_allclose(sum(routed) + shared, whole, atol=3e-5, rtol=0)
    np.testing.assert_allclose(sum(shares) - (ep_size - 1) * shared, whole,
                               atol=3e-5 * ep_size, rtol=0)


# ---------------------------------------------------------------------------
# each new piece against a hand-rolled form
# ---------------------------------------------------------------------------


def test_the_shared_expert_by_hand():
    layer = _whole_layer(8, seed=9, tokens=12)
    got = (_share_of(layer, 8, 1, 0) - _share_of(layer, 8, 1, 0, shared=False))
    m = np.asarray(layer["m"], np.float64).reshape(-1, D)
    mat = lambda name: np.asarray(layer[name]["kernel"], np.float64)
    silu = lambda x: x / (1 + np.exp(-x))
    want = (silu(m @ mat("shared_wg")) * (m @ mat("shared_wi"))) @ mat(
        "shared_wo") / (1 + np.exp(-(m @ mat("shared_gate"))))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_the_capacity_path_adds_the_shared_expert_too():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, D))
    with_shared = MoEMLP(n_experts=4, d_ff=FF, k=2, shared_d_ff=FF,
                         dtype=jnp.float32)
    params = with_shared.init(jax.random.PRNGKey(1), x)["params"]
    assert "shared_wg" not in params and "shared_gate" not in params
    routed = {k: v for k, v in params.items() if not k.startswith("shared")}
    without = MoEMLP(n_experts=4, d_ff=FF, k=2, dtype=jnp.float32)
    extra = (with_shared.apply({"params": params}, x)
             - without.apply({"params": routed}, x))
    want = jax.nn.silu(x @ params["shared_wi"]["kernel"]) @ params[
        "shared_wo"]["kernel"]
    np.testing.assert_allclose(extra, want, atol=1e-5, rtol=0)


def test_the_zero_centred_norm_by_hand():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    got = RMSNorm(jnp.float32, jnp.float32, EPS, True).apply(
        {"params": {"scale": w}}, x)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + EPS) * (
        1 + np.asarray(w))
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    plain = RMSNorm(jnp.float32, jnp.float32, EPS).apply(
        {"params": {"scale": w}}, x)
    assert float(jnp.abs(plain - got).max()) > 0.1


def test_the_causal_convolution_by_hand():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    got = causal_depthwise_conv(x, taps)
    want = np.zeros(x.shape)
    for t in range(9):
        for j in range(4):
            if t - (3 - j) >= 0:
                want[:, t] += np.asarray(taps[j]) * np.asarray(x[:, t - (3 - j)])
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    # causal: position 4's output does not see position 5
    moved = causal_depthwise_conv(x.at[:, 5].add(1.0), taps)
    np.testing.assert_array_equal(moved[:, :5], got[:, :5])


def _attention(params_seed=0, **overrides):
    cfg = qwen3_next(**overrides)[0].cfg
    attn = Attention(cfg, None, None, True)
    x = jax.random.normal(jax.random.PRNGKey(params_seed), (2, 12, D))
    params = attn.init(jax.random.PRNGKey(1), x)["params"]
    return attn, params, x


def test_the_rotation_leaves_the_rest_of_a_head_alone():
    """Lanes ``rotary_dim ..`` of q and k reach the attention unrotated:
    with every lane of the rotated part zeroed out of q and k's kernels the
    layer does not see positions at all."""
    attn, params, x = _attention()
    zeroed = jax.tree.map(jnp.copy, params)
    for name in "qk":
        kernel = zeroed[name]["kernel"]
        zeroed[name]["kernel"] = kernel.at[..., :ROTARY].set(0.0)
    whole, _, _ = _attention(rotary_dim=None)
    a = attn.apply({"params": zeroed}, x)
    b = whole.apply({"params": zeroed}, x)
    assert float(jnp.abs(a - b).max()) > 1e-3     # the whole head rotates
    unrotated, _, _ = _attention(rope_theta=None)
    np.testing.assert_allclose(
        a, unrotated.apply({"params": zeroed}, x), atol=1e-5, rtol=0)


def test_the_partial_rotation_by_hand():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    got = jnp.concatenate([rope_rotate(x[..., :4], THETA), x[..., 4:]], -1)
    want = np.array(x, np.float64)
    for t in range(6):
        for i, inv in enumerate([1.0, THETA ** -0.5]):   # 2 of 4 lanes a pair
            c, s = math.cos(t * inv), math.sin(t * inv)
            a, b = np.array(x[0, t, :, i]), np.array(x[0, t, :, i + 2])
            want[0, t, :, i], want[0, t, :, i + 2] = a * c - b * s, b * c + a * s
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_the_output_gate_by_hand():
    """With the gate's half of W_q zero the gate is one half everywhere."""
    attn, params, x = _attention()
    ungated, _, _ = _attention(attn_gate=False)
    halves = params["q"]["kernel"]
    plain = {**params, "q": {"kernel": halves[..., :HEAD_DIM]}}
    closed = {**params, "q": {"kernel": halves.at[..., HEAD_DIM:].set(0.0)}}
    o_proj = lambda p: p["o"]["kernel"]
    np.testing.assert_allclose(
        attn.apply({"params": closed}, x),
        0.5 * ungated.apply({"params": plain}, x), atol=1e-5, rtol=0)
    assert o_proj(params).shape == (HEADS, HEAD_DIM, D)
    assert float(jnp.abs(attn.apply({"params": params}, x)
                         - attn.apply({"params": closed}, x)).max()) > 1e-3


def test_the_gated_delta_layer_by_hand():
    """One linear-attention layer against the recurrence written with
    loops, float64."""
    cfg = qwen3_next()[0].cfg
    layer = GatedDeltaNet(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, D))
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.apply({"params": p}, x))[0]
    f = lambda a: np.asarray(a, np.float64)
    silu = lambda a: a / (1 + np.exp(-a))
    kw, vw = LIN_K * LIN_DIM, LIN_V * LIN_DIM
    qkvz, ba = f(x[0]) @ f(p["in_proj_qkvz"]["kernel"]), f(x[0]) @ f(
        p["in_proj_ba"]["kernel"])
    mixed = np.zeros((10, 2 * kw + vw))
    for t in range(10):
        for j in range(TAPS):
            if t - (TAPS - 1 - j) >= 0:
                mixed[t] += f(p["conv"][j]) * qkvz[t - (TAPS - 1 - j),
                                                   :2 * kw + vw]
    mixed = silu(mixed)
    z = qkvz[:, 2 * kw + vw:].reshape(10, LIN_V, LIN_DIM)
    q = mixed[:, :kw].reshape(10, LIN_K, LIN_DIM)
    k = mixed[:, kw:2 * kw].reshape(10, LIN_K, LIN_DIM)
    v = mixed[:, 2 * kw:].reshape(10, LIN_V, LIN_DIM)
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(LIN_DIM), unit(k)
    beta = 1 / (1 + np.exp(-ba[:, :LIN_V]))
    alpha = np.exp(-np.exp(f(p["A_log"])) * np.log1p(
        np.exp(ba[:, LIN_V:] + f(p["dt_bias"]))))
    y = np.zeros((10, LIN_V, LIN_DIM))
    for h in range(LIN_V):
        state, kh = np.zeros((LIN_DIM, LIN_DIM)), h // (LIN_V // LIN_K)
        for t in range(10):
            state = alpha[t, h] * state
            delta = beta[t, h] * (v[t, h] - state.T @ k[t, kh])
            state = state + np.outer(k[t, kh], delta)
            o = state.T @ q[t, kh]
            y[t, h] = (f(p["norm"]) * o / np.sqrt((o * o).mean() + EPS)
                       * silu(z[t, h]))
    want = y.reshape(10, vw) @ f(p["out_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# ---------------------------------------------------------------------------
# the paths that cannot take the new options refuse them by name
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overrides,match", [
    ({"decode": True}, "mixer_layers.*decode"),
    ({"sp_axis": "sp"}, "mixer_layers.*sp_axis"),
    ({"tp_axis": "tp", "tp_size": 2}, "mixer_layers.*tensor-parallel"),
    ({"n_passes": 2}, "mixer_layers.*looped"),
    ({"decode": True, "mixer_layers": None, "n_kv_heads": HEADS},
     "attn_gate and rotary_dim"),
])
def test_the_other_paths_refuse_the_new_options(overrides, match):
    model = qwen3_next(**overrides)[0]
    with pytest.raises(NotImplementedError, match=match):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))


def test_pipeline_stages_refuse_the_new_options():
    from bagua_tpu.parallel.pipeline import PipelinedTransformerLM

    for overrides in ({}, {"mixer_layers": None}):
        cfg = qwen3_next(**overrides)[0].cfg
        with pytest.raises(NotImplementedError,
                           match="mixer_layers.*norm_zero_centered"):
            PipelinedTransformerLM(cfg, pp_size=2).init(
                jax.random.PRNGKey(0), jnp.zeros((2, 9), jnp.int32))


def test_linear_layers_need_their_sizes():
    model = qwen3_next(linear_value_heads=3)[0]
    with pytest.raises(ValueError, match="linear_key_heads"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# ---------------------------------------------------------------------------
# tracing: areas, scopes, gauges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path,area", [
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/linear_attn/"
     "in_proj_qkvz/dot_general", "linattn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_1/"
     "linear_attn/jit(_kernel_bwd)/gdn_bwd/pallas_call", "linattn"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_2/"
     "linear_attn_norm/mul", "linattn"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_3/attn/q/"
     "dot_general", "attn"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/mlp/"
     "bagua.moe/shared/shared_wi/dot_general", "moe/shared"),
])
def test_area_of_reads_the_new_modules(path, area):
    assert obs_spans.area_of(path) == area
    assert area in obs_spans.AREAS


def test_a_traced_step_carries_the_scopes_and_sets_the_gauges():
    model, _ = qwen3_next(layers=2)
    params, tokens = seeded(model)
    text = jax.jit(jax.grad(lm_loss_fn(model))).lower(
        params, {"tokens": tokens}).as_text(debug_info=True)
    assert "linear_attn" in text and "bagua.moe/shared" in text
    assert counters.get("linattn/layers") == 1
    assert counters.get("linattn/chunk") == gd.CHUNK == 64
    assert counters.get("linattn/key_heads") == LIN_K
    assert counters.get("linattn/value_heads") == LIN_V
    # the CPU: the rows between the projections are the jax.numpy form
    assert counters.get("linattn/row_kernel_layers") == 0
    assert counters.get("attn/rotary_dim") == ROTARY
    assert counters.get("attn/full_layers") == 1
    assert counters.get("attn/rope_kernel_layers") == 0
    assert counters.get("attn/head_norm_kernel_layers") == 0
    assert counters.get("moe/shared_width") == FF
