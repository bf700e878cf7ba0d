"""The looped decoder (``TransformerConfig(n_passes=, post_norms=,
exit_gate=)`` + ``looped_lm_loss_fn``) against its plain reference
(``perfbench/reference/ouro.py``: float32 ``jax.numpy``, imports nothing of
``bagua_tpu``) on seeded random weights at tiny widths, and what the
mechanism promises by itself: one parameter tree of ``L`` blocks used ``T``
times, a gradient that is the sum over the passes, an exit distribution
that sums to one, the same gradients under every remat policy, a per-token
loss tail with no gather, and a data-parallel step that equals the
one-rank step."""

import re
import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu import BaguaTrainer
from bagua_tpu.algorithms import GradientAllReduceAlgorithm
from bagua_tpu.models import transformer
from bagua_tpu.models.generate import generate
from bagua_tpu.models.transformer import (
    Block, RMSNorm, TransformerConfig, TransformerLM, exit_distribution,
    lm_loss_fn, looped_lm_loss_fn, token_loss_tail,
)
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.parallel.mesh import build_mesh

ROOT = Path(__file__).resolve().parents[1]
VOCAB, D, HEADS, LAYERS, FF, SEQ = 64, 32, 4, 2, 64, 16
BETA = 0.05


def _reference():
    sys.path.insert(0, str(ROOT))
    from perfbench import cells

    return cells.load_plugin("reference", "ouro")


def hyper(passes, **over):
    return {"passes": passes, "rope_theta": 1e6,
            "rms_norm_eps": 1e-6, "beta": BETA, "post_norms": True,
            "feed_normed": True, "uniform_weights": False,
            "last_takes_rest": True, "parts_dtype": jnp.float32, **over}


def looped(passes=4, **over):
    kw = dict(vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
              d_ff=FF, max_seq_len=SEQ, rope_theta=1e6, norm_eps=1e-6,
              n_passes=passes, post_norms=True, exit_gate=True,
              dtype=jnp.float32)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


def seeded(model, seed=0, batch=3):
    """Weights moved off their defaults (norm scales off one, the gate's
    bias off zero), so that no term of the mathematics is switched off."""
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0,
                                VOCAB)
    params = model.init(jax.random.PRNGKey(seed + 1), tokens[:, :-1])["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return params, tokens


def assert_close(got, want, err_msg=""):
    """Float32 gradients of one function computed two ways: equal but for
    the order of the sums (a few ulp of the leaf's largest entry)."""
    np.testing.assert_allclose(
        got, want, rtol=1e-3, atol=1e-4 * float(np.abs(want).max()) + 1e-30,
        err_msg=err_msg)


def _flat(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module", params=[4, 1], ids=["T4", "T1"])
def both(request):
    passes = request.param
    model = looped(passes)
    params, tokens = seeded(model)
    reference = _reference()
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(looped_lm_loss_fn(model, BETA))(
            params, {"tokens": tokens})
        want = jax.value_and_grad(reference.loss_fn)(params, tokens,
                                                     hyper(passes))
    return passes, model, params, tokens, got, want


_LEAVES = sorted(_flat(jax.eval_shape(
    lambda: looped().init(jax.random.PRNGKey(0),
                          jnp.zeros((1, SEQ), jnp.int32))["params"])))


def test_the_parameter_tree_holds_each_layer_once(both):
    passes, _, params, *_ = both
    assert sorted(k for k in params if k.startswith("block_")) == [
        f"block_{i}" for i in range(LAYERS)]
    assert set(params) == {"embed", "final_norm", "lm_head", "exit_gate",
                           *(f"block_{i}" for i in range(LAYERS))}
    assert set(params["block_0"]) == {
        "attn_norm", "attn", "attn_post_norm", "mlp_norm", "mlp",
        "mlp_post_norm"}
    assert params["exit_gate"]["kernel"].shape == (D, 1)
    assert params["exit_gate"]["bias"].shape == (1,)
    # the tree does not know the number of passes
    assert jax.tree.structure(params) == jax.tree.structure(
        seeded(looped(5 - passes))[0])


def test_loss_agrees_with_the_reference(both):
    *_, got, want = both
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_agrees_with_the_reference(both, leaf):
    passes, *_, got, want = both
    g, w = _flat(got[1])[leaf], _flat(want[1])[leaf]
    if passes == 1 and leaf.startswith("exit_gate"):
        # one pass: the gate is read by nothing
        assert not np.any(g) and not np.any(w)
        return
    assert_close(g, w)


def test_logits_and_gates_agree_with_the_reference(both):
    passes, model, params, tokens, *_ = both
    reference = _reference()
    with jax.default_matmul_precision("highest"):
        logits, gates = model.apply({"params": params}, tokens[:, :-1])
        want_logits, want_gates = reference.logits_fn(
            params, tokens[:, :-1], hyper(passes))
    assert logits.shape == (passes, 3, SEQ, VOCAB)
    assert gates.shape == (3, SEQ, passes)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(gates, want_gates, atol=2e-5)


def test_one_pass_without_post_norms_is_the_plain_loss():
    plain = TransformerLM(TransformerConfig(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS, d_ff=FF,
        max_seq_len=SEQ, rope_theta=1e6, dtype=jnp.float32))
    one = looped(1, post_norms=False)
    params, tokens = seeded(one)
    rest = {k: v for k, v in params.items() if k != "exit_gate"}
    np.testing.assert_allclose(
        looped_lm_loss_fn(one, BETA)(params, {"tokens": tokens}),
        lm_loss_fn(plain)(rest, {"tokens": tokens}), rtol=1e-6)


# ---- the mechanism by itself -------------------------------------------------


class Untied(nn.Module):
    """``TransformerLM``'s looped stack with a block of its own for every
    (pass, layer): what the shared weights are untied FROM."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, name="embed",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)(tokens)
        final_norm = RMSNorm(cfg.dtype, cfg.param_dtype, cfg.norm_eps,
                             name="final_norm")
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")
        gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate")
        logits, gates = [], []
        for t in range(cfg.n_passes):
            for i in range(cfg.n_layers):
                x = Block(cfg, None, None, i, name=f"pass_{t}_block_{i}")(x)
            x = final_norm(x)
            logits.append(head(x).astype(jnp.float32))
            gates.append(gate(x.astype(jnp.float32))[..., 0])
        return jnp.stack(logits), jnp.stack(gates, axis=-1)


@pytest.fixture(scope="module")
def tied_and_untied():
    model = looped(4)
    params, tokens = seeded(model, seed=3)
    untied = {k: v for k, v in params.items() if not k.startswith("block_")}
    for t in range(4):
        for i in range(LAYERS):
            untied[f"pass_{t}_block_{i}"] = params[f"block_{i}"]
    batch = {"tokens": tokens}
    with jax.default_matmul_precision("highest"):
        tied = jax.value_and_grad(looped_lm_loss_fn(model, BETA))(params,
                                                                  batch)
        free = jax.value_and_grad(looped_lm_loss_fn(Untied(model.cfg), BETA))(
            untied, batch)
    return tied, free


def test_the_untied_copy_is_the_same_function(tied_and_untied):
    tied, free = tied_and_untied
    np.testing.assert_allclose(tied[0], free[0], rtol=1e-6)
    for name in ("embed", "final_norm", "lm_head", "exit_gate"):
        for a, b in zip(jax.tree.leaves(tied[1][name]),
                        jax.tree.leaves(free[1][name])):
            assert_close(a, b)


@pytest.mark.parametrize("leaf", sorted(
    n.split("/", 1)[1] for n in _LEAVES if n.startswith("block_0/")))
@pytest.mark.parametrize("layer", range(LAYERS))
def test_a_shared_weights_gradient_is_the_sum_over_the_passes(
        tied_and_untied, layer, leaf):
    tied, free = tied_and_untied
    shared = _flat(tied[1][f"block_{layer}"])[leaf]
    per_pass = [_flat(free[1][f"pass_{t}_block_{layer}"])[leaf]
                for t in range(4)]
    assert_close(shared, sum(per_pass))
    # and no pass is idle: each adds its own, different part
    sizes = [float(np.abs(g).max()) for g in per_pass]
    assert min(sizes) > 0 and len({round(s, 9) for s in sizes}) == 4


@pytest.mark.parametrize("passes", [1, 2, 4, 7])
def test_the_exit_distribution_sums_to_one(passes):
    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(passes),
                                    (5, 11, passes))
    p, log_p = exit_distribution(gates)
    assert p.dtype == jnp.float32 and p.shape == gates.shape
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    lam = np.asarray(jax.nn.sigmoid(gates), np.float64)
    stay = np.cumprod(1.0 - lam, axis=-1)
    # p_T is what is left, whatever the last gate says
    near = dict(rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(p[..., -1], stay[..., -2] if passes > 1
                               else np.ones((5, 11)), **near)
    if passes > 1:
        np.testing.assert_allclose(p[..., 0], lam[..., 0], **near)
        np.testing.assert_allclose(p[..., 1:-1],
                                   lam[..., 1:-1] * stay[..., :-2], **near)
        moved = exit_distribution(gates.at[..., -1].add(5.0))[0]
        np.testing.assert_array_equal(moved, p)


def test_the_exit_distribution_survives_saturated_gates():
    gates = jnp.array([[200.0, -200.0, 0.0], [-200.0, 200.0, 0.0],
                       [-200.0, -200.0, 0.0]])
    loss = lambda g: -jnp.sum(jnp.prod(jnp.stack(exit_distribution(g)), 0))
    p, _ = exit_distribution(gates)
    np.testing.assert_allclose(p, np.eye(3), atol=1e-30)
    assert np.all(np.isfinite(jax.grad(loss)(gates)))


@pytest.mark.parametrize("policy", [None, "dots", "dots_no_batch"])
def test_each_remat_policy_gives_the_gradients_of_no_remat(policy):
    plain = looped(4)
    params, tokens = seeded(plain, seed=5)
    batch = {"tokens": tokens}
    rematted = looped(4, remat=True, remat_policy=policy)
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(looped_lm_loss_fn(plain, BETA))(params,
                                                                  batch)
        got = jax.jit(jax.value_and_grad(looped_lm_loss_fn(rematted, BETA)))(
            params, batch)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        assert_close(a, b, str(path))
    # the scanned body replays each of its blocks
    text = str(jax.make_jaxpr(jax.grad(looped_lm_loss_fn(rematted, BETA)))(
        params, batch))
    assert len(re.findall(r"\b(?:checkpoint|remat2?)\[", text)) >= LAYERS


def test_the_token_tail_is_the_mean_tail_before_its_mean():
    logits = 4.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 8, 96))
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 96)
    per_token = token_loss_tail(logits, targets)
    assert per_token.shape == (2, 8) and per_token.dtype == jnp.float32
    np.testing.assert_allclose(
        per_token, optax.softmax_cross_entropy_with_integer_labels(
            logits, targets), rtol=1e-6)
    np.testing.assert_allclose(per_token.mean(),
                               transformer.loss_tail(logits, targets),
                               rtol=1e-6)


@pytest.mark.parametrize("shape", [(2, 8, 96), (1, 16, 512)],
                         ids=lambda s: "x".join(map(str, s)))
def test_the_token_tail_holds_no_gather_and_no_scatter(shape):
    """The weighted per-token tail, forward and backward, from bf16 logits
    as the head makes them: no gather and no scatter, traced, lowered or
    compiled.  (That the TPU compiler writes no float32 array of the logits'
    size for it is held where the real compiler is:
    ``tests/test_flash_attention_v5e.py``.)"""
    b, s, vocab = shape
    logits = jax.random.normal(jax.random.PRNGKey(0), shape).astype(
        jnp.bfloat16)
    targets = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, vocab)
    weights = jax.random.uniform(jax.random.PRNGKey(2), (b, s))

    def weighed(logits, targets, weights):
        return jnp.mean(weights * token_loss_tail(
            logits.astype(jnp.float32), targets))

    grad = jax.value_and_grad(weighed)
    args = (logits, targets, weights)
    lowered = jax.jit(grad).lower(*args)
    for text in (str(jax.make_jaxpr(grad)(*args)), lowered.as_text()):
        assert "gather" not in text and "scatter" not in text
    assert not re.search(r" (gather|scatter)\(", lowered.compile().as_text())
    # the lens sees them where they are: optax's form holds both
    by_gather = lambda l, t, w: jnp.mean(
        w * optax.softmax_cross_entropy_with_integer_labels(
            l.astype(jnp.float32), t))
    parent = jax.jit(jax.value_and_grad(by_gather)).lower(*args).as_text()
    assert "gather" in parent and "scatter" in parent


@pytest.mark.parametrize("family", ["bert", "gpt2_remat", "rope"])
def test_defaults_leave_the_older_models_as_they_were(family):
    """The new fields at their defaults, and the same fields spelled out,
    build one model: the same parameter tree, the same jaxpr of loss and
    gradient, the same bits out; no pass is named and no gauge set."""
    from bagua_tpu.telemetry import counters

    kw = dict(vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
              d_ff=FF, max_seq_len=SEQ, dtype=jnp.float32)
    if family == "gpt2_remat":
        kw.update(remat=True, remat_policy="dots_no_batch")
    if family == "rope":
        kw.update(rope_theta=1e4, qk_norm=True)
    default = TransformerLM(TransformerConfig(**kw))
    explicit = TransformerLM(TransformerConfig(
        **kw, n_passes=1, post_norms=False, exit_gate=False))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, VOCAB)
    params = default.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]
    other = explicit.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]
    assert jax.tree.structure(params) == jax.tree.structure(other)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(other)):
        np.testing.assert_array_equal(a, b)
    assert set(params["block_0"]) == {"attn_norm", "attn", "mlp_norm", "mlp"}
    assert "exit_gate" not in params
    before = dict(counters.snapshot())
    run = lambda m: re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
        jax.value_and_grad(lm_loss_fn(m)))(params, {"tokens": tokens})))
    text = run(default)
    assert text == run(explicit)
    assert "loop_" not in text and "exit" not in text
    assert {k: v for k, v in counters.snapshot().items()
            if k.startswith("loop/")} == {
                k: v for k, v in before.items() if k.startswith("loop/")}
    logits = default.apply({"params": params}, tokens[:, :-1])
    assert logits.shape == (2, SEQ, VOCAB)
    np.testing.assert_array_equal(
        logits, explicit.apply({"params": params}, tokens[:, :-1]))


def test_a_looped_model_without_the_gate_returns_the_last_pass():
    gated = looped(3)
    params, tokens = seeded(gated, seed=7)
    bare = looped(3, exit_gate=False)
    rest = {k: v for k, v in params.items() if k != "exit_gate"}
    logits = bare.apply({"params": rest}, tokens[:, :-1])
    assert logits.shape == (3, SEQ, VOCAB)
    np.testing.assert_allclose(
        logits, gated.apply({"params": params}, tokens[:, :-1])[0][-1],
        rtol=1e-6, atol=1e-6)


def test_tracing_sets_the_gauges_and_names_the_pass():
    from bagua_tpu.telemetry import counters

    model = looped(4, remat=True)
    params, tokens = seeded(model)
    text = jax.jit(jax.grad(looped_lm_loss_fn(model, BETA))).lower(
        params, {"tokens": tokens}).as_text(debug_info=True)
    assert counters.snapshot()["loop/passes"] == 4
    assert counters.snapshot()["loop/shared_layers"] == LAYERS
    paths = set(re.findall(r'"([^"]*/[^"]*)"', text))
    inside = {p for p in paths if obs_spans.in_loop(p)}
    # a pass names no area and hides none: the modules inside it do, and
    # the heads and the exits lie outside it
    assert {obs_spans.area_of(p) for p in inside} >= {"attn", "mlp"}
    assert not {obs_spans.area_of(p) for p in inside} & {"head", "exit",
                                                          "embed"}
    assert {obs_spans.area_of(p) for p in paths - inside} >= {"head", "exit"}
    assert not re.search(r"bagua\.\w+", obs_spans.LOOP_SCOPE)
    assert not obs_spans.in_loop("jit(f)/TransformerLM/final_norm/mul")
    assert not obs_spans.in_loop(None)


def test_the_passes_are_one_scanned_body():
    """However many passes run, the traced program holds each block once:
    a scan of that length over one body."""
    def text(passes):
        model = looped(passes)
        params, tokens = seeded(model)
        return str(jax.make_jaxpr(
            lambda p, t: model.apply({"params": p}, t))(params,
                                                        tokens[:, :-1]))

    four, seven = text(4), text(7)
    assert "length=4" in four and "length=7" in seven
    assert len(four.splitlines()) == len(seven.splitlines())


@pytest.mark.parametrize("paged", [False, True])
def test_the_decode_paths_refuse_more_than_one_pass(paged):
    import dataclasses

    # learned positions: RoPE is refused by the decode paths before this
    model = looped(2, rope_theta=None)
    prompt = jnp.zeros((1, 4), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    with pytest.raises(NotImplementedError, match="n_passes"):
        if paged:
            cfg = dataclasses.replace(model.cfg, decode=True, page_size=4,
                                      num_pages=8)
            TransformerLM(cfg).init(jax.random.PRNGKey(0), prompt[:, :1])
        else:
            generate(model, params, prompt, 2)


def test_the_pipelined_stack_refuses_a_looped_one():
    from bagua_tpu.parallel.pipeline import PipelinedTransformerLM

    cfg = looped(2, rope_theta=None).cfg
    with pytest.raises(NotImplementedError, match="n_passes=2"):
        PipelinedTransformerLM(cfg, pp_size=1).init(
            jax.random.PRNGKey(0), jnp.zeros((2, SEQ + 1), jnp.int32))


# ---- through the trainer: a weight used four times in the bucket plan -------


def _one_step(dp, overlap, accum_steps=1):
    model = looped(4)
    params, tokens = seeded(model, seed=11, batch=8)
    trainer = BaguaTrainer(
        looped_lm_loss_fn(model, BETA), optax.adamw(1e-2),
        GradientAllReduceAlgorithm(hierarchical=False),
        mesh=build_mesh({"dp": dp}, jax.devices()[:dp]), autotune=False,
        overlap=overlap, accum_steps=accum_steps, bucket_bytes=4096)
    state = trainer.init(params)
    with jax.default_matmul_precision("highest"):
        for _ in range(2):
            state, loss = trainer.train_step(
                state, trainer.shard_batch({"tokens": tokens}))
            loss = float(loss)
    return loss, trainer.unstack_params(state), trainer


@pytest.fixture(scope="module")
def one_rank():
    return _one_step(1, "off")


@pytest.mark.parametrize("overlap", ["auto", "off", "on"])
def test_a_dp4_step_equals_the_dp1_step_on_the_same_global_batch(one_rank,
                                                                  overlap):
    want_loss, want, _ = one_rank
    loss, got, trainer = _one_step(4, overlap)
    assert len(trainer._plan.buckets) > 4     # several buckets to order
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5,
                                   err_msg=str(path))


def test_accumulation_under_the_readiness_plan_equals_the_plain_step(
        one_rank):
    want_loss, want, _ = one_rank
    loss, got, _ = _one_step(4, "auto", accum_steps=2)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


# ---- the comparison that decides ``correct``: the reference's own pieces ----

_SWITCHED = {"no_entropy": {"beta": 0.0}, "no_post_norms": {"post_norms": False},
             "unnormed_fed_on": {"feed_normed": False},
             "uniform_weights": {"uniform_weights": True},
             "last_exit_gated": {"last_takes_rest": False}}


@pytest.mark.parametrize("fault", ["published", *_SWITCHED])
def test_a_switch_as_an_argument_is_the_switch_as_a_constant(fault):
    """``replay_losses`` hands the switches that change no shape to ONE
    compiled program as arrays (``_pick`` by a ``where``); written into the
    program as Python values they choose a branch.  Same loss, same
    gradient: the faults tool's readings are the plain reference's."""
    reference = _reference()
    params, tokens = seeded(looped())
    h = hyper(4, **_SWITCHED.get(fault, {}))
    want = jax.value_and_grad(reference.loss_fn)(params, tokens, h)
    switches, fixed = reference._split(h)
    got = reference._loss_and_grads(reference.stacked(params), tokens,
                                    switches, fixed=fixed)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    got_leaves = reference.watched(got[1])
    for name, leaf in reference.watched(want[1]).items():
        assert_close(got_leaves[name], leaf, err_msg=name)
    if fault != "published":  # and the fault is one
        sound = reference.loss_fn(params, tokens, hyper(4))
        assert abs(float(want[0]) - float(sound)) > 1e-4


def test_the_watched_leaves():
    """The gate's kernel and bias are ONE leaf; the first and the last
    layer's seven matrices and the head; a stacked tree reads the same."""
    reference = _reference()
    params, _ = seeded(looped())
    leaves = reference.watched(params, reference.CHANGE_ALSO)
    assert set(leaves) == {
        "exit_gate", "lm_head/kernel", "final_norm/scale",
        *(f"block_{i}/{m}" for i in (0, LAYERS - 1)
          for m in reference.LAYER_MATRICES)}
    np.testing.assert_array_equal(leaves["exit_gate"], np.concatenate([
        np.ravel(params["exit_gate"]["kernel"]), params["exit_gate"]["bias"]]))
    again = reference.watched(reference.stacked(params), reference.CHANGE_ALSO)
    for name, leaf in leaves.items():
        np.testing.assert_array_equal(again[name], leaf, err_msg=name)


@pytest.mark.parametrize("losses,verdict", [
    ([1.0, 2.0, 3.0], True),
    ([1.0009, 2.0, 3.0], True), ([1.0011, 2.0, 3.0], False),
    ([1.0, 2.011, 3.0], False),
    ([1.0, 2.0, 9.0], True),              # a step without a limit is not held
    ([1.0, 2.0, float("nan")], False),    # but it is a number
    ([1.0, 2.0], False)], ids=str)
def test_agree_holds_the_steps_that_have_a_limit(losses, verdict):
    reference = _reference()
    assert reference.agree(losses, [1.0, 2.0, 3.0],
                           tolerance=(0.001, 0.01)) is verdict


def test_the_replay_hands_back_its_first_gradient_and_its_change():
    """Three AdamW steps of the reference from the program's tree: the loss
    falls, the first gradient is ``loss_fn``'s, the change is what three
    updates of about the learning rate make, and weights rounded to bfloat16
    leave a norm's scale where it was (no distance: refused)."""
    reference = _reference()
    params, tokens = seeded(looped())
    params["final_norm"]["scale"] = jnp.ones_like(params["final_norm"]["scale"])
    seen = {}
    optimizer = {"name": "adamw", "kwargs": {"learning_rate": 1e-4}}
    copy = lambda: jax.tree.map(jnp.copy, params)
    losses = reference.replay_losses(
        copy(), tokens, 3, optimizer, tokens.shape[0], hyper(4),
        first_gradient=lambda g: seen.update(gradient=g),
        last_change=lambda c: seen.update(change=c))
    assert losses[0] > losses[1] > losses[2]
    want = reference.watched(jax.grad(reference.loss_fn)(params, tokens,
                                                         hyper(4)))
    for name, leaf in want.items():
        assert_close(seen["gradient"][name], leaf, err_msg=name)
    for name, leaf in seen["change"].items():
        assert 0 < float(jnp.abs(leaf).max()) <= 3.2e-4, name
    same = reference.gradient_distance(seen["change"], seen["change"])
    assert reference.changes_agree({k: float(v) for k, v in same.items()})

    round_to_bf16 = lambda tree: jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, 8, 7), tree)
    reference.replay_losses(
        copy(), tokens, 3, optimizer, tokens.shape[0], hyper(4),
        round_weights=round_to_bf16,
        last_change=lambda c: seen.update(rounded=c))
    assert float(jnp.abs(seen["rounded"]["final_norm/scale"]).max()) == 0.0
    far = {k: float(v) for k, v in reference.gradient_distance(
        seen["change"], seen["rounded"]).items()}
    assert not reference.changes_agree(far)
    assert not np.isfinite(far["final_norm/scale"])
