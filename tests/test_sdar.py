"""SDAR's block-diffusion training step on the normal path: the
``flash_bd_*`` kernels and ``reference_attention`` under the four-quadrant
mask over the rows ``[x ; x~]``, positions that restart, RMSNorm on each head
of q and k, ``TransformerLM`` (the head on the noised half) +
``block_diffusion_loss_fn``, against a golden written out with loops,
against the benchmark's plain float32 reference
(``perfbench/reference/sdar.py``, which imports nothing of ``bagua_tpu``)
and against the generation view of the same model.  Tiny widths, seeded,
CPU; the kernels in interpret mode.
"""

import dataclasses
import importlib
import importlib.util
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.model_parallel.moe.layer import MoEMLP
from bagua_tpu.models.transformer import (
    Attention, TransformerConfig, TransformerLM, block_diffusion_loss_fn,
    block_diffusion_noise, rope_rotate,
)
from bagua_tpu.obs.spans import DIFFUSION_INPUT_SCOPE, area_of
from bagua_tpu.telemetry import counters
from internal import row_kernels

flash = importlib.import_module("bagua_tpu.ops.flash_attention")
ROOT = Path(__file__).resolve().parents[1]


def _reference():
    # the reference loads its shared pieces through ``perfbench.cells``
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    spec = importlib.util.spec_from_file_location(
        "sdar_reference", ROOT / "perfbench" / "reference" / "sdar.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def golden_mask(half: int, block: int) -> np.ndarray:
    """The four rules, a pair at a time."""
    mask = np.zeros((2 * half, 2 * half), bool)
    for r in range(2 * half):
        for c in range(2 * half):
            i, j = r % half, c % half
            if r < half and c < half:
                mask[r, c] = j // block <= i // block
            elif r >= half and c < half:
                mask[r, c] = j // block < i // block
            elif r >= half and c >= half:
                mask[r, c] = j // block == i // block
    return mask


def golden_attention(q, k, v, mask):
    """Softmax attention under a dense mask with grouped heads, float64."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, group, axis=2), np.repeat(v, group, axis=2)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = np.where(mask[None, None], scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", w, v)


# ---------------------------------------------------------------------------
# the mask, and the blocks the kernels visit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("half,block", [(8, 4), (12, 2), (16, 16), (6, 1)])
def test_the_mask_is_the_four_rules(half, block):
    mask = np.asarray(flash.block_diffusion_mask(2 * half, block))
    np.testing.assert_array_equal(mask, golden_mask(half, block))
    np.testing.assert_array_equal(np.asarray(ref.dense_mask(half, block)),
                                  mask)
    # L (L + B) of the (2 L)^2 pairs are visible
    assert mask.sum() == half * (half + block)


def _blocks(segments):
    out = []
    for lo, hi in segments:
        assert int(hi) >= int(lo), segments
        out += list(range(int(lo), int(hi)))
    return out


#: (half, diffusion block, block_q, block_k): halves that the kernel blocks
#: divide and halves they straddle, diffusion blocks under, at and over a
#: kernel block
GRIDS = [(8, 4, 4, 4), (16, 4, 8, 4), (16, 4, 4, 8), (12, 4, 8, 8),
         (12, 2, 8, 4), (24, 8, 16, 16), (20, 4, 8, 8), (32, 16, 8, 8),
         (48, 4, 32, 16), (40, 8, 16, 16)]


@pytest.mark.parametrize("side", ["k_blocks_of_a_q_block",
                                  "q_blocks_of_a_k_block"])
@pytest.mark.parametrize("half,block,bq,bk", GRIDS)
def test_the_loops_visit_the_blocks_with_a_visible_pair_and_no_other(
        half, block, bq, bk, side):
    """No block without a visible pair is visited, none with one is left
    out or visited twice, and a block walked without the mask is visible
    whole."""
    mask = golden_mask(half, block)
    n_q, n_k = 2 * half // bq, 2 * half // bk
    for own in range(n_q if side.startswith("k") else n_k):
        if side.startswith("k"):
            whole, edges = flash._bd_k_segments(own * bq, bq, bk, half, block)
            tile = lambda other: mask[own * bq:(own + 1) * bq,
                                      other * bk:(other + 1) * bk]
            others = n_k
        else:
            whole, edges = flash._bd_q_segments(own * bk, bq, bk, half, block)
            tile = lambda other: mask[other * bq:(other + 1) * bq,
                                      own * bk:(own + 1) * bk]
            others = n_q
        whole, edges = _blocks(whole), _blocks(edges)
        assert len(set(whole + edges)) == len(whole + edges)
        for other in range(others):
            if other in whole:
                assert tile(other).all()
            elif other in edges:
                assert tile(other).any()
            else:
                assert not tile(other).any()


# ---------------------------------------------------------------------------
# the three kernels and the fallback against the golden
# ---------------------------------------------------------------------------

HEADS, KV_HEADS, HEAD_DIM = 4, 2, 128
#: (half, diffusion block, block_q, block_k): a half the kernel's block does
#: not divide; a diffusion block as long as a kernel block; unequal blocks
KERNEL_CASES = {"ragged_half": (192, 4, 128, 128),
                "block_of_a_kernel_block": (256, 128, 128, 128),
                "unequal_blocks": (256, 4, 128, 256)}
QUANTITIES = ["o", "dq", "dk", "dv"]


def _qkvw(half, batch=1, heads=HEADS, kv_heads=KV_HEADS, d=HEAD_DIM):
    keys = jax.random.split(jax.random.PRNGKey(half), 4)
    shape = lambda h: (batch, 2 * half, h, d)
    return (jax.random.normal(keys[0], shape(heads)),
            jax.random.normal(keys[1], shape(kv_heads)),
            jax.random.normal(keys[2], shape(kv_heads)),
            jax.random.normal(keys[3], shape(heads)))


def _forward_and_gradients(attend, q, k, v, w):
    with jax.default_matmul_precision("highest"):
        o = attend(q, k, v)
        dq, dk, dv = jax.grad(
            lambda q, k, v: jnp.sum(w * attend(q, k, v)), (0, 1, 2))(q, k, v)
    return {"o": o, "dq": dq, "dk": dk, "dv": dv}


@pytest.fixture(scope="module", params=list(KERNEL_CASES))
def kernels_and_golden(request):
    half, block, bq, bk = KERNEL_CASES[request.param]
    q, k, v, w = _qkvw(half)
    kernel = _forward_and_gradients(
        lambda q, k, v: flash.block_diffusion_attention(
            q, k, v, jnp.float32, diffusion_block=block, block_q=bq,
            block_k=bk, interpret=True, force=True), q, k, v, w)
    fallback = _forward_and_gradients(
        lambda q, k, v: flash.reference_attention(
            q, k, v, jnp.float32, diffusion_block=block), q, k, v, w)
    mask = golden_mask(half, block)
    golden = {"o": golden_attention(q, k, v, mask)}
    return kernel, fallback, golden


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_the_kernels_are_the_dense_mask_attention(kernels_and_golden,
                                                  quantity):
    """``flash_bd_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` in interpret mode under
    grouped heads: the output against the float64 golden, the three
    gradients against the materialising form's (which the golden holds)."""
    kernel, fallback, golden = kernels_and_golden
    if quantity == "o":
        np.testing.assert_allclose(fallback["o"], golden["o"], atol=2e-5)
        np.testing.assert_allclose(kernel["o"], golden["o"], atol=2e-5)
    scale = float(jnp.abs(fallback[quantity]).max())
    np.testing.assert_allclose(kernel[quantity], fallback[quantity],
                               atol=2e-5 * max(scale, 1.0))


def test_nothing_of_the_rows_squared_is_written_on_the_kernel_path():
    """The kernel path's jaxpr holds three ``flash_bd_*`` calls and no
    array with a ``[2 L, 2 L]`` face, forward or backward; the fallback's
    does (the mask and the scores)."""
    half, block = 192, 4          # 384 rows: no other axis is as long
    q, k, v, w = _qkvw(half, batch=1)

    def faces(attend):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(w * attend(q, k, v)), (0, 1, 2)))(q, k, v)
        text = str(jaxpr)
        square = [v.aval.shape for eqn in jaxpr.jaxpr.eqns
                  for v in eqn.outvars
                  if getattr(v.aval, "shape", ())[-2:] == (2 * half, 2 * half)]
        return text, square

    text, square = faces(lambda q, k, v: flash.block_diffusion_attention(
        q, k, v, jnp.float32, diffusion_block=block, force=True))
    assert square == []
    for name in ("flash_bd_fwd", "flash_bd_bwd_dq", "flash_bd_bwd_dkv"):
        assert f"name={name}" in text
    assert "name=flash_fwd" not in text and "flash_win" not in text
    _, square = faces(lambda q, k, v: flash.reference_attention(
        q, k, v, jnp.float32, diffusion_block=block))
    assert square


def test_the_causal_calls_keep_their_names():
    q, k, v, _ = _qkvw(128, batch=1)
    text = str(jax.make_jaxpr(lambda q, k, v: flash.flash_attention(
        q, k, v, force=True))(q, k, v))
    assert "name=flash_fwd" in text and "flash_bd" not in text


@pytest.mark.parametrize("why,call", [
    ("odd rows", lambda: flash.block_diffusion_attention(
        jnp.zeros((1, 9, 2, 16)), jnp.zeros((1, 9, 2, 16)),
        jnp.zeros((1, 9, 2, 16)), diffusion_block=3)),
    ("half of no whole blocks", lambda: flash.block_diffusion_attention(
        jnp.zeros((1, 12, 2, 16)), jnp.zeros((1, 12, 2, 16)),
        jnp.zeros((1, 12, 2, 16)), diffusion_block=4)),
])
def test_rows_that_are_no_two_halves_of_blocks_are_refused(why, call):
    with pytest.raises(ValueError, match="diffusion blocks"):
        call()


def test_heads_that_fill_no_lane_block_take_the_fallback():
    """``force`` or not: head_dim 16 has no kernel; the result is the
    materialising form's."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(key, (1, 16, 2, 16)) for key in keys)
    got = flash.block_diffusion_attention(q, k, v, jnp.float32,
                                          diffusion_block=4, force=True)
    np.testing.assert_allclose(got, golden_attention(q, k, v,
                                                     golden_mask(8, 4)),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# positions that restart, the norm on each head
# ---------------------------------------------------------------------------

THETA, EPS = 1e6, 1e-6


def test_the_rope_kernel_rotates_each_half_from_zero():
    """What ``Attention`` does where the kernels run: ``[b, 2 L, h, d]`` ->
    ``[2 b, L, h, d]`` around one ``rope`` call, against ``rope_rotate`` on
    each half by itself."""
    from bagua_tpu.ops.rope import rope

    b, half, h, d = 2, 128, 2, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (b, 2 * half, h, d))
    got = rope(x.reshape(2 * b, half, h, d), THETA,
               interpret=True).reshape(x.shape)
    want = jnp.concatenate([rope_rotate(x[:, :half], THETA),
                            rope_rotate(x[:, half:], THETA)], axis=1)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and it is not the rotation of 2 L positions in a row
    assert float(jnp.abs(got - rope_rotate(x, THETA)).max()) > 0.1


def _attention_config(**kw):
    return TransformerConfig(
        vocab_size=97, d_model=48, n_heads=4, n_kv_heads=2, d_head=16,
        n_layers=2, d_ff=24, max_seq_len=64, dtype=jnp.float32,
        rope_theta=THETA, qk_norm="head", norm_eps=EPS,
        attention="block_diffusion", diffusion_block=4, **kw)


def test_attention_by_hand():
    """One ``Attention`` layer under the block-diffusion kind: per-head
    RMSNorm with ONE [head_dim] scale, each half rotated at 0 .. L-1, the
    dense mask, grouped heads."""
    cfg = _attention_config()
    half = 8
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 2 * half, cfg.d_model))
    layer = Attention(cfg)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert params["q_norm"]["scale"].shape == (16,)
    assert params["k_norm"]["scale"].shape == (16,)
    scales = jax.random.normal(jax.random.PRNGKey(2), (2, 16)) * 0.1 + 1.0
    params = {**params, "q_norm": {"scale": scales[0]},
              "k_norm": {"scale": scales[1]}}
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x)
        project = lambda n: jnp.einsum("bsd,dhe->bshe", x, params[n]["kernel"])

        def normed(t, scale):                   # over a head's own lanes
            t = np.asarray(t, np.float64)
            rms = np.sqrt((t ** 2).mean(-1, keepdims=True) + EPS)
            return jnp.asarray(t / rms * np.asarray(scale), jnp.float32)

        def rotated(t):
            return jnp.concatenate([rope_rotate(t[:, :half], THETA),
                                    rope_rotate(t[:, half:], THETA)], axis=1)

        q = rotated(normed(project("q"), scales[0]))
        k = rotated(normed(project("k"), scales[1]))
        o = golden_attention(q, k, project("v"), golden_mask(half, 4))
        want = np.einsum("bshe,hed->bsd", o,
                         np.asarray(params["o"]["kernel"], np.float64))
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_the_flat_qk_norm_is_untouched():
    """OLMoE's form: one scale over all the heads' lanes."""
    cfg = TransformerConfig(vocab_size=97, d_model=48, n_heads=4, n_layers=1,
                            d_ff=24, max_seq_len=32, dtype=jnp.float32,
                            rope_theta=1e4, qk_norm=True)
    x = jnp.zeros((1, 8, 48))
    params = Attention(cfg).init(jax.random.PRNGKey(0), x)["params"]
    assert params["q_norm"]["scale"].shape == (48,)


# ---------------------------------------------------------------------------
# the model and its loss against the plain reference
# ---------------------------------------------------------------------------

EXPERTS, K, MASK_ID = 8, 3, 96
#: float32 against float32 on the CPU, both with exact products
LOSS_ATOL = 3e-6
GRAD_RTOL = 3e-5


def sdar(ep_size=1, ep_rank=0, **kw):
    cfg = _attention_config(**kw)
    moe = lambda: MoEMLP(
        n_experts=EXPERTS, d_ff=24, k=K, ep_size=ep_size, ep_rank=ep_rank,
        dropless=True, gated=True, activation="silu", norm_topk_prob=True,
        dtype=jnp.float32, name="mlp")
    model = TransformerLM(cfg, mlp_factory=lambda _i: moe)
    hyper = {"layers": cfg.n_layers, "experts_per_token": K,
             "first_expert": ep_rank * (EXPERTS // ep_size),
             "rope_theta": THETA, "rms_norm_eps": EPS, "block": 4,
             "mask_id": MASK_ID, "mask": "block_diffusion",
             "restart_positions": True, "shift": False,
             "weigh_by_noise": True}
    return model, hyper


def seeded(model, seed=0, batch=3, length=16):
    tokens = np.random.default_rng(seed).integers(0, MASK_ID,
                                                  (batch, length))
    drawn = block_diffusion_noise(tokens, np.random.default_rng(seed + 1),
                                  block=4, mask_id=MASK_ID)
    params = model.init(jax.random.PRNGKey(seed + 2),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # norm scales off their all-ones init, so that a norm applied in the
    # wrong place shows
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 3), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if "scale" in jax.tree_util.keystr(path) else leaf
        for (path, leaf), key in zip(leaves, keys)])
    return params, {name: jnp.asarray(x) for name, x in drawn.items()}


def _reference_gradient(params, batch, hyper):
    return jax.jit(lambda p, b: jax.value_and_grad(ref.loss_fn)(
        p, b, hyper))(params, batch)


#: (expert-parallel degree, rank): all experts here, and ranks of eight
SHARES = [(1, 0), (8, 0), (8, 5)]


@pytest.fixture(scope="module", params=SHARES,
                ids=lambda s: f"rank{s[1]}of{s[0]}")
def both(request):
    """Loss and gradients of system and reference, computed once."""
    model, hyper = sdar(*request.param)
    params, batch = seeded(model)
    with jax.default_matmul_precision("highest"):
        rows = jnp.concatenate(
            [batch["tokens"],
             jnp.where(batch["masked"], MASK_ID, batch["tokens"])], axis=1)
        sys_logits = jax.jit(
            lambda p, rows: model.apply({"params": p}, rows))(params, rows)
        ref_logits = jax.jit(lambda p, b: ref.logits_fn(
            p, b["tokens"], b["masked"], hyper))(params, batch)
        sys_loss, sys_grads = jax.jit(jax.value_and_grad(
            block_diffusion_loss_fn(model, MASK_ID)))(params, batch)
        ref_loss, ref_grads = _reference_gradient(params, batch, hyper)
    return {"logits": (sys_logits, ref_logits), "loss": (sys_loss, ref_loss),
            "grads": (sys_grads, ref_grads)}


def _flat(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


_LEAVES = sorted(_flat(jax.eval_shape(
    lambda: sdar()[0].init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])))


def test_the_head_reads_the_noised_half(both):
    sys_logits, ref_logits = both["logits"]
    assert sys_logits.shape == (3, 16, 97)          # [b, L, vocab] of 2 L rows
    np.testing.assert_allclose(sys_logits, ref_logits, atol=3e-5)


def test_loss_agrees_with_the_reference(both):
    sys_loss, ref_loss = both["loss"]
    assert abs(float(sys_loss) - float(ref_loss)) < LOSS_ATOL
    assert float(sys_loss) > 1.0


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_agrees_with_the_reference(both, leaf):
    got, want = (_flat(g)[leaf] for g in both["grads"])
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=GRAD_RTOL * scale)


FAULTS = {"causal_over_2L": {"mask": "causal"},
          "noised_sees_own_clean_block": {"mask": "own_clean_block"},
          "clean_sees_noised": {"mask": "clean_sees_noised"},
          "positions_not_restarted": {"restart_positions": False},
          "loss_with_a_shift": {"shift": True},
          "no_one_over_t": {"weigh_by_noise": False}}


@pytest.fixture(scope="module")
def system_gradient():
    model, hyper = sdar()
    params, batch = seeded(model)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            block_diffusion_loss_fn(model, MASK_ID)))(params, batch)
    return params, batch, hyper, float(loss), ref.watched(grads)


def _verdict(system_gradient, hyper) -> bool:
    """``correct``'s two comparisons, at their limits."""
    params, batch, _, loss, got = system_gradient
    with jax.default_matmul_precision("highest"):
        ref_loss, grads = _reference_gradient(params, batch, hyper)
    distance = {name: float(d) for name, d in ref.gradient_distance(
        got, ref.watched(grads)).items()}
    return (ref.agree([loss], [float(ref_loss)])
            and ref.gradients_agree(distance))


def test_the_comparison_passes_the_sound_reference(system_gradient):
    assert _verdict(system_gradient, system_gradient[2])


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_comparison_tells_each_mechanism_from_its_absence(
        system_gradient, fault):
    """``correct``'s two comparisons against the reference with one
    mechanism wrong: refused by the loss or by the first gradient."""
    hyper = system_gradient[2]
    assert not _verdict(system_gradient, {**hyper, **FAULTS[fault]})


def test_the_watched_leaves_are_the_attention_matrices(system_gradient):
    watched = system_gradient[-1]
    assert sorted(watched) == [f"block_{i}/attn/{w}/kernel"
                               for i in range(2) for w in "koqv"]


def _replay(round_weights=None, learning_rate=1e-3):
    """Two replayed steps of the reference: its losses, the first gradient
    and the last change it handed over, and the weights it started from."""
    model, hyper = sdar()
    params, batch = seeded(model)
    gradients, changes = [], []
    losses = ref.replay_losses(
        jax.tree.map(jnp.copy, params), batch, 2,
        {"name": "adamw", "kwargs": {"learning_rate": learning_rate}}, hyper,
        round_weights=round_weights, first_gradient=gradients.append,
        last_change=changes.append)
    return losses, gradients, changes, params


def test_the_replay_hands_over_its_first_gradient_and_trains():
    losses, gradients, _, params = _replay()
    assert len(losses) == 2 and losses[1] < losses[0]
    assert set(gradients[0]) == set(ref.watched(params))


def test_the_replay_hands_over_the_parameters_change():
    """The change of the watched leaves, the head and the final norm's scale
    over the replayed updates: AdamW's two steps of 1e-3 move every entry by
    about 2e-3; the same replay again is at distance 0, a state left as it
    was at distance 1."""
    _, _, changes, params = _replay()
    change = changes[0]
    assert set(change) == set(ref.watched(params, ref.CHANGE_ALSO))
    assert set(change) > set(ref.watched(params))
    assert 1e-3 < float(jnp.abs(change["lm_head/kernel"]).mean()) < 3e-3
    again = _replay()[2][0]
    unmoved = jax.tree.map(jnp.zeros_like, change)
    same = {n: float(d) for n, d in
            ref.gradient_distance(again, change).items()}
    still = {n: float(d) for n, d in
             ref.gradient_distance(unmoved, change).items()}
    assert ref.changes_agree(same) and max(same.values()) == 0.0
    assert not ref.changes_agree(still)
    assert all(d == pytest.approx(1.0) for d in still.values())


def test_the_change_refuses_weights_kept_in_bfloat16():
    """Weights rounded to bfloat16 at the start and after every update: two
    updates of 1e-4 are under half a step of most entries (and of a norm's
    scale at one), so the change is nowhere near the float32 one's: refused,
    where the first loss and the first gradient cannot see the rounding."""
    round_to_bf16 = jax.jit(lambda tree: jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=7), tree))
    losses, gradients, changes, _ = _replay(learning_rate=1e-4)
    r_losses, r_gradients, r_changes, _ = _replay(round_to_bf16, 1e-4)
    distance = lambda got, want: {
        n: float(d) for n, d in ref.gradient_distance(got, want).items()}
    assert ref.agree(r_losses, losses)
    assert ref.gradients_agree(distance(r_gradients[0], gradients[0]))
    change = distance(r_changes[0], changes[0])
    assert not ref.changes_agree(change)
    assert min(change.values()) > ref.CHANGE_TOLERANCE


@pytest.mark.parametrize("ours, theirs, agrees", [
    ([10.0, 9.0, 8.0], [10.0, 9.0, 8.0], True),
    # the first step is held ...
    ([10.0, 9.0, 8.0], [10.0 + 2 * ref.LOSS_TOLERANCE[0], 9.0, 8.0], False),
    ([10.0, 9.0, 8.0], [10.0 - ref.LOSS_TOLERANCE[0] / 2, 9.0, 8.0], True),
    # ... a later one is reported and not held, but has to be a number
    ([10.0, 9.0, 8.0], [10.0, 9.5, 8.0], True),
    ([10.0, 9.0, 8.0], [10.0, float("nan"), 8.0], False),
    ([10.0, float("inf"), 8.0], [10.0, 9.0, 8.0], False),
    ([10.0, 9.0], [10.0, 9.0, 8.0], False),
    ([], [], False),
])
def test_which_replayed_losses_are_held(ours, theirs, agrees):
    assert len(ref.LOSS_TOLERANCE) == 1
    assert ref.agree(ours, theirs) is agrees


# ---------------------------------------------------------------------------
# the training view is the generation view
# ---------------------------------------------------------------------------


def _block_causal(block):
    """Attention of block-wise generation over ``[x_<bB ; x~_b]``: key ``j``
    is visible to query ``i`` where ``j // B <= i // B``."""
    def attend(q, k, v, dtype):
        s = q.shape[1]
        blk = np.arange(s) // block
        mask = blk[None, :] <= blk[:, None]
        k, v = (jnp.repeat(t, q.shape[2] // t.shape[2], axis=2)
                for t in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return attend


@pytest.mark.parametrize("beta", [0, 1, 3])
def test_the_training_view_is_the_generation_view(beta):
    """The logits of noised block ``beta`` out of the one ``2 L`` pass are
    those of a forward over ``[x_{< beta B} ; x~_beta]`` at positions ``0
    .. (beta + 1) B - 1`` under the block-causal mask: what generation by
    blocks computes when it denoises block ``beta`` after the clean
    prefix."""
    block = 4
    model, _ = sdar()
    params, batch = seeded(model, seed=5)
    tokens, masked = batch["tokens"], batch["masked"]
    noised = jnp.where(masked, MASK_ID, tokens)
    causal = dataclasses.replace(model.cfg, attention="causal",
                               diffusion_block=0)
    generator = TransformerLM(causal, attn_fn=_block_causal(block),
                              mlp_factory=model.mlp_factory)
    lo, hi = beta * block, (beta + 1) * block
    with jax.default_matmul_precision("highest"):
        training = jax.jit(lambda p, rows: model.apply({"params": p}, rows))(
            params, jnp.concatenate([tokens, noised], axis=1))
        generation = jax.jit(
            lambda p, rows: generator.apply({"params": p}, rows))(
            params, jnp.concatenate([tokens[:, :lo], noised[:, lo:hi]],
                                    axis=1))
    np.testing.assert_allclose(training[:, lo:hi], generation[:, lo:hi],
                               atol=3e-5)


# ---------------------------------------------------------------------------
# the noise, the refusals, the names
# ---------------------------------------------------------------------------


def test_the_noise_is_a_level_a_block_and_a_coin_a_position():
    tokens = np.random.default_rng(0).integers(0, MASK_ID, (64, 256))
    drawn = block_diffusion_noise(tokens, np.random.default_rng(1), block=4,
                                  mask_id=MASK_ID, eps=1e-3)
    assert drawn["tokens"] is not None and drawn["t"].shape == (64, 64)
    assert drawn["masked"].shape == (64, 256) and drawn["masked"].dtype == bool
    assert drawn["t"].dtype == np.float32
    assert 1e-3 <= drawn["t"].min() and drawn["t"].max() <= 1.0
    # a position is masked about as often as its block's level says
    level = np.repeat(drawn["t"], 4, axis=1)
    assert abs(drawn["masked"].mean() - 0.5) < 0.02
    assert drawn["masked"][level > 0.9].mean() > 0.9
    assert drawn["masked"][level < 0.1].mean() < 0.1
    assert counters.snapshot()["diffusion/masked_tokens_per_step"] == int(
        drawn["masked"].sum())
    # the same generator state, the same draw
    again = block_diffusion_noise(tokens, np.random.default_rng(1), block=4,
                                  mask_id=MASK_ID, eps=1e-3)
    np.testing.assert_array_equal(again["masked"], drawn["masked"])
    with pytest.raises(ValueError, match="whole blocks"):
        block_diffusion_noise(tokens[:, :255], np.random.default_rng(1),
                              block=4, mask_id=MASK_ID)
    with pytest.raises(ValueError, match="mask_id"):
        block_diffusion_noise(tokens, np.random.default_rng(1), block=4,
                              mask_id=int(tokens[0, 0]))


@pytest.mark.parametrize("option,sentence", [
    ({"decode": True}, "decode paths"),
    ({"sp_axis": "sp"}, "sp_axis"),
    ({"n_passes": 2}, "looped"),
    ({"window": 8}, "one kind of layer"),
])
def test_the_paths_that_cannot_take_the_mask_refuse_it(option, sentence):
    model = TransformerLM(_attention_config(**option))
    with pytest.raises(NotImplementedError, match=sentence):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_the_pipelined_stack_refuses_the_mask():
    from bagua_tpu.parallel.pipeline import PipelinedTransformerLM

    cfg = TransformerConfig(vocab_size=97, d_model=48, n_heads=4, n_layers=2,
                            d_ff=24, max_seq_len=32, dtype=jnp.float32,
                            attention="block_diffusion", diffusion_block=4)
    with pytest.raises(NotImplementedError, match="pipeline stages"):
        PipelinedTransformerLM(cfg, pp_size=1).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 9), jnp.int32))


@pytest.mark.parametrize("rows,block", [(9, 4), (12, 4), (8, 0)])
def test_rows_that_are_not_two_halves_are_refused(rows, block):
    cfg = dataclasses.replace(_attention_config(), diffusion_block=block)
    with pytest.raises(ValueError, match="whole diffusion blocks"):
        TransformerLM(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, rows), jnp.int32))


def test_an_unknown_attention_kind_is_refused():
    cfg = dataclasses.replace(_attention_config(), attention="bidirectional")
    with pytest.raises(ValueError, match="attention kind"):
        TransformerLM(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))


def test_the_gauges_and_the_scopes_are_published():
    """A traced step says what it is (the gauges the benchmark's readers
    take the diffusion block and the token count from) and names what no
    module does: the input's assembly reads as ``embed``, the weighing of
    the loss as ``head``."""
    model, _ = sdar()
    params, batch = seeded(model)
    loss = block_diffusion_loss_fn(model, MASK_ID)

    def step_loss(params, batch):          # as the trainer's step names it
        with jax.named_scope("bagua.loss"):
            return loss(params, batch)

    text = jax.jit(jax.grad(step_loss)).lower(params, batch).as_text(
        debug_info=True)
    gauges = counters.snapshot()
    assert gauges["attn/diffusion_block"] == 4
    assert gauges["attn/block_diffusion_layers"] == 2
    assert gauges["attn/full_layers"] == 0 and gauges["attn/window"] == 0
    assert gauges["diffusion/tokens_per_step"] == 3 * 16
    assert gauges["attn/kv_heads"] == 2
    assert f"{DIFFUSION_INPUT_SCOPE}/concatenate" in text
    assert area_of(f"jit(f)/jvp(bagua.loss)/{DIFFUSION_INPUT_SCOPE}/"
                   "concatenate") == "embed"
    assert "loss_tail" in text and area_of("a/loss_tail/mul") == "head"
    from bagua_tpu.obs.export import is_registered

    for name in ("attn/diffusion_block", "attn/block_diffusion_layers",
                 "diffusion/tokens_per_step",
                 "diffusion/masked_tokens_per_step"):
        assert is_registered(name)


@pytest.fixture(scope="module")
def row_kernel_paths():
    """Rank 3 of eight's share of SDAR's expert layer (SiLU-gated, eight of
    32 experts a token, the winners renormalised) at the kernels' lane
    width: seven eighths of a token's pairs enter no group."""
    return row_kernels.both_paths(MoEMLP(n_experts=32, d_ff=128, k=8, ep_size=8,
                             ep_rank=3, dropless=True, gated=True,
                             activation="silu", norm_topk_prob=True,
                             dtype=jnp.float32))


@pytest.mark.parametrize("quantity", row_kernels.QUANTITIES)
def test_a_share_on_the_row_kernels_is_the_fallbacks_share(
        row_kernel_paths, quantity):
    row_kernels.assert_the_same_layer(*row_kernel_paths, quantity)


def test_the_model_where_the_kernels_run(monkeypatch):
    """The kernel-side model on the CPU (every ``pallas_call`` in interpret
    mode, the backend said to be a TPU — steered here, not by an option of
    the program): ``HeadsDense`` projections, the ``rope`` kernel on the
    halves, the ``flash_bd_*`` kernels, against the same parameters through
    the fallback."""
    cfg = TransformerConfig(
        vocab_size=97, d_model=128, n_heads=2, n_kv_heads=1, d_head=128,
        n_layers=1, d_ff=64, max_seq_len=1024, dtype=jnp.float32,
        rope_theta=THETA, qk_norm="head", attention="block_diffusion",
        diffusion_block=4)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 1024), 0, 97)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, tokens)
        real = flash.pl.pallas_call
        monkeypatch.setattr(flash.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            flash.pl, "pallas_call",
            lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
        text = str(jax.make_jaxpr(
            lambda p, t: model.apply({"params": p}, t))(params, tokens))
        got = model.apply({"params": params}, tokens)
    assert "name=flash_bd_fwd" in text and "name=rope" in text
    assert counters.snapshot()["attn/rope_kernel_layers"] == 1
    # and its q / k norm rides that pass
    assert counters.snapshot()["attn/head_norm_kernel_layers"] == 1
    np.testing.assert_allclose(got, want, atol=5e-5)
