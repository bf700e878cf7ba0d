"""The ``rope`` kernel (``bagua_tpu/ops/rope.py``) in interpret mode on the
CPU, against ``rope_rotate`` — the form it replaces where the flash kernels
run, and its golden — the pass that carries a per-head ``RMSNorm`` as well
(``norm_rope``) against ``RMSNorm`` then ``rope_rotate``, and ``Attention``'s
gate around both.  What Mosaic makes of the calls at the cells' shapes is
``tests/test_flash_attention_v5e.py``'s."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bagua_tpu.models.transformer as transformer
from bagua_tpu.models.transformer import (
    Attention, RMSNorm, TransformerConfig, TransformerLM,
    block_diffusion_loss_fn, rope_rotate, rotates_by_kernel,
)
from bagua_tpu.ops import rope as rope_mod
from bagua_tpu.ops.rope import norm_rope, rope, rope_supported, row_block

THETA = 10000.0
#: (heads, head_dim): the lanes of Ouro's and OLMoE's q and k (2048), of
#: SmallThinker's q (3584) and of its k (512)
LANES = [(16, 128), (28, 128), (4, 128)]
lanes = pytest.mark.parametrize("h, d", LANES,
                                ids=[f"{h * d}-lanes" for h, d in LANES])


def heads(h, d, dtype=jnp.bfloat16, b=2, s=256, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, h, d), jnp.float32)
    return (3.0 * x).astype(dtype)


def bf16_ulp(x):
    """The spacing of bfloat16 (8 significant bits) at ``|x|``, never under
    float32's own rounding of terms of this size (where the two products
    cancel the result is far smaller than either)."""
    x = np.abs(np.asarray(x, np.float64))
    return np.maximum(2.0 ** (np.floor(np.log2(np.maximum(x, 1e-30))) - 7),
                      2e-6)


def rotation_in_float64(x, theta, start=0):
    """The rotation of ``x`` in float64 by the float32 angles' ``cos`` and
    ``sin`` (``rope_rotate``'s own: the arithmetic is what is under test,
    not the angles)."""
    x = np.asarray(x, np.float64)
    seq, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = (jnp.arange(seq, dtype=jnp.int32) + start).astype(jnp.float32)
    angles = pos[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos = np.asarray(jnp.cos(angles), np.float64)[None, :, None]
    sin = np.asarray(jnp.sin(angles), np.float64)[None, :, None]
    rotated = np.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rotated * sin


@lanes
def test_the_call_is_rope_rotate_to_one_bf16_ulp(h, d):
    """Element by element; what is left is whether the compiler contracts
    the multiply-add."""
    x = heads(h, d)
    got = np.asarray(rope(x, THETA, interpret=True), np.float64)
    want = np.asarray(rope_rotate(x, THETA), np.float64)
    assert got.shape == x.shape
    assert np.all(np.abs(got - want) <= bf16_ulp(want))
    assert np.mean(got == want) > 0.9


@lanes
def test_the_call_rounds_once(h, d):
    """bf16 in, float32 arithmetic, one rounding: within half a bf16 ulp
    (and float32's own rounding of the two products) of the rotation in
    float64 — a bf16 product or sum on the way would show as up to one and
    a half."""
    x = heads(h, d)
    got = rope(x, THETA, interpret=True)
    assert got.dtype == jnp.bfloat16
    exact = rotation_in_float64(x, THETA)
    err = np.abs(np.asarray(got, np.float64) - exact)
    assert np.all(err <= 0.5 * bf16_ulp(exact) + 2e-6)


@lanes
def test_float32_in_is_the_float32_rotation(h, d):
    x = heads(h, d, jnp.float32)
    got = np.asarray(rope(x, THETA, interpret=True), np.float64)
    exact = rotation_in_float64(x, THETA)
    np.testing.assert_allclose(got, exact, atol=2e-6, rtol=0)
    np.testing.assert_allclose(
        got, np.asarray(rope_rotate(x, THETA), np.float64), atol=2e-6, rtol=0)


@lanes
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_vjp_is_autodiff_of_rope_rotate(h, d, dtype):
    """The same call with ``-sin``; no residual but ``start``."""
    x = heads(h, d, dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), x.shape, jnp.float32)

    def grad_of(rotate):
        return np.asarray(jax.grad(lambda t: jnp.sum(
            rotate(t).astype(jnp.float32) * g))(x), np.float64)

    got = grad_of(lambda t: rope(t, THETA, 3, interpret=True))
    want = grad_of(lambda t: rope_rotate(t, THETA, 3))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    else:
        assert np.all(np.abs(got - want) <= bf16_ulp(want))
    _, residuals = jax.vjp(lambda t: rope(t, THETA, 3, interpret=True), x)
    kept = [r for r in jax.tree.leaves(residuals) if hasattr(r, "shape")]
    assert all(r.size == 1 for r in kept), [r.shape for r in kept]


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
def test_a_chunk_at_start_is_the_long_sequences_chunk(traced):
    """A sequence-parallel chunk: positions ``start .. start + seq - 1``,
    ``start`` an ``axis_index`` in the model — the tables are built outside
    the call, the kernel sees none of it."""
    whole = heads(4, 128, s=384)
    chunk, start = whole[:, 256:], 256
    fn = lambda t, at: rope(t, THETA, at, interpret=True)
    got = jax.jit(fn)(chunk, start) if traced else fn(chunk, start)
    got = np.asarray(got, np.float64)
    of_whole = np.asarray(rope(whole, THETA, interpret=True)[:, 256:],
                          np.float64)
    if traced:
        # another program builds the tables: an angle may be its float32
        # neighbour (2^-23 x 384 positions, times |x| up to 15)
        assert np.all(np.abs(got - of_whole) <= bf16_ulp(of_whole) + 1e-3)
    else:
        np.testing.assert_array_equal(got, of_whole)
    want = np.asarray(rope_rotate(chunk, THETA, start), np.float64)
    assert np.all(np.abs(got - want) <= bf16_ulp(want) + 1e-3 * traced)
    assert not np.array_equal(got, np.asarray(fn(chunk, 0), np.float64))


@pytest.mark.parametrize("seq, lanes_, head_dim, itemsize, rows", [
    (4096, 2048, 128, 2, 512),     # Ouro, OLMoE
    (8192, 3584, 128, 2, 512),     # SmallThinker's q
    (8192, 512, 128, 2, 512),      # its k
    (1152, 2048, 128, 2, 384),     # a sequence 512 does not divide
    (4096, 3584, 128, 4, 512),     # float32 in and out
    (4096, 32768, 128, 4, 128),    # a block that wide: fewer rows
    (200, 2048, 128, 2, 0),        # no block divides it
])
def test_the_row_block_divides_the_sequence_and_fits(seq, lanes_, head_dim,
                                                     itemsize, rows):
    assert row_block(seq, lanes_, head_dim, itemsize) == rows
    assert rope_supported(seq, head_dim) == bool(rows)


def test_a_shape_the_kernel_does_not_cover_is_refused():
    assert not rope_supported(256, 64) and not rope_supported(200, 128)
    with pytest.raises(ValueError, match="no fallback"):
        rope(heads(2, 64), THETA, interpret=True)
    with pytest.raises(ValueError, match="no fallback"):
        rope(heads(2, 128, s=200), THETA, interpret=True)


# ---- the per-head norm in the rotation's pass ---------------------------------

EPS = 1e-6
#: (heads, head_dim): SDAR's q and its k, and Qwen3-Next's head width
NORMED = [(32, 128), (4, 128), (2, 256)]
normed = pytest.mark.parametrize(
    "h, d", NORMED, ids=[f"{h}-heads-of-{d}" for h, d in NORMED])
centred = pytest.mark.parametrize("zero_centered", [False, True],
                                  ids=["scale", "one-plus-scale"])


def scale_of(d, zero_centered, seed=3):
    """A trained-looking scale: around 1, or around 0 where the module adds
    the 1."""
    noise = 0.3 * jax.random.normal(jax.random.PRNGKey(seed), (d,))
    return noise if zero_centered else 1.0 + noise


def norm_then_rotate(x, scale, zero_centered, start=0, dtype=None):
    """The two modules the pass replaces: ``RMSNorm`` over each head (its
    result rounded to ``dtype``), then ``rope_rotate``."""
    norm = RMSNorm(dtype or x.dtype, jnp.float32, EPS, zero_centered)
    return rope_rotate(norm.apply({"params": {"scale": scale}}, x), THETA,
                       start)


def norm_in_float64(x, scale, zero_centered):
    x, w = np.asarray(x, np.float64), np.asarray(scale, np.float64)
    w = 1.0 + w if zero_centered else w
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + EPS) * w


def passed(x, scale, zero_centered, start=0):
    return norm_rope(x, scale, THETA, start, eps=EPS,
                     zero_centered=zero_centered, interpret=True)


@normed
@centred
def test_the_pass_is_rmsnorm_then_rope_rotate(h, d, zero_centered):
    """bf16 in and out: the pass differs from the two modules only by their
    rounding of the normalised tensor, half a bf16 ulp of each of the
    rotation's two products, and the last rounding's own ulp."""
    x, scale = heads(h, d), scale_of(d, zero_centered)
    got = np.asarray(passed(x, scale, zero_centered), np.float64)
    want = np.asarray(norm_then_rotate(x, scale, zero_centered), np.float64)
    assert got.shape == x.shape and got.dtype == want.dtype
    y = norm_in_float64(x, scale, zero_centered)
    half_turn = np.concatenate([y[..., d // 2:], y[..., : d // 2]], -1)
    cos, sin = (np.asarray(t, np.float64)[None, :, None]
                for t in rope_mod.tables(THETA, x.shape[1], d))
    room = (bf16_ulp(np.maximum(np.abs(got), np.abs(want)))
            + 0.5 * bf16_ulp(y) * np.abs(cos)
            + 0.5 * bf16_ulp(half_turn) * np.abs(sin))
    assert np.all(np.abs(got - want) <= room)
    assert np.mean(np.abs(got - want) <= bf16_ulp(want)) > 0.95


@normed
@centred
def test_the_pass_rounds_once(h, d, zero_centered):
    """Within half a bf16 ulp of norm and rotation in float64: a rounding
    of the normalised tensor on the way would show as up to one and a
    half.  And float32 in is the float32 composition."""
    x, scale = heads(h, d), scale_of(d, zero_centered)
    got = passed(x, scale, zero_centered)
    assert got.dtype == jnp.bfloat16
    exact = rotation_in_float64(norm_in_float64(x, scale, zero_centered),
                                THETA)
    err = np.abs(np.asarray(got, np.float64) - exact)
    assert np.all(err <= 0.5 * bf16_ulp(exact) + 1e-5)
    # the two modules do round twice: the test can tell
    twice = np.asarray(norm_then_rotate(x, scale, zero_centered), np.float64)
    assert np.any(np.abs(twice - exact) > 0.5 * bf16_ulp(exact) + 1e-5)

    x32 = heads(h, d, jnp.float32)
    got = np.asarray(passed(x32, scale, zero_centered), np.float64)
    exact = rotation_in_float64(norm_in_float64(x32, scale, zero_centered),
                                THETA)
    np.testing.assert_allclose(got, exact, atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        got, np.asarray(norm_then_rotate(x32, scale, zero_centered),
                        np.float64), atol=1e-5, rtol=0)


@normed
@centred
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_pass_vjp_is_autodiff_of_the_xla_form(h, d, zero_centered, dtype):
    """``x`` and ``scale``: the cotangent un-rotated and taken through the
    norm in one call, against autodiff of ``RMSNorm`` -> ``rope_rotate`` in
    float32 on the same values — bf16 in, the pass's ``d_x`` is that
    gradient rounded once.  It keeps ``x``, ``scale`` and ``start``."""
    x, scale = heads(h, d, dtype), scale_of(d, zero_centered)
    g = jax.random.normal(jax.random.PRNGKey(1), x.shape, jnp.float32)
    g = g.astype(dtype).astype(jnp.float32)     # the cotangent's own dtype

    def grads(fn, x):
        return jax.grad(lambda t, s: jnp.sum(
            fn(t, s).astype(jnp.float32) * g), argnums=(0, 1))(x, scale)

    d_x, d_scale = grads(lambda t, s: passed(t, s, zero_centered, 3), x)
    want_x, want_scale = grads(
        lambda t, s: norm_then_rotate(t, s, zero_centered, 3),
        x.astype(jnp.float32))
    assert d_x.dtype == x.dtype and d_scale.dtype == scale.dtype
    d_x, want_x = np.asarray(d_x, np.float64), np.asarray(want_x, np.float64)
    if dtype == jnp.float32:
        np.testing.assert_allclose(d_x, want_x, atol=2e-5, rtol=0)
    else:
        assert np.all(np.abs(d_x - want_x) <= 0.5 * bf16_ulp(want_x) + 2e-5)
    size = float(jnp.abs(want_scale).max())
    np.testing.assert_allclose(d_scale, want_scale, atol=2e-5 * size, rtol=0)
    _, residuals = jax.vjp(lambda t, s: passed(t, s, zero_centered, 3),
                           x, scale)
    kept = sorted(r.size for r in jax.tree.leaves(residuals)
                  if hasattr(r, "shape"))
    assert kept == [1, d, x.size], kept


@normed
@centred
def test_a_chunk_of_the_pass_at_start_is_the_long_sequences_chunk(
        h, d, zero_centered):
    """The norm sees no position; the tables carry the chunk's offset,
    traced or not."""
    whole, scale = heads(h, d, s=384), scale_of(d, zero_centered)
    chunk, start = whole[:, 256:], 256
    of_whole = np.asarray(passed(whole, scale, zero_centered)[:, 256:],
                          np.float64)
    got = np.asarray(passed(chunk, scale, zero_centered, start), np.float64)
    np.testing.assert_array_equal(got, of_whole)
    traced = np.asarray(jax.jit(
        lambda t, at: passed(t, scale, zero_centered, at))(chunk, start),
        np.float64)
    # another program builds the tables: an angle may be its float32
    # neighbour (2^-23 x 384 positions, times |y| up to 8)
    assert np.all(np.abs(traced - of_whole) <= bf16_ulp(of_whole) + 1e-3)
    assert not np.array_equal(
        got, np.asarray(passed(chunk, scale, zero_centered), np.float64))


@normed
@centred
def test_both_halves_of_a_block_diffusion_batch_sit_at_zero(h, d,
                                                            zero_centered):
    """What ``Attention`` does under block diffusion: ``[b, 2 L, h, d]`` ->
    ``[2 b, L, h, d]`` around one call; the noised half restarts at 0."""
    b, half = 2, 128
    x, scale = heads(h, d, b=b, s=2 * half), scale_of(d, zero_centered)
    got = passed(x.reshape(2 * b, half, h, d), scale,
                 zero_centered).reshape(x.shape)
    for rows in (slice(0, half), slice(half, None)):
        np.testing.assert_array_equal(
            np.asarray(got[:, rows], np.float32),
            np.asarray(passed(x[:, rows], scale, zero_centered), np.float32))
    in_a_row = passed(x, scale, zero_centered)
    np.testing.assert_array_equal(np.asarray(got[:, :half], np.float32),
                                  np.asarray(in_a_row[:, :half], np.float32))
    assert float(jnp.abs(got[:, half:].astype(jnp.float32)
                         - in_a_row[:, half:].astype(jnp.float32)).max()) > 0.1


@centred
def test_a_shape_the_pass_does_not_cover_is_refused(zero_centered):
    for x in (heads(2, 64), heads(2, 128, s=200)):
        with pytest.raises(ValueError, match="no fallback"):
            passed(x, scale_of(x.shape[-1], zero_centered), zero_centered)


# ---- Attention's gate ---------------------------------------------------------


def _force_kernels(patch, flash=True):
    """The kernels' path on the CPU: ``flash_supported`` says ``flash`` and
    every ``pallas_call`` runs in interpret mode — steered here, in the
    test, not by an option of the program."""
    # ``bagua_tpu.ops`` exports the function under the module's name
    flash_mod = importlib.import_module("bagua_tpu.ops.flash_attention")
    real = rope_mod.pl.pallas_call
    patch.setattr(flash_mod, "flash_supported", lambda *a, **kw: flash)
    patch.setattr(rope_mod.pl, "pallas_call",
                  lambda *a, **kw: real(*a, **{**kw, "interpret": True}))


def attention(seq=128, n_heads=4, kv_heads=2, d_head=128, attn_fn=None,
              **cfg):
    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_heads=n_heads, n_kv_heads=kv_heads,
        d_head=d_head, n_layers=1, d_ff=64, max_seq_len=seq,
        rope_theta=THETA, dtype=jnp.float32, **cfg)
    layer = Attention(cfg, attn_fn)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, cfg.d_model))
    return layer, x


def rotations(patch):
    """Count the calls of either rotation from here on."""
    calls = {"rope": 0, "rope_rotate": 0}

    def counted(name, fn):
        def call(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return call

    patch.setattr(rope_mod, "rope", counted("rope", rope_mod.rope))
    patch.setattr(transformer, "rope_rotate",
                  counted("rope_rotate", transformer.rope_rotate))
    return calls


def plain_attention(q, k, v, dtype, window=None):
    from bagua_tpu.ops.flash_attention import reference_attention

    return reference_attention(q, k, v, dtype, causal=True, window=window)


#: what the gate refuses: (why, Attention's arguments, whether the flash
#: path is forced).  Each must run the layer on ``rope_rotate`` alone
REFUSALS = [
    ("head_dim-64", dict(d_head=64), True),
    ("ragged-sequence", dict(seq=192), True),
    ("einsum-path", dict(), False),
    ("attn_fn-drop-in", dict(attn_fn=plain_attention), True),
    ("rope_layers-off", dict(), True),
]


@pytest.mark.parametrize("why, kw, flash", REFUSALS,
                         ids=[r[0] for r in REFUSALS])
def test_what_the_gate_refuses_is_rope_rotate_bit_for_bit(why, kw, flash,
                                                          monkeypatch):
    layer, x = attention(**kw)
    if why == "rope_layers-off":
        layer = Attention(layer.cfg, None, None, False)
    params = layer.init(jax.random.PRNGKey(1), x)
    want = layer.apply(params, x)      # off the TPU: today's layer
    _force_kernels(monkeypatch, flash)
    calls = rotations(monkeypatch)
    got = layer.apply(params, x)
    rotary = why != "rope_layers-off"
    assert calls == {"rope": 0, "rope_rotate": 2 * rotary}
    assert not (rotary and rotates_by_kernel(layer.cfg, x.shape[1],
                                             layer.attn_fn))
    if why in ("einsum-path", "attn_fn-drop-in", "ragged-sequence"):
        # the same program as unforced, or one whose attention is the
        # reference's either way
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_off_the_tpu_the_layer_never_reaches_the_kernel(monkeypatch):
    """No patch of the gate: a CPU process at a cell's own shape (4,096
    positions, heads of 128) rotates by ``rope_rotate``."""
    layer, _ = attention(seq=4096)
    assert not rotates_by_kernel(layer.cfg, 4096)
    calls = rotations(monkeypatch)
    layer, x = attention(seq=128)
    layer.apply(layer.init(jax.random.PRNGKey(1), x), x)
    assert calls == {"rope": 0, "rope_rotate": 4}      # init and apply


QUANTITIES = ["out", "d_x", "q", "k", "v", "o"]


@pytest.fixture(scope="module", params=[(4, 2), (2, 2)],
                ids=["grouped", "one-kv-head-a-head"])
def forced_and_fallback(request):
    """A whole ``Attention`` layer's output and gradients, once on the
    fallback (einsums, ``rope_rotate``) and once with the flash path and
    the ``rope`` kernel forced (interpret mode)."""
    n_heads, kv_heads = request.param
    layer, x = attention(n_heads=n_heads, kv_heads=kv_heads, qk_norm=True)
    g = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]

    def quantities():
        def loss(params, x):
            out = layer.apply({"params": params}, x)
            return jnp.sum(out * g), out
        (_, out), (d_params, d_x) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        return {"out": out, "d_x": d_x,
                **{n: d_params[n]["kernel"] for n in "qkvo"}}

    fallback = quantities()
    with pytest.MonkeyPatch.context() as patch:
        _force_kernels(patch)
        calls = rotations(patch)
        assert rotates_by_kernel(layer.cfg, x.shape[1])
        forced = quantities()
    assert calls == {"rope": 2, "rope_rotate": 0}
    return forced, fallback


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_the_layer_on_the_kernel_is_the_fallback_layer(forced_and_fallback,
                                                       quantity):
    forced, fallback = forced_and_fallback
    got, want = forced[quantity], fallback[quantity]
    assert got.shape == want.shape
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a quantity that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)


# ---- the norm on the pass: Attention's gate, the layer, the step --------------


def passes(patch):
    """Count the calls of ``norm_rope`` from here on (``RMSNorm`` looks it
    up in the module at every call)."""
    calls = {"norm_rope": 0}
    real = rope_mod.norm_rope

    def counted(*a, **kw):
        calls["norm_rope"] += 1
        return real(*a, **kw)

    patch.setattr(rope_mod, "norm_rope", counted)
    return calls


#: what keeps a layer's head norm off the pass: (why, Attention's arguments,
#: whether the flash path is forced, the rotations the layer must make)
NORM_REFUSALS = [
    ("flat-qk_norm", dict(qk_norm=True), True, {"rope": 2, "rope_rotate": 0}),
    ("head_dim-64", dict(qk_norm="head", d_head=64), True,
     {"rope": 0, "rope_rotate": 2}),
    ("rotary_dim-under-head_dim", dict(qk_norm="head", rotary_dim=64), True,
     {"rope": 0, "rope_rotate": 2}),
    ("attn_fn-drop-in", dict(qk_norm="head", attn_fn=plain_attention), True,
     {"rope": 0, "rope_rotate": 2}),
    ("off-the-tpu", dict(qk_norm="head"), False,
     {"rope": 0, "rope_rotate": 2}),
]


@pytest.mark.parametrize("why, kw, flash, rotated", NORM_REFUSALS,
                         ids=[r[0] for r in NORM_REFUSALS])
def test_what_the_gate_refuses_keeps_rmsnorm(why, kw, flash, rotated,
                                             monkeypatch):
    """The parent's layer: ``RMSNorm`` as XLA ops, then whichever rotation
    the gate always chose — the same values as with nothing forced."""
    layer, x = attention(**kw)
    params = layer.init(jax.random.PRNGKey(1), x)
    want = layer.apply(params, x)      # off the TPU: the layer as it was
    _force_kernels(monkeypatch, flash)
    calls, on_pass = rotations(monkeypatch), passes(monkeypatch)
    got = layer.apply(params, x)
    assert calls == rotated and on_pass == {"norm_rope": 0}
    if why in ("attn_fn-drop-in", "off-the-tpu"):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_a_decode_layer_is_refused_as_it_was(monkeypatch):
    layer, x = attention(seq=128, n_heads=2, kv_heads=2, qk_norm="head",
                         decode=True)
    _force_kernels(monkeypatch)
    on_pass = passes(monkeypatch)
    with pytest.raises(NotImplementedError, match="decode"):
        layer.init(jax.random.PRNGKey(1), x[:, :1])
    assert on_pass == {"norm_rope": 0}


#: layers whose head norm rides the pass: SDAR's kind (both halves from 0),
#: a causal layer with grouped heads, Qwen3's ``1 + scale``
ON_THE_PASS = [
    ("block-diffusion", dict(seq=256, n_heads=2, kv_heads=1,
                             attention="block_diffusion", diffusion_block=4)),
    ("causal-grouped", dict(n_heads=4, kv_heads=2)),
    ("zero-centred", dict(n_heads=2, kv_heads=2, norm_zero_centered=True)),
]
LEAVES = ["q/kernel", "k/kernel", "v/kernel", "o/kernel", "q_norm/scale",
          "k_norm/scale"]


@pytest.fixture(scope="module", params=ON_THE_PASS,
                ids=[c[0] for c in ON_THE_PASS])
def pass_and_fallback(request):
    """A whole ``Attention`` layer with ``qk_norm="head"``: its output and
    the gradient of every leaf, once on the fallback (einsums, ``RMSNorm``,
    ``rope_rotate``) and once with the flash path forced, where norm and
    rotation are the one pass (interpret mode)."""
    layer, x = attention(qk_norm="head", **request.param[1])
    g = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    for i, name in enumerate(("q_norm", "k_norm")):     # not the initial 1s
        params[name]["scale"] += 0.3 * jax.random.normal(
            jax.random.PRNGKey(7 + i), params[name]["scale"].shape)

    def quantities():
        def loss(params, x):
            out = layer.apply({"params": params}, x)
            return jnp.sum(out * g), out
        (_, out), (d_params, d_x) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        leaves = {"/".join(k.key for k in path): leaf for path, leaf
                  in jax.tree_util.tree_leaves_with_path(d_params)}
        assert sorted(leaves) == sorted(LEAVES)
        return {"out": out, "d_x": d_x, **leaves}

    fallback = quantities()
    with pytest.MonkeyPatch.context() as patch:
        _force_kernels(patch)
        calls, on_pass = rotations(patch), passes(patch)
        assert rotates_by_kernel(layer.cfg, x.shape[1])
        tree = jax.eval_shape(layer.init, jax.random.PRNGKey(1), x)
        assert jax.tree.structure(tree["params"]) == jax.tree.structure(
            params)
        forced = quantities()
    assert calls == {"rope": 0, "rope_rotate": 0}
    assert on_pass == {"norm_rope": 4}        # q and k: the init, the layer
    return forced, fallback


@pytest.mark.parametrize("quantity", ["out", "d_x"] + LEAVES)
def test_the_layer_on_the_pass_is_the_fallback_layer(pass_and_fallback,
                                                     quantity):
    forced, fallback = pass_and_fallback
    got, want = forced[quantity], fallback[quantity]
    assert got.shape == want.shape
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a quantity that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)


def _float32_head_views(jaxpr, heads_of, found):
    """Every float32 ``[b, s, heads, head_dim]`` value of ``jaxpr`` outside
    the attention's own jitted calls (``_bd_fwd`` / ``_bd_bwd``, whose
    backward sums ``do * o`` over each head that way)."""
    for eqn in jaxpr.eqns:
        if eqn.params.get("name") in ("_bd_fwd", "_bd_bwd"):
            continue
        for var in eqn.outvars:
            aval = var.aval
            if (getattr(aval, "ndim", 0) == 4 and aval.dtype == jnp.float32
                    and tuple(aval.shape[2:]) in heads_of):
                found.append((eqn.primitive.name, aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _float32_head_views(sub, heads_of, found)
    return found


def test_a_step_on_the_pass_holds_no_float32_head_view(monkeypatch):
    """A one-layer SDAR step in bfloat16, forward and backward: on the
    fallback ``RMSNorm`` casts q and k to float32 ``[b, s, h, d]`` (and its
    transpose does again); on the pass no such value exists between the
    projections and the attention call, nor anywhere else."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_heads=2, n_kv_heads=1, d_head=128,
        n_layers=1, d_ff=128, max_seq_len=256, rope_theta=1e6,
        qk_norm="head", attention="block_diffusion", diffusion_block=4)
    model = TransformerLM(cfg)
    batch = {"tokens": jnp.zeros((1, 128), jnp.int32),
             "masked": jnp.ones((1, 128), jnp.bool_),
             "t": jnp.full((1, 32), 0.5, jnp.float32)}
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    heads_of = {(2, 128), (1, 128)}

    def views():    # a new function each time: traced anew, not from a cache
        step = jax.grad(block_diffusion_loss_fn(model, 63))
        return _float32_head_views(jax.make_jaxpr(step)(params, batch).jaxpr,
                                   heads_of, [])

    assert views(), "the walk must see RMSNorm's float32 view"
    _force_kernels(monkeypatch)
    on_pass = passes(monkeypatch)
    assert views() == []
    assert on_pass == {"norm_rope": 2}
