"""The ``rope`` kernel (``bagua_tpu/ops/rope.py``) in interpret mode on the
CPU, against ``rope_rotate`` — the form it replaces where the flash kernels
run, and its golden — and ``Attention``'s gate around it.  What Mosaic makes
of the call at the cells' shapes is ``tests/test_flash_attention_v5e.py``'s."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bagua_tpu.models.transformer as transformer
from bagua_tpu.models.transformer import (
    Attention, TransformerConfig, rope_rotate, rotates_by_kernel,
)
from bagua_tpu.ops import rope as rope_mod
from bagua_tpu.ops.rope import rope, rope_supported, row_block

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
