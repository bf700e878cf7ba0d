"""Flash-attention kernel vs plain attention — golden-model equivalence
(SURVEY.md §4: every fused/native op is validated against a pure
reimplementation; same pattern as the codec goldens)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_with_lse,
    heads_per_block,
    reference_attention,
)

#: (heads, head_dim): two heads side by side in a 128-lane block (one block,
#: and two), and a head that fills a block by itself
HEADS = [(2, 64), (4, 64), (2, 128)]


def _qkv(key, b=2, s=256, h=2, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize("h,d", HEADS)
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal, h, d):
    q, k, v = _qkv(jax.random.PRNGKey(0), h=h, d=d)
    want = reference_attention(q, k, v, jnp.float32, causal=causal)
    got = flash_attention(q, k, v, jnp.float32, causal=causal,
                          interpret=True, force=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_rectangular_blocks():
    # seq 384 picks a single 384 block: one grid step, diagonal-only
    q, k, v = _qkv(jax.random.PRNGKey(3), s=384)
    want = reference_attention(q, k, v, jnp.float32)
    got = flash_attention(q, k, v, jnp.float32, interpret=True, force=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
def test_mismatched_blocks_fwd_and_bwd(block_q, block_k):
    # block_q != block_k exercises the causal loop bounds (n_kb ceil-div) and
    # the dkv kernel's qb_start floor-div with multi-block diagonals
    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, s=512, h=2, d=64)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape, jnp.float32)

    def loss(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v) * g).sum(),
                        argnums=(0, 1, 2))

    ref_fn = lambda q, k, v: reference_attention(q, k, v, jnp.float32)
    fl_fn = lambda q, k, v: flash_attention(
        q, k, v, jnp.float32, block_q=block_q, block_k=block_k,
        interpret=True, force=True,
    )
    np.testing.assert_allclose(fl_fn(q, k, v), ref_fn(q, k, v),
                               atol=2e-5, rtol=2e-5)
    for w, o, name in zip(loss(ref_fn)(q, k, v), loss(fl_fn)(q, k, v), "qkv"):
        np.testing.assert_allclose(o, w, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("h,d", HEADS)
@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_reference(causal, h, d):
    q, k, v = _qkv(jax.random.PRNGKey(1), b=1, s=256, h=h, d=d)
    g = jax.random.normal(jax.random.PRNGKey(2), q.shape, jnp.float32)

    def loss(fn):
        def f(q, k, v):
            return (fn(q, k, v) * g).sum()

        return jax.grad(f, argnums=(0, 1, 2))

    want = loss(
        lambda q, k, v: reference_attention(q, k, v, jnp.float32,
                                            causal=causal)
    )(q, k, v)
    got = loss(
        lambda q, k, v: flash_attention(q, k, v, jnp.float32, causal=causal,
                                        interpret=True, force=True)
    )(q, k, v)
    for w, o, name in zip(want, got, "qkv"):
        np.testing.assert_allclose(o, w, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("h,d", HEADS)
def test_lse_and_its_cotangent_match_reference(h, d):
    """The ``[batch, heads, seq]`` logsumexp the ring merge consumes, and the
    gradients where the loss reads it (the ``dlse`` path), two batch rows so
    that a block's batch index is not always 0."""
    b, s = 2, 256
    q, k, v = _qkv(jax.random.PRNGKey(11), b=b, s=s, h=h, d=d)
    g = jax.random.normal(jax.random.PRNGKey(12), q.shape, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(13), (b, h, s), jnp.float32)

    def plain(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        logits = jnp.where(jnp.tril(jnp.ones((s, s), jnp.bool_)), logits,
                           -1e30)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
        return o, jax.nn.logsumexp(logits, axis=-1)

    def kernels(q, k, v):
        return flash_attention_with_lse(q, k, v, causal=True, interpret=True)

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return (o * g).sum() + (lse * w).sum()

        return jax.grad(f, argnums=(0, 1, 2))

    for got, want in zip(kernels(q, k, v), plain(q, k, v)):
        assert got.shape == want.shape and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for got, want, name in zip(loss(kernels)(q, k, v), loss(plain)(q, k, v),
                               "qkv"):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("h,d,per_block", [
    (16, 64, 2), (16, 128, 1), (8, 256, 1), (8, 32, 4),
    (3, 64, 0),    # an odd head count at 64: the last block would be half
    (6, 32, 0),    # four a block, six heads
    (4, 96, 0),    # 96 neither divides 128 nor is divided by it
])
def test_heads_the_128_lane_blocks_take(h, d, per_block):
    assert heads_per_block(h, d) == per_block


def test_odd_head_count_at_64_takes_the_reference():
    """Three heads of 64 do not fill 128-lane blocks: also under ``force``
    the dispatcher answers with the plain path (no kernel in the jaxpr), and
    the variant with no fallback refuses."""
    q, k, v = _qkv(jax.random.PRNGKey(14), b=1, s=256, h=3, d=64)
    forced = lambda q, k, v: flash_attention(q, k, v, jnp.float32,
                                             interpret=True, force=True)
    np.testing.assert_array_equal(
        forced(q, k, v), reference_attention(q, k, v, jnp.float32))
    assert _kernel_calls(jax.make_jaxpr(forced)(q, k, v).jaxpr) == {}
    with pytest.raises(ValueError, match="128-lane"):
        flash_attention_with_lse(q, k, v, causal=True, interpret=True)


def test_bf16_forward_close():
    q, k, v = _qkv(jax.random.PRNGKey(4), dtype=jnp.bfloat16)
    want = reference_attention(q, k, v, jnp.bfloat16)
    got = flash_attention(q, k, v, jnp.bfloat16, interpret=True, force=True)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), atol=2e-2,
        rtol=2e-2,
    )


def test_cpu_fallback_is_reference():
    # on CPU (no force) the dispatcher must return the plain path
    q, k, v = _qkv(jax.random.PRNGKey(5), s=96)
    want = reference_attention(q, k, v, jnp.float32)
    got = flash_attention(q, k, v, jnp.float32)
    np.testing.assert_allclose(got, want, atol=0, rtol=0)


def test_model_dispatch_unchanged_on_cpu():
    # causal_attention (the model hot path) must equal the old jnp math
    from bagua_tpu.models.transformer import causal_attention

    q, k, v = _qkv(jax.random.PRNGKey(6), s=128)
    want = reference_attention(q, k, v, jnp.float32)
    got = causal_attention(q, k, v, jnp.float32)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


# --- what a remat policy keeps of the kernel's outputs -----------------------


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested jaxprs included."""
    from bagua_tpu.analysis.jaxpr_check import _sub_jaxprs

    for eqn in jaxpr.eqns:
        yield eqn
        for _, inner in _sub_jaxprs(eqn):
            yield from _eqns(inner)


def _kernel_calls(jaxpr):
    """``{kernel name: pallas_call eqns}`` of a jaxpr."""
    import collections

    return dict(collections.Counter(
        eqn.params["name"] for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "pallas_call"))


def _forced_flash(q, k, v, dtype):
    return flash_attention(q, k, v, dtype, causal=True, interpret=True,
                           force=True)


def _two_layer_lm_grad_jaxpr(remat_policy):
    """Gradient jaxpr of a two-layer LM whose attention is the kernels."""
    import optax

    from bagua_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=97, d_model=128, n_heads=2,
                            n_layers=2, d_ff=256, max_seq_len=128,
                            remat=True, remat_policy=remat_policy)
    model = TransformerLM(cfg, attn_fn=_forced_flash)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 129), 0, 97)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"])

    def loss_fn(p):
        logits = model.apply({"params": p}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean()

    return jax.make_jaxpr(jax.grad(loss_fn))(params).jaxpr


@pytest.mark.parametrize("remat_policy,forwards_per_layer",
                         [(None, 2), ("dots", 1), ("dots_no_batch", 1)])
def test_dots_policies_keep_the_forward_kernels_outputs(remat_policy,
                                                        forwards_per_layer):
    """A policy that keeps matmul outputs keeps ``o`` and ``lse`` too, so the
    backward pass does not run ``flash_fwd`` a second time; with nothing
    kept (``None``) the replay runs it again, as it must."""
    layers = 2
    calls = _kernel_calls(_two_layer_lm_grad_jaxpr(remat_policy))
    assert calls == {"flash_fwd": forwards_per_layer * layers,
                     "flash_bwd_dq": layers, "flash_bwd_dkv": layers}


def test_the_layers_share_one_trace_of_each_kernel_body():
    """``_fwd`` and ``_bwd`` are jitted so that a model's layers, which call
    them with the same shapes, trace each kernel body once and not once a
    layer (a warm start's time on the chip's host)."""
    bodies = {}
    for eqn in _eqns(_two_layer_lm_grad_jaxpr("dots_no_batch")):
        if eqn.primitive.name == "pallas_call":
            bodies.setdefault(eqn.params["name"], set()).add(
                id(eqn.params["jaxpr"]))
    assert {name: len(ids) for name, ids in bodies.items()} == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}


@pytest.mark.parametrize("remat_policy", [None, "dots", "dots_no_batch"])
def test_no_transpose_stands_between_a_projection_and_a_kernel(remat_policy):
    """The kernels read and write ``[batch, seq, heads * head_dim]``, what
    the projections make and consume by reshape: forward, backward and in
    the remat replay the program writes no rank-4 ``transpose`` (the old
    fold ``[b, s, h, d] -> [b * h, s, d]`` and its inverse), and every
    tensor a kernel takes or gives has that shape."""
    b, s, hd = 2, 128, 128
    kernels = 0
    for eqn in _eqns(_two_layer_lm_grad_jaxpr(remat_policy)):
        if eqn.primitive.name == "transpose":
            assert len(eqn.params["permutation"]) < 4, eqn
        if eqn.primitive.name == "pallas_call":
            kernels += 1
            tensors = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)
                       if v.aval.dtype != jnp.float32]
            assert tensors and set(tensors) == {(b, s, hd)}, tensors
    assert kernels >= 6


@pytest.mark.parametrize("kept", [False, True])
def test_tags_leave_the_lse_cotangent_path_as_it_was(kept, monkeypatch):
    """Ring attention consumes ``lse``: with a non-zero ``dlse`` the tagged
    rule's gradients equal those of the rule without tags, bit for bit —
    also where a names policy keeps the tagged values (``kept``)."""
    # the package exports the function under the module's name
    fa = importlib.import_module("bagua_tpu.ops.flash_attention")

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
    def untagged(q, k, v, heads, causal, block_q, block_k, interpret,
                 window):
        return fa._fwd(q, k, v, heads, causal, block_q, block_k, interpret,
                       window)

    def untagged_fwd(q, k, v, heads, causal, block_q, block_k, interpret,
                     window):
        o, lse = untagged(q, k, v, heads, causal, block_q, block_k, interpret,
                          window)
        return (o, lse), (q, k, v, o, lse)

    untagged.defvjp(untagged_fwd, fa._flash_lse_bwd)

    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, s=256, h=2, d=64)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 256), jnp.float32)

    def grads(rule):
        def f(q, k, v):
            o, lse = fa.flash_attention_with_lse(q, k, v, causal=True,
                                                 interpret=True)
            return (o * g).sum() + (lse * w).sum()

        if kept:
            f = jax.checkpoint(
                f, policy=jax.checkpoint_policies.save_only_these_names(
                    fa.KEPT_O, fa.KEPT_LSE))
        monkeypatch.setattr(fa, "_flash_lse", rule)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for got, want, name in zip(grads(fa._flash_lse), grads(untagged), "qkv"):
        assert jnp.abs(want).max() > 0
        np.testing.assert_array_equal(got, want, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# grouped key / value heads and the causal window
# ---------------------------------------------------------------------------

#: (window, what it is against the 512-token sequence)
WINDOWS = [(None, "none"), (200, "shorter, inside a block pair"),
           (256, "shorter, block-aligned"), (1, "the query alone"),
           (512, "equal"), (700, "longer")]


def _grouped(key, group, s=512, kv_heads=1, d=128):
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (1, s, kv_heads * group, d), jnp.float32)
    k = jax.random.normal(kk, (1, s, kv_heads, d), jnp.float32)
    v = jax.random.normal(kv, (1, s, kv_heads, d), jnp.float32)
    return q, k, v, jax.random.normal(kg, q.shape, jnp.float32)


def _kernel_and_reference(window, block_q=128, block_k=128):
    ref_fn = lambda q, k, v: reference_attention(q, k, v, jnp.float32,
                                                 window=window)
    fl_fn = lambda q, k, v: flash_attention(
        q, k, v, jnp.float32, window=window, block_q=block_q,
        block_k=block_k, interpret=True, force=True)
    return fl_fn, ref_fn


@pytest.mark.parametrize("window", [w for w, _ in WINDOWS],
                         ids=[name for _, name in WINDOWS])
@pytest.mark.parametrize("group,kv_heads", [(1, 2), (7, 1), (2, 2)])
def test_grouped_windowed_forward_matches_reference(group, kv_heads, window):
    q, k, v, _ = _grouped(jax.random.PRNGKey(20), group, kv_heads=kv_heads)
    fl_fn, ref_fn = _kernel_and_reference(window)
    np.testing.assert_allclose(fl_fn(q, k, v), ref_fn(q, k, v), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("grad", ["dq", "dk", "dv"])
@pytest.mark.parametrize("window", [w for w, _ in WINDOWS],
                         ids=[name for _, name in WINDOWS])
@pytest.mark.parametrize("group,kv_heads", [(1, 2), (7, 1)])
def test_grouped_windowed_gradients_match_reference(group, kv_heads, window,
                                                    grad):
    """dK / dV of a key / value head are the sums over its group's query
    heads (inside the kernel, in float32); the band's bounds hold from both
    sides (k blocks under a q block, q blocks over a k block)."""
    q, k, v, g = _grouped(jax.random.PRNGKey(21), group, kv_heads=kv_heads)
    i = ["dq", "dk", "dv"].index(grad)
    got, want = (
        jax.grad(lambda q, k, v: (fn(q, k, v) * g).sum(), argnums=i)(q, k, v)
        for fn in _kernel_and_reference(window))
    assert got.shape == (q, k, v)[i].shape
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128),
                                             (512, 512)])
def test_the_band_holds_under_mismatched_blocks(block_q, block_k):
    q, k, v, g = _grouped(jax.random.PRNGKey(22), 2, kv_heads=2)
    fl_fn, ref_fn = _kernel_and_reference(300, block_q, block_k)
    np.testing.assert_allclose(fl_fn(q, k, v), ref_fn(q, k, v), atol=2e-5,
                               rtol=2e-5)
    grads = lambda fn: jax.grad(lambda q, k, v: (fn(q, k, v) * g).sum(),
                                argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(fl_fn), grads(ref_fn), "qkv"):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")


def _kernel_names(fn, *operands):
    return [eqn.params["name"] for eqn in _eqns(jax.make_jaxpr(fn)(*operands))
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("window,prefix", [(None, "flash_"), (512, "flash_"),
                                           (700, "flash_"),
                                           (200, "flash_win_")])
def test_windowed_calls_carry_names_of_their_own(window, prefix):
    """A window that cuts nothing (as long as the sequence, or longer) is
    plain causal attention under the plain kernels' names, which the gpt2
    cell's readers key on."""
    q, k, v, g = _grouped(jax.random.PRNGKey(23), 7)
    fl_fn, _ = _kernel_and_reference(window)
    names = _kernel_names(jax.grad(lambda q, k, v: (fl_fn(q, k, v) * g).sum(),
                                   argnums=(0, 1, 2)), q, k, v)
    assert sorted(names) == sorted(
        prefix + kernel for kernel in ("fwd", "bwd_dkv", "bwd_dq"))


def test_grouped_heads_below_a_lane_block_take_the_reference():
    """Two heads of 64 share a 128-lane block; their key / value heads would
    have to sit side by side in one too: no kernel, the reference's result."""
    from bagua_tpu.ops.flash_attention import kv_grouping_supported

    assert kv_grouping_supported(28, 4, 128) and kv_grouping_supported(4, 4, 64)
    assert not kv_grouping_supported(4, 2, 64)
    assert not kv_grouping_supported(28, 8, 128)
    q, k, v, _ = _grouped(jax.random.PRNGKey(24), 2, kv_heads=2, d=64)
    fl_fn, ref_fn = _kernel_and_reference(100)
    assert _kernel_names(fl_fn, q, k, v) == []
    np.testing.assert_array_equal(fl_fn(q, k, v), ref_fn(q, k, v))


def test_a_window_needs_causal_attention():
    q, k, v, _ = _grouped(jax.random.PRNGKey(25), 1)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64, interpret=True,
                        force=True)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=0, interpret=True, force=True)
