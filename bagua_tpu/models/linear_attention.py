"""Gated DeltaNet — the linear-attention token mixer of the hybrid decoders
(Qwen3-Next, Olmo-Hybrid: three layers of four), a drop-in for ``Attention``
inside :class:`bagua_tpu.models.transformer.Block` where the configuration's
``mixer_layers`` pattern says so.

From the block's input ``u`` (normed, or — Olmo-Hybrid's trunk norms a
sub-layer's output instead — as it comes):

    [q, k, v, z] = u W_qkvz                  one fused projection
    [b, a]       = u W_ba                    two scalars a value head, float32
    [q, k, v]    = silu(conv(q, k, v))       causal, depthwise, no bias
    beta = sigmoid(b)  (``linear_neg_eigval``: 2 sigmoid(b), a write strength
                        in (0, 2), the transition's eigenvalues in (-1, 1))
    g    = -exp(A_log) * softplus(a + dt_bias)
    q, k L2-normalised a head, q scaled by d_k^-1/2
    o    = gated_delta_rule(q, k, v, g, beta)          (ops/gated_delta.py)
    y    = w_n * o / rms(o) * silu(z)        a value head's lanes, plain scale
    out  = y W_out

A key head serves ``linear_value_heads // linear_key_heads`` consecutive
value heads.  The fused projections' columns are laid ``[q | k | v | z]`` and
``[b | a]``, heads in order inside each part; the published checkpoints
interleave them a key head at a time (``[q_h, k_h, v_h.., z_h..]``), which is
a permutation of columns at load time and nothing to a seeded model.

The state is per sequence and starts at zero: no decode path, no sequence or
tensor parallel form yet (the callers refuse those).

Which path runs where.  The two projections are ``nn.Dense`` everywhere.
Between them, on a TPU and where their grids cover the shape
(:func:`rows_by_kernel`: heads that are whole 128-lane tiles alone — 128 x
128 — or, key and value heads as many, in blocks of up to four — 96-lane
keys under 192-lane values, any head count —, a sequence of whole 128-row
blocks, bfloat16 or float32), the
rows are Pallas passes on the
projection's own buffer around the ``gdn_fwd`` / ``gdn_bwd`` kernels
(``ops/gated_delta_rows.py::gated_delta_rows``: ``gdn_mix`` for lines three
and five above, ``gdn_gate`` for the seventh, float32 from the load to one
rounding at the store, one VJP that writes the buffer's cotangent where it
lands).  Everywhere else — the CPU, a ragged sequence, heads of which more
than four make whole tiles (16-lane keys), grouped heads that share a tile
— they
are the ``jax.numpy`` code of this file (:func:`mix_rows`,
:func:`gate_rows`) around ``ops.gated_delta.gated_delta_rule``, which picks
between its kernels and its own ``jax.numpy`` chunks by itself; that code is
also the golden the passes are tested against.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

#: epsilon of the L2 norm of q and k (the family's kernels': x / sqrt(sum x^2
#: + eps))
L2_EPS = 1e-6


def behind(x, k: int):
    """``x[t - k]`` along the sequence, zeros in front."""
    return x if k == 0 else jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, :x.shape[1]]


def ahead(x, k: int):
    """``x[t + k]`` along the sequence, zeros behind."""
    return x if k == 0 else jnp.pad(x, ((0, 0), (0, k), (0, 0)))[:, k:]


@jax.custom_vjp
def causal_depthwise_conv(x, taps):
    """``y_t = sum_j taps[j] * x_{t - (n - 1 - j)}`` a channel, zeros in
    front of the sequence: ``x`` [batch, seq, channels], ``taps`` [n,
    channels]; float32 sums, result in ``x.dtype``.  ``taps[n - 1]`` weighs
    the position itself (``torch.nn.Conv1d``'s order under left padding).

    Its VJP is written out — the input's cotangent is the same sum over the
    positions AHEAD, the taps' a reduction over batch and sequence — so
    that each is one pass over arrays in ``x.dtype``: left to autodiff, the
    transpose of ``n`` slices of one padded float32 copy is ``n`` padded
    float32 arrays as large as the input (1 GiB each at 8,192 rows of 8,192
    channels)."""
    n = taps.shape[0]
    y = sum(behind(x, n - 1 - j).astype(jnp.float32)
            * taps[j].astype(jnp.float32) for j in range(n))
    return y.astype(x.dtype)


def _conv_fwd(x, taps):
    return causal_depthwise_conv(x, taps), (x, taps)


def _conv_bwd(res, dy):
    x, taps = res
    n = taps.shape[0]
    dx = sum(ahead(dy, n - 1 - j).astype(jnp.float32)
             * taps[j].astype(jnp.float32) for j in range(n))
    d_taps = jnp.stack([
        jnp.sum(dy.astype(jnp.float32)
                * behind(x, n - 1 - j).astype(jnp.float32), axis=(0, 1))
        for j in range(n)])
    return dx.astype(x.dtype), d_taps.astype(taps.dtype)


causal_depthwise_conv.defvjp(_conv_fwd, _conv_bwd)


def _head_pool(heads: int, dim: int):
    """``[heads * dim, heads]`` float32, one where the lane is the head's: a
    product with it sums a head's lanes, a product with its transpose lays a
    per-head number over them.  The per-head reductions of this layer go
    through it on ``[batch, seq, heads * dim]`` as the projections write it:
    a ``[batch, seq, heads, dim]`` view is another tiling on the TPU, and
    the compiler re-lays every float32 value that crosses between the two
    (six such copies of the rows a layer and pass, before)."""
    lane = jnp.arange(heads * dim)[:, None] // dim
    return (lane == jnp.arange(heads)[None, :]).astype(jnp.float32)


def per_head(x, heads: int, reduce):
    """``x`` [..., heads * dim] times ``reduce(mean of x^2 over a head's
    lanes)`` laid back over those lanes; float32."""
    x = x.astype(jnp.float32)
    pool = _head_pool(heads, x.shape[-1] // heads)
    exact = jax.lax.Precision.HIGHEST
    mean = jnp.einsum("...k,kh->...h", x * x, pool, precision=exact) * (
        heads / x.shape[-1])
    return x * jnp.einsum("...h,kh->...k", reduce(mean), pool,
                          precision=exact)


def mix_rows(qkvz, taps, dims):
    """The fused projection's q | k | v columns through the convolution and
    SiLU, q and k L2-normalised a head (``x / sqrt(sum x^2 + eps)``, on the
    flat rows: ``_head_pool``) and q scaled by ``d_k^-1/2``: ``qkvz`` [b, s,
    2 hk dk + 2 hv dv], ``dims`` ``(hk, hv, dk, dv)`` -> ``(q, k [b, s, hk
    dk], v [b, s, hv dv])`` in ``qkvz.dtype``."""
    hk, hv, dk, dv = dims
    key_width, value_width = hk * dk, hv * dv
    mixed = nn.silu(causal_depthwise_conv(
        qkvz[..., :2 * key_width + value_width], taps))
    unit = lambda t: per_head(
        t, hk, lambda mean: jax.lax.rsqrt(mean * dk + L2_EPS))
    q = (unit(mixed[..., :key_width]) / math.sqrt(dk)).astype(qkvz.dtype)
    k = unit(mixed[..., key_width:2 * key_width]).astype(qkvz.dtype)
    return q, k, mixed[..., 2 * key_width:]


def gate_rows(o, z, norm_scale, heads: int, eps: float):
    """The gated norm, ``w_n * o / rms(o) * silu(z)`` over a value head's
    lanes: float32, one rounding to the rows' dtype."""
    o = per_head(o, heads, lambda mean: jax.lax.rsqrt(mean + eps))
    return (jnp.tile(norm_scale.astype(jnp.float32), heads) * o
            * nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def _dims(cfg):
    return (cfg.linear_key_heads, cfg.linear_value_heads,
            cfg.linear_key_dim, cfg.linear_value_dim)


def rows_by_kernel(cfg, seq: int) -> bool:
    """Whether a layer of ``cfg`` over ``seq`` positions runs its rows
    between the two projections as the Pallas passes of
    ``ops/gated_delta_rows.py`` (on a TPU, where their grids cover the
    shape: heads of whole 128-lane tiles alone or, key and value heads as
    many, in blocks of up to four — 128 x 128, 96 x 192 —, a sequence of
    whole 128-row blocks) and not as
    :func:`mix_rows` / :func:`gate_rows`."""
    from ..ops.gated_delta_rows import rows_supported

    return rows_supported(seq, _dims(cfg), cfg.linear_conv, cfg.dtype)


def set_gauges(cfg, layers: int, seq: int) -> None:
    """The trace-time gauges of a step with ``layers`` linear-attention
    layers over ``seq`` positions (``linattn/*``)."""
    from ..ops.gated_delta import CHUNK
    from ..telemetry import counters

    counters.set_gauge("linattn/layers", layers)
    # of those, the layers whose rows between the projections are the
    # ``gdn_mix`` / ``gdn_gate`` passes
    counters.set_gauge("linattn/row_kernel_layers",
                       layers * rows_by_kernel(cfg, seq))
    counters.set_gauge("linattn/chunk", CHUNK)
    for size in ("key_heads", "value_heads", "key_dim", "value_dim"):
        counters.set_gauge(f"linattn/{size}", getattr(cfg, f"linear_{size}"))
    counters.set_gauge("linattn/neg_eigval", int(cfg.linear_neg_eigval))


def decay_init(key, shape, dtype):
    """``A_log``: the log of ``A ~ U(0, 16)`` a value head (the family's
    initialisation; the lower end kept off zero)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


class GatedDeltaNet(nn.Module):
    """Tokens [batch, seq, d_model] -> the same, by the gated delta rule."""

    cfg: "TransformerConfig"  # noqa: F821 - models.transformer's

    @nn.compact
    def __call__(self, x):
        from ..ops.gated_delta import gated_delta_rule

        cfg = self.cfg
        hk, hv, dk, dv = dims = _dims(cfg)
        if min(hk, hv, dk, dv, cfg.linear_conv) < 1 or hv % hk:
            raise ValueError(
                "mixer_layers names linear-attention layers: they need "
                "linear_key_heads, linear_value_heads (a multiple of the key "
                "heads), linear_key_dim, linear_value_dim and linear_conv "
                "(any positive widths: the kernels take heads that are "
                "whole 128-lane tiles alone or, key and value heads as many, "
                "in blocks of up to four, 128 x 128 as 96 x 192, the "
                "jax.numpy form every other); "
                f"got {hk} / {hv} / {dk} / {dv} / {cfg.linear_conv}")
        b, s, _ = x.shape
        key_width, value_width = hk * dk, hv * dv
        dense = lambda name, features: nn.Dense(
            features, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        qkvz = dense("in_proj_qkvz", 2 * key_width + 2 * value_width)(x)
        # the two gates a value head: float32 like the routers (2 hv columns)
        ba = nn.Dense(2 * hv, use_bias=False, dtype=jnp.float32,
                      param_dtype=cfg.param_dtype, name="in_proj_ba")(
                          x.astype(jnp.float32))
        taps = self.param(
            "conv", nn.initializers.lecun_normal(in_axis=0, out_axis=1),
            (cfg.linear_conv, 2 * key_width + value_width), cfg.param_dtype)
        a_log = self.param("A_log", decay_init, (hv,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                             cfg.param_dtype)
        norm_scale = self.param("norm", nn.initializers.ones, (dv,),
                                cfg.param_dtype)

        beta = jax.nn.sigmoid(ba[..., :hv])
        if cfg.linear_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
            ba[..., hv:] + dt_bias.astype(jnp.float32))
        if rows_by_kernel(cfg, s):
            # where the kernels run: the same rows as Pallas passes on the
            # projection's buffer
            from ..ops.gated_delta_rows import gated_delta_rows

            y = gated_delta_rows(qkvz, taps, g, beta, norm_scale, dims,
                                 l2_eps=L2_EPS, norm_eps=cfg.norm_eps)
        else:
            q, k, v = mix_rows(qkvz, taps, dims)
            o = gated_delta_rule(
                q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk),
                v.reshape(b, s, hv, dv), g, beta)
            y = gate_rows(o.reshape(b, s, value_width),
                          qkvz[..., 2 * key_width + value_width:],
                          norm_scale, hv, cfg.norm_eps)
        return dense("out_proj", cfg.d_model)(y)
