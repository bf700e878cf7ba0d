"""Mamba-2 — the state-space token mixer of the hybrid decoders (Nemotron-H:
the ``M`` blocks of its pattern), the one sub-layer of a
:class:`bagua_tpu.models.transformer.Block` whose layer the plan
(``TransformerConfig.layer_plan``) names ``"ssm"``.

From the block's normed input ``u`` (``H`` heads of width ``P``, ``G`` groups,
state ``N``; ``d_inner = H P``):

    [z | xBC | dt] = u W_in                  one fused projection (d_inner,
                                             d_inner + 2 G N, H columns)
    xBC   = silu(conv(xBC) + b_conv)         causal, depthwise, WITH bias
    [x | B | C] = xBC                        (d_inner, G N, G N columns)
    delta = softplus(dt + dt_bias);  A = -exp(A_log)            float32
    y     = ssd_scan(x, delta, A, B, C, D)                 (ops/ssd.py)
    g     = y * silu(z)                      the gate FIRST
    o     = w_n * g / rms(g)                 over each GROUP's d_inner / G lanes
    out   = o W_out

Head ``h`` reads group ``h // (H / G)``.  The fused projection is ONE leaf
``in_proj`` computed as two products over the same rows: the ``z | xBC``
columns in the model's dtype, the ``H`` columns of ``dt`` with a float32
result — the step sizes, the decays and the state are float32 from there on.

The state is per sequence and starts at zero: no decode path, no sequence or
tensor parallel form yet (the callers refuse those).

Which path runs where: the projections are plain matmuls everywhere.  Where
:func:`rows_by_kernel` says so (a TPU; what the ``ssd_*`` kernels cover — a
group's heads and the state of whole 128-lane tiles —; a sequence of whole
row blocks that the scan does not pad; bfloat16 or float32) everything
between the two projections is ``ops/ssd_rows.py::ssd_rows``: the Pallas row
passes ``ssd_mix`` (lines two and three above, reading the projection's
buffer where it lies) and ``ssd_gate`` (the gate and the grouped norm)
around the ``ssd_fwd`` kernel, their transposes ``ssd_gate_bwd`` /
``ssd_mix_bwd`` around ``ssd_bwd`` writing the buffer's cotangent in place.
Everywhere else (the CPU, a ragged sequence, odd widths) the scan is
:func:`bagua_tpu.ops.ssd.ssd_scan`, which picks between its kernels and its
``jax.numpy`` chunks by itself, and the rows around it are ``jax.numpy``
(:func:`conv_bias_silu`, one VJP so that each direction is one pass over
arrays in the rows' dtype, and :func:`gated_group_norm`): the fallback, and
the tests' golden.  The choice is read off the input; nothing sets it.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from .linear_attention import ahead, behind, per_head


def _pre_activation(x, taps, bias):
    """``sum_j taps[j] x_{t - (n - 1 - j)} + bias`` a channel, float32."""
    n = taps.shape[0]
    return sum(behind(x, n - 1 - j).astype(jnp.float32)
               * taps[j].astype(jnp.float32) for j in range(n)) + bias.astype(
                   jnp.float32)


@jax.custom_vjp
def conv_bias_silu(x, taps, bias):
    """``silu(conv(x) + bias)``: the causal depthwise convolution of
    :func:`bagua_tpu.models.linear_attention.causal_depthwise_conv`
    (``taps[n - 1]`` weighs the position itself) with a bias a channel, then
    SiLU; ``x`` [batch, seq, channels], ``taps`` [n, channels], ``bias``
    [channels]; float32 from the load to one rounding to ``x.dtype``.

    The VJP is written out for that function's reason: left to autodiff the
    transpose of ``n`` slices of a padded float32 copy is ``n`` padded
    float32 arrays as large as the input.  Here the backward pass rebuilds
    the pre-activation (one pass) and each cotangent is one more."""
    return nn.silu(_pre_activation(x, taps, bias)).astype(x.dtype)


def _conv_fwd(x, taps, bias):
    return conv_bias_silu(x, taps, bias), (x, taps, bias)


def _conv_bwd(res, dy):
    x, taps, bias = res
    n = taps.shape[0]
    pre = _pre_activation(x, taps, bias)
    gate = jax.nn.sigmoid(pre)
    # d silu = sigmoid(a) (1 + a (1 - sigmoid(a)))
    d_pre = dy.astype(jnp.float32) * gate * (1.0 + pre * (1.0 - gate))
    dx = sum(ahead(d_pre, n - 1 - j) * taps[j].astype(jnp.float32)
             for j in range(n))
    d_taps = jnp.stack([
        jnp.sum(d_pre * behind(x, n - 1 - j).astype(jnp.float32),
                axis=(0, 1)) for j in range(n)])
    return (dx.astype(x.dtype), d_taps.astype(taps.dtype),
            jnp.sum(d_pre, axis=(0, 1)).astype(bias.dtype))


conv_bias_silu.defvjp(_conv_fwd, _conv_bwd)


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``w_n * g / sqrt(mean(g^2) + eps)`` with ``g = y * silu(z)`` — the
    gate first, then RMSNorm over each of the ``groups`` runs of lanes by
    itself: ``y`` / ``z`` [..., d_inner], ``scale`` [d_inner]; float32, one
    rounding to ``z.dtype``.  The per-group mean is taken on the flat rows
    (``linear_attention.per_head``: a ``[..., groups, lanes]`` view of a
    float32 value is another tiling on the TPU)."""
    g = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    normed = per_head(g, groups, lambda mean: jax.lax.rsqrt(mean + eps))
    return (scale.astype(jnp.float32) * normed).astype(z.dtype)


def _dims(cfg):
    return (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state)


def rows_by_kernel(cfg, seq: int) -> bool:
    """Whether a layer of ``cfg`` over ``seq`` positions runs its rows
    between the two projections as the Pallas passes of ``ops/ssd_rows.py``
    (on a TPU, where their grids cover the shape) and not as
    :func:`conv_bias_silu` / :func:`gated_group_norm` around ``ssd_scan``."""
    from ..ops.ssd_rows import rows_supported

    return rows_supported(seq, _dims(cfg), cfg.ssm_conv, cfg.ssm_chunk,
                          cfg.dtype)


def set_gauges(cfg, layers: int, seq: int) -> None:
    """The trace-time gauges of a step with ``layers`` state-space layers
    over ``seq`` positions (``ssm/*``)."""
    from ..telemetry import counters

    counters.set_gauge("ssm/layers", layers)
    # of those, the layers whose rows between the projections are the
    # ``ssd_mix`` / ``ssd_gate`` passes
    counters.set_gauge("ssm/row_kernel_layers",
                       layers * rows_by_kernel(cfg, seq))
    for size in ("chunk", "heads", "head_dim", "groups", "state"):
        counters.set_gauge(f"ssm/{size}", getattr(cfg, f"ssm_{size}"))


#: the step sizes ``dt_bias`` starts from: log-uniform in this range, then
#: floored (the family's ``time_step_min`` / ``time_step_max`` /
#: ``time_step_floor`` defaults, which the published configs keep)
STEP_RANGE = (1e-3, 0.1)
STEP_FLOOR = 1e-4


def conv_init(taps: int):
    """Taps and bias of the depthwise convolution: ``U(-k^-1/2, k^-1/2)`` at
    ``k`` taps (the family's ``Conv1d`` default at one input channel a
    group)."""
    bound = 1.0 / math.sqrt(taps)
    return lambda key, shape, dtype: jax.random.uniform(
        key, shape, dtype, -bound, bound)


def step_bias_init(key, shape, dtype):
    """``dt_bias``: the inverse softplus of a step size drawn log-uniform in
    ``STEP_RANGE`` and floored at ``STEP_FLOOR`` (the family's
    initialisation)."""
    lo, hi = (math.log(v) for v in STEP_RANGE)
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo),
        STEP_FLOOR)
    return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)


def decay_init(key, shape, dtype):
    """``A_log = log(1 .. heads)`` (the family's ``A = arange(1, H + 1)``)."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(
        dtype)


class Mamba2(nn.Module):
    """Tokens [batch, seq, d_model] -> the same, by the selective
    state-space scan."""

    cfg: "TransformerConfig"  # noqa: F821 - models.transformer's

    @nn.compact
    def __call__(self, x):
        from ..ops.ssd import ssd_scan

        cfg = self.cfg
        h, p, groups, n = dims = _dims(cfg)
        if min(h, p, groups, n, cfg.ssm_conv, cfg.ssm_chunk) < 1 or h % groups:
            raise ValueError(
                "layer_kinds names state-space layers: they need ssm_heads "
                "(a multiple of ssm_groups), ssm_head_dim, ssm_groups, "
                "ssm_state, ssm_conv and ssm_chunk; got "
                f"{h} / {p} / {groups} / {n} / {cfg.ssm_conv} / "
                f"{cfg.ssm_chunk}")
        b, s, d = x.shape
        inner, maps = h * p, groups * n
        conv_width = inner + 2 * maps
        # one leaf, [z | xBC | dt]; the dt columns' product keeps its
        # float32 result
        w_in = self.param("in_proj", nn.initializers.lecun_normal(),
                          (d, inner + conv_width + h), cfg.param_dtype)
        rows = x.astype(cfg.dtype)
        zxbc = jnp.dot(rows, w_in[:, :inner + conv_width].astype(cfg.dtype))
        dt = jnp.dot(rows, w_in[:, inner + conv_width:].astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
        taps = self.param("conv", conv_init(cfg.ssm_conv),
                          (cfg.ssm_conv, conv_width), cfg.param_dtype)
        conv_bias = self.param("conv_bias", conv_init(cfg.ssm_conv),
                               (conv_width,), cfg.param_dtype)
        a_log = self.param("A_log", decay_init, (h,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", step_bias_init, (h,), cfg.param_dtype)
        skip = self.param("D", nn.initializers.ones, (h,), cfg.param_dtype)
        norm_scale = self.param("norm", nn.initializers.ones, (inner,),
                                cfg.param_dtype)

        if rows_by_kernel(cfg, s):
            # where the kernels run: the same rows as Pallas passes on the
            # projection's buffer
            from ..ops.ssd_rows import ssd_rows

            o = ssd_rows(
                zxbc, taps, conv_bias,
                jax.nn.softplus(dt + dt_bias.astype(jnp.float32)),
                -jnp.exp(a_log.astype(jnp.float32)), skip, norm_scale, dims,
                chunk=cfg.ssm_chunk, norm_eps=cfg.norm_eps)
        else:
            xbc = conv_bias_silu(zxbc[..., inner:], taps, conv_bias)
            delta = jax.nn.softplus(dt + dt_bias.astype(jnp.float32))
            y = ssd_scan(
                xbc[..., :inner].reshape(b, s, h, p), delta,
                -jnp.exp(a_log.astype(jnp.float32)),
                xbc[..., inner:inner + maps].reshape(b, s, groups, n),
                xbc[..., inner + maps:].reshape(b, s, groups, n), skip,
                chunk=cfg.ssm_chunk)
            o = gated_group_norm(y.reshape(b, s, inner), zxbc[..., :inner],
                                 norm_scale, groups, cfg.norm_eps)
        return nn.Dense(d, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="out_proj")(o)
