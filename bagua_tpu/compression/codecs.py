"""Ring-hop codec registry — the wire formats of the compressed collectives.

The Bagua paper's core relaxation is communication compression
(arXiv 2107.01499; 1-bit Adam, arXiv 2102.02888).  Until ISSUE 15 the
codecs ran as a *separate stage around* full-precision collectives; the
compressed ring collectives (``BaguaCommunicator.ring_*(codec=)``) instead
quantize ON the hop: every ``ppermute`` carries a codec payload plus its
small f32 sidecar, the receiver dequantizes and accumulates in fp32, and
the reduce-scatter result is re-quantized exactly once for the allgather
phase.  This module owns the payload formats.

Codec contract (all methods traced-safe):

* ``encode(x2d)`` — ``[k, m]`` float input -> a tuple of arrays, small f32
  sidecars first, the payload LAST, every part with leading dim ``k`` so
  the parts of one chunk travel (and stack) together.
* ``decode(parts, m=None)`` — exact inverse layout; returns ``[k, m]``
  **float32**.  Dequantize-to-f32 is the accumulation-dtype contract: ring
  hops add their local block in fp32, so quantization error never
  compounds through the accumulator dtype, only through the per-hop
  re-quantization.  ``m`` is the chunk element count: the uniform codecs
  infer it from the payload shape and ignore the argument, but the
  bit-packed and variable-payload codecs (``variable_payload = True``)
  cannot invert payload-shape -> m and REQUIRE it.
* ``wire_bytes(numel)`` — host-side bytes one encoded chunk of ``numel``
  elements puts on the wire (payload + sidecar); the byte-accounting
  source for ``bucket_tier_bytes``, the launch spans, and the benches.
  Codecs whose payload is not one byte per element (onebit_ef's packed
  bits, topk's index+value pairs) override it — accounting consumes the
  codec's ACTUAL per-hop bytes, never a numel*itemsize guess.

Stateful codecs (``error_feedback = True``): the codec itself stays a
pure wire format, but it only CONVERGES when the per-bucket
error-feedback residual folds the quantization error back into the next
step's gradient (EF-SignSGD, arXiv 1901.09847; 1-bit Adam, arXiv
2102.02888).  The residual lives in the algorithm state
(:meth:`bagua_tpu.algorithms.base.Algorithm.compensate_flats`), not here
— encode/decode see the already-compensated flats.

Non-finite contract: a NaN/Inf element poisons (at least) its own decoded
element and, for the scale-based codecs, its whole chunk — conservative on
purpose, so the gradient-health sentinel still sees the poison after a
compressed collective.

Pallas fast path: the min/max **reduction** is where a fused kernel pays
(round-5 profile: +8% at 1 MiB chunks, 7x at 8 MiB); purely elementwise maps
(quantize against known bounds, every decompress, the fp8 cast) measured
FASTER through the XLA lowering at every size, so only the reduction side
gates on :data:`~bagua_tpu.compression.minmax_uint8._PALLAS_MIN_CHUNK_BYTES`.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from .minmax_uint8 import (
    _PALLAS_MIN_CHUNK_BYTES,
    compress_chunked,
    decompress_chunked,
)


def _pallas_ok(chunk_bytes: int, platform: Optional[str] = None) -> bool:
    """The ONE gate for the fused Pallas reduction kernels: TPU, not
    disabled, and the per-chunk payload past the measured crossover —
    shared with :func:`..minmax_uint8._codec` so the crossover can never
    be retuned in one place and not the other.  ``platform`` lets a
    mesh-aware caller pass its comm mesh's platform; default is the
    ambient backend."""
    from .. import env

    if chunk_bytes < _PALLAS_MIN_CHUNK_BYTES:
        return False
    if platform is None:
        # a backend that fails to initialize raises here: it must never be
        # read as "not a TPU, take the jnp codec"
        platform = jax.devices()[0].platform
    return platform == "tpu" and not env.is_pallas_codec_disabled()


def _absmax_sidecar(x: jax.Array, chunk_bytes: int,
                    fmax: float) -> Tuple[jax.Array, jax.Array]:
    """Shared scaled-quantize front half of the int8/fp8 codecs: per-chunk
    absmax (fused Pallas past the crossover) mapped onto a grid of
    ``fmax``.  Returns ``(sidecar, safe)`` — ``safe`` is the
    division-ready scale (1.0 for all-zero chunks), ``sidecar`` the wire
    copy, which deliberately keeps a NaN absmax (a NaN fails every
    comparison, so ``safe`` would silently become 1 and the cast would
    flush the poison to a finite value — the sidecar NaN makes DECODE
    propagate it, the grad-guard contract)."""
    k, m = x.shape
    if _pallas_ok(chunk_bytes):
        from .pallas_codec import absmax_chunked_pallas

        absmax = absmax_chunked_pallas(x.reshape(-1), k)
    else:
        absmax = jnp.abs(x).max(axis=1)
    scale = absmax / fmax
    safe = jnp.where(scale > 0, scale, 1.0)
    return jnp.where(jnp.isnan(scale), scale, safe), safe


class RingCodec:
    """One wire format for the compressed ring hops."""

    #: registry key (the user-facing knob value)
    name: str = ""
    #: dtype of the payload array (the bulk of the wire bytes)
    payload_itemsize: int = 1
    #: f32 sidecar scalars per encoded chunk
    sidecar_floats: int = 0
    #: True for codecs that only converge with the per-bucket
    #: error-feedback residual (the algorithm layer engages it)
    error_feedback: bool = False
    #: True when the payload shape is not [k, m] — decode REQUIRES ``m``
    #: and byte accounting must go through ``wire_bytes``, never
    #: numel * itemsize
    variable_payload: bool = False
    #: True for codecs whose wire format depends on a BAGUA_* env knob:
    #: :func:`get_codec` re-constructs them per lookup so the knob is
    #: read when the codec is *resolved* (trainer construction / step
    #: trace), not frozen at process import — matching every other
    #: BAGUA_* knob and the podsim numpy mirror.
    env_tuned: bool = False

    def encode(self, x2d: jax.Array) -> Tuple[jax.Array, ...]:
        raise NotImplementedError

    def decode(self, parts: Tuple[jax.Array, ...],
               m: Optional[int] = None) -> jax.Array:
        raise NotImplementedError

    def wire_bytes(self, numel: int) -> int:
        """Wire bytes of ONE encoded chunk of ``numel`` elements."""
        return int(numel) * self.payload_itemsize + 4 * self.sidecar_floats

    def payload_numel(self, numel: int) -> int:
        """Host-side element count of the PAYLOAD array for an
        ``numel``-element chunk — what a traced collective's operand shape
        shows (bagua-lint's per-bucket attribution matches on it).  The
        uniform codecs carry one payload element per input element; the
        bit-packed/sparse codecs override."""
        return int(numel)

    def __repr__(self) -> str:  # stable in logs / span attrs
        return f"<RingCodec {self.name}>"


class MinMaxUInt8Codec(RingCodec):
    """The reference MinMaxUInt8 format: per-chunk ``[mn, mx]`` f32 sidecar
    + u8 levels (``tests/internal/compressor.py`` golden math).  Fused
    Pallas min/max+quantize past the measured chunk-size crossover."""

    name = "minmax_uint8"
    payload_itemsize = 1
    sidecar_floats = 2

    def encode(self, x2d):
        k, m = x2d.shape
        flat = x2d.reshape(-1)
        if _pallas_ok(m * x2d.dtype.itemsize):
            from .pallas_codec import compress_chunked_pallas

            mn, mx, payload = compress_chunked_pallas(flat, k)
        else:
            mn, mx, payload = compress_chunked(flat, k)
        return mn, mx, payload

    def decode(self, parts, m=None):
        mn, mx, payload = parts
        return decompress_chunked(mn, mx, payload).reshape(payload.shape)


class Int8Codec(RingCodec):
    """Symmetric absmax int8: per-chunk f32 ``scale`` sidecar, payload
    ``round(x / scale)`` clipped to [-127, 127].  One fewer sidecar float
    than MinMaxUInt8 and a zero-centered grid (a zero gradient stays
    exactly zero — MinMaxUInt8's grid need not contain 0).  The absmax
    reduction takes the fused Pallas kernel past the crossover."""

    name = "int8"
    payload_itemsize = 1
    sidecar_floats = 1

    def encode(self, x2d):
        x = x2d.astype(jnp.float32)
        sidecar, safe = _absmax_sidecar(
            x, x2d.shape[1] * x2d.dtype.itemsize, 127.0
        )
        q = jnp.clip(jnp.round(x / safe[:, None]), -127.0, 127.0)
        return sidecar, q.astype(jnp.int8)

    def decode(self, parts, m=None):
        scale, payload = parts
        return payload.astype(jnp.float32) * scale[:, None]


class Fp8Codec(RingCodec):
    """Scaled fp8: per-chunk f32 ``scale`` sidecar mapping the chunk's
    absmax onto the format's max finite value, payload ``x / scale`` cast
    to the fp8 dtype.  ``e4m3`` (3 mantissa bits, higher resolution) suits
    gradient payloads; ``e5m2`` keeps bf16's exponent spread for
    heavy-tailed chunks.  The scaling keeps denormal-range inputs
    representable (the payload always spans the full fp8 range), and a
    non-finite input propagates: ``inf/inf -> nan`` lands IN the payload.
    The cast is elementwise, so the only reduction (absmax) gates on the
    Pallas crossover like int8."""

    payload_itemsize = 1
    sidecar_floats = 1

    def __init__(self, name: str, dtype):
        self.name = name
        self.dtype = dtype
        self.fmax = float(jnp.finfo(dtype).max)

    def encode(self, x2d):
        x = x2d.astype(jnp.float32)
        sidecar, safe = _absmax_sidecar(
            x, x2d.shape[1] * x2d.dtype.itemsize, self.fmax
        )
        return sidecar, (x / safe[:, None]).astype(self.dtype)

    def decode(self, parts, m=None):
        scale, payload = parts
        return payload.astype(jnp.float32) * scale[:, None]


def _onebit_payload_bytes(m: int) -> int:
    """Packed-payload bytes of one m-element chunk: ceil(m/1024)*128 —
    the planar layout pads to whole 8x(8,128) bit-plane groups so pack
    and unpack stay contiguous sublane slices on TPU (pallas_codec)."""
    return -(-int(m) // 1024) * 128


class OneBitEfCodec(RingCodec):
    """Sign/1-bit codec: per-chunk f32 mean-abs ``scale`` sidecar + a
    bit-packed sign payload (~32x fewer wire bytes than f32; the Bagua
    paper's signature relaxation).  Decode is ``scale * sign(x)`` — the
    L1-optimal magnitude for a sign quantizer (EF-SignSGD §4).  An
    all-zero chunk round-trips exactly (scale 0); a NaN/Inf element
    drives the mean-abs scale non-finite, poisoning the whole decoded
    chunk — the grad-guard propagation contract, same as the absmax
    codecs.  Pack/unpack + the mean-abs reduction take the fused Pallas
    kernels past the shared crossover; below it (or off-TPU) the
    byte-identical jnp planar pack runs.

    ``error_feedback = True``: without the per-bucket residual this is
    biased sign-SGD and diverges — the algorithm layer engages
    ``compensate_flats`` wherever this codec rides."""

    name = "onebit_ef"
    payload_itemsize = 1  # uint8, but ~m/8 of them: wire_bytes overrides
    sidecar_floats = 1
    error_feedback = True
    variable_payload = True

    def encode(self, x2d):
        x = x2d.astype(jnp.float32)
        k, m = x.shape
        if _pallas_ok(m * x2d.dtype.itemsize):
            from .pallas_codec import sign_compress_chunked_pallas

            scale, payload = sign_compress_chunked_pallas(x.reshape(-1), k)
        else:
            from .pallas_codec import _jnp_sign_pack

            scale = jnp.abs(x).sum(axis=1) / m
            payload = _jnp_sign_pack(x)
        return scale, payload

    def decode(self, parts, m=None):
        scale, payload = parts
        k, B = payload.shape
        if m is None:
            m = 8 * B  # full padded block (no slicing possible)
        if _pallas_ok(_onebit_payload_bytes(m) * 8 * 4):
            from .pallas_codec import sign_decompress_chunked_pallas

            out = sign_decompress_chunked_pallas(scale, payload)
            return out[:, :m]
        shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 8, 1)
        bits = (payload[:, None, :] >> shifts) & jnp.uint8(1)
        signs = bits.reshape(k, 8 * B)[:, :m].astype(jnp.float32) * 2.0 - 1.0
        return signs * scale[:, None]

    def wire_bytes(self, numel: int) -> int:
        return _onebit_payload_bytes(numel) + 4 * self.sidecar_floats

    def payload_numel(self, numel: int) -> int:
        # lane-padded uint8 byte count: the traced ppermute operand shape
        return _onebit_payload_bytes(int(numel))


class TopKCodec(RingCodec):
    """Top-k sparsification — the first VARIABLE-PAYLOAD ring codec:
    parts are ``(int32 indices, f32 values)`` of the ``kk`` largest-
    magnitude elements per chunk, ``kk = clamp(ceil(m * ratio), 1, m)``
    with ``ratio`` the compression knob (``BAGUA_TOPK_RATIO``, default
    1% -> ~50x fewer DCN bytes).  Values travel exact f32, so there is
    no scale sidecar and no quantization error on the SELECTED elements
    — all the loss is the dropped tail, which is exactly what the
    error-feedback residual re-injects next step
    (``error_feedback = True``; stateless top-k loses the small-gradient
    mass forever).  Non-finite elements are force-selected (their sort
    magnitude becomes +inf), so a poisoned element always survives
    decode — the grad-guard contract without a scale sidecar to carry
    it."""

    payload_itemsize = 4
    sidecar_floats = 0
    error_feedback = True
    variable_payload = True
    env_tuned = True  # ratio from BAGUA_TOPK_RATIO at resolution time

    def __init__(self, ratio: Optional[float] = None, name: str = "topk"):
        from .. import env

        self.name = name
        self.ratio = float(env.get_topk_ratio() if ratio is None else ratio)
        if not 0.0 < self.ratio <= 1.0:
            raise ValueError(
                f"topk ratio must be in (0, 1], got {self.ratio}"
            )

    def k_for(self, numel: int) -> int:
        """Selected elements for an m-element chunk (host-static: the
        payload shape is compiled into the step)."""
        n = int(numel)
        return max(1, min(n, int(math.ceil(n * self.ratio))))

    def encode(self, x2d):
        x = x2d.astype(jnp.float32)
        k, m = x.shape
        kk = self.k_for(m)
        mag = jnp.where(jnp.isfinite(x), jnp.abs(x), jnp.inf)
        _, idx = jax.lax.top_k(mag, kk)
        vals = jnp.take_along_axis(x, idx, axis=1)
        return idx.astype(jnp.int32), vals

    def decode(self, parts, m=None):
        idx, vals = parts
        if m is None:
            raise ValueError(
                "topk is variable-payload: decode(parts, m) needs the "
                "chunk element count"
            )
        k, kk = idx.shape
        out = jnp.zeros((k, int(m)), jnp.float32)
        rows = jnp.arange(k, dtype=jnp.int32)[:, None]
        return out.at[rows, idx].set(vals.astype(jnp.float32))

    def wire_bytes(self, numel: int) -> int:
        # int32 index + f32 value per selected element
        return 8 * self.k_for(numel)

    def payload_numel(self, numel: int) -> int:
        # each of the two part arrays carries k_for(m) elements per row
        return self.k_for(numel)


CODECS: Dict[str, RingCodec] = {
    c.name: c
    for c in (
        MinMaxUInt8Codec(),
        Int8Codec(),
        Fp8Codec("fp8_e4m3", jnp.float8_e4m3fn),
        Fp8Codec("fp8_e5m2", jnp.float8_e5m2),
        OneBitEfCodec(),
        TopKCodec(),
    )
}

#: the autopilot's compress_dcn escalation ladder: each sustained
#: DCN-dominance verdict climbs one rung (docs/compression.md) — 8-bit
#: first (cheap, stateless), fp8 next (same bytes, cheaper decode),
#: then the stateful 1-bit/sparse codecs where the residual machinery
#: buys the last 4-8x.
CODEC_LADDER = ("minmax_uint8", "fp8_e4m3", "onebit_ef", "topk")

#: codec-policy knob values beyond the codec names themselves:
#: ``off`` forces full precision on the tier (even where the algorithm
#: family compresses natively), ``auto`` defers to the family default —
#: DCN compressed for the compression families (ByteGrad/QAdam), ICI
#: full-precision for everyone (docs/compression.md).
POLICY_OFF = "off"
POLICY_AUTO = "auto"
POLICY_VALUES = (POLICY_OFF, POLICY_AUTO) + tuple(sorted(CODECS))


def get_codec(name: str) -> RingCodec:
    codec = CODECS.get(name)
    if codec is None:
        raise ValueError(
            f"unknown ring codec {name!r} (available: {sorted(CODECS)})"
        )
    if codec.env_tuned:
        # a fresh instance re-reads the codec's env knobs (topk's
        # BAGUA_TOPK_RATIO): the import-time singleton would freeze the
        # value for the whole process, silently ignoring a knob set
        # before trainer construction.  The backend keys the step cache
        # on the effective ratio so a changed knob retraces.
        return type(codec)()
    return codec


def resolve_codec(
    codec: Union[None, str, RingCodec]
) -> Optional[RingCodec]:
    """None passes through (full precision); names resolve via the
    registry; codec instances pass through."""
    if codec is None:
        return None
    if isinstance(codec, RingCodec):
        return codec
    return get_codec(codec)


def validate_codec_policy(value: str, knob: str) -> str:
    """Normalize + validate one per-tier codec-policy knob value
    (``BAGUA_COMPRESS_{INTRA,INTER}`` / the trainer kwargs)."""
    v = (value or POLICY_AUTO).strip().lower()
    if v not in POLICY_VALUES:
        raise ValueError(
            f"{knob} must be one of {'|'.join(POLICY_VALUES)}, got {value!r}"
        )
    return v
