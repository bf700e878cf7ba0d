"""Decoder-only Transformer LM — the flagship model for benchmarks and the
driver's compile checks.

The reference's headline language workload is BERT-Large SQuAD finetuning
(/root/reference/examples/squad/main.py); this is the equivalent first-class
transformer family, designed TPU-first rather than ported:

- all matmuls in bfloat16 (MXU-native), params kept in f32,
- static shapes and a static causal mask (XLA tiles cleanly onto the MXU),
- head/ffn dims kept at multiples of 128 (MXU lane width),
- optional ``jax.checkpoint`` over blocks to trade FLOPs for HBM,
- attention pluggable so the sequence-parallel paths (ring attention /
  Ulysses all-to-all, SURVEY.md §5.7) drop in without touching the model.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..obs.spans import (
    DIFFUSION_INPUT_SCOPE, EXIT_SCOPE, LOOP_SCOPE, LOSS_TAIL_SCOPE,
    POS_EMBED_SCOPE, phase_scope,
)
from ..parallel.mesh import axis_bound as _axis_bound
from ..utils import remat_wrap


@dataclasses.dataclass(frozen=True)
class SubLayer:
    """One sub-layer of a block, ``x + N'(F(N(x)))``: what ``F`` is
    (``"attn"`` softmax attention, ``"linear_attn"`` the gated delta rule,
    ``"ssm"`` a Mamba-2 mixer, ``"mlp"`` the feed-forward or what
    ``mlp_factory`` makes) and, for softmax attention, the layer's causal
    window (None: full) and whether it rotates q and k."""
    kind: str
    window: Optional[int] = None
    rotary: bool = False


#: the words of ``TransformerConfig.layer_kinds`` -> the sub-layer each names
LAYER_KINDS = {"ssm": "ssm", "attn": "attn", "moe": "mlp"}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """The decoder's sizes and options.  Which option which path (decode,
    ``sp_axis``, ``tp_axis``, pipeline stages, a looped stack, the
    block-diffusion mask) does not carry yet: ``NOT_BUILT``."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    #: what a rematerialized block keeps for the backward pass
    #: (``utils.remat_wrap`` builds the policy).  None: its input only, the
    #: whole block is recomputed (lowest memory).  "dots": every matmul
    #: output, and the flash kernel's ``o`` and ``lse``.  "dots_no_batch":
    #: q / k / v, the FFN's gate and up, ``o`` and ``lse`` — what the
    #: block tags (``KEPT_QKV``, ``KEPT_FFN_IN``) and the kernel tags; the
    #: attention out-projection's output, as large as ``o``, is rebuilt
    #: from ``o`` in the replay (one matmul), so the kernel is not run a
    #: second time and memory stays level.  A custom MLP tags nothing and
    #: keeps what the dots rule gives it (then the out-projection too).
    #: Measured on one v5e, gpt2-medium at seq 1024, batch 8,
    #: "dots_no_batch" (PERF.md §6, PR 27, on the kernels' layout of then):
    #: + 4.2 % tokens/s/chip for + 0.1 % peak memory against replaying the
    #: kernel; keeping the out-projection's output as well, + 1.9 % more
    #: for + 2.5 % more memory
    remat_policy: Optional[str] = None
    #: sequence-parallel mesh axis: when set and bound (inside shard_map),
    #: each shard holds a contiguous sequence chunk and position embeddings
    #: are offset by axis_index * local_len
    sp_axis: Optional[str] = None
    #: tensor-parallel mesh axis (Megatron-style): attention heads and FFN
    #: width are sharded tp_size ways; params are LOCAL slices inside the
    #: step (see parallel/tensor_parallel.py).  n_heads and d_ff must be
    #: divisible by tp_size.
    tp_axis: Optional[str] = None
    tp_size: int = 1
    #: autoregressive decode mode: attention keeps a KV cache ("cache"
    #: variable collection) and consumes one token per call.  Only valid
    #: through models/generate.py — a decode=True config cannot train
    #: (single-token attention, mutable cache).
    decode: bool = False
    #: paged KV-cache decode (the serving plane, docs/serving.md): instead
    #: of one dense ``[b, max_seq_len, h, d]`` cache per layer, each layer
    #: keeps a shared **page pool** ``[num_pages, page_size, h, d]`` and
    #: requests map positions onto pool pages through a per-slot block
    #: table passed via the ``slots`` call argument — requests of different
    #: lengths share the pool while the compiled program stays one static
    #: shape.  ``page_size`` must divide ``max_seq_len``; 0 keeps the dense
    #: decode cache.  Only meaningful with ``decode=True``.
    page_size: int = 0
    #: page-pool capacity (pages per layer) for paged decode.  Pages 0 and
    #: 1 are reserved by convention: page 0 is the permanent ZERO page
    #: (unallocated block-table entries gather zeros, exactly like the
    #: dense cache's untouched rows) and page 1 is the TRASH page
    #: (masked writes of inactive slots land there) — the serving
    #: allocator never hands either out.
    num_pages: int = 0
    #: rotary position embedding base (RoPE, rotate-half over the whole
    #: head).  None keeps the learned absolute ``pos_embed`` table; set,
    #: ``TransformerLM`` creates no table and ``Attention`` rotates q and k
    #: (sin/cos in f32, positions offset by the ``sp_axis`` chunk like the
    #: table)
    rope_theta: Optional[float] = None
    #: RMSNorm on the flat ``n_heads * head_dim`` wide q and k, before the
    #: head split (OLMoE's ``q_norm`` / ``k_norm``).  ``"head"``: on each
    #: head's ``head_dim`` lanes by itself, one ``[head_dim]`` scale for all
    #: the heads (the Qwen3 family's)
    qk_norm: Any = False
    #: epsilon of every RMSNorm of the model
    norm_eps: float = 1e-6
    #: key / value heads, each read by ``n_heads // n_kv_heads`` consecutive
    #: query heads (grouped-query attention); None: one per query head
    n_kv_heads: Optional[int] = None
    #: width of a head where it is not ``d_model // n_heads`` (the q and o
    #: projections are then ``d_model x n_heads * d_head``); read it as
    #: ``cfg.head_dim``
    d_head: Optional[int] = None
    #: causal window of the windowed layers: query ``i`` sees keys ``i -
    #: window < j <= i`` (its own position counts)
    window: Optional[int] = None
    #: which layers are windowed, a period of 0 / 1 repeated over the depth
    #: (``(0, 1, 1, 1)``: full attention on every fourth layer, the window
    #: on the three behind it); None: every layer where ``window`` is set
    window_layers: Optional[tuple] = None
    #: which layers rotate q and k by ``rope_theta``, a period like
    #: ``window_layers``; a 0 layer has no positional encoding at all
    #: (NoPE).  None: every layer
    rope_layers: Optional[tuple] = None
    #: the MLP's router reads the block's INPUT (before the attention norm)
    #: and its experts the post-attention norm: ``Block`` hands a custom MLP
    #: that input as ``route_x`` (``MoEMLP``)
    route_before_attention: bool = False
    #: passes over the stack (a looped, depth-shared decoder): the parameter
    #: tree holds each of the ``n_layers`` blocks ONCE, the blocks run
    #: ``n_passes`` times in order over the same weights, ``final_norm``
    #: closes every pass and its output is what the next pass reads.  Every
    #: pass rotates q and k at positions ``0 .. s - 1``
    n_passes: int = 1
    #: a second RMSNorm behind each sub-layer, on its output before the
    #: residual add ("sandwich" norm): ``x + N'(Attn(N(x)))``
    post_norms: bool = False
    #: the RMSNorm in FRONT of each sub-layer.  Off together with
    #: ``post_norms``, a block norms a sub-layer's output only: ``x +
    #: N(Mixer(x))``, ``x + N(MLP(x))`` (the Olmo 2 / 3 trunk's reordered
    #: norm); a block without any norm is refused
    pre_norms: bool = True
    #: the model ends every pass in a head and an exit gate (``exit_gate``:
    #: ``Dense(1)`` with bias, float32 like the routers, on the pass's normed
    #: state) and hands back what ``looped_lm_loss_fn`` weighs: ``(logits
    #: [n_passes, batch, seq, vocab], gate logits [batch, seq, n_passes])``.
    #: Off, a looped model returns the last pass's logits like any other
    exit_gate: bool = False
    #: the mask of every layer: ``"causal"`` (cut to ``window`` where a layer
    #: has one), or ``"block_diffusion"``, the training view of a block-
    #: diffusion model: the model is handed ``[x ; x~]``, a clean sequence
    #: and then its noised copy (``block_diffusion_loss_fn`` assembles it),
    #: both halves sit at positions ``0 .. L - 1``, every layer attends
    #: under ``ops.flash_attention.block_diffusion_mask``, and the head
    #: reads the noised half alone: ``[b, 2 L]`` tokens -> ``[b, L, vocab]``
    #: logits
    attention: str = "causal"
    #: positions of a diffusion block: within one, noised rows see each
    #: other in both directions.  Read with ``attention="block_diffusion"``
    diffusion_block: int = 0
    #: which layers mix tokens by LINEAR attention (the gated delta rule,
    #: ``models.linear_attention.GatedDeltaNet``) in place of softmax
    #: attention, a period like ``window_layers`` (``(1, 1, 1, 0)``: softmax
    #: attention on every fourth layer); None: no layer
    mixer_layers: Optional[tuple] = None
    #: the linear-attention layers' key and value heads (a key head serves
    #: ``value_heads // key_heads`` value heads), their widths, and the taps
    #: of the causal depthwise convolution over q / k / v
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    #: the delta rule's write strength is ``2 sigmoid(b)`` in (0, 2) and not
    #: ``sigmoid(b)``: a state's transition ``I - beta k k^T`` then has
    #: eigenvalues in (-1, 1) (``linear_allow_neg_eigval``)
    linear_neg_eigval: bool = False
    #: softmax attention's output gate: the q projection is twice as wide, a
    #: head's second ``head_dim`` outputs are its gate, and the attention's
    #: output is multiplied by their sigmoid before the out-projection
    attn_gate: bool = False
    #: lanes of a head that ``rope_theta`` rotates (rotate-half over the
    #: first ``rotary_dim``, the rest unrotated); None: the whole head
    rotary_dim: Optional[int] = None
    #: every RMSNorm of the trunk (the blocks', the final one, ``qk_norm=
    #: "head"``'s) multiplies by ``1 + scale`` with ``scale`` initialised to
    #: zero (the Qwen3-Next family's zero-centred norm)
    norm_zero_centered: bool = False
    #: the kind of every layer over the whole depth, ``"ssm"`` (a Mamba-2
    #: state-space mixer, ``models.state_space.Mamba2``), ``"attn"`` (softmax
    #: attention) or ``"moe"`` (what ``mlp_factory`` makes), as an aperiodic
    #: pattern states them (``("ssm", "moe", "ssm", "moe", "ssm", "attn",
    #: ...)``).  Where set, a block is ONE norm and ONE sub-layer of that
    #: kind, ``x + F(N(x))``; None: a mixer and an MLP a block
    layer_kinds: Optional[tuple] = None
    #: the state-space layers' heads and their width (``d_inner = ssm_heads
    #: * ssm_head_dim``), the groups that share one B / C pair (head ``h``
    #: reads group ``h // (ssm_heads / ssm_groups)``), the state's size, the
    #: taps of the causal depthwise convolution (with bias) over x / B / C
    #: and the positions of a chunk of the scan
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128

    @property
    def block_diffusion(self) -> bool:
        if self.attention not in ("causal", "block_diffusion"):
            raise ValueError(f"attention kind {self.attention!r}: "
                             "'causal' or 'block_diffusion'")
        return self.attention == "block_diffusion"

    @property
    def head_dim(self) -> int:
        if self.d_head is not None:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_heads if self.n_kv_heads is None else self.n_kv_heads

    @functools.cache
    def layer_plan(self) -> tuple:
        """What each of the ``n_layers`` layers is: a tuple of
        :class:`SubLayer` a layer, in the order its block runs them — ``(mixer,
        mlp)``, or the one sub-layer that ``layer_kinds`` names.  The one
        reader of the layer patterns (``window`` / ``window_layers``,
        ``rope_theta`` / ``rope_layers``, ``mixer_layers``, ``layer_kinds``):
        blocks, gauges, the pipelined stack and ``NOT_BUILT`` read this."""
        def on(pattern, layer, default):
            # a period of 0 / 1 repeated over the depth
            return default if pattern is None else bool(
                pattern[layer % len(pattern)])

        def attention(layer):
            windowed = self.window is not None and on(
                self.window_layers, layer, True)
            return SubLayer(
                "attn", self.window if windowed else None,
                self.rope_theta is not None and on(
                    self.rope_layers, layer, True))

        kinds = self.layer_kinds
        if kinds is None:
            return tuple(
                (SubLayer("linear_attn") if on(self.mixer_layers, i, False)
                 else attention(i), SubLayer("mlp"))
                for i in range(self.n_layers))
        if len(kinds) != self.n_layers or any(
                k not in LAYER_KINDS for k in kinds):
            raise ValueError(
                f"layer_kinds names the kind of each of the {self.n_layers} "
                f"layers, one of {tuple(LAYER_KINDS)}; got {kinds}")
        if (self.mixer_layers is not None or self.post_norms
                or self.route_before_attention):
            raise ValueError(
                "layer_kinds replaces the two-sub-layer block: mixer_layers, "
                "post_norms and route_before_attention are that block's "
                "options")
        return tuple(
            (attention(i) if k == "attn" else SubLayer(LAYER_KINDS[k]),)
            for i, k in enumerate(kinds))

    def _attention(self, layer: int) -> SubLayer:
        subs = self.layer_plan()[layer % self.n_layers]
        return next((s for s in subs if s.kind == "attn"), SubLayer("attn"))

    def layer_window(self, layer: int) -> Optional[int]:
        """The window of layer ``layer``'s attention, None where it is
        full."""
        return self._attention(layer).window

    def layer_rotary(self, layer: int) -> bool:
        """Whether layer ``layer`` rotates q and k."""
        return self._attention(layer).rotary


def bert_large_config(**kw) -> TransformerConfig:
    """BERT-Large-scale shapes (the reference's SQuAD workload scale).
    Keyword overrides (e.g. ``max_seq_len=384`` for SQuAD) replace defaults."""
    defaults = dict(
        vocab_size=30528, d_model=1024, n_heads=16, n_layers=24, d_ff=4096,
        max_seq_len=512,
    )
    defaults.update(kw)
    return TransformerConfig(**defaults)


class TokenEmbed(nn.Module):
    """The token table: ``nn.Embed``'s parameter (``embedding``, ``[vocab,
    features]``, the same initialiser: a tree or checkpoint made with
    ``nn.Embed`` under the same name loads as it is) and its forward to the
    bit, by :func:`bagua_tpu.ops.embed_grad.token_lookup`: the float32 rows
    are gathered and THEN rounded to ``dtype``, where ``nn.Embed`` casts the
    whole table for a few thousand rows of it, a decode step's one row too.
    Where the kernel runs (on the TPU, whole lane tiles) the table's
    gradient is the ``embed_grad`` segment product, float32 sums rounded
    once, and not XLA's row-by-row ``scatter`` with its bf16 adds; elsewhere
    ``jnp.take``'s own transpose."""
    num_embeddings: int
    features: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        from ..ops.embed_grad import token_lookup

        if not jnp.issubdtype(tokens.dtype, jnp.integer):
            raise ValueError("tokens must be integers")
        table = self.param(
            "embedding", nn.linear.default_embed_init,
            (self.num_embeddings, self.features), self.param_dtype)
        return token_lookup(table, tokens, self.dtype)


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    eps: float = 1e-6
    #: multiply by ``1 + scale``, ``scale`` initialised to zero
    zero_centered: bool = False

    @nn.compact
    def __call__(self, x, rope=None):
        """``rope``: ``(theta, start)`` — ``x`` is ``[batch, seq, heads,
        head_dim]``, and each normalised head is rotated as well, norm and
        rotation in one pass over the merged rows
        (:func:`bagua_tpu.ops.rope.norm_rope`; the caller gates on
        :func:`rotates_by_kernel`)."""
        scale = self.param(
            "scale", nn.initializers.zeros if self.zero_centered
            else nn.initializers.ones, (x.shape[-1],), self.param_dtype
        )
        if rope is not None:
            from ..ops.rope import norm_rope

            return norm_rope(
                x, scale, *rope, eps=self.eps,
                zero_centered=self.zero_centered).astype(self.dtype)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        if self.zero_centered:
            scale = 1.0 + scale
        return (y * scale).astype(self.dtype)


def rope_rotate(x, theta: float, start=0):
    """Rotary position embedding, rotate-half form (GPT-NeoX / HF): the
    head's two halves are the pairs, every one of its ``head_dim`` lanes is
    rotated.  ``x``: [batch, seq, heads, head_dim] at positions ``start ..
    start + seq - 1``; angles, sin and cos in f32, result in ``x.dtype``."""
    seq, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = (jnp.arange(seq, dtype=jnp.int32) + start).astype(jnp.float32)
    angles = pos[:, None] * inv_freq[None, :]                 # [seq, d/2]
    angles = jnp.concatenate([angles, angles], axis=-1)       # [seq, d]
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def rotates_by_kernel(cfg: TransformerConfig, seq: int, attn_fn=None) -> bool:
    """Whether a rotary layer of ``cfg`` rotates q and k at ``seq`` positions
    by the ``rope`` kernel (:mod:`bagua_tpu.ops.rope`): where the flash
    kernels run, one pass over the ``[b, s, h * d]`` that ``HeadsDense``
    writes and they read, on heads of whole 128-lane tiles.  Everywhere else
    (off the TPU, short or ragged sequences, head_dim 64, a rotation of part
    of a head, the einsum path, an ``attn_fn`` drop-in, decode)
    :func:`rope_rotate`.  Where it is true and ``cfg.qk_norm == "head"``, the
    per-head norm of q and k rides the same pass (``RMSNorm``'s ``rope=``),
    and neither takes a float32 ``[b, s, h, d]`` form on the way."""
    from ..ops.flash_attention import (
        block_diffusion_supported, flash_supported,
    )
    from ..ops.rope import rope_supported

    heads, kv_heads = cfg.n_heads // cfg.tp_size, cfg.kv_heads // cfg.tp_size
    if cfg.rotary_dim not in (None, cfg.head_dim):
        return False  # the kernel rotates whole heads
    if cfg.block_diffusion:
        # ``seq`` rows are two halves, each rotated at ``0 .. seq / 2 - 1``
        return (attn_fn is None and rope_supported(seq // 2, cfg.head_dim)
                and block_diffusion_supported(seq, heads, cfg.head_dim,
                                              kv_heads=kv_heads))
    return (attn_fn is None and not cfg.decode
            and rope_supported(seq, cfg.head_dim)
            and flash_supported(seq, heads, cfg.head_dim, kv_heads=kv_heads))


def causal_attention(q, k, v, dtype, window=None):
    """Causal attention; softmax in f32, matmuls in ``dtype``.

    ``q``: [batch, seq, heads, head_dim], ``k/v`` the same or with fewer
    (key / value) heads; ``window``: a layer's causal window.  The SP paths
    (ring/Ulysses) provide drop-in replacements with the four-argument
    signature.

    On TPU with block-aligned sequence lengths this dispatches to the fused
    Pallas flash-attention kernel (:mod:`bagua_tpu.ops.flash_attention`),
    which never materializes the [seq, seq] score matrix and reads q / k /
    v as the projections write them (heads merged into the last axis by
    reshape, no transposed copy); elsewhere it runs the plain jnp form
    (identical math).  ``BAGUA_FLASH_ATTENTION=0`` disables the kernel.
    """
    from ..ops.flash_attention import flash_attention

    return flash_attention(q, k, v, dtype, causal=True, window=window)


#: reserved page ids of the paged decode pool (see
#: ``TransformerConfig.num_pages``): ZERO_PAGE is never written (gathers as
#: zeros for unallocated block-table entries), TRASH_PAGE absorbs the
#: masked writes of inactive slots
ZERO_PAGE = 0
TRASH_PAGE = 1
RESERVED_PAGES = 2


#: ``checkpoint_name`` tags on the matmul outputs a stock :class:`Block`
#: keeps under remat (q / k / v; FFN gate and up).  With the attention
#: kernel's ``o`` and ``lse`` they are the block's whole kept set under
#: ``remat_policy="dots_no_batch"`` (see ``TransformerConfig.remat_policy``)
KEPT_QKV = "attn_qkv"
KEPT_FFN_IN = "ffn_in"


class HeadsDense(nn.Module):
    """``nn.DenseGeneral`` between a flat feature axis and a ``(heads,
    head_dim)`` pair — the same parameter (``kernel``, its shape, its
    initial values) — computed as ONE 2-D matmul over the merged ``heads *
    head_dim`` axis, whose split into heads is a free reshape.  It is the
    form :class:`Attention` gives its four projections where the flash
    kernels run: they read and write the row-major ``[batch, seq, heads *
    head_dim]``, and around ``DenseGeneral``'s contraction with two feature
    axes the TPU compiler put a transposed copy before or behind every one
    of their operands and results (gpt2-medium compiled for a v5e: 12 a
    layer).  As plain matmuls the out-projection and the four backward
    products take and give that layout as it is; the q / k / v results the
    compiler still writes sequence-minor at gpt2-medium's sizes and re-lays
    once for the kernel (PERF.md §7)."""
    heads: int
    head_dim: int
    #: None: ``[..., in] -> [..., heads, head_dim]`` (q / k / v); set:
    #: ``[..., heads, head_dim] -> [..., out_features]`` (the out-projection)
    out_features: Optional[int] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        merged = self.heads * self.head_dim
        if self.out_features is None:
            shape = (x.shape[-1], self.heads, self.head_dim)
            flat = (x.shape[-1], merged)
        else:
            shape = (self.heads, self.head_dim, self.out_features)
            flat = (merged, self.out_features)
            x = x.reshape(*x.shape[:-2], merged)

        def init(rng, shape, dtype):  # DenseGeneral's: drawn flat
            return nn.initializers.lecun_normal()(rng, flat, dtype).reshape(
                shape)

        kernel = self.param("kernel", init, shape, self.param_dtype)
        y = jax.lax.dot_general(
            x.astype(self.dtype), kernel.reshape(flat).astype(self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())))
        if self.out_features is None:
            y = y.reshape(*y.shape[:-1], self.heads, self.head_dim)
        return y


def _tp_active(cfg) -> bool:
    return (
        cfg.tp_axis is not None and cfg.tp_size > 1
        and _axis_bound(cfg.tp_axis)
    )


#: What is not built: rows of ``(features, consumers, why)``, names separated
#: by spaces.  A feature is something a configuration or its layer plan has,
#: a consumer a path that would have to carry it (:func:`refuse_not_built`
#: reads both off the configuration; ``"pipeline"`` is named by
#: ``PipelinedTransformerLM``).  The FIRST row that applies is raised, so the
#: order is the precedence: the pipelined stack's own rows, the structural
#: features (a mask, a looped stack, a block of one sub-layer, a recurrent
#: mixer), then what one attention layer cannot do.  ``why`` is a format
#: string over ``cfg`` and ``n``, the number of distinct layers in the plan.
#: To build a pair, take it out of its row.
NOT_BUILT = (
    ("looped exit_gate", "pipeline",
     "the pipelined stack runs its layers once and ends in one head: "
     "n_passes={cfg.n_passes} / exit_gate={cfg.exit_gate} (a looped stack's "
     "passes under pipeline stages) are not implemented"),
    ("block_diffusion", "pipeline",
     "the pipelined stack attends causally over tokens[:, :-1]: "
     "attention='block_diffusion' (the [x ; x~] rows, their mask, the "
     "noised half's head) is not implemented under pipeline stages"),
    ("linear_attn norm_zero_centered", "pipeline",
     "the pipelined stack scans ONE kind of block and closes in a plain "
     "RMSNorm: mixer_layers (linear-attention layers by period) and "
     "norm_zero_centered are not implemented under pipeline stages"),
    ("single_sublayer", "pipeline",
     "the pipelined stack scans ONE kind of two-sub-layer block: "
     "layer_kinds (one sub-layer a block, a kind a layer) is not "
     "implemented under pipeline stages"),
    ("mixed_layers", "pipeline",
     "the pipelined stack scans ONE block over its layers: the layer "
     "patterns (windows, rotations) ask for layers of {n} kinds"),
    ("block_diffusion", "decode",
     "attention='block_diffusion' is the training view of a block-diffusion "
     "model; generation by blocks is not implemented on the decode paths"),
    ("block_diffusion", "sp_axis",
     "attention='block_diffusion' is not implemented under sp_axis: a "
     "sequence shard would need the other half's keys and the drop-ins' "
     "masks are causal"),
    ("block_diffusion", "looped",
     "attention='block_diffusion' is not implemented for a looped stack "
     "(n_passes > 1, exit_gate)"),
    ("looped", "decode",
     "n_passes > 1 is not implemented for the decode paths (a key / value "
     "cache per pass)"),
    ("single_sublayer", "decode",
     "layer_kinds (one sub-layer a block, state-space layers) is not "
     "implemented on the decode paths: a recurrent state and the "
     "convolution's taps beside the key / value cache"),
    ("single_sublayer", "sp_axis",
     "layer_kinds is not implemented under sp_axis: a sequence shard of a "
     "state-space layer would need the state of the shard before it"),
    ("single_sublayer", "tp_axis",
     "layer_kinds is not implemented under the tensor-parallel axis "
     "(tp_axis / tp_size): the state-space heads and groups are not sharded"),
    ("single_sublayer", "looped block_diffusion",
     "layer_kinds is not implemented for a looped stack (n_passes > 1, "
     "exit_gate) or under attention='block_diffusion'"),
    ("linear_attn", "decode",
     "mixer_layers (linear attention) is not implemented on the decode "
     "paths: a recurrent state beside the key / value cache"),
    ("linear_attn", "sp_axis",
     "mixer_layers (linear attention) is not implemented under sp_axis: a "
     "sequence shard would need the state of the shard before it"),
    ("linear_attn", "tp_axis",
     "mixer_layers (linear attention) is not implemented under the "
     "tensor-parallel axis (tp_axis / tp_size): its heads are not sharded"),
    ("linear_attn", "looped block_diffusion",
     "mixer_layers (linear attention) is not implemented for a looped stack "
     "or under attention='block_diffusion'"),
    ("window mixed_layers", "block_diffusion",
     "attention='block_diffusion' is one kind of layer: no window, no "
     "rope_layers pattern"),
    ("grouped_kv window", "decode",
     "grouped key / value heads and windows are not implemented for the "
     "decode paths"),
    ("attn_gate rotary_dim", "decode",
     "attn_gate and rotary_dim are not implemented for the decode paths"),
    ("flat_qk_norm", "tp_axis",
     "qk_norm normalizes over all heads; they are sharded under tensor "
     "parallelism"),
    ("rope", "decode", "rope_theta is not implemented for the decode paths"),
)


def refuse_not_built(cfg: TransformerConfig, plan=None, consumers=()) -> None:
    """Raise the first row of ``NOT_BUILT`` with a feature that ``cfg`` has
    (its layers being ``plan``; None: ``cfg.layer_plan()``) under a consumer
    that it turns on or that ``consumers`` names.  Every model calls this
    once, before it builds anything."""
    plan = cfg.layer_plan() if plan is None else plan
    subs = [sub for layer in plan for sub in layer]
    have = {
        "linear_attn": any(sub.kind == "linear_attn" for sub in subs),
        "single_sublayer": any(len(layer) == 1 for layer in plan),
        "mixed_layers": len(set(plan)) > 1,
        "window": any(sub.window is not None for sub in subs),
        "rope": any(sub.rotary for sub in subs),
        "block_diffusion": cfg.block_diffusion,
        "looped": cfg.n_passes > 1,
        "exit_gate": cfg.exit_gate,
        "grouped_kv": cfg.kv_heads != cfg.n_heads,
        "attn_gate": cfg.attn_gate,
        "rotary_dim": cfg.rotary_dim is not None,
        "flat_qk_norm": bool(cfg.qk_norm) and cfg.qk_norm != "head",
        "norm_zero_centered": cfg.norm_zero_centered,
    }
    under = {
        "decode": cfg.decode,
        "sp_axis": cfg.sp_axis is not None,
        "tp_axis": cfg.tp_axis is not None or cfg.tp_size > 1,
        "looped": cfg.n_passes > 1 or cfg.exit_gate,
        "block_diffusion": cfg.block_diffusion,
        **dict.fromkeys(consumers, True),
    }
    for features, paths, why in NOT_BUILT:
        if (any(have[f] for f in features.split())
                and any(under.get(c) for c in paths.split())):
            raise NotImplementedError(why.format(cfg=cfg, n=len(set(plan))))


class Attention(nn.Module):
    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None
    #: this layer's kind (``TransformerConfig.layer_window`` /
    #: ``.layer_rotary`` of its index): its causal window, None for full
    #: attention, and whether it rotates q and k
    window: Optional[int] = None
    rotary: bool = True

    @nn.compact
    def __call__(self, x, slots=None):
        cfg = self.cfg
        assert cfg.n_heads % cfg.tp_size == 0, (cfg.n_heads, cfg.tp_size)
        assert cfg.kv_heads % cfg.tp_size == 0, (cfg.kv_heads, cfg.tp_size)
        assert cfg.n_heads % cfg.kv_heads == 0, (cfg.n_heads, cfg.kv_heads)
        h, d = cfg.n_heads // cfg.tp_size, cfg.head_dim  # local heads
        kv_h = cfg.kv_heads // cfg.tp_size
        rotary = cfg.rope_theta is not None and self.rotary
        # built by itself, this layer is all the model there is: the plan of
        # the one block that would hold it
        refuse_not_built(cfg, ((SubLayer("attn", self.window, rotary),
                                SubLayer("mlp")),))
        if _tp_active(cfg):
            from ..parallel.tensor_parallel import tp_gather_grad

            x = tp_gather_grad(x, cfg.tp_axis)
        # the projections hand the attention what it reads: the flash
        # kernels [b, s, h * d] (HeadsDense), the plain einsums [b, s, h, d];
        # same parameters either way
        from ..ops.flash_attention import (
            block_diffusion_attention, block_diffusion_supported,
            flash_supported,
        )

        by_kernel = (block_diffusion_supported if cfg.block_diffusion
                     else flash_supported)
        if not cfg.decode and by_kernel(x.shape[1], h, d, kv_heads=kv_h):
            dense = lambda name, out=None, heads=h, width=d: HeadsDense(
                heads, width, out, name=name, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype)
        else:
            dense = lambda name, out=None, heads=h, width=d: nn.DenseGeneral(
                (heads, width) if out is None else out,
                axis=-1 if out is None else (-2, -1), name=name,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, use_bias=False)
        # with the output gate a head's q projection is [q | gate]
        q, k, v = (checkpoint_name(
            dense(n, heads=h if n == "q" else kv_h,
                  width=2 * d if cfg.attn_gate and n == "q" else d)(x),
            KEPT_QKV) for n in "qkv")
        if cfg.attn_gate:
            q, gate = q[..., :d], q[..., d:]
        by_kernel = rotary and rotates_by_kernel(cfg, q.shape[1],
                                                 self.attn_fn)
        # where the ``rope`` kernel rotates, a per-head norm rides its pass:
        # q and k stay the rows the projection wrote, all the way to the
        # attention kernel
        norm_rides = cfg.qk_norm == "head" and by_kernel
        if cfg.qk_norm == "head":
            head_norm = lambda name: RMSNorm(
                cfg.dtype, cfg.param_dtype, cfg.norm_eps,
                cfg.norm_zero_centered, name=name)
            if not norm_rides:
                q, k = head_norm("q_norm")(q), head_norm("k_norm")(k)
        elif cfg.qk_norm:
            flat_norm = lambda name, t: RMSNorm(
                cfg.dtype, cfg.param_dtype, cfg.norm_eps, name=name,
            )(t.reshape(*t.shape[:-2], t.shape[-2] * d)).reshape(t.shape)
            q, k = flat_norm("q_norm", q), flat_norm("k_norm", k)
        if rotary:
            start = 0
            if cfg.sp_axis is not None and _axis_bound(cfg.sp_axis):
                start = jax.lax.axis_index(cfg.sp_axis) * q.shape[1]
            rotate = rope_rotate
            if by_kernel:
                from ..ops.rope import rope as rotate
            elif cfg.rotary_dim not in (None, d):
                # the first rotary_dim lanes of a head, the rest as they are
                rotate = lambda t, theta, start: jnp.concatenate(
                    [rope_rotate(t[..., :cfg.rotary_dim], theta, start),
                     t[..., cfg.rotary_dim:]], axis=-1)

            def turn(name, t):
                rows = t
                if cfg.block_diffusion:
                    # positions restart: the noised half sits at 0 .. L - 1
                    # like the clean one.  [b, 2 L, h, d] -> [2 b, L, h, d]
                    # and back are free reshapes around the one pass
                    rows = t.reshape(2 * t.shape[0], t.shape[1] // 2,
                                     *t.shape[2:])
                if norm_rides:
                    return head_norm(name)(
                        rows, rope=(cfg.rope_theta, start)).reshape(t.shape)
                return rotate(rows, cfg.rope_theta, start).reshape(t.shape)

            q = turn("q_norm", q)
            if norm_rides:
                # Two ties that compute nothing, here for the step's memory
                # alone.  With q and k off their float32 detour the TPU
                # compiler's scheduler leaves the head's weight-gradient
                # fusion, and the logits it reads, to the end of SDAR's step:
                # + 0.21 GB at the peak, twice ``peak_hbm_gb``'s bound.  Of
                # the orders tried this one keeps that fusion where the
                # parent's step has it, for three bfloat16 re-tilings of q a
                # layer (PERF.md §6 and §7, PR 51: it is a nudge, not a rule)
                k, v = jax.lax.optimization_barrier((k, v))
                q, v = jax.lax.optimization_barrier((q, v))
            k = turn("k_norm", k)
        if cfg.decode and cfg.page_size > 0:
            o = self._paged_decode_attend(q, k, v, slots)
        elif cfg.decode:
            o = self._decode_attend(q, k, v)
        elif cfg.block_diffusion:
            # the ``flash_bd_*`` kernels where they run, the plain jnp form
            # under the same mask elsewhere
            fn = self.attn_fn or block_diffusion_attention
            o = fn(q, k, v, cfg.dtype, diffusion_block=cfg.diffusion_block)
        else:
            fn = self.attn_fn or causal_attention
            # a full layer keeps the four-argument call the sequence-
            # parallel drop-ins take
            o = (fn(q, k, v, cfg.dtype) if self.window is None
                 else fn(q, k, v, cfg.dtype, window=self.window))
        if cfg.attn_gate:
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
        out = dense("o", cfg.d_model)(o)
        if _tp_active(cfg):
            from ..parallel.tensor_parallel import tp_reduce

            out = tp_reduce(out, cfg.tp_axis)  # row-parallel partial sums
        return out

    def _decode_attend(self, q, k, v):
        """Single-token attention against a KV cache ("cache" collection;
        flax's canonical decode pattern).  ``q/k/v`` are ``[b, 1, h, d]``;
        new K/V land at ``cache_index`` and q attends to positions
        ``<= cache_index``."""
        cfg = self.cfg
        b, qlen, h, d = q.shape
        assert qlen == 1, f"decode consumes one token per call, got {qlen}"
        # flax's canonical guard: the init pass also runs this code, and
        # must NOT advance the cache it is creating
        is_initialized = self.has_variable("cache", "cached_key")
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros,
            (b, cfg.max_seq_len, h, d), cfg.dtype,
        )
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros,
            (b, cfg.max_seq_len, h, d), cfg.dtype,
        )
        cache_index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if not is_initialized:
            return v  # init trace: single token attends only to itself
        idx = cache_index.value
        cached_k.value = jax.lax.dynamic_update_slice(
            cached_k.value, k.astype(cfg.dtype), (0, idx, 0, 0)
        )
        cached_v.value = jax.lax.dynamic_update_slice(
            cached_v.value, v.astype(cfg.dtype), (0, idx, 0, 0)
        )
        cache_index.value = idx + 1
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, cached_k.value,
            preferred_element_type=jnp.float32,
        ) / jnp.sqrt(d).astype(jnp.float32)
        mask = jnp.arange(cfg.max_seq_len) <= idx  # [k]
        scores = jnp.where(mask[None, None, None, :], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, cached_v.value)

    def _paged_decode_attend(self, q, k, v, slots):
        """Attention against this layer's **page pool** (the serving
        plane's paged KV-cache).  ``q/k/v`` are ``[b, s, h, d]`` where b is
        the engine's slot count and s is 1 (a decode tick) or the static
        prefill chunk; ``slots`` carries the shared per-slot state the
        scheduler maintains host-side:

        * ``block_table`` int32 ``[b, max_seq_len // page_size]`` — page id
          of each logical page of each slot (unallocated entries point at
          the reserved ZERO page),
        * ``lengths`` int32 ``[b]`` — tokens already cached per slot (the
          positions this call writes are ``lengths .. lengths + s - 1``),
        * ``active`` bool ``[b]`` — inactive slots' writes are routed to
          the reserved TRASH page (their outputs are garbage the engine
          ignores).

        The gather reconstructs, per slot, exactly the dense
        ``[b, max_seq_len, h, d]`` cache `_decode_attend` would hold
        (pages in position order, unallocated rows zero), and the
        score/mask/softmax/value math is the same expression — so greedy
        decode through the pool is bit-identical to the dense path
        (pinned in ``tests/test_serve.py``)."""
        cfg = self.cfg
        b, s, h, d = q.shape
        assert cfg.page_size > 0 and cfg.max_seq_len % cfg.page_size == 0, (
            cfg.page_size, cfg.max_seq_len)
        assert cfg.num_pages > RESERVED_PAGES, cfg.num_pages
        pages_per_slot = cfg.max_seq_len // cfg.page_size
        is_initialized = self.has_variable("cache", "pool_key")
        pool_k = self.variable(
            "cache", "pool_key", jnp.zeros,
            (cfg.num_pages, cfg.page_size, h, d), cfg.dtype,
        )
        pool_v = self.variable(
            "cache", "pool_value", jnp.zeros,
            (cfg.num_pages, cfg.page_size, h, d), cfg.dtype,
        )
        if not is_initialized:
            return v  # init trace: single token attends only to itself
        if slots is None:
            raise ValueError(
                "paged decode (page_size > 0) needs the `slots` call "
                "argument (block_table / lengths / active)"
            )
        lengths = slots["lengths"]          # [b]
        block_table = slots["block_table"]  # [b, pages_per_slot]
        active = slots["active"]            # [b]
        # destination (page, offset) of each written position; inactive
        # slots write to the trash page so the pool stays clean
        positions = lengths[:, None] + jnp.arange(s)[None, :]   # [b, s]
        dest_page = jnp.take_along_axis(
            block_table, positions // cfg.page_size, axis=1
        )                                                       # [b, s]
        dest_page = jnp.where(active[:, None], dest_page, TRASH_PAGE)
        offsets = positions % cfg.page_size
        pool_k.value = pool_k.value.at[dest_page, offsets].set(
            k.astype(cfg.dtype))
        pool_v.value = pool_v.value.at[dest_page, offsets].set(
            v.astype(cfg.dtype))
        # gather each slot's pages back into position order: elementwise
        # equal to the dense cache (zero page rows = untouched zeros)
        def view(pool):  # [b, max_seq_len, h, d]
            return pool[block_table].reshape(b, cfg.max_seq_len, h, d)

        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, view(pool_k.value),
            preferred_element_type=jnp.float32,
        ) / jnp.sqrt(d).astype(jnp.float32)
        # causal per slot: position lengths+i attends to keys <= lengths+i
        mask = jnp.arange(cfg.max_seq_len)[None, None, :] <= positions[:, :, None]
        scores = jnp.where(mask[:, None, :, :], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, view(pool_v.value))


class MLPBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        assert cfg.d_ff % cfg.tp_size == 0, (cfg.d_ff, cfg.tp_size)
        d_ff = cfg.d_ff // cfg.tp_size                   # local width
        if _tp_active(cfg):
            from ..parallel.tensor_parallel import tp_gather_grad

            x = tp_gather_grad(x, cfg.tp_axis)
        gate = nn.Dense(d_ff, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="wi_gate")(x)
        up = nn.Dense(d_ff, use_bias=False, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="wi_up")(x)
        gate, up = (checkpoint_name(t, KEPT_FFN_IN) for t in (gate, up))
        y = nn.silu(gate) * up
        out = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="wo")(y)
        if _tp_active(cfg):
            from ..parallel.tensor_parallel import tp_reduce

            out = tp_reduce(out, cfg.tp_axis)
        return out


#: the token mixers that read a block's normed input alone, by sub-layer
#: kind: the file of this package and the module in it (named after its kind
#: in a block); the file's ``set_gauges`` sets its trace-time gauges
MIXERS = {"linear_attn": ("linear_attention", "GatedDeltaNet"),
          "ssm": ("state_space", "Mamba2")}


def _mixer(kind: str):
    """``(module class, set_gauges)`` of a mixer kind; its file is imported
    by the first model that has such a layer."""
    name, cls = MIXERS[kind]
    module = importlib.import_module(f"{__package__}.{name}")
    return getattr(module, cls), module.set_gauges


class Block(nn.Module):
    """Layer ``layer`` of the configuration's plan: its sub-layers in
    order, each ``x + N'(F(N(x)))`` under the names ``<kind>_norm``,
    ``<kind>``, ``<kind>_post_norm`` (a custom MLP under the name its
    factory gives it)."""
    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None
    mlp: Optional[Callable[[], nn.Module]] = None  # MoE drops in here
    #: index of the layer in ``cfg.layer_plan()``
    layer: int = 0

    @nn.compact
    def __call__(self, x, slots=None):
        cfg = self.cfg
        block_in = x
        if not (cfg.pre_norms or cfg.post_norms):
            raise ValueError("a block needs pre_norms, post_norms or both")
        norm = lambda name: RMSNorm(cfg.dtype, cfg.param_dtype, cfg.norm_eps,
                                    cfg.norm_zero_centered, name=name)
        # a sub-layer's input through its norm, and its output through its
        # own, where the configuration has each
        pre = lambda name, t: norm(name)(t) if cfg.pre_norms else t
        post = lambda name, t: norm(name)(t) if cfg.post_norms else t
        subs = cfg.layer_plan()[self.layer]
        for sub in subs:
            y = pre(f"{sub.kind}_norm", x)
            if sub.kind == "mlp":
                if self.mlp is None and (len(subs) == 1
                                         or cfg.route_before_attention):
                    raise ValueError(
                        "layer_kinds names an expert layer ('moe'): it needs "
                        "an mlp_factory that makes one" if len(subs) == 1 else
                        "route_before_attention names a router: it needs an "
                        "expert MLP (mlp_factory) that takes `route_x`")
                mlp = (self.mlp() if self.mlp is not None
                       else MLPBlock(cfg, name="mlp"))
                out = (mlp(y, route_x=block_in) if cfg.route_before_attention
                       else mlp(y))
            elif sub.kind == "attn":
                attn = Attention(cfg, self.attn_fn, sub.window, sub.rotary,
                                 name="attn")
                # dense/training call sites keep their exact one-arg form
                # (the goldens pin those programs); only paged decode
                # threads slots
                out = attn(y) if slots is None else attn(y, slots)
            else:
                out = _mixer(sub.kind)[0](cfg, name=sub.kind)(y)
            x = x + post(f"{sub.kind}_post_norm", out)
        return x


class TransformerLM(nn.Module):
    """Causal LM: token ids [batch, seq] -> logits [batch, seq, vocab]
    (``attention="block_diffusion"``: ``[x ; x~]`` [batch, 2 seq] -> the
    noised half's logits [batch, seq, vocab])."""

    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None
    mlp_factory: Optional[Callable[[int], Optional[Callable]]] = None
    head: bool = True  # False: return final hidden states (encoder trunk)

    @nn.compact
    def __call__(self, tokens, slots=None):
        cfg = self.cfg
        if slots is not None and not (cfg.decode and cfg.page_size > 0):
            raise ValueError(
                "`slots` is only meaningful for paged decode configs "
                "(decode=True, page_size > 0)"
            )
        refuse_not_built(cfg)
        rows = tokens.shape[1]
        if cfg.block_diffusion and (cfg.diffusion_block < 1 or rows % 2
                                    or (rows // 2) % cfg.diffusion_block):
            raise ValueError(
                f"attention='block_diffusion' reads [x ; x~], a sequence of "
                f"whole diffusion blocks twice over; got {rows} rows at "
                f"diffusion_block={cfg.diffusion_block}")
        x = TokenEmbed(
            cfg.vocab_size, cfg.d_model, name="embed",
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        )(tokens)
        if cfg.rope_theta is None:  # learned absolute positions
            pos = self.param(
                "pos_embed", nn.initializers.normal(0.02),
                (cfg.max_seq_len, cfg.d_model), cfg.param_dtype,
            )
            s = tokens.shape[1]
            # the table is this module's own parameter, under no sub-module's
            # name: its slice and add name themselves
            with phase_scope(POS_EMBED_SCOPE):
                if cfg.decode and cfg.page_size > 0:
                    # paged decode: every slot sits at its OWN position
                    # (continuous batching admits requests mid-flight), so
                    # the position comes from the scheduler's per-slot
                    # lengths, not a shared counter.  During init (no slots
                    # yet) position 0 stands in.
                    if slots is None:
                        pos_ids = jnp.zeros((tokens.shape[0], s), jnp.int32)
                    else:
                        pos_ids = (slots["lengths"][:, None]
                                   + jnp.arange(s, dtype=jnp.int32)[None, :])
                    # pos[idx] equals the dense path's dynamic_slice row for
                    # the same position — elementwise identical, per slot
                    pos_slice = jnp.take(pos, pos_ids, axis=0)  # [b, s, d]
                    x = x + pos_slice.astype(cfg.dtype)
                else:
                    start = 0
                    if cfg.sp_axis is not None and _axis_bound(cfg.sp_axis):
                        start = jax.lax.axis_index(cfg.sp_axis) * s
                    if cfg.decode:
                        # autoregressive position counter (mirrors the
                        # attention cache; same init-pass guard — see
                        # Attention._decode_attend)
                        advance = self.has_variable("cache", "pos_index")
                        pos_index = self.variable(
                            "cache", "pos_index",
                            lambda: jnp.zeros((), jnp.int32))
                        if advance:
                            start = pos_index.value
                            pos_index.value = start + s
                    pos_slice = jax.lax.dynamic_slice_in_dim(pos, start, s,
                                                             axis=0)
                    x = x + pos_slice[None].astype(cfg.dtype)
        if not self.is_initializing() and not cfg.decode:
            # trace-time facts of this model's step, for the operator and
            # the benchmark's readers of the windowed kernels
            from ..ops.embed_grad import grad_kernel_supported
            from ..telemetry import counters

            subs = [sub for layer in cfg.layer_plan() for sub in layer]
            softmax = [sub for sub in subs if sub.kind == "attn"]
            windowed = sum(sub.window is not None for sub in softmax)
            rotary = sum(sub.rotary for sub in softmax)
            counters.set_gauge("attn/kv_heads", cfg.kv_heads // cfg.tp_size)
            counters.set_gauge("attn/window", cfg.window or 0)
            counters.set_gauge("attn/window_layers", windowed)
            counters.set_gauge(
                "attn/full_layers",
                0 if cfg.block_diffusion else len(softmax) - windowed)
            # the rotary layers whose rotation is the ``rope`` kernel
            by_kernel = rotates_by_kernel(cfg, tokens.shape[1], self.attn_fn)
            counters.set_gauge("attn/rope_kernel_layers", by_kernel * rotary)
            # of those, the layers whose q / k norm rides the same pass
            counters.set_gauge(
                "attn/head_norm_kernel_layers",
                by_kernel * (cfg.qk_norm == "head") * rotary)
            # 1: this step's token-table gradient is the ``embed_grad``
            # kernel; 0: it fell back to the gather's own transpose
            counters.set_gauge("embed/grad_kernel",
                               int(grad_kernel_supported(cfg.d_model)))
            for kind in MIXERS:
                layers = sum(sub.kind == kind for sub in subs)
                if layers:
                    _mixer(kind)[1](cfg, layers, tokens.shape[1])
            if cfg.rope_theta is not None:
                counters.set_gauge("attn/rotary_dim",
                                   cfg.rotary_dim or cfg.head_dim)
            if cfg.n_passes > 1:
                counters.set_gauge("loop/passes", cfg.n_passes)
                counters.set_gauge("loop/shared_layers", cfg.n_layers)
            if cfg.block_diffusion:
                counters.set_gauge("attn/diffusion_block",
                                   cfg.diffusion_block)
                counters.set_gauge("attn/block_diffusion_layers",
                                   cfg.n_layers)
                # positions that can carry loss: the clean tokens, each
                # trained through two rows
                counters.set_gauge("diffusion/tokens_per_step",
                                   tokens.shape[0] * tokens.shape[1] // 2)

        def stack(x):
            """The ``n_layers`` blocks, once through."""
            for i in range(cfg.n_layers):
                mlp = (self.mlp_factory(i) if self.mlp_factory is not None
                       else None)
                block_cls = Block
                if cfg.remat:
                    # a custom MLP tags nothing: its matmuls keep the dots rule
                    own = (KEPT_QKV, KEPT_FFN_IN) if mlp is None else ()
                    block_cls = remat_wrap(block_cls, cfg.remat_policy, own)
                blk = block_cls(cfg, self.attn_fn, mlp, i, name=f"block_{i}")
                x = blk(x) if slots is None else blk(x, slots)
            return x

        def final_norm(x):
            return RMSNorm(cfg.dtype, cfg.param_dtype, cfg.norm_eps,
                           cfg.norm_zero_centered, name="final_norm")(x)

        def lm_head(x):
            return nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="lm_head",
            )(x)

        def float32_logits(logits):
            # the cast is the head's work and reads under the head's name.
            # No float32 array comes of it: ``loss_tail`` has no reader that
            # needs the logits in HBM, so the cast fuses into the softmax's
            # passes
            with phase_scope("lm_head"):
                return logits.astype(jnp.float32)

        if cfg.n_passes == 1 and not cfg.exit_gate:
            x = stack(x)
            if cfg.block_diffusion:
                # the prediction for position i is read at the noised row
                # i: logits of the clean half are never used
                x = x[:, x.shape[1] // 2:]
            x = final_norm(x)
            if not self.head:
                return x.astype(jnp.float32)
            return float32_logits(lm_head(x))

        # the looped stack, a scan over the passes with the weights
        # broadcast: ONE body holds each block once, so the parameters are
        # the same in every pass, the tree has ``n_layers`` blocks, and the
        # step compiles one pass however many run.  ``final_norm`` closes a
        # pass and the NORMED state goes on.  With the exit gate the scan
        # hands back every pass's state, [n_passes, batch, seq, d_model],
        # and head and gate read all of them OUTSIDE the body: a head
        # inside it would hand its logits' cotangent to the backward scan
        # as an array, where the head's own backward matmuls rebuild it in
        # their operand fusions (``loss_tail``)
        def one_pass(mdl, x, _):
            with phase_scope(LOOP_SCOPE):
                x = stack(x)
            x = final_norm(x)
            return x, x if cfg.exit_gate else None

        x, states = nn.scan(
            one_pass, variable_broadcast="params",
            split_rngs={"params": False}, length=cfg.n_passes)(self, x, None)
        read = states if cfg.exit_gate else x
        out = float32_logits(lm_head(read)) if self.head else (
            read.astype(jnp.float32))
        if not cfg.exit_gate:
            return out
        # the cast in front of the gate and the re-layout behind it are the
        # gate's work and read under its name
        with phase_scope("exit_gate"):
            gates = nn.Dense(
                1, use_bias=True, dtype=jnp.float32,
                param_dtype=cfg.param_dtype, name="exit_gate",
            )(states.astype(jnp.float32))[..., 0]
            return out, jnp.moveaxis(gates, 0, -1)


#: dotted-name suffix -> (sharded dim of the GLOBAL kernel, contracting
#: dims) for the trainer's tp leaf sharding and the global-init redraw
#: (column-parallel kernels shard an output feature dim; row-parallel
#: kernels shard a contracting dim)
_TP_DIMS = {
    # q/k/v: [d_model, heads, head_dim] — shard heads, contract d_model
    "attn.q.kernel": (1, (0,)),
    "attn.k.kernel": (1, (0,)),
    "attn.v.kernel": (1, (0,)),
    # o: [heads, head_dim, d_model] — shard heads, contract heads*head_dim
    "attn.o.kernel": (0, (0, 1)),
    # wi: [d_model, d_ff] — shard d_ff, contract d_model
    "mlp.wi_gate.kernel": (1, (0,)),
    "mlp.wi_up.kernel": (1, (0,)),
    # wo: [d_ff, d_model] — shard d_ff, contract d_ff
    "mlp.wo.kernel": (0, (0,)),
}


def tp_param_dim(name: str):
    """Sharded dim for a TP param of :class:`TransformerLM` (None: dense)."""
    for suffix, (dim, _) in _TP_DIMS.items():
        if name.endswith(suffix):
            return dim
    return None


def tp_param_fan_in_dims(name: str):
    """Contracting dims of a TP kernel's GLOBAL shape (for init redraw)."""
    for suffix, (_, fan_in) in _TP_DIMS.items():
        if name.endswith(suffix):
            return fan_in
    return None


@jax.jit
def _mean_cross_entropy(logits, targets):
    # jitted for the lowered module's sake, not the trace's: JAX keys its
    # persistent compile cache on the module WITHOUT metadata, so a step
    # that differs from an older build's in scope names alone is handed
    # that build's executable, old names and all.  A called function
    # (``@_mean_cross_entropy``) is part of the key; XLA inlines it, and
    # the optimized step is the inline form's, instruction for instruction
    #
    # optax's ``softmax_cross_entropy_with_integer_labels`` term for term,
    # but for how the target's logit is picked: not ``take_along_axis``.  A
    # gather's operand must exist in HBM (the float32 logits, 1.2-1.65 GB a
    # step, written beside the bf16 ones for 8,192 scalars) and its
    # transpose is a scatter of scalars (at batch 1 into a float32 flat that
    # takes three more passes to get back into tiles).  A compare against
    # an iota and a sum fuse into the passes that read the logits anyway,
    # forward and backward; the sum of one logit and zeros is that logit
    vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    hit = vocab == targets[..., None]
    label_logits = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return (jax.nn.logsumexp(logits, axis=-1) - label_logits).mean()


def loss_tail(logits, targets):
    """Mean cross-entropy of ``logits`` [b, s, vocab] against the integer
    ``targets`` [b, s]: what every LM loss function runs after the model.
    No module names it, so it names itself: the plain scope ``loss_tail``
    (``obs.spans.area_of`` reads it, forward and backward, as the head).

    The target's logit is a masked sum over the vocabulary axis, not a
    gather: the operand of a gather must exist in HBM and its transpose is
    a scatter, while the compare-and-sum fuses into the softmax's own
    passes (optax's terms: value and gradient equal to its to the bit,
    operation for operation).  A target outside ``[0, vocab)`` matches no
    column: its label logit is 0 and the loss stays finite, where
    ``take_along_axis`` wrapped a negative target around and filled in NaN
    past the end."""
    with phase_scope(LOSS_TAIL_SCOPE):
        return _mean_cross_entropy(logits, targets)


@jax.jit
def _token_cross_entropy(logits, targets):
    # ``_mean_cross_entropy`` without its mean, for a loss that weighs each
    # token's cross-entropy before it averages (``looped_lm_loss_fn``): the
    # same compare-and-sum for the target's logit, so nothing here needs the
    # float32 logits in HBM either, forward or backward, and a cotangent that
    # differs by token is one more factor in the fusions that rebuild
    # softmax - one-hot.  A function of its own, and jitted for the same
    # reason: the older function's name and body are part of the six older
    # steps' compile-cache keys and stay as they are
    vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    hit = vocab == targets[..., None]
    label_logits = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return jax.nn.logsumexp(logits, axis=-1) - label_logits


def token_loss_tail(logits, targets):
    """Cross-entropy of ``logits`` [b, s, vocab] against the integer
    ``targets`` [b, s], token by token ([b, s], float32): ``loss_tail``
    before its mean, under the same scope (the head's area) and by the same
    compare-and-sum."""
    with phase_scope(LOSS_TAIL_SCOPE):
        return _token_cross_entropy(logits, targets)


def exit_distribution(gate_logits):
    """The exit distribution of a looped model over its ``T`` passes, from
    the gate logits ``[..., T]`` (float32): with ``lambda_t =
    sigmoid(g_t)``, ``p_t = lambda_t * prod_{j<t} (1 - lambda_j)`` for ``t <
    T`` and ``p_T = prod_{j<T} (1 - lambda_j)``, the mass that is left (the
    last gate's own logit is not read).  Returns ``(p, log p)``, computed in
    log space: the products are sums of ``log_sigmoid``, so no ``p_t`` that
    underflows takes its logarithm with it."""
    gates = gate_logits.astype(jnp.float32)
    g = gates[..., :-1]                                # the gates that are read
    # log prod_{j<t} (1 - lambda_j) for t = 1 .. T: zero, then the running sum
    stayed = jnp.cumsum(jnp.concatenate(
        [jnp.zeros_like(gates[..., :1]), jax.nn.log_sigmoid(-g)], axis=-1),
        axis=-1)
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(g) + stayed[..., :-1], stayed[..., -1:]], axis=-1)
    return jnp.exp(log_p), log_p


def looped_lm_loss_fn(model: TransformerLM, beta: float = 0.05):
    """Next-token loss of a looped model with an exit gate
    (``TransformerConfig(n_passes=T, exit_gate=True)``); batch =
    dict(tokens=[b, s+1]).  The expected cross-entropy over the exits less
    ``beta`` times the exit distribution's entropy, a mean over the tokens:

        mean_i [ sum_t p_t(i) * CE(logits_t(i), target_i) - beta * H(p(i)) ]

    (the entropy-regularised objective of looped language models'
    pre-training, arXiv:2510.25741).  Each pass's cross-entropy stays per
    token until it is weighed (``token_loss_tail``); the exit distribution,
    its entropy and the weighting are float32 under the plain scope
    ``exit_dist`` (area ``exit``).  With one pass this is ``lm_loss_fn``'s
    number."""

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits, gates = model.apply({"params": params}, tokens[:, :-1])
        nll = token_loss_tail(logits, tokens[None, :, 1:])  # [passes, b, s]
        with phase_scope(EXIT_SCOPE):
            p, log_p = exit_distribution(gates)
            expected = jnp.sum(p * jnp.moveaxis(nll, 0, -1), axis=-1)
            entropy = -jnp.sum(p * log_p, axis=-1)
            return jnp.mean(expected - beta * entropy)

    return loss_fn


def block_diffusion_noise(tokens, rng, *, block: int, mask_id: int,
                          eps: float = 1e-3) -> dict:
    """A batch of ``block_diffusion_loss_fn`` from the clean ``tokens`` [b,
    L], drawn on the host for an input pipeline: per diffusion block of
    ``block`` positions a noise level ``t ~ U[eps, 1]`` (the linear
    schedule: ``t`` is the probability of a mask), per position ``masked ~
    Bernoulli(t)`` of its block.  ``rng``: a ``numpy.random.Generator``.
    ``mask_id`` is the id the loss puts in the masked positions of the
    noised copy; here it only must not occur among ``tokens``.  Publishes
    how many positions of the batch carry loss
    (``diffusion/masked_tokens_per_step``)."""
    import numpy as np

    from ..telemetry import counters

    tokens = np.asarray(tokens)
    b, seq = tokens.shape
    if seq % block:
        raise ValueError(f"{seq} positions are not whole blocks of {block}")
    if (tokens == mask_id).any():
        raise ValueError(f"mask_id {mask_id} occurs among the clean tokens")
    t = rng.uniform(eps, 1.0, size=(b, seq // block)).astype(np.float32)
    masked = rng.random(size=(b, seq)) < np.repeat(t, block, axis=1)
    counters.set_gauge("diffusion/masked_tokens_per_step", int(masked.sum()))
    return {"tokens": tokens, "masked": masked, "t": t}


def block_diffusion_loss_fn(model: TransformerLM, mask_id: int):
    """Masked block-diffusion loss of a ``TransformerConfig(attention=
    "block_diffusion")`` model; batch = dict(tokens=[b, L] clean ids,
    masked=[b, L] bool, t=[b, L // diffusion_block] float32), as
    :func:`block_diffusion_noise` draws it.  The model reads ``[x ; x~]``,
    ``x~`` the tokens with ``mask_id`` at the masked positions, and its
    logits at noised row ``i`` predict token ``i`` itself (no shift):

        (1 / (b L)) sum_i masked_i / t_block(i) * CE(logits_i, x_i)

    (the 1 / t-weighted objective of masked diffusion under the linear
    schedule, a block's own ``t``: arXiv:2503.09573).  The cross-entropy
    stays per token until it is weighed (``token_loss_tail``: no float32
    logits in HBM)."""
    block = model.cfg.diffusion_block

    def loss_fn(params, batch):
        tokens, masked = batch["tokens"], batch["masked"]
        with phase_scope(DIFFUSION_INPUT_SCOPE):
            noised = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype),
                               tokens)
            rows = jnp.concatenate([tokens, noised], axis=1)
        logits = model.apply({"params": params}, rows)
        nll = token_loss_tail(logits, tokens)                   # [b, L]
        with phase_scope(LOSS_TAIL_SCOPE):
            weight = masked / jnp.repeat(
                batch["t"].astype(jnp.float32), block, axis=1)
            return jnp.mean(weight * nll)

    return loss_fn


def lm_loss_fn(model: TransformerLM):
    """Next-token cross-entropy; batch = dict(tokens=[b, s+1])."""

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits = model.apply({"params": params}, tokens[:, :-1])
        return loss_tail(logits, tokens[:, 1:])

    return loss_fn


def sp_lm_loss_fn(model: TransformerLM, sp_size: int, sp_axis: str = "sp"):
    """Sequence-parallel next-token loss.

    ``batch['tokens']`` is the FULL [batch, seq_global+1] array, replicated
    over the sp axis; each shard slices its contiguous chunk, runs the model
    on local positions, and computes the loss for its targets.  The trainer's
    loss allreduce (over dp × sp) averages the shard means, which equals the
    global mean because chunks are equal-sized.
    """

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        seq_global = tokens.shape[1] - 1
        assert seq_global % sp_size == 0, (seq_global, sp_size)
        s_local = seq_global // sp_size
        start = jax.lax.axis_index(sp_axis) * s_local
        inputs = jax.lax.dynamic_slice_in_dim(tokens, start, s_local, axis=1)
        targets = jax.lax.dynamic_slice_in_dim(tokens, start + 1, s_local, axis=1)
        logits = model.apply({"params": params}, inputs)
        return loss_tail(logits, targets)

    return loss_fn
