"""Expert-parallel MoE layer.

Counterpart of /root/reference/bagua/torch_api/model_parallel/moe/layer.py:22
(``MoE``) + sharded_moe.py:306 (``MOELayer``: gate → einsum dispatch →
all-to-all → local experts → all-to-all → einsum combine) + experts.py
(expert params flagged so DP averaging skips them, experts.py:26-29).

TPU-first shape: the all-to-all is ``lax.all_to_all`` over an ``'ep'`` mesh
axis inside the jitted step (the reference drives
``torch.distributed.all_to_all_single`` from autograd, sharded_moe.py:77-90);
expert weights live as one leaf ``[n_experts, ...]`` sharded over ``'ep'``.
Two routings share them: the capacity path (dense ``[T, E, C]`` dispatch
and combine einsums around batched per-expert matmuls) and the dropless
path (rows sorted by expert through the grouped-matmul kernels of
:mod:`bagua_tpu.ops.gmm`, resident in their padded layout from dispatch to
combine).  Parameters whose name contains ``"expert"`` are excluded from
the data-parallel bucket plan by the trainer (the analog of
``param.expert`` flags).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ...obs.spans import phase_scope
from ...parallel.mesh import axis_bound as _axis_bound
from .gating import top1_gating, top2_gating


#: the experts' activations by ``MoEMLP.activation``; all keep a zero row zero
_ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu,
                "relu2": lambda x: jnp.square(nn.relu(x))}


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: tokens [batch, seq, d_model] -> same.

    Plugs into ``TransformerLM`` via ``mlp_factory``.  ``ep_size`` is the
    static expert-parallel degree (= mesh ``'ep'`` axis size); each shard owns
    ``n_experts // ep_size`` experts, and its expert leaves are the LOCAL
    table.  Inside a bound ``'ep'`` axis the shards exchange rows.  Outside
    one (``model.init``; one chip running one rank of a larger deployment)
    the layer computes ONE rank's share by itself, the dropless path for
    rank ``ep_rank``: the router scores all ``n_experts``, every token
    keeps its ``k`` winners and their gate weights, the pairs whose expert
    is one of ``ep_rank * n_local .. + n_local - 1`` are computed, and the
    rest add zero — what the absent ranks would have added is left out,
    nothing stands in for them or for their traffic.  Summed over the
    ``ep_size`` ranks the shares are the whole layer
    (``tests/test_smallthinker.py``).  The capacity path outside the axis
    still computes the first ``n_local`` experts' slots only.  Parameter
    shapes are the same in and outside the axis, so init-outside /
    apply-inside works.

    ``dropless=False`` (default) is the GShard capacity path: top-1 / top-2
    gates, a dense ``[T, E, C]`` dispatch einsum, one batched einsum per
    expert matmul, overflow tokens dropped.  ``dropless=True`` computes every
    routed (token, expert) pair for any ``k`` (:meth:`_dropless`): the rows
    are gathered once into the grouped-matmul kernels' padded layout, the
    expert FFN (gated or not) runs there, and each token gathers its ``k``
    rows back out.
    """

    n_experts: int
    d_ff: int
    ep_size: int = 1
    #: experts a token is routed to.  The capacity path has gates for 1
    #: and 2 only; ``dropless`` routes any ``k`` (OLMoE: 8 of 64)
    k: int = 2
    capacity_factor: float = 1.25
    axis_name: str = "ep"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: capacity-free routing: no token is ever dropped.  Tokens are sorted by
    #: expert and run through the grouped-matmul Pallas kernel
    #: (:mod:`bagua_tpu.ops.gmm`) instead of the dense [T,E,C] dispatch
    #: einsum.  With ``ep_size > 1`` the inter-shard exchange carries a
    #: peer's rows in a fixed slot range with their exact counts beside them
    #: (the reference's ``alltoall_v``, communicators/mod.rs:632-676; one
    #: dense ``all_to_all`` over worst-case slots) instead of capacity slots
    #: an expert.
    #:
    #: Regime selection: the only speed record of capacity against
    #: dropless was the pre-chip yardstick's at TOY widths (E=8, k=2,
    #: d_model 512; "capacity wins below ~12K tokens per shard per layer,
    #: dropless above") — deleted in PR 46, never measured by perfbench
    #: (ROADMAP Queue 3 item 3).  Measured at published widths, through
    #: ``perfbench`` (cell ``olmoe-1b-7b.pretrain4096-dp1``: E=64, k=8,
    #: d_model 2048, expert width 1024, 8,192 tokens a step on one v5e):
    #: PERF.md §5 / §6 (PR 28); no capacity run exists there (its [T, E, C]
    #: dispatch tensor at these sizes is 8,192 x 64 x 1,280).  The default
    #: stays False because the two paths have different TRAINING semantics
    #: (capacity drops overflow tokens; dropless never drops) — switching
    #: is the user's modelling decision.
    dropless: bool = False
    #: gated experts (OLMoE, Mixtral, DeepSeek): ``(silu(x wg) * (x wi)) wo``
    #: with the extra leaf ``expert_wg``; False: ``silu(x wi) wo``
    gated: bool = False
    #: renormalize the ``k > 1`` winners' probabilities to sum to one (HF
    #: ``norm_topk_prob``; OLMoE: False).  ``dropless`` only
    norm_topk_prob: bool = True
    #: balance loss over all ``k`` assignments of a token (HF's
    #: ``load_balancing_loss_func``) instead of the top-1 (GShard eq. 4).
    #: ``dropless`` only
    balance_over_topk: bool = False
    #: the experts' activation: ``silu`` (``silu(x wg) * (x wi)``), ``relu``
    #: (ReLU-gated experts, SmallThinker's sparse ReGLU) or ``relu2``
    #: (``relu(x)^2``, Nemotron-H's, on ungated experts: ``relu(x wi)^2
    #: wo``).  All keep a zero row zero.  ``dropless`` only
    activation: str = "silu"
    #: the expert-parallel rank whose share is computed outside a bound
    #: ``axis_name`` axis (``dropless`` only; see the class docstring)
    ep_rank: int = 0
    #: width of a SHARED expert beside the routed ones (0: none): every
    #: token runs through it, its output is added to the routed sum.  Its
    #: leaves (``shared_wg`` / ``shared_wi`` / ``shared_wo``, gated like the
    #: routed experts) are no expert leaves: every rank holds and computes
    #: it, expert-parallel or not, and nothing of it is exchanged.  Of the
    #: ranks' shares of a layer it is the part all compute alike: counted
    #: once when they are summed
    shared_d_ff: int = 0
    #: the shared expert's output is multiplied by ``sigmoid(x w_s)``, one
    #: float32 scalar a token (leaf ``shared_gate``)
    shared_gate: bool = False
    #: the router's scores: ``softmax`` over the experts, or ``sigmoid`` of
    #: each expert's logit by itself (not normalised over the experts; the
    #: winners' renormalised where ``norm_topk_prob``).  ``dropless`` only
    router_score: str = "softmax"
    #: a per-expert bias (leaf ``score_bias``, float32 ``[n_experts]``) added
    #: to the scores for the CHOICE of the ``k`` winners and not for their
    #: weights: its gradient is zero (the families move it by a load-balance
    #: rule outside the gradient; here only the optimizer's decay touches
    #: it).  Drawn at ``score_bias_std``.  ``dropless`` only
    score_bias: bool = False
    score_bias_std: float = 0.0
    #: the routed experts' weights are multiplied by it after the
    #: renormalisation (``routed_scaling_factor``); the shared expert is
    #: not.  ``dropless`` only
    routed_scale: float = 1.0

    @nn.compact
    def __call__(self, x, route_x=None):
        """``route_x`` [batch, seq, d_model]: what the router reads, where
        that is not what the experts read (a router placed before attention
        reads the block's input, the experts the post-attention norm)."""
        assert self.n_experts % self.ep_size == 0
        assert 0 <= self.ep_rank < self.ep_size, (self.ep_rank, self.ep_size)
        n_local = self.n_experts // self.ep_size
        b, s, d = x.shape
        tokens = b * s
        xt = x.reshape(tokens, d)

        # router in f32 (small, precision-sensitive; reference TopKGate
        # casts to fp32 too, sharded_moe.py:241-303)
        with phase_scope("bagua.moe/route"):
            routed = xt if route_x is None else route_x.reshape(tokens, d)
            logits = nn.Dense(
                self.n_experts, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32, name="router",
            )(routed.astype(jnp.float32))

        # one definition of the expert weights for both routing paths —
        # always the LOCAL table [n_experts // ep_size, ...]
        wi = self.param(
            "expert_wi", nn.initializers.lecun_normal(batch_axis=(0,)),
            (n_local, d, self.d_ff), self.param_dtype,
        )
        wo = self.param(
            "expert_wo", nn.initializers.lecun_normal(batch_axis=(0,)),
            (n_local, self.d_ff, d), self.param_dtype,
        )

        # the gate projection of gated experts; created after wi / wo so
        # that an ungated layer's parameters are what they always were
        wg = self.param(
            "expert_wg", nn.initializers.lecun_normal(batch_axis=(0,)),
            (n_local, d, self.d_ff), self.param_dtype,
        ) if self.gated else None

        shared = self._shared(xt)
        if self.dropless:
            return (self._dropless(xt, logits, wi, wo, wg)
                    + shared).reshape(b, s, d)

        if self.k > 2:
            raise ValueError(
                f"the capacity path gates top-1 and top-2 only, not k="
                f"{self.k}: set dropless=True")
        if (wg is not None or not self.norm_topk_prob or self.balance_over_topk
                or self.activation != "silu" or self.ep_rank):
            raise ValueError(
                "gated experts, norm_topk_prob=False, balance_over_topk="
                "True, an activation other than silu and a rank's share "
                "(ep_rank) are options of the dropless path: set "
                "dropless=True")
        if (self.router_score != "softmax" or self.score_bias
                or self.routed_scale != 1.0):
            raise ValueError(
                "router_score other than softmax, score_bias and "
                "routed_scale are options of the dropless path: set "
                "dropless=True")
        capacity = max(1, math.ceil(self.k * tokens * self.capacity_factor
                                    / self.n_experts))
        gate = top1_gating if self.k == 1 else top2_gating
        dispatch, combine, l_aux = gate(logits, capacity)
        self.sow("intermediates", "l_aux", l_aux)

        # dispatch: [T,E,C] x [T,d] -> [E,C,d]
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(self.dtype), xt.astype(self.dtype)
        )

        inside_mesh = self.ep_size > 1 and _axis_bound(self.axis_name)
        if inside_mesh:
            # [E, C, d] -> [E/ep, ep*C, d]: expert shards receive their
            # tokens from every ep peer
            expert_in = lax.all_to_all(
                expert_in, self.axis_name, split_axis=0, concat_axis=1,
                tiled=True,
            )
        elif self.ep_size > 1:
            # init path (outside shard_map): only shapes matter
            expert_in = expert_in[:n_local]

        h = nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(self.dtype)))
        out = jnp.einsum("ecf,efd->ecd", h, wo.astype(self.dtype))

        if inside_mesh:
            out = lax.all_to_all(
                out, self.axis_name, split_axis=1, concat_axis=0, tiled=True
            )
        elif self.ep_size > 1:
            out = jnp.concatenate(
                [out] + [jnp.zeros_like(out)] * (self.ep_size - 1), axis=0
            )

        y = jnp.einsum("tec,ecd->td", combine.astype(self.dtype), out)
        return (y + shared).reshape(b, s, d)

    def _shared(self, xt):
        """The shared expert on every token [T, d], under the scope
        ``bagua.moe/shared``; 0 where the layer has none."""
        if not self.shared_d_ff:
            return 0
        from ...telemetry import counters

        if not self.is_initializing():
            counters.set_gauge("moe/shared_width", self.shared_d_ff)
        act = _ACTIVATIONS[self.activation]
        dense = lambda name, features: nn.Dense(
            features, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        with phase_scope("bagua.moe/shared"):
            up = dense("shared_wi", self.shared_d_ff)(xt)
            h = (act(dense("shared_wg", self.shared_d_ff)(xt)) * up
                 if self.gated else act(up))
            y = dense("shared_wo", xt.shape[1])(h)
            if self.shared_gate:
                gate = nn.Dense(1, use_bias=False, dtype=jnp.float32,
                                param_dtype=jnp.float32, name="shared_gate")(
                                    xt.astype(jnp.float32))
                y = (jax.nn.sigmoid(gate) * y).astype(y.dtype)
            return y

    def _experts(self, x_p, layout, wi, wo, wg):
        """The expert FFN on rows resident in ``layout`` (grouped by local
        expert, padding rows zero): two grouped matmuls, three where the
        experts are gated, and the elementwise work between them, all on
        padded rows — ``act(0) * 0`` keeps the padding rows zero.  What
        the backward pass keeps is ``x_p``, the in-projections and the
        result; the hidden rows are rebuilt there (one elementwise pass)."""
        from ...ops.gmm import gmm_padded
        from ...telemetry import counters

        act = _ACTIVATIONS[self.activation]
        extra = _whole_tiles(self.d_ff) - self.d_ff
        if extra and layout.block_rows > 1:
            # the kernels take whole lane tiles of the hidden width (1,856
            # is 14.5): zero columns behind wi (and wg), zero rows behind
            # wo.  ``act(0) * 0`` is zero, so the padding adds nothing, and
            # its cotangent is sliced off again by the pad's transpose
            wi, wg = (w if w is None else jnp.pad(
                w, ((0, 0), (0, 0), (0, extra))) for w in (wi, wg))
            wo = jnp.pad(wo, ((0, 0), (0, extra), (0, 0)))

        if not self.is_initializing():
            # what the kernels really multiply (under ``ep`` the rows of
            # the worst-case receive buffer, padded), and whether they run
            counters.set_gauge("moe/padded_rows_per_step", x_p.shape[0])
            counters.set_gauge("moe/padded_resident_layers",
                               int(layout.block_rows > 1))

        @jax.checkpoint
        def down(up, gate, wo):
            h = act(up) if gate is None else act(gate) * up
            return gmm_padded(h, wo, layout)

        with phase_scope("bagua.moe/experts"):
            up = gmm_padded(x_p, wi.astype(self.dtype), layout)
            gate = (None if wg is None
                    else gmm_padded(x_p, wg.astype(self.dtype), layout))
            return down(up, gate, wo.astype(self.dtype))

    def _dropless(self, xt, logits, wi, wo, wg=None):
        """Sort-by-expert + grouped matmul: every routed (token, expert)
        pair is computed — the capacity-overflow drops of the GShard path
        (sharded_moe.py:93-238) cannot happen.

        The routed rows enter the grouped-matmul kernels' padded layout
        (:class:`bagua_tpu.ops.gmm.PaddedLayout`, computed once a layer)
        by ONE gather straight from the tokens, stay there through the
        expert FFN, and leave by ONE gather: each token reads its ``k``
        rows, weights them by its gates and sums them in float32.  Both
        maps are injective and carried with their inverses (``slots``:
        routed pair -> padded slot, ``reader``: padded slot -> routed
        pair), so the backward pass is gathers too — no scatter of rows in
        either direction.  Off the TPU, or at shapes the kernels do not
        take, the layout is the sorted rows themselves and the products are
        the dense reference's (:func:`bagua_tpu.ops.gmm.kernel_layout`).

        With expert parallelism the exchange is an all-to-all of fixed slot
        ranges with exact counts: rows sorted by global expert are already grouped by
        owning shard, so shard p receives only the rows routed to its
        experts (worst-case receive buffer: every peer routes all its rows
        here).  The receiver pads once from its receive buffer and unpads
        once into it; expert outputs ride the symmetric reverse transfer
        back to their source rows, and gates are applied at the source.

        The compiled step reads by phase: ``bagua.moe/route`` (router,
        softmax, top-k, balance loss), ``/dispatch`` (sort, the layout and
        its index maps, the gather in; backward: the sum over a token's
        ``k`` rows), ``/experts`` (the grouped matmuls, the gate, the
        hidden rows rebuilt in the backward pass), ``/combine`` (the
        weighted gather out; backward: the gather of the output's
        cotangent into the layout and the gates' gradient).

        Which body moves the rows (:func:`_row_kernels`; the gauge
        ``moe/row_kernel_sites``): the gather in is XLA's on every backend
        (``ops.gmm.take_or_zero``).  The other three are the kernels of
        :mod:`bagua_tpu.ops.moe_rows` where the grouped-matmul kernels run
        (a TPU, ``d`` of whole lane tiles, bf16 or float32, a
        block-aligned layout): ``rows_sum`` for the sum over a token's
        ``k`` rows and for the weighted sum out — the layout's rows
        streamed once into a float32 accumulator of the tokens that is
        resident in VMEM, no ``[T, k, d]`` array written — and ``rows_in``
        for the combine's transpose — the cotangent resident, each slot's
        row times its gate and its product with the layout's row in one
        pass.  Elsewhere the ``jnp`` bodies beside them (``y[slots]``
        summed over ``k``, ``take_or_zero`` of the cotangent), which are
        also their goldens (``tests/test_moe_rows.py``).  Inside a bound
        ``ep`` axis the rows travel sorted, not laid out, and every move
        is a ``jnp`` body.
        """
        from ...ops.gmm import kernel_layout, pad_rows
        from ...telemetry import counters
        from .gating import topk_routing

        n_local = self.n_experts // self.ep_size
        tokens, k = xt.shape[0], self.k
        with phase_scope("bagua.moe/route"):
            bias = self.param(
                "score_bias", nn.initializers.normal(self.score_bias_std),
                (self.n_experts,), jnp.float32) if self.score_bias else None
            eidx, gates, l_aux = topk_routing(
                logits, k, renormalize=self.norm_topk_prob,
                balance_over_topk=self.balance_over_topk,
                score=self.router_score, choice_bias=bias,
                scale=self.routed_scale)
        self.sow("intermediates", "l_aux", l_aux)
        if not self.is_initializing():
            counters.set_gauge("moe/routed_scale", self.routed_scale)
            counters.set_gauge("moe/score_bias", int(self.score_bias))
            # trace-time facts of this layer's step (not of ``init``'s stub
            # batch), for the operator and the benchmark's moe_padding_share
            counters.set_gauge("moe/experts", n_local)
            counters.set_gauge("moe/experts_total", self.n_experts)
            counters.set_gauge("moe/rows_per_step", tokens * k)

        inside_mesh = self.ep_size > 1 and _axis_bound(self.axis_name)
        with phase_scope("bagua.moe/dispatch"):
            flat_e = eidx.reshape(-1)                   # [T*k] routed pairs
            if self.ep_size > 1 and not inside_mesh:
                # one rank's share by itself: ids on this rank's table,
                # and for a pair another rank holds the sentinel
                # ``n_local``, which sorts last, counts in no group and is
                # not carried into the layout (its slot is one of the
                # trailing padding slots, which hold zero)
                local = flat_e - self.ep_rank * n_local
                flat_e = jnp.where((local >= 0) & (local < n_local), local,
                                   n_local)
            order = jnp.argsort(flat_e)                 # stable: ties by token
            rank = _inverse_permutation(order)          # pair -> sorted row
            x = xt.astype(self.dtype)
        if inside_mesh:
            # the rows travel sorted; the combine reads them back as such
            slots, reader = rank.reshape(tokens, k), order
            # rows sorted, not laid out: a token's rows may be neighbours
            by_kernel = (False, False)
            with phase_scope("bagua.moe/dispatch"):
                x_rows = pad_rows(x, order // k, slots)
            y = self._dropless_exchange(x_rows, flat_e[order], wi, wo, wg,
                                        n_local)
        else:
            with phase_scope("bagua.moe/dispatch"):
                # rows per expert by compare-and-sum, not ``bincount``'s
                # scatter-add of 65,536 ones
                sizes = (flat_e[:, None] == jnp.arange(n_local)[None, :]).sum(
                    0, dtype=jnp.int32)
                layout = kernel_layout(sizes, tokens * k, x.shape[1],
                                       _whole_tiles(self.d_ff))
                reader = _take_index(order, layout.src)
                slots = layout.pos[rank].reshape(tokens, k)
                by_kernel = _row_kernels(tokens, k, layout, x.shape[1],
                                         x.dtype)
                x_p = pad_rows(x, reader // k, slots, by_kernel[0])
            y = self._experts(x_p, layout, wi, wo, wg)
        if not self.is_initializing():
            # rows_sum serves two of the four movements, rows_in one
            counters.set_gauge("moe/row_kernel_sites",
                               2 * by_kernel[0] + by_kernel[1])
        with phase_scope("bagua.moe/combine"):
            return _combine(y, gates, slots, reader, by_kernel)

    def _dropless_exchange(self, x_rows, e_rows, wi, wo, wg, n_local):
        """EP dispatch for dropless routing: [T*k, d] rows grouped by global
        expert → owning shards → local grouped matmul → reverse transfer.

        The analog of the reference's ``alltoall_v``-driven MoE all-to-all
        (communicators/mod.rs:632-676, sharded_moe.py:77-90).  Rows for peer
        ``p`` occupy the fixed slot range ``[p*tk, p*tk + count_p)`` of a
        worst-case send buffer, so the transfer is one dense ``all_to_all``
        (validatable on the virtual CPU mesh) and every downstream index is
        slot-deterministic.
        """
        ep, ax = self.ep_size, self.axis_name
        tk, d = x_rows.shape
        cap = ep * tk                                   # worst-case slots

        # per-destination counts (rows sorted by global expert are already
        # grouped by owning shard); the [ep, n_local] counts exchange lets
        # the receiver reconstruct every row's local expert id from the
        # deterministic slot layout — no per-row metadata on the wire
        sizes_global = jnp.bincount(e_rows, length=self.n_experts)
        counts = sizes_global.reshape(ep, n_local).astype(jnp.int32)
        send_sizes = counts.sum(-1)
        input_offsets = (jnp.cumsum(send_sizes) - send_sizes).astype(jnp.int32)
        r = jnp.arange(tk, dtype=jnp.int32)
        peer_of_row = jnp.searchsorted(
            jnp.cumsum(send_sizes), r, side="right"
        ).astype(jnp.int32)
        slot = peer_of_row * tk + (r - input_offsets[peer_of_row])

        # counts_recv[p, e] = rows peer p routed to my local expert e
        counts_recv = lax.all_to_all(counts, ax, 0, 0, tiled=False).reshape(
            ep, n_local
        )
        # rows from peer p occupy slots [p*tk, p*tk + Σe counts_recv[p])
        # ordered by local expert; beyond that the slot is empty (sentinel
        # id n_local, zero payload)
        cums = jnp.cumsum(counts_recv, axis=1)          # [ep, n_local]
        within = jnp.arange(tk, dtype=jnp.int32)
        lid_recv = (
            (within[None, :, None] >= cums[:, None, :]).sum(-1)
            .astype(jnp.int32).reshape(cap)
        )
        sizes = counts_recv.sum(0)                      # rows per local expert

        x_send = jnp.zeros((cap, d), x_rows.dtype).at[slot].set(x_rows)
        x_recv = lax.all_to_all(
            x_send.reshape(ep, tk, d), ax, 0, 0, tiled=False
        ).reshape(cap, d)

        # group received rows by local expert, straight into the layout:
        # sentinel (empty-slot) rows sort last, fall outside the grouped
        # range and are not carried in; their slots read back zero
        from ...ops.gmm import kernel_layout, pad_rows, unpad_rows

        local_order = jnp.argsort(lid_recv)             # sorted row -> slot
        layout = kernel_layout(sizes, cap, d, _whole_tiles(self.d_ff))
        reader = _take_index(local_order, layout.src)   # padded -> receive
        slots = layout.pos[_inverse_permutation(local_order)]
        x_p = pad_rows(x_recv, reader, slots[:, None])
        y_p = self._experts(x_p, layout, wi, wo, wg)
        y_local = unpad_rows(y_p, slots, reader)

        # reverse transfer over the same slots, then gather my rows back
        y_back = lax.all_to_all(
            y_local.reshape(ep, tk, d), ax, 0, 0, tiled=False
        ).reshape(cap, d)
        return y_back[slot]


def _whole_tiles(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles: the hidden width the
    grouped-matmul kernels are handed (``MoEMLP._experts`` pads the expert
    stacks with zeros up to it where they run)."""
    from ...ops.tiles import LANE

    return -(-width // LANE) * LANE


def _inverse_permutation(perm):
    """``inv[perm[i]] = i``: a scatter over an int32 index vector, the only
    kind of scatter the dropless path emits."""
    iota = jnp.arange(perm.shape[0], dtype=perm.dtype)
    return jnp.zeros_like(perm).at[perm].set(iota, unique_indices=True)


def _take_index(index, src):
    """``index[src]`` where ``src`` may point one past the end (a padding
    slot of the layout): those read ``len(index)``, out of range in turn."""
    return jnp.take(index, src, mode="fill", fill_value=index.shape[0])


def _row_kernels(tokens, k, layout, d, dtype):
    """Which of the layer's row movements run ``ops/moe_rows.py``'s
    kernels, ``(by rows_sum, by rows_in)``: ``rows_sum`` the dispatch's
    transpose and the combine, ``rows_in`` the combine's transpose — decided
    by what the call can see: the backend, the shapes, the dtype, and
    whether the layout is block-aligned (a token then lies at most once in
    a tile of slots, which ``rows_sum``'s batches rely on).  The dispatch
    itself never does: XLA's gather out of the tokens is the faster one
    (:func:`bagua_tpu.ops.gmm.take_or_zero`)."""
    from ...ops.moe_rows import rows_in_supported, rows_sum_supported

    rows = layout.src.shape[0]
    return (k > 1 and layout.block_rows > 1
            and rows_sum_supported(tokens, rows, d, dtype),
            rows_in_supported(tokens, rows, d, dtype, with_dot=True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(y, gates, slots, reader, by_kernel=(False, False)):
    """``out[t] = sum_j gates[t, j] * y[slots[t, j]]``, accumulated in
    float32 and rounded once.  ``reader`` [len(y)] is the routed pair
    ``t * k + j`` that reads each row of ``y`` (``T * k``, one past the
    end, for a padding row): with it the transpose is a gather of ``out``'s
    cotangent into ``y``'s layout, where the gates' gradient is a row
    sum.  ``by_kernel`` = (forward, transpose), :func:`_row_kernels`': where
    set, the forward is
    ``ops/moe_rows.py::rows_sum`` over ``reader`` (``y`` read once, a
    token's rows added in slot order, no ``[T, k, d]`` array) and the
    transpose ``rows_in`` (the cotangent's rows times their gates, and
    their products with ``y`` for the gates' gradient, in one pass)."""
    if by_kernel[0]:
        from ...ops.moe_rows import rows_sum

        tokens, k = slots.shape
        return rows_sum(y, reader // k, tokens, (gates, reader))
    rows = y[slots].astype(jnp.float32)
    return (rows * gates[..., None]).sum(1).astype(y.dtype)


def _combine_fwd(y, gates, slots, reader, by_kernel):
    return (_combine(y, gates, slots, reader, by_kernel),
            (y, gates, slots, reader))


def _combine_bwd(by_kernel, res, g):
    from ...ops.gmm import take_or_zero

    y, gates, slots, reader = res
    if by_kernel[1]:
        from ...ops.moe_rows import rows_in

        d_y, products = rows_in(g, reader // slots.shape[1], (gates, reader),
                                dot=y)
        return d_y, products[slots].astype(gates.dtype), None, None
    g_rows = take_or_zero(g, reader // slots.shape[1]).astype(jnp.float32)
    w = take_or_zero(gates.reshape(-1), reader)
    d_gates = (g_rows * y.astype(jnp.float32)).sum(-1)[slots]
    return ((g_rows * w[:, None]).astype(y.dtype), d_gates.astype(gates.dtype),
            None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


# The exact parameter names MoEMLP creates.  Marking is by path *segment*
# equality against this set — the explicit analog of the reference's
# ``param.expert = True`` flags (experts.py:26-29) — never by substring, so a
# user param that merely contains "expert" in its name can't be silently
# pulled out of the data-parallel plan.
EXPERT_PARAM_NAMES = frozenset({"expert_wi", "expert_wo", "expert_wg"})


def is_expert_param(name: str) -> bool:
    """True for params created by :class:`MoEMLP` (exact segment match).

    Accepts any common path spelling: dotted (``a.b.expert_wi``), slashed,
    or raw ``jax.tree_util.keystr`` output (``['a']['expert_wi']``).
    """
    import re

    return not EXPERT_PARAM_NAMES.isdisjoint(re.split(r"[\[\]'\"./]+", name))


def globalize_expert_params(params, rng, ep_size: int, is_expert=None):
    """Re-draw expert leaves at global shape for the expert-parallel trainer.

    ``model.init`` outside the mesh yields expert leaves of LOCAL shape
    ``[n_experts/ep_size, ...]`` (identical on every rank — a bad symmetric
    init).  This expands each such leaf to ``[n_experts, ...]`` with an
    independent per-expert draw; ``BaguaTrainer(expert_axis=...)`` then shards
    the leading dim over ``'ep'``.  The returned tree is only valid inside the
    trainer (direct ``model.apply`` would see a shape mismatch).
    """
    if is_expert is None:
        is_expert = is_expert_param
    init = nn.initializers.lecun_normal(batch_axis=(0,))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if is_expert(name) and ep_size > 1:
            rng, sub = jax.random.split(rng)
            shape = (leaf.shape[0] * ep_size,) + leaf.shape[1:]
            out.append(init(sub, shape, leaf.dtype))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def moe_lm_loss_fn(model, aux_loss_weight: float = 0.01):
    """Next-token loss + load-balancing aux loss collected from every MoE
    layer (the reference accumulates ``l_aux`` per gate, sharded_moe.py:354)."""
    from ...models.transformer import loss_tail

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits, mutated = model.apply(
            {"params": params}, tokens[:, :-1], mutable=["intermediates"]
        )
        nll = loss_tail(logits, tokens[:, 1:])
        aux = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(mutated.get("intermediates", {})):
            aux = aux + jnp.sum(leaf)
        return nll + aux_loss_weight * aux

    return loss_fn
