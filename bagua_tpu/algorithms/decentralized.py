"""Decentralized SGD algorithms (gossip weight averaging).

Counterparts of /root/reference/bagua/torch_api/algorithms/decentralized.py and
the Rust comm ops:

- :class:`DecentralizedAlgorithm` — full-precision weight averaging, peer
  modes ``all`` (allreduce-avg of weights) and ``shift_one`` (pairwise
  exchange with a step-rotating partner, peer formula from
  comm_ops/decentralized_full_precision_synchronous.rs:79-83), executed as
  ``lax.pmean`` / ``lax.ppermute`` over the mesh.
- :class:`LowPrecisionDecentralizedAlgorithm` — ring compressed-difference
  exchange (comm_ops/decentralized_low_precision_synchronous.rs:45-151):
  each rank keeps replicas of its own and both neighbors' weights, sends the
  MinMaxUInt8-compressed difference ``x + L/3 + R/3 - 5w/3`` both ways, and
  applies the quantized update — communication happens after the optimizer
  step (reference decentralized.py:142-152).

Timing note: the reference starts weight communication in the forward-pre
hook (weights as of step start) and copies the averaged peer weight back in
the post-backward hook, i.e. *before* the optimizer step.  Functionally the
weights are unchanged between those two points, so here the full-precision
average runs in ``process_pre_step`` on the same values — identical math, and
XLA still overlaps it with backward because the collective's inputs are ready
before the gradients are.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..communication import BaguaCommunicator, ReduceOp
from ..compression import compress_chunked, decompress_chunked
from ..obs.spans import phase_scope
from .base import Algorithm, AlgorithmContext


def shift_one_peer(rank: int, nranks: int, step: int) -> int:
    """Partner formula from decentralized_full_precision_synchronous.rs:79-83.

    Symmetric pairing: ranks in the lower half pair with a step-rotating rank
    in the upper half; requires an even world size.
    """
    half = nranks // 2
    if rank < half:
        return (step + rank) % ((nranks + 1) // 2) + half
    return (rank - half - step) % half


class DecentralizedAlgorithm(Algorithm):
    replicated_params = False
    #: the gossip exchange already runs on flat buckets; under the
    #: resident layout the weights ARE those buckets, so the exchange (and
    #: the tracked peer replicas) needs no per-step flatten at all
    supports_flat_resident = True

    def __init__(
        self,
        hierarchical: bool = True,
        peer_selection_mode: str = "all",
        communication_interval: int = 1,
        track_peer_weights: bool = False,
    ):
        """
        Args:
            hierarchical: Enable hierarchical communication (intra-node
                average first, gossip across nodes).
            peer_selection_mode: ``"all"`` (average everyone) or
                ``"shift_one"`` (rotating pairwise exchange).
            communication_interval: Iterations between communications
                (reference decentralized.py:34-36).
            track_peer_weights: keep the post-communication weights in the
                algorithm state (the analog of the reference's ``peer_weight``
                bucket tensor, bucket.py:197-263) — lets tests assert the
                exact peer-equality invariant at the communication point.
        """
        assert peer_selection_mode in ("all", "shift_one"), peer_selection_mode
        self.hierarchical = hierarchical
        self.peer_selection_mode = peer_selection_mode
        self.communication_interval = communication_interval
        self.track_peer_weights = track_peer_weights

    def init_state(self, ctx: AlgorithmContext, params) -> Any:
        if not self.track_peer_weights:
            return None
        return {"peer_weights": ctx.plan.flatten_tree(params)}

    def _exchange(self, ctx: AlgorithmContext, flat, step):
        use_hier = (
            self.hierarchical
            and ctx.internode is not None
            and ctx.intranode is not None
            and ctx.intranode.nranks() > 1
            and ctx.internode is not ctx.intranode
        )
        gossip_comm = ctx.internode if use_hier else ctx.comm
        if use_hier:
            flat = ctx.intranode.allreduce(flat, ReduceOp.AVG)
        n = gossip_comm.nranks()
        if n <= 1:
            return flat
        if self.peer_selection_mode == "all":
            return gossip_comm.allreduce(flat, ReduceOp.AVG)
        assert n % 2 == 0, (
            "shift_one requires an even number of ranks, got %d" % n
        )
        comm_idx = step // self.communication_interval
        peer_val = gossip_comm.exchange_with_peer(flat, shift_one_peer, comm_idx)
        return (flat + peer_val) * 0.5

    def process_pre_step(self, ctx: AlgorithmContext, params, algo_state, step):
        flats = ctx.bucket_flats(params)

        def do_comm(fs):
            # the weight exchange runs inside the trainer's bagua.optimizer
            # scope and names itself bagua.comm (innermost bagua.* wins)
            with phase_scope("bagua.comm"):
                return [self._exchange(ctx, f, step) for f in fs]

        if self.communication_interval > 1:
            # non-communication steps must KEEP the previously tracked
            # peer weights, not overwrite them with local weights
            prev_peer = (
                algo_state["peer_weights"] if self.track_peer_weights else flats
            )

            def comm_branch(op):
                fs, _ = op
                out = do_comm(fs)
                return out, out

            def skip_branch(op):
                fs, prev = op
                return fs, prev

            flats, peer = lax.cond(
                step % self.communication_interval == 0,
                comm_branch, skip_branch, (flats, prev_peer),
            )
        else:
            flats = do_comm(flats)
            peer = flats
        if self.track_peer_weights:
            algo_state = {"peer_weights": peer}
        return ctx.from_bucket_flats(flats, params), algo_state

    def relayout_algo_state(self, old_plan, new_plan, algo_state):
        if algo_state is None:
            return None
        from ..bucket import relayout_flats

        return {"peer_weights": relayout_flats(
            old_plan, new_plan, algo_state["peer_weights"]
        )}


class LowPrecisionDecentralizedAlgorithm(Algorithm):
    replicated_params = False
    #: the compressed ring exchange and its three weight replicas are
    #: flat-bucket-shaped already; the resident layout feeds them directly
    supports_flat_resident = True

    def __init__(self, hierarchical: bool = True, communication_interval: int = 1):
        """
        Args:
            hierarchical: Enable hierarchical communication.
            communication_interval: Iterations between communications.
        """
        self.hierarchical = hierarchical
        self.communication_interval = communication_interval

    def init_state(self, ctx: AlgorithmContext, params) -> Any:
        # three weight replicas per bucket: left peer, right peer, self
        # (reference decentralized.py:154-165 _init_states)
        flats = ctx.plan.flatten_tree(params)
        return {
            "left": [jnp.array(f) for f in flats],
            "right": [jnp.array(f) for f in flats],
            "self": [jnp.array(f) for f in flats],
        }

    def _ring_step(self, ctx: AlgorithmContext, x, left, right, mine):
        """One compressed ring exchange for one bucket
        (decentralized_low_precision_synchronous.rs:45-151)."""
        if jnp.ndim(x) != 1:
            # a shaped bucket (bucket.py): the codec quantizes a 1-D run,
            # so ravel here, at the point of use
            outs = self._ring_step(
                ctx, *(a.reshape(-1) for a in (x, left, right, mine)))
            return tuple(o.reshape(x.shape) for o in outs)
        use_hier = (
            self.hierarchical
            and ctx.internode is not None
            and ctx.intranode is not None
            and ctx.intranode.nranks() > 1
            and ctx.internode is not ctx.intranode
        )
        ring_comm = ctx.internode if use_hier else ctx.comm
        if use_hier:
            x = ctx.intranode.allreduce(x, ReduceOp.AVG)
        n = ring_comm.nranks()
        if n <= 1:
            return x, left, right, mine

        diff = x + left / 3.0 + right / 3.0 - (5.0 / 3.0) * mine
        mn, mx, payload = compress_chunked(diff, 1)

        # ring neighbors: value sent left arrives from the right, etc.
        right_shift = [(r, (r + 1) % n) for r in range(n)]   # recv from left
        left_shift = [(r, (r - 1) % n) for r in range(n)]    # recv from right
        from_left = (
            ring_comm.ppermute(mn, right_shift),
            ring_comm.ppermute(mx, right_shift),
            ring_comm.ppermute(payload, right_shift),
        )
        from_right = (
            ring_comm.ppermute(mn, left_shift),
            ring_comm.ppermute(mx, left_shift),
            ring_comm.ppermute(payload, left_shift),
        )

        left = left + decompress_chunked(*from_left)
        right = right + decompress_chunked(*from_right)
        # apply own quantized diff: x' = w + Q(diff); w' = x'
        x_new = mine + decompress_chunked(mn, mx, payload)
        return x_new, left, right, x_new

    def process_post_step(self, ctx: AlgorithmContext, params, algo_state, step):
        flats = ctx.bucket_flats(params)

        def do_comm(operand):
            fs, st = operand
            new_fs, nl, nr, nw = [], [], [], []
            for f, l, r, w in zip(fs, st["left"], st["right"], st["self"]):
                with phase_scope("bagua.comm"):
                    f2, l2, r2, w2 = self._ring_step(ctx, f, l, r, w)
                new_fs.append(f2)
                nl.append(l2)
                nr.append(r2)
                nw.append(w2)
            return new_fs, {"left": nl, "right": nr, "self": nw}

        if self.communication_interval > 1:
            flats, algo_state = lax.cond(
                step % self.communication_interval == 0,
                do_comm,
                lambda op: op,
                (flats, algo_state),
            )
        else:
            flats, algo_state = do_comm((flats, algo_state))
        return ctx.from_bucket_flats(flats, params), algo_state

    def relayout_algo_state(self, old_plan, new_plan, algo_state):
        if algo_state is None:
            return None
        from ..bucket import relayout_flats

        return {
            k: relayout_flats(old_plan, new_plan, algo_state[k])
            for k in ("left", "right", "self")
        }
