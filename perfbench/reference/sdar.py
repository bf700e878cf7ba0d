"""Plain reference for the ``sdar`` builder: SDAR-30B-A3B-Chat's decoder
block (``sdar_moe``: Qwen3-MoE's), the block-diffusion training objective
and AdamW in ``jax.numpy`` and float32, from the catalog row's ``config``
(JetLM/SDAR-30B-A3B-Chat) and, where it is silent, from what the
configuration file lists under ``assumed``.  Imports nothing from
``bagua_tpu``; no kernel, no sort, no grouped matmul.  The pieces every
decoder reference shares (RMSNorm, rotate-half RoPE, top-k by argmax, AdamW
written out, the comparison of two loss sequences) are
``reference/olmoe.py``'s, loaded by file name.

**Input.**  A clean sequence ``x`` of ``L`` tokens, per diffusion block of
``B`` positions a noise level ``t``, per position ``m_i`` (masked or not).
``x~_i = MASK if m_i else x_i``.  The trunk reads ``z = [x ; x~]``, ``2 L``
rows, both halves at positions ``0 .. L - 1``.

**Layer**, ``h`` [batch, 2 L, d], no biases:

    u = RMSNorm(h);  q = u W_q (32 heads of 128), k = u W_k, v = u W_v (4 heads of 128)
    q, k <- RMSNorm over each head's 128 lanes, ONE [128] scale for all heads, then RoPE at the row's position
    query head i reads key / value head i // 8
    h' = h + W_o . softmax(q k^T / sqrt(128) under M) v       one softmax over the keys of both halves
    m  = RMSNorm'(h')
    pi = softmax(m W_r) over all 128 experts; the 8 largest, renormalised to sum to 1
    out = h' + sum over the winners e HELD HERE of pi_e . W_down[e]( silu(W_gate[e] m) * W_up[e] m )

**Mask** ``M[r, c]``, query row ``r`` (half ``a``, position ``i``, block
``i // B``), key row ``c`` (half ``a'``, position ``j``):

    clean  -> clean    j // B <= i // B
    noised -> clean    j // B <  i // B
    noised -> noised   j // B == i // B
    clean  -> noised   never

built here as ONE dense boolean ``[2 L, 2 L]`` matrix from those four rules
(:func:`dense_mask`).

**Head and loss.**  Final RMSNorm and the untied head over the held slice
of the vocabulary on the ``L`` NOISED rows; no shift, the logits of row ``i``
predict ``x_i``:  ``loss = (1 / (b L)) sum_i m_i / t_block(i) . CE(logits_i,
x_i)``.

**The share.**  One expert-parallel rank's share of each layer
(``deployment``): of the 128 experts it holds ``held`` from ``first_expert``
on; the router scores all 128 and ``pi`` is renormalised over all eight
winners; the sum runs over the winners held here only, here and in the
program alike, and that partial result goes on.  With ``held`` = 128 this is
the whole model, and over the eight ranks the shares add up to it
(``tests/test_smallthinker.py``, SDAR's settings).

How it is computed (none of it changes a number): all matrix products under
``jax.default_matmul_precision("highest")``; attention one head and one
block of ``QUERY_BLOCK`` queries at a time (32 x 8,192^2 float32 scores
never exist whole; the boolean mask does, 67 MB), the head's loss in chunks
of rows and the held experts one after the other, each — and each whole
layer — re-computed in the backward pass (``jax.checkpoint``); the layers
a ``lax.scan`` over the blocks' stacked leaves, so that the executable holds
one layer's code (:func:`stack_layers`; ``replay_losses`` stacks the weights
once and trains them so), and the two moments on the host between steps.

``hyper`` carries four switches that are all on in the architecture and
that ``tools/sdar_reference_check.py faults`` turns off one at a time, to
show that ``correct`` refuses a system that lacks the mechanism:
``mask`` (:data:`MASKS`), ``restart_positions``, ``shift`` and
``weigh_by_noise``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench import cells

_shared = cells.load_plugin("reference", "olmoe")
rms_norm, rope, top_k_by_argmax = (_shared.rms_norm, _shared.rope,
                                   _shared.top_k_by_argmax)

#: Largest |trainer loss - reference loss| accepted on the FIRST replayed
#: step; THE SECOND AND THE THIRD ARE REPORTED AND NOT HELD.  The trainer
#: computes matrix products, attention probabilities and logits in bfloat16
#: from float32 weights, as its configuration states; the reference is
#: float32 throughout.  The loss is a mean over 4,096 positions of ``m / t``
#: times a cross-entropy, and ``m / t`` has a heavy tail (E[(m / t)^2] =
#: ln(1 / eps) = 6.9, most of it from a few positions): of 3,000 seeds'
#: replay batches one in ten holds a position of weight 137 or more, one in
#: a hundred one of 450 (a block at t = 0.002 with a masked position), which
#: then carries half to nine tenths of the gradient's square.  Two readings
#: a step (my chip runs, PR 47, v5e, published widths, kernels on; PERF.md
#: section 6): over twenty seeds, nine of them picked along the tail (the
#: median to one in a thousand: a largest weight of 41 to 858), the system differs on the first step by at most
#: 0.00204 (the seed the driver's check drew, 2050016367: one position of
#: weight 137); the faults that a forward pass can show read 0.0150 (plain
#: causal over the 2 L rows), 0.061 (the loss read with a shift) and 5.1
#: (``1 / t`` dropped).  The limit is 2.9 times the system's largest and 2.5
#: times under the nearest of them.  On the second and third step no number
#: stands between the system and a fault.  AdamW's first update moves every
#: one of 0.6 billion weights by the learning rate along its gradient's
#: sign; where one position carries the gradient that step overshoots it,
#: the loss RISES (10.72 -> 11.61 -> 10.09 on that seed, 11.04 -> 13.45 ->
#: 11.13 on another, where the eleven light seeds fall at every step), and
#: what bfloat16 does to the signs of the small components then moves the
#: next loss by 0.0007 to 0.0696 / 0.0009 to 0.0289 on the nine heavy seeds
#: (0.0003 to 0.0028 / 0.0001 to 0.0056 on the eleven light ones), past what
#: the reference reads with its weights rounded to bfloat16 at the start and
#: after every update, the nearest precision below the float32 weights the
#: configuration states: 0.0084 to 0.1187 / 0.0140 to 0.1921 on five seeds.
#: Limits of 0.006 / 0.011 stood here first, from the eleven light seeds,
#: and the driver's first check refused the cell on its seed's 0.0180.  What
#: the trainer's state makes of three updates is held directly instead
#: (``CHANGE_TOLERANCE``), as ``reference/ouro.py`` holds it, and that is
#: the limit that refuses the rounded weights.  Of the mechanism faults,
#: noised rows that see their own clean block (0.0002), clean rows that see
#: noised keys (0.0018) and positions that do not restart (0.0017) are
#: inside this limit: under uniform random targets a change of the logits
#: that is not aligned with the targets averages out of the mean loss.
#: What refuses those three is ``GRADIENT_TOLERANCE``.
LOSS_TOLERANCE = (0.006,)

#: Largest relative distance ``|g_system - g_reference| / |g_reference|``
#: (Frobenius norms) accepted on any ``WATCHED`` leaf of the FIRST gradient
#: of the replay batch: the loss function the trainer's step differentiates
#: (``block_diffusion_loss_fn`` of the model as timed: bfloat16 products, the
#: ``flash_bd_*`` and grouped-matmul kernels forward and backward, the cell's
#: 8,192 rows) against this file's float32 gradient, which ``replay_losses``
#: computes for its first update anyway.  It is the comparison that sees the
#: mask's quadrants and the restarted positions: a gradient keeps the
#: direction that the mean loss averages away.  Two readings (my chip runs,
#: PR 47, as above): the system reads at most 0.0225 to 0.0358 on eleven
#: seeds (a layer's k or q; o and v two thirds of that); against a
#: reference whose noised rows see their own clean block it reads 0.186
#: (layer 2's q; a second seed 0.196), clean rows that see noised keys 0.431,
#: positions not restarted 1.26, causal over 2 L 1.54, the shift 1.30, no 1 /
#: t 2.86.  The limit is 2.5 times the system's largest reading and 2.1 times
#: under the nearest fault.  The tail of ``m / t`` does not move the system's
#: reading (0.0198 to 0.0273 on the nine seeds picked along it: each
#: position's gradient is off by its own few per cent, whatever its weight)
#: but it does move the nearest fault's: 0.155 on the seed the driver's check
#: drew and 0.074, UNDER the limit, where one position of weight 450 carries
#: nine tenths of the gradient and sees little of what the fault changes; on
#: the one seed in a hundred that is so heavy this comparison is blind to
#: that fault (PERF.md section 7).  Weights rounded to bfloat16 read what
#: the system reads (0.017 to 0.027): that fault is the change's to refuse.
GRADIENT_TOLERANCE = 0.09

#: Largest relative distance accepted on any ``WATCHED`` leaf and any leaf
#: of ``CHANGE_ALSO`` between the system's and the reference's CHANGE of the
#: parameters over the replayed updates, ``|d_system - d_reference| /
#: |d_reference|`` with ``d = weights after the last update - weights at the
#: start``: the system's from the trainer's own compiled step
#: (``builders/sdar.py::system_change``); a state left as it was reads 1.
#: It holds what the later losses cannot (``LOSS_TOLERANCE``): the
#: precision of the trainer's weights and moments, and the three updates.
#: AdamW's first update is the gradient's sign times the learning rate, so
#: a component whose sign the bfloat16 products flip counts twice its size,
#: and a first gradient that is 0.02 off makes a change that is 0.1 off: no
#: rounding of the state, the update's own arithmetic.  Two readings (my
#: chip runs, PR 47, call G: seven seeds along the tail, the largest weight
#: 41 to 858): the system reads 0.057 to 0.122 on the attention matrices of
#: six seeds and up to 0.190 on the seventh (layer 3's k, the seed whose loss
#: rises furthest), 0.058 to 0.123 on the final norm's scale, 0.021 to 0.033
#: on the head.  The reference with its weights rounded to bfloat16 at the
#: start and after every update (a trainer that kept bfloat16 master
#: weights) reads 0.486 to 0.528 on every q, k and v, 0.298 to 0.331 on o
#: (smaller entries, finer steps), 0.468 to 0.480 on the head, and has not
#: moved the norm's scale at all (no distance: refused), alike on a light
#: seed and on two heavy ones.  The limit is ``reference/ouro.py``'s: 1.8
#: times the system's largest, with the more room on that side (fresh seeds
#: read higher), and 1.4 times under the rounded weights' matrices.
CHANGE_TOLERANCE = 0.35

#: the leaves compared: the four attention matrices of every layer
WATCHED = ("q", "k", "v", "o")

#: compared in the parameters' change besides: the head, and a norm's scale,
#: which starts at one, where bfloat16 steps by 0.004 or 0.008: three updates
#: of 1e-4 do not move a scale that is kept in bfloat16 at all
CHANGE_ALSO = ("final_norm/scale", "lm_head/kernel")

#: rows per chunk of the head's cross-entropy; queries per attention block
HEAD_CHUNK = 1024
QUERY_BLOCK = 1024


def _block_diffusion(q_noised, k_noised, q_blk, k_blk):
    return jnp.where(k_noised, q_noised & (k_blk == q_blk),
                     jnp.where(q_noised, k_blk < q_blk, k_blk <= q_blk))


#: the architecture's mask and three faulted ones, each ``(query is noised,
#: key is noised, query's block, key's block, query's row, key's row) ->
#: visible``
MASKS = {
    "block_diffusion": lambda qn, kn, qb, kb, r, c: _block_diffusion(
        qn, kn, qb, kb),
    # fault: plain causal attention over the 2 L rows
    "causal": lambda qn, kn, qb, kb, r, c: c <= r,
    # fault: a noised row also sees the CLEAN keys of its own block (the
    # answer it is trained to predict)
    "own_clean_block": lambda qn, kn, qb, kb, r, c: _block_diffusion(
        qn, kn, qb, kb) | (qn & ~kn & (kb == qb)),
    # fault: a clean row also sees the noised keys of its own block
    "clean_sees_noised": lambda qn, kn, qb, kb, r, c: _block_diffusion(
        qn, kn, qb, kb) | (~qn & kn & (kb == qb)),
}


def dense_mask(length: int, block: int, kind: str = "block_diffusion"):
    """The ``[2 length, 2 length]`` boolean matrix of visible (query, key)
    pairs over the rows ``[x ; x~]``."""
    row = jnp.arange(2 * length)
    noised = row >= length
    blk = jnp.where(noised, row - length, row) // block
    return MASKS[kind](noised[:, None], noised[None, :], blk[:, None],
                       blk[None, :], row[:, None], row[None, :])


def attention(q, k, v, mask):
    """Softmax attention under the dense boolean ``mask`` [rows, rows] with
    grouped key / value heads, one query head and one block of queries at a
    time.  ``q``: [batch, rows, heads, head_dim]; ``k/v``: [batch, rows,
    kv_heads, head_dim]."""
    batch, rows, heads, head_dim = q.shape
    group = heads // k.shape[2]
    block = math.gcd(rows, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(kh, vh, piece):
        qb, keep = piece                       # [batch, block, dim], [block, rows]
        scores = jnp.einsum("bqd,bkd->bqk", qb, kh) / math.sqrt(head_dim)
        scores = jnp.where(keep[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), vh)

    @jax.checkpoint
    def one_head(head):
        qh = jnp.take(q, head, axis=2)                     # [batch, rows, dim]
        kh = jnp.take(k, head // group, axis=2)
        vh = jnp.take(v, head // group, axis=2)
        blocks = jnp.moveaxis(qh.reshape(batch, rows // block, block,
                                         head_dim), 1, 0)
        out = jax.lax.map(functools.partial(one_block, kh, vh),
                          (blocks, mask.reshape(rows // block, block, rows)))
        return jnp.moveaxis(out, 0, 1).reshape(batch, rows, head_dim)

    return jnp.moveaxis(jax.lax.map(one_head, jnp.arange(heads)), 0, 2)


def moe(m, p, hyper):
    """The held experts' part of the expert layer on ``m`` [tokens, d]."""
    held = p["expert_wi"].shape[0]
    probs = jax.nn.softmax(m @ p["router"]["kernel"], axis=-1)   # [tokens, 128]
    top, chosen = top_k_by_argmax(probs, hyper["experts_per_token"])
    weights = top / jnp.sum(top, axis=-1, keepdims=True)   # norm_topk_prob
    local = chosen - hyper["first_expert"]                 # [tokens, k]
    # one_hot of an id outside 0 .. held-1 is a zero row: a winner another
    # rank holds adds nothing here
    combine = jnp.einsum("tk,tke->te", weights,
                         jax.nn.one_hot(local, held, dtype=jnp.float32))

    @jax.checkpoint
    def expert_part(expert):
        w_up, w_gate, w_down, weight = expert
        hidden = jax.nn.silu(m @ w_gate) * (m @ w_up)
        return weight[:, None] * (hidden @ w_down)

    # the running sum is added OUTSIDE the checkpointed function: it enters
    # linearly, and as one of its inputs a copy of it would be kept for
    # every expert (16 x 67 MB a layer)
    out, _ = jax.lax.scan(
        lambda out, expert: (out + expert_part(expert), None),
        jnp.zeros_like(m),
        (p["expert_wi"], p["expert_wg"], p["expert_wo"], combine.T))
    return out


def rotate(x, hyper):
    """RoPE on ``x`` [batch, 2 L, heads, head_dim]: each half at positions
    ``0 .. L - 1``."""
    if not hyper["restart_positions"]:         # fault: rows at 0 .. 2 L - 1
        return rope(x, hyper["rope_theta"])
    length = x.shape[1] // 2
    return jnp.concatenate([rope(x[:, :length], hyper["rope_theta"]),
                            rope(x[:, length:], hyper["rope_theta"])], axis=1)


def attend(x, p, mask, hyper):
    """The attention sub-layer with its residual: ``x + W_o Attn(...)``."""
    batch, rows, d = x.shape
    eps, attn = hyper["rms_norm_eps"], p["attn"]
    h = rms_norm(x, p["attn_norm"]["scale"], eps)

    def project(name):
        # the program's kernel is [d, heads, head_dim]
        kernel = attn[name]["kernel"]
        return (h @ kernel.reshape(d, -1)).reshape(batch, rows,
                                                   *kernel.shape[1:])

    # the norm is over a head's lanes, its one scale broadcast over the heads
    q = rotate(rms_norm(project("q"), attn["q_norm"]["scale"], eps), hyper)
    k = rotate(rms_norm(project("k"), attn["k_norm"]["scale"], eps), hyper)
    o = attention(q, k, project("v"), mask)
    return x + o.reshape(batch, rows, -1) @ attn["o"]["kernel"].reshape(-1, d)


def block(x, p, mask, hyper):
    batch, rows, d = x.shape
    x1 = attend(x, p, mask, hyper)
    m = rms_norm(x1, p["mlp_norm"]["scale"], hyper["rms_norm_eps"])
    out = moe(m.reshape(batch * rows, d), p["mlp"], hyper)
    return x1 + out.reshape(batch, rows, d)


def stack_layers(params: dict, layers: int) -> dict:
    """The program's tree with its ``block_<i>`` sub-trees stacked leaf by
    leaf under ``blocks`` ([layers, ...]): the form the equations below
    scan over, so that the compiled reference holds ONE layer's code."""
    rest = {name: tree for name, tree in params.items()
            if not name.startswith("block_")}
    return {**rest, "blocks": jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *(params[f"block_{i}"] for i in range(layers)))}


def unstack_layers(stacked: dict) -> dict:
    """:func:`stack_layers` undone (parameters or their gradients)."""
    blocks = stacked["blocks"]
    layers = jax.tree.leaves(blocks)[0].shape[0]
    rest = {name: tree for name, tree in stacked.items() if name != "blocks"}
    return {**rest, **{f"block_{i}": jax.tree.map(lambda leaf: leaf[i], blocks)
                       for i in range(layers)}}


def noised_states(stacked: dict, tokens, masked, hyper: dict):
    """Final-norm hidden states of the NOISED half, [batch, L, d], from the
    :func:`stack_layers` form of the parameters."""
    length = tokens.shape[1]
    noised = jnp.where(masked, hyper["mask_id"], tokens)
    x = stacked["embed"]["embedding"][
        jnp.concatenate([tokens, noised], axis=1)]
    mask = dense_mask(length, hyper["block"], hyper["mask"])
    # a layer's activations are alive only while its own backward runs
    layer = jax.checkpoint(functools.partial(block, hyper=hyper))
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p, mask), None), x,
                        stacked["blocks"])
    return rms_norm(x[:, length:], stacked["final_norm"]["scale"],
                    hyper["rms_norm_eps"])


def logits_fn(params: dict, tokens, masked, hyper: dict):
    """[batch, L, vocab] logits of the noised rows, from the program's tree
    (tests)."""
    return noised_states(stack_layers(params, hyper["layers"]), tokens,
                         masked, hyper) @ params["lm_head"]["kernel"]


def loss_fn(params: dict, batch: dict, hyper: dict) -> jax.Array:
    """The masked, 1 / t-weighted cross-entropy of ``batch`` = {tokens [b,
    L], masked [b, L] bool, t [b, L / B]} at the program's tree ``params``
    (tests: its gradient comes back in the program's layout)."""
    return stacked_loss_fn(stack_layers(params, hyper["layers"]), batch,
                           hyper)


def stacked_loss_fn(params: dict, batch: dict, hyper: dict) -> jax.Array:
    """:func:`loss_fn` at the :func:`stack_layers` form of the parameters:
    what ``replay_losses`` differentiates, so that no second copy of the
    layers is made at the published widths."""
    tokens, masked = batch["tokens"], batch["masked"]
    x = noised_states(params, tokens, masked, hyper)
    weight = masked.astype(jnp.float32)
    if hyper["weigh_by_noise"]:                # fault off: 1 / t dropped
        weight = weight / jnp.repeat(batch["t"], hyper["block"], axis=1)
    targets = tokens
    if hyper["shift"]:      # fault: row i predicts token i + 1, the last none
        targets = jnp.roll(tokens, -1, axis=1)
        weight = weight.at[:, -1].set(0.0)
    head = params["lm_head"]["kernel"]
    rows = x.reshape(-1, x.shape[-1])
    chunk = math.gcd(rows.shape[0], HEAD_CHUNK)

    @jax.checkpoint
    def chunk_loss(piece):
        xs, ts, ws = piece
        logp = jax.nn.log_softmax(xs @ head, axis=-1)
        return -jnp.sum(ws * jnp.take_along_axis(logp, ts[:, None],
                                                 axis=-1)[:, 0])

    sums = jax.lax.map(chunk_loss, (rows.reshape(-1, chunk, rows.shape[-1]),
                                    targets.reshape(-1, chunk),
                                    weight.reshape(-1, chunk)))
    return jnp.sum(sums) / rows.shape[0]


def hyperparameters(config: dict) -> dict:
    """What the equations need, from a configuration file that keeps the
    source's key names; the share from its ``deployment``, what the source
    leaves open from its ``assumed``."""
    held = int(config["num_experts"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
        "first_expert": int(config["deployment"]["expert_rank"]) * held,
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "block": int(config["assumed"]["block_length"]),
        "mask_id": int(config["assumed"]["mask_token_id"]),
        "mask": "block_diffusion",
        "restart_positions": True,
        "shift": False,
        "weigh_by_noise": True,
    }


@functools.partial(jax.jit, static_argnames=("hyper",))
def _loss_and_grads(stacked, batch, *, hyper):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(stacked_loss_fn)(stacked, batch,
                                                   dict(hyper))


@functools.partial(jax.jit, static_argnames=("layers",))
def _stacked(params, *, layers):
    return stack_layers(params, layers)


def watched(tree: dict, also: tuple = ()) -> dict:
    """``{"block_0/attn/q/kernel": leaf, ...}``: the ``WATCHED`` leaves of a
    tree in the program's layout (parameters or their gradients), and the
    leaves named in ``also``."""
    flat = {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    return {name: leaf for name, leaf in flat.items()
            if name in also
            or name.split("/")[-3:-1] in [["attn", w] for w in WATCHED]}


watched_copy = jax.jit(lambda tree: jax.tree.map(
    jnp.copy, watched(tree, CHANGE_ALSO)))


def parameter_change(before: dict, after: dict) -> dict:
    """Per leaf, ``after - before`` (two ``watched`` dicts)."""
    return {name: after[name] - before[name] for name in before}


@jax.jit
def gradient_distance(got: dict, want: dict) -> dict:
    """Per watched leaf ``|got - want| / |want|`` (Frobenius norms, float32):
    ``got`` the system's leaves, ``want`` the reference's."""
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return {name: norm(got[name] - want[name]) / norm(want[name])
            for name in want}


def replay_losses(params: dict, batch: dict, steps: int, optimizer: dict,
                  hyper: dict, round_weights=None,
                  first_gradient=None, last_change=None) -> list[float]:
    """Train ``steps`` AdamW steps on the one ``batch`` from the
    program-layout ``params`` (float32) and return the loss seen at each step
    (before its update), as floats.  ``params`` is not kept.
    ``round_weights(params) -> params`` is applied to the weights at the
    start and after every update (the probe that rounds them to a lower
    precision).  ``first_gradient(leaves)`` is handed the ``watched`` leaves
    of the first step's gradient before the update consumes it;
    ``last_change(leaves)`` the change of the ``watched`` and
    ``CHANGE_ALSO`` leaves from the start (before any rounding) to after
    the last update."""
    adamw = _shared.adamw_hyperparameters(optimizer)
    start = watched_copy(params) if last_change is not None else None
    params = _stacked(params, layers=hyper["layers"])
    if round_weights is not None:
        params = round_weights(params)
    moments = None
    batch = {"tokens": jnp.asarray(batch["tokens"]),
             "masked": jnp.asarray(batch["masked"]),
             "t": jnp.asarray(batch["t"], jnp.float32)}
    frozen = tuple(sorted(hyper.items()))
    losses = []
    for step in range(1, steps + 1):
        loss, grads = _loss_and_grads(params, batch, hyper=frozen)
        losses.append(loss)
        if step == 1 and first_gradient is not None:
            first_gradient(watched(unstack_layers(grads)))
        if moments is None:
            moments = (jax.tree.map(jnp.zeros_like, params),
                       jax.tree.map(jnp.zeros_like, params))
        params, moments = _shared._adamw(
            params, moments, grads, jnp.int32(step), n_micro=1, **adamw)
        del grads
        if step < steps:
            # the moments wait on the host while the next gradient is made
            # (``reference/ouro.py``'s way): weights, gradient and a layer's
            # float32 working set are then all the chip holds
            moments = jax.device_get(moments)
        if round_weights is not None:
            params = round_weights(params)
    del moments
    if last_change is not None:
        last_change(parameter_change(
            start, watched(unstack_layers(params), CHANGE_ALSO)))
    return [float(x) for x in losses]


def agree(trainer_losses, reference_losses,
          tolerance: tuple = LOSS_TOLERANCE) -> bool:
    """Whether the two loss sequences are finite and of equal length and
    agree within ``tolerance`` at each step that has a limit (the first
    ``len(tolerance)``: a later step is reported and not held, see
    ``LOSS_TOLERANCE``)."""
    if len(trainer_losses) != len(reference_losses) or not trainer_losses:
        return False
    if not all(map(math.isfinite, [*trainer_losses, *reference_losses])):
        return False
    return all(abs(a - b) <= limit for a, b, limit in
               zip(trainer_losses, reference_losses, tolerance))


def gradients_agree(distances: dict,
                    tolerance: float = GRADIENT_TOLERANCE) -> bool:
    """Whether every watched leaf of the system's first gradient is within
    ``tolerance`` of the reference's (and there is one, and all finite)."""
    return bool(distances) and all(
        math.isfinite(d) and d <= tolerance for d in distances.values())


def changes_agree(distances: dict,
                  tolerance: float = CHANGE_TOLERANCE) -> bool:
    """Whether every watched leaf's change over the replayed updates is
    within ``tolerance`` of the reference's (and there is one, and all
    finite: a leaf the reference did not move at all has no distance)."""
    return gradients_agree(distances, tolerance)
