"""Plain reference for the ``smallthinker`` builder: SmallThinker's decoder
block, its loss and AdamW in ``jax.numpy`` and float32, from the catalog
row's ``config`` and ``described_as`` (PowerInfer/SmallThinker-21BA3B-
Instruct).  Imports nothing from ``bagua_tpu``; no kernel, no sort, no
grouped matmul.  The pieces every decoder reference shares (RMSNorm,
rotate-half RoPE, top-k by argmax, AdamW written out, the comparison of two
loss sequences) are ``reference/olmoe.py``'s, loaded by file name.

Layer ``l`` (0-based), ``x`` [batch, seq, d] the block's input, no biases:

    r  = x W_r                          the router reads the block's INPUT (before any norm)
    (e, w) = top-6 of r, w = softmax over the six winning logits
    h  = RMSNorm(x);  q = h W_q (28 heads of 128), k = h W_k, v = h W_v (4 heads of 128)
    query head i reads key / value head i // 7
    windowed layer (sliding_window_layout[l] = 1): RoPE(theta) on q and k,
        query i sees keys j <= i with i - j < 4096
    full layer (0): NO positional encoding, keys j <= i
    x' = x + W_o . softmax(q k^T / sqrt(128)) v
    m  = RMSNorm'(x')
    out = x' + sum over the winners e HELD HERE of w_e . W_down[e]( relu(W_gate[e] m) * W_up[e] m )

then a final RMSNorm and an untied head over the held slice of the
vocabulary; the loss is the mean next-token cross-entropy over that slice.
No balance or z-loss (the config carries no coefficient).

**The share.**  The configuration is one expert-parallel rank's share of
each layer (``deployment``): of the 64 experts it holds ``held`` from
``first_expert`` on.  The router scores all 64 and ``w`` is the softmax
over all six winners; the sum runs over the winners held here only, here
and in the program alike, and that partial result goes on to the next
layer.  With ``held`` = 64 this is the whole model, and over the four
ranks the shares add up to it (``tests/test_smallthinker.py``).

How it is computed (none of it changes a number): all matrix products under
``jax.default_matmul_precision("highest")``; attention one head and one
block of ``QUERY_BLOCK`` queries at a time (28 x 8,192^2 float32 scores
never exist whole), the head's loss in sequence chunks and the held experts
one after the other, each — and each whole layer — re-computed in the
backward pass (``jax.checkpoint``): float32 weights, gradients and two
moments are 9.8 of the chip's 15.75 GiB.  The experts are a loop with a
mask: every held expert runs on every token and the router's weights (zero
for the tokens that did not choose it) combine them.
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

#: Largest |trainer loss - reference loss| accepted on the first, second and
#: third replayed step (later steps take the last).  The trainer computes
#: matrix products, attention probabilities and logits in bfloat16 from
#: float32 weights, as its configuration states; the reference is float32
#: throughout.  They differ by bfloat16 rounding averaged over 8,192 tokens,
#: by the near-ties of the top-6 that the two precisions break differently,
#: and from the second step on by what that does to an update on a loss that
#: falls by 0.7 a step.  Two readings (my chip runs, PR 34, v5e, published
#: widths; PERF.md §6): the system over thirteen seeds differs by at most
#: 0.00037 / 0.00057 / 0.00083; the reference itself with its weights
#: rounded to bfloat16 at the start and after every update (the nearest
#: precision below the float32 weights the configuration states) differs
#: from the clean reference by 0.0001 / 0.0286 / 0.0607 (a second seed:
#: 0.0001 / 0.0297 / 0.0625): the first step cannot see the rounding (its
#: limit is four times the system's own reading), the later two refuse it
#: ten times over from limits five and seven times the system's.  Of the
#: mechanism faults, five of six winners (0.0008 / 0.028 / 0.056) and SiLU
#: for ReLU (0.0003 / 0.051 / 0.101) are refused likewise; full attention on
#: a window layer (0.0002 / 0.0009 / 0.0013) and RoPE on the full layer
#: (0.0006 / 0.0016 / 0.0025) move three replayed losses by less than the
#: limits: under uniform random targets a change of the logits that is not
#: aligned with the targets averages out of the mean loss, and AdamW's first
#: update carries a gradient's signs, not its size.  What refuses those two
#: is ``GRADIENT_TOLERANCE`` below.  The FIRST step's limit has no reading
#: above it: no fault tried moves the loss at the seeded weights by more
#: than 0.0008, so 0.0015 only keeps a forward pass that is grossly wrong
#: out; the first step's comparison with a reading on either side is the
#: gradient's.
LOSS_TOLERANCE = (0.0015, 0.003, 0.006)

#: Largest relative distance ``|g_system - g_reference| / |g_reference|``
#: (Frobenius norms) accepted on any ``WATCHED`` leaf of the FIRST gradient
#: of the replay batch: the loss function the trainer's step differentiates
#: (``lm_loss_fn`` of the model as timed: bfloat16 products, the flash and
#: grouped-matmul kernels forward and backward, the cell's 8,192 tokens)
#: against this file's float32 gradient, which ``replay_losses`` computes for
#: its first update anyway.  It is the number that sees the two attention
#: kinds and the grouped heads: a gradient keeps the direction that the mean
#: loss averages away.  Two readings (my chip runs, PR 34, v5e, published
#: widths, kernels on; PERF.md §6): the system reads at most 0.033 / 0.040 /
#: 0.032 on three seeds (q and k; o and v two thirds of that), the same on
#: every layer, and 0.033 to 0.042 on the seven seeds of the cell's own runs
#: behind them; against a reference whose layer 1 attends without its window
#: it reads 0.232 on that layer's q and k (0.10 on its o and v, 0.04
#: elsewhere), against one that rotates the full layer 1.01 on layer 0's q
#: and k (0.21 to 0.23 on the layers behind), five of six winners 0.17, SiLU
#: for ReLU 0.19 (a second seed: the system 0.033, the window fault 0.230,
#: the rotated full layer 1.00, its losses inside their limits both times).
#: The limit, set after the first three seeds at 2.2 times their largest, is
#: 2.1 times the largest of all eleven and 2.6 times under the nearest fault.  Weights rounded
#: to bfloat16 read what the system reads (0.028): that fault is the
#: losses' to refuse.
GRADIENT_TOLERANCE = 0.09

#: the leaves compared: the four attention matrices of every layer (335 MB
#: at the published widths).  The routers' gradients are left out: the
#: system itself reads 0.05 to 0.09 there (the top-6's near-ties, broken
#: differently in the two precisions, move whole tokens between experts)
#: against 0.14 for the window fault; the experts' 6 GB and the two
#: vocabulary matrices are left to the losses.
WATCHED = "attn"

#: tokens per chunk of the head's cross-entropy; queries per attention block
HEAD_CHUNK = 1024
QUERY_BLOCK = 1024


def attention(q, k, v, window):
    """Causal softmax attention with grouped key / value heads, one query
    head and one block of queries at a time.  ``q``: [batch, seq, heads,
    head_dim]; ``k/v``: [batch, seq, kv_heads, head_dim]; ``window``: query
    ``i`` sees keys ``i - window < j <= i`` (None: all ``j <= i``)."""
    batch, seq, heads, head_dim = q.shape
    group = heads // k.shape[2]
    block = math.gcd(seq, QUERY_BLOCK)
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(kh, vh, piece):
        qb, start = piece                                  # [batch, block, dim]
        query_pos = start + jnp.arange(block)
        keep = key_pos[None, :] <= query_pos[:, None]
        if window is not None:
            keep &= query_pos[:, None] - key_pos[None, :] < window
        scores = jnp.einsum("bqd,bkd->bqk", qb, kh) / math.sqrt(head_dim)
        scores = jnp.where(keep[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), vh)

    @jax.checkpoint
    def one_head(head):
        qh = jnp.take(q, head, axis=2)                     # [batch, seq, dim]
        kh = jnp.take(k, head // group, axis=2)
        vh = jnp.take(v, head // group, axis=2)
        blocks = jnp.moveaxis(qh.reshape(batch, seq // block, block,
                                         head_dim), 1, 0)
        out = jax.lax.map(functools.partial(one_block, kh, vh),
                          (blocks, jnp.arange(0, seq, block)))
        return jnp.moveaxis(out, 0, 1).reshape(batch, seq, head_dim)

    return jnp.moveaxis(jax.lax.map(one_head, jnp.arange(heads)), 0, 2)


def moe(m, route_from, p, hyper):
    """The held experts' part of the expert layer: ``m`` [tokens, d] is what
    the experts read, ``route_from`` [tokens, d] what the router reads."""
    held = p["expert_wi"].shape[0]
    logits = route_from @ p["router"]["kernel"]            # [tokens, 64]
    top, chosen = top_k_by_argmax(logits, hyper["experts_per_token"])
    weights = jax.nn.softmax(top, axis=-1)                 # over the winners
    local = chosen - hyper["first_expert"]                 # [tokens, k]
    # one_hot of an id outside 0 .. held-1 is a zero row: a winner another
    # rank holds adds nothing here
    combine = jnp.einsum("tk,tke->te", weights,
                         jax.nn.one_hot(local, held, dtype=jnp.float32))

    @jax.checkpoint
    def one_expert(out, expert):
        w_up, w_gate, w_down, weight = expert
        hidden = hyper["activation"](m @ w_gate) * (m @ w_up)
        return out + weight[:, None] * (hidden @ w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (p["expert_wi"], p["expert_wg"], p["expert_wo"], combine.T))
    return out


def block(x, p, hyper, layer: int):
    batch, seq, d = x.shape
    eps, attn = hyper["rms_norm_eps"], p["attn"]
    h = rms_norm(x, p["attn_norm"]["scale"], eps)

    def project(name):
        # the program's kernel is [d, heads, head_dim]
        kernel = attn[name]["kernel"]
        return (h @ kernel.reshape(d, -1)).reshape(batch, seq,
                                                   *kernel.shape[1:])

    q, k, v = project("q"), project("k"), project("v")
    if hyper["rope_layout"][layer % len(hyper["rope_layout"])]:
        q, k = rope(q, hyper["rope_theta"]), rope(k, hyper["rope_theta"])
    windowed = hyper["window_layout"][layer % len(hyper["window_layout"])]
    o = attention(q, k, v, hyper["window"] if windowed else None)
    x1 = x + o.reshape(batch, seq, -1) @ attn["o"]["kernel"].reshape(-1, d)
    m = rms_norm(x1, p["mlp_norm"]["scale"], eps)
    out = moe(m.reshape(batch * seq, d), x.reshape(batch * seq, d), p["mlp"],
              hyper)
    return x1 + out.reshape(batch, seq, d)


def hidden_states(params: dict, inputs, hyper: dict):
    """Final-norm hidden states [batch, seq, d]."""
    x = params["embed"]["embedding"][inputs]
    for i in range(hyper["layers"]):
        # a layer's activations (a head's q, k, v; the experts' running
        # sums) are alive only while its own backward pass runs
        x = jax.checkpoint(functools.partial(block, hyper=hyper, layer=i))(
            x, params[f"block_{i}"])
    return rms_norm(x, params["final_norm"]["scale"], hyper["rms_norm_eps"])


def logits_fn(params: dict, inputs, hyper: dict):
    """[batch, seq, vocab] logits (tests and the one-sequence chip check)."""
    return hidden_states(params, inputs, hyper) @ params["lm_head"]["kernel"]


def loss_fn(params: dict, tokens, hyper: dict) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` [batch, seq + 1]."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = hidden_states(params, inputs, hyper)
    head = params["lm_head"]["kernel"]
    rows, wanted = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    chunk = math.gcd(rows.shape[0], HEAD_CHUNK)

    @jax.checkpoint
    def chunk_nll(piece):
        xs, ts = piece
        logp = jax.nn.log_softmax(xs @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, ts[:, None], axis=-1))

    nll = jax.lax.map(chunk_nll, (rows.reshape(-1, chunk, rows.shape[-1]),
                                  wanted.reshape(-1, chunk)))
    return jnp.sum(nll) / rows.shape[0]


ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def hyperparameters(config: dict) -> dict:
    """What the equations need, from a configuration file that keeps the
    source's key names; the share from its ``deployment``."""
    held = int(config["moe_num_primary_experts"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "experts_per_token": int(config["moe_num_active_primary_experts"]),
        "first_expert": int(config["deployment"]["expert_rank"]) * held,
        "rope_theta": float(config["rope_theta"]),
        "rope_layout": tuple(config["rope_layout"]),
        "window_layout": tuple(config["sliding_window_layout"]),
        "window": int(config["sliding_window_size"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "activation": ACTIVATIONS[config["assumed"]["expert_activation"]],
    }


@functools.partial(jax.jit, static_argnames=("hyper",))
def _loss_and_grads(params, tokens, *, hyper):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, dict(hyper))


def watched(tree: dict) -> dict:
    """``{"block_0/attn/q/kernel": leaf, ...}``: the ``WATCHED`` leaves of a
    tree in the program's layout (parameters or their gradients)."""
    flat = {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    return {name: leaf for name, leaf in flat.items()
            if WATCHED in name.split("/")}


@jax.jit
def gradient_distance(got: dict, want: dict) -> dict:
    """Per watched leaf ``|got - want| / |want|`` (Frobenius norms, float32):
    ``got`` the system's leaves, ``want`` the reference's."""
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return {name: norm(got[name] - want[name]) / norm(want[name])
            for name in want}


def replay_losses(params: dict, tokens, steps: int, optimizer: dict,
                  micro_batch: int, hyper: dict, round_weights=None,
                  first_gradient=None) -> list[float]:
    """Train ``steps`` AdamW steps on the one batch ``tokens`` from the
    program-layout ``params`` (float32) and return the loss seen at each step
    (before its update), as floats.  ``params`` is not kept.
    ``round_weights(params) -> params`` is applied to the weights at the
    start and after every update (the probe that rounds them to a lower
    precision).  ``first_gradient(leaves)`` is handed the ``watched`` leaves
    of the first step's gradient (the mean over the micro-batches) before
    the update consumes it."""
    adamw = _shared.adamw_hyperparameters(optimizer)
    batch = tokens.shape[0]
    if batch % micro_batch:
        raise ValueError(f"batch {batch} is not a multiple of the "
                         f"micro-batch {micro_batch}")
    n_micro = batch // micro_batch
    if round_weights is not None:
        params = round_weights(params)
    zeros = functools.partial(jax.tree.map, jnp.zeros_like)
    moments = (zeros(params), zeros(params))
    tokens = jnp.asarray(tokens)
    frozen = tuple(sorted(hyper.items()))
    losses = []
    for step in range(1, steps + 1):
        loss_sum = grad_sum = None
        for i in range(n_micro):
            loss, grads = _loss_and_grads(
                params, tokens[i * micro_batch:(i + 1) * micro_batch],
                hyper=frozen)
            if grad_sum is None:  # the first micro-batch's are the sum
                loss_sum, grad_sum = loss, grads
            else:
                loss_sum = loss_sum + loss
                grad_sum = _shared._add(grad_sum, grads)
        losses.append(loss_sum / n_micro)
        if step == 1 and first_gradient is not None:
            first_gradient({name: g / n_micro
                            for name, g in watched(grad_sum).items()})
        params, moments = _shared._adamw(
            params, moments, grad_sum, jnp.int32(step), n_micro=n_micro,
            **adamw)
        if round_weights is not None:
            params = round_weights(params)
    return [float(x) for x in losses]


def agree(trainer_losses, reference_losses,
          tolerance: tuple = LOSS_TOLERANCE) -> bool:
    """Whether the two loss sequences agree within the step's ``tolerance``
    at every step (and are finite and of equal length)."""
    return _shared.agree(trainer_losses, reference_losses, tolerance)


def gradients_agree(distances: dict,
                    tolerance: float = GRADIENT_TOLERANCE) -> bool:
    """Whether every watched leaf of the system's first gradient is within
    ``tolerance`` of the reference's (and there is one, and all finite)."""
    return bool(distances) and all(
        math.isfinite(d) and d <= tolerance for d in distances.values())
