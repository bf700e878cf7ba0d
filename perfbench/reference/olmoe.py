"""Plain reference for the ``olmoe`` builder: OLMoE's decoder block, its loss
and AdamW written out in ``jax.numpy`` and float32, from the published
description (HF ``modeling_olmoe.py``, arXiv:2409.02060).  Imports nothing
from ``bagua_tpu``; no kernel, no sort, no grouped matmul.

One layer, ``x`` [batch, seq, d] the token embeddings (no position table):

    y  = RMSNorm(x);  q = RMSNorm_q(W_q y), k = RMSNorm_k(W_k y)   norm over all d, before the head split
    v  = W_v y;  q, k <- RoPE(theta), rotate-half, every lane of a head
    h  = x + W_o . causal_softmax(q k^T / sqrt(head_dim)) v
    z  = RMSNorm(h);  p = softmax(W_r z) over the experts, float32
    (g, e) = top-k(p), NOT renormalised (``norm_topk_prob: false``)
    out = h + sum_j g_j . W_down[e_j]( silu(W_gate[e_j] z) * W_up[e_j] z )

then a final RMSNorm and an untied head; no biases.  The loss is the mean
next-token cross-entropy plus ``aux_coef`` times the sum over layers of HF's
``load_balancing_loss_func``: ``E . sum_i (assignments on expert i / tokens)
. mean_t p[t, i]``, all top-k assignments counted.

Departures from the published model, each also in the configuration's
``departures``:

* the router z-loss of OLMoE's training recipe (``router_z_loss_coef``
  0.001 in the paper, absent from the HF class's loss) is left out;
* the balance loss is taken per layer over the tokens of one micro-batch and
  the layers' values are summed (HF concatenates the layers' tokens, which
  for one layer is the same number);
* RMSNorm multiplies by its scale in float32 before casting back (HF casts
  first): the same number in float32.

How it is computed (none of it changes a number): all matrix products under
``jax.default_matmul_precision("highest")`` (a TPU otherwise runs a float32
product in bfloat16 passes); attention one head at a time, the head's loss
in sequence chunks and the experts one after the other, each re-computed in
the backward pass (``jax.checkpoint``), so that the [seq, seq] scores, the
[seq, vocab] logits and 64 experts' activations are never all alive.  The
experts are a loop with a mask: every expert runs on every token and the
router's weights (zero for the tokens that did not choose it) combine them.
Top-k is k rounds of argmax.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: Largest |trainer loss - reference loss| accepted on the first, second and
#: third replayed step (later steps take the last).  The trainer computes
#: matrix products, attention probabilities and logits in bfloat16 from
#: float32 weights, as its configuration states; the reference is float32
#: throughout.  They differ by bfloat16 rounding averaged over 8,192 tokens,
#: by the near-ties of the top-8 that the two precisions break differently
#: (a token whose 8th and 9th experts swap), and from the second step on by
#: what that does to an update on a loss that falls by 1.5 to 1.9 a step:
#: the difference grows about 2.3 x a step, so one number for all three
#: would be loose on the first step or tight on the third.  Two readings
#: (my chip runs, PR 28, v5e, published widths; PERF.md §6): the system over
#: thirteen seeds differs by at most 0.00112 / 0.0045 / 0.0122 (root mean
#: square 0.0005 / 0.0023 / 0.0060), and each limit is about 2.5 times the
#: largest, five times the root mean square; the reference itself with its
#: weights rounded to bfloat16 at the start and after every update (the
#: nearest precision below the float32 weights the configuration states)
#: differs from the clean reference, over three seeds, by 0.0001-0.0003 /
#: 0.128-0.130 / 0.189-0.207, and with one expert a token dropped (7 of 64)
#: by 0.0066-0.0125 / 0.0123-0.0212 / 0.0373-0.0656: neither agrees on any
#: seed (the first step cannot see the rounding, the later two refuse it).
LOSS_TOLERANCE = (0.003, 0.011, 0.03)

#: tokens per chunk of the head's cross-entropy
HEAD_CHUNK = 1024


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """Rotate-half rotary embedding; ``x``: [batch, seq, heads, head_dim] at
    positions 0 .. seq-1."""
    seq, dim = x.shape[1], x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.tile(jnp.cos(angles), (1, 2))[None, :, None, :]
    sin = jnp.tile(jnp.sin(angles), (1, 2))[None, :, None, :]
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def attention(q, k, v):
    """Causal softmax attention, one head at a time; ``q/k/v``:
    [batch, seq, heads, head_dim]."""
    seq, head_dim = q.shape[1], q.shape[3]
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv                                   # [batch, seq, dim]
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) / math.sqrt(head_dim)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), vh)

    heads_first = lambda t: jnp.moveaxis(t, 2, 0)          # [heads, b, s, dim]
    out = jax.lax.map(one_head, (heads_first(q), heads_first(k),
                                 heads_first(v)))
    return jnp.moveaxis(out, 0, 2)


def top_k_by_argmax(probs, k):
    """(values, indices) [tokens, k] of the k largest of each row, by k
    rounds of argmax (the lowest index wins a tie)."""
    values, indices = [], []
    rest = probs
    for _ in range(k):
        idx = jnp.argmax(rest, axis=-1)
        values.append(jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0])
        indices.append(idx)
        rest = jnp.where(jax.nn.one_hot(idx, probs.shape[-1], dtype=bool),
                         -jnp.inf, rest)
    return jnp.stack(values, axis=-1), jnp.stack(indices, axis=-1)


def moe(z, p, hyper):
    """The expert layer on ``z`` [tokens, d] -> ([tokens, d], balance loss)."""
    n_experts, k = p["expert_wi"].shape[0], hyper["experts_per_token"]
    probs = jax.nn.softmax(z @ p["router"]["kernel"], axis=-1)
    gates, chosen = top_k_by_argmax(probs, k)
    if hyper["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32)  # [t, k, E]
    combine = jnp.einsum("tk,tke->te", gates, picked)
    assignments_per_token = picked.sum(axis=(0, 1)) / z.shape[0]   # [E]
    balance = n_experts * jnp.sum(assignments_per_token * probs.mean(axis=0))

    @jax.checkpoint
    def one_expert(out, expert):
        w_up, w_gate, w_down, weight = expert
        hidden = jax.nn.silu(z @ w_gate) * (z @ w_up)
        return out + weight[:, None] * (hidden @ w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(z),
        (p["expert_wi"], p["expert_wg"], p["expert_wo"], combine.T))
    return out, balance


def block(x, p, hyper):
    batch, seq, d = x.shape
    eps, attn = hyper["rms_norm_eps"], p["attn"]
    y = rms_norm(x, p["attn_norm"]["scale"], eps)
    heads = attn["q"]["kernel"].shape[1]

    def project(name):
        # the program's kernel is [d, heads, head_dim]: a [d, d] matrix
        return y @ attn[name]["kernel"].reshape(d, d)

    q = rms_norm(project("q"), attn["q_norm"]["scale"], eps)
    k = rms_norm(project("k"), attn["k_norm"]["scale"], eps)
    split = lambda t: t.reshape(batch, seq, heads, d // heads)
    q, k = rope(split(q), hyper["rope_theta"]), rope(split(k),
                                                     hyper["rope_theta"])
    o = attention(q, k, split(project("v"))).reshape(batch, seq, d)
    h = x + o @ attn["o"]["kernel"].reshape(d, d)
    z = rms_norm(h, p["mlp_norm"]["scale"], eps)
    out, balance = moe(z.reshape(batch * seq, d), p["mlp"], hyper)
    return h + out.reshape(batch, seq, d), balance


def hidden_states(params: dict, inputs, hyper: dict):
    """Final-norm hidden states [batch, seq, d] and the summed balance loss."""
    x = params["embed"]["embedding"][inputs]
    balance = jnp.zeros((), jnp.float32)
    for i in range(hyper["layers"]):
        x, layer_balance = block(x, params[f"block_{i}"], hyper)
        balance = balance + layer_balance
    return rms_norm(x, params["final_norm"]["scale"],
                    hyper["rms_norm_eps"]), balance


def logits_fn(params: dict, inputs, hyper: dict):
    """[batch, seq, vocab] logits (tests and the one-sequence chip check)."""
    x, _ = hidden_states(params, inputs, hyper)
    return x @ params["lm_head"]["kernel"]


def loss_fn(params: dict, tokens, hyper: dict) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` [batch, seq + 1] plus the
    weighted balance loss."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, balance = hidden_states(params, inputs, hyper)
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
    return jnp.sum(nll) / rows.shape[0] + hyper["aux_coef"] * balance


def hyperparameters(config: dict) -> dict:
    """What the equations need, from a configuration file that keeps the
    source's key names."""
    return {
        "layers": int(config["num_hidden_layers"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "aux_coef": float(config["assumed"]["router_aux_loss_coef"]),
    }


def _frozen(hyper: dict) -> tuple:
    return tuple(sorted(hyper.items()))


@functools.partial(jax.jit, static_argnames=("hyper",))
def _loss_and_grads(params, tokens, *, hyper):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, dict(hyper))


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, new):
    return jax.tree.map(jnp.add, acc, new)


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("n_micro", "lr", "b1", "b2", "eps",
                                    "weight_decay"))
def _adamw(params, moments, grad_sum, step, *, n_micro, lr, b1, b2, eps,
           weight_decay):
    """One AdamW update (optax's: bias-corrected moments, ``eps`` outside the
    square root, decoupled weight decay on every leaf) from the summed
    micro-batch gradients; ``step`` is the 1-based update count."""
    mu, nu = moments
    grads = jax.tree.map(lambda g: g / n_micro, grad_sum)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    t = step.astype(jnp.float32)

    def update(p, m, n):
        m_hat = m / (1 - b1 ** t)
        n_hat = n / (1 - b2 ** t)
        return p - lr * (m_hat / (jnp.sqrt(n_hat) + eps) + weight_decay * p)

    return jax.tree.map(update, params, mu, nu), (mu, nu)


def adamw_hyperparameters(optimizer: dict) -> dict:
    """optax.adamw's arguments with its defaults, from a traffic file's
    ``optimizer`` entry."""
    if optimizer.get("name") != "adamw":
        raise NotImplementedError(
            f"the reference writes out adamw only, not {optimizer.get('name')!r}")
    kw = dict(optimizer.get("kwargs", {}))
    out = {
        "lr": float(kw.pop("learning_rate")),
        "b1": float(kw.pop("b1", 0.9)),
        "b2": float(kw.pop("b2", 0.999)),
        "eps": float(kw.pop("eps", 1e-8)),
        "weight_decay": float(kw.pop("weight_decay", 1e-4)),
    }
    if kw:
        raise NotImplementedError(f"adamw arguments not written out: {sorted(kw)}")
    return out


def replay_losses(params: dict, tokens, steps: int, optimizer: dict,
                  micro_batch: int, hyper: dict, after_update=None) -> list[float]:
    """Train ``steps`` AdamW steps on the one batch ``tokens`` from the
    program-layout ``params`` (float32) and return the loss seen at each step
    (before its update), as floats.  ``params`` is not kept.
    ``after_update(params) -> params`` is applied after every update (the
    probe that rounds the weights to a lower precision)."""
    adamw = adamw_hyperparameters(optimizer)
    batch = tokens.shape[0]
    if batch % micro_batch:
        raise ValueError(f"batch {batch} is not a multiple of the "
                         f"micro-batch {micro_batch}")
    n_micro = batch // micro_batch
    zeros = functools.partial(jax.tree.map, jnp.zeros_like)
    moments = (zeros(params), zeros(params))
    tokens = jnp.asarray(tokens)
    losses = []
    for step in range(1, steps + 1):
        loss_sum = grad_sum = None
        for i in range(n_micro):
            loss, grads = _loss_and_grads(
                params, tokens[i * micro_batch:(i + 1) * micro_batch],
                hyper=_frozen(hyper))
            if grad_sum is None:  # the first micro-batch's are the sum
                loss_sum, grad_sum = loss, grads
            else:
                loss_sum, grad_sum = loss_sum + loss, _add(grad_sum, grads)
        losses.append(loss_sum / n_micro)
        params, moments = _adamw(params, moments, grad_sum, jnp.int32(step),
                                 n_micro=n_micro, **adamw)
        if after_update is not None:
            params = after_update(params)
    return [float(x) for x in losses]


def agree(trainer_losses, reference_losses,
          tolerance: tuple = LOSS_TOLERANCE) -> bool:
    """Whether the two loss sequences agree within the step's ``tolerance``
    at every step (and are finite and of equal length)."""
    if len(trainer_losses) != len(reference_losses) or not trainer_losses:
        return False
    limits = list(tolerance) + [tolerance[-1]] * len(trainer_losses)
    return all(
        math.isfinite(a) and math.isfinite(b) and abs(a - b) <= limit
        for a, b, limit in zip(trainer_losses, reference_losses, limits))
