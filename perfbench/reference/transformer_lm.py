"""Plain reference for the ``transformer_lm`` builder: the same equations as
``bagua_tpu.models.transformer.TransformerLM`` + ``lm_loss_fn`` + AdamW,
written out in ``jax.numpy`` and float32.  Imports nothing from ``bagua_tpu``.

The model (every departure from the published BERT / GPT-2 architectures is
the program's own and is listed in the configuration files): token table +
learned absolute positions; per block ``x += Attn(RMSNorm(x))`` then
``x += W_o (silu(W_g y) * (W_u y))`` with ``y = RMSNorm(x)``; causal
multi-head attention, scores scaled by ``1/sqrt(head_dim)``; a final RMSNorm
and an untied head; no biases; RMSNorm ``eps = 1e-6``.  The loss is the mean
next-token cross-entropy.  AdamW is optax's: bias-corrected moments,
``eps`` outside the square root, decoupled weight decay on every leaf.

All matrix products run under ``jax.default_matmul_precision("highest")``:
on a TPU a float32 product otherwise runs in bfloat16 passes.  Gradients
accumulate over micro-batches of a few sequences, which equals the
full-batch mean (every sequence has the same number of targets) and keeps
the memory under the trainer's.  The blocks are stacked and scanned, and the
block is re-computed in the backward pass (``jax.checkpoint``): neither
changes a number, both keep compile time and memory small.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6

#: Largest |trainer loss - reference loss| accepted on each of the replayed
#: steps.  The trainer computes every matrix product, the attention
#: probabilities and the logits in bfloat16 (8 bits of mantissa) as its
#: configuration states, the reference in float32, so they cannot agree
#: closer than bfloat16 rounding averaged over a batch.  Measured on the
#: v5e (my chip runs, PR 23): at most 0.0030 over three steps in 33 runs of
#: the three cells, at losses of 8.5 to 11.3 that fall by about 1.0 a step;
#: the tolerance is three times that.  Three losses are compared because
#: the first alone cannot see a fault in the gradients or the optimizer.
#: Probed on the CPU at tiny widths (perfbench/tests/test_reference.py): a
#: dropped position table, a missing bias correction and a wrong update
#: rule each move a loss by more than this; bfloat16 compute as configured
#: does not.  It cannot see weight decay (parts in 10^8 over three steps),
#: and whether it sees a bfloat16 gradient exchange on the chip has not
#: been tried (PERF.md, Open questions).
LOSS_TOLERANCE = 0.01


def rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + RMS_EPS) * scale


def attention(q, k, v):
    """Causal softmax attention; ``q/k/v``: [batch, seq, heads, head_dim]."""
    seq, head_dim = q.shape[1], q.shape[3]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(head_dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def block(x, p):
    y = rms_norm(x, p["attn_norm"]["scale"])
    q = jnp.einsum("bsd,dhk->bshk", y, p["attn"]["q"]["kernel"])
    k = jnp.einsum("bsd,dhk->bshk", y, p["attn"]["k"]["kernel"])
    v = jnp.einsum("bsd,dhk->bshk", y, p["attn"]["v"]["kernel"])
    x = x + jnp.einsum("bshk,hkd->bsd", attention(q, k, v),
                       p["attn"]["o"]["kernel"])
    y = rms_norm(x, p["mlp_norm"]["scale"])
    gated = jax.nn.silu(y @ p["mlp"]["wi_gate"]["kernel"]) * (
        y @ p["mlp"]["wi_up"]["kernel"])
    return x + gated @ p["mlp"]["wo"]["kernel"]


def stack_blocks(params: dict) -> dict:
    """The program's ``block_0 .. block_{L-1}`` subtrees stacked on a leading
    layer axis under ``"blocks"`` (float32), everything else unchanged."""
    names = sorted((k for k in params if k.startswith("block_")),
                   key=lambda k: int(k.split("_")[1]))
    out = {k: v for k, v in params.items() if not k.startswith("block_")}
    out["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                 *[params[k] for k in names])
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), out)


def loss_fn(params: dict, tokens) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` [batch, seq + 1] under the
    stacked ``params``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    seq = inputs.shape[1]
    x = params["embed"]["embedding"][inputs] + params["pos_embed"][:seq][None]

    def body(x, p):
        return jax.checkpoint(block)(x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"]["scale"])
    logits = x @ params["lm_head"]["kernel"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


@functools.partial(jax.jit, donate_argnums=(1,))
def _accumulate(params, acc, tokens):
    """Add one micro-batch's loss and gradients to the running sums."""
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
    loss_sum, grad_sum = acc
    return loss_sum + loss, jax.tree.map(jnp.add, grad_sum, grads)


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("n_micro", "lr", "b1", "b2", "eps",
                                    "weight_decay"))
def _adamw(params, moments, grad_sum, step, *, n_micro, lr, b1, b2, eps,
           weight_decay):
    """One AdamW update from the summed micro-batch gradients; ``step`` is
    the 1-based update count."""
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
                  micro_batch: int) -> list[float]:
    """Train ``steps`` AdamW steps on the one batch ``tokens`` from the
    program-layout ``params`` and return the loss seen at each step (before
    its update), as floats.  ``params`` is not kept."""
    hyper = adamw_hyperparameters(optimizer)
    batch = tokens.shape[0]
    if batch % micro_batch:
        raise ValueError(f"batch {batch} is not a multiple of the "
                         f"micro-batch {micro_batch}")
    n_micro = batch // micro_batch
    params = jax.jit(stack_blocks)(params)  # the unstacked copy is dropped
    zeros = functools.partial(jax.tree.map, jnp.zeros_like)
    moments = (zeros(params), zeros(params))
    tokens = jnp.asarray(tokens)
    losses = []
    for step in range(1, steps + 1):
        acc = (jnp.zeros((), jnp.float32), zeros(params))
        for i in range(n_micro):
            acc = _accumulate(
                params, acc, tokens[i * micro_batch:(i + 1) * micro_batch])
        loss_sum, grad_sum = acc
        losses.append(loss_sum / n_micro)
        params, moments = _adamw(params, moments, grad_sum, jnp.int32(step),
                                 n_micro=n_micro, **hyper)
    return [float(x) for x in losses]


def agree(trainer_losses, reference_losses,
          tolerance: float = LOSS_TOLERANCE) -> bool:
    """Whether the two loss sequences agree within ``tolerance`` at every
    step (and are finite and of equal length)."""
    if len(trainer_losses) != len(reference_losses) or not trainer_losses:
        return False
    return all(
        math.isfinite(a) and math.isfinite(b) and abs(a - b) <= tolerance
        for a, b in zip(trainer_losses, reference_losses))
