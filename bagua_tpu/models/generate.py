"""Autoregressive generation with a KV cache (inference path).

Additive — the reference is a training accelerator with no serving story;
a complete LM framework needs one.  TPU-idiomatic formulation: the whole
generation loop is ONE jitted program — a ``lax.scan`` over decode steps,
each consuming one token against the flax ``"cache"`` collection that
:class:`~bagua_tpu.models.transformer.TransformerLM` maintains in decode
mode (``TransformerConfig(decode=True)``).  Static shapes throughout: the
cache is pre-allocated at ``max_seq_len`` and the scan length is
``prompt_len + max_new_tokens - 1``, so one compile serves a fixed
(batch, prompt_len, max_new) signature.

Sampling: greedy at ``temperature=0`` (exact continuation of the argmax
chain), else temperature-scaled categorical.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["decode_model", "generate", "generate_tp",
           "clear_generate_cache", "clear_tp_generate_cache"]


def decode_model(model):
    """The decode-mode twin of a ``TransformerLM`` (same params, KV-cached
    single-token attention; ``attn_fn`` is unused in decode)."""
    cfg = dataclasses.replace(model.cfg, decode=True)
    return type(model)(cfg, attn_fn=None, mlp_factory=model.mlp_factory,
                       head=model.head)


def _generate_core(model, params, prompt, max_new_tokens, rng, temperature):
    b, prompt_len = prompt.shape
    cache = model.init(
        jax.random.PRNGKey(0), jnp.zeros((b, 1), jnp.int32)
    )["cache"]

    def step(carry, inputs):
        cache, feed = carry  # feed: [b] token consumed this step
        key, forced, forced_tok = inputs
        logits, mutated = model.apply(
            {"params": params, "cache": cache},
            feed[:, None], mutable=["cache"],
        )
        logits = logits[:, 0]  # [b, vocab]
        sampled = jnp.where(
            temperature > 0.0,
            jax.random.categorical(key, logits / jnp.maximum(temperature, 1e-6)),
            jnp.argmax(logits, axis=-1),
        ).astype(prompt.dtype)
        # while still inside the prompt, the "next token" is forced
        nxt = jnp.where(forced, forced_tok, sampled)
        return (mutated["cache"], nxt), nxt

    n_steps = prompt_len + max_new_tokens - 1
    keys = jax.random.split(rng, n_steps)
    # step i feeds token i; for i < prompt_len - 1 the output is forced to
    # prompt[i + 1] (teacher forcing through the prompt)
    forced = jnp.arange(n_steps) < (prompt_len - 1)
    forced_tok = jnp.concatenate(
        [prompt[:, 1:], jnp.zeros((b, max_new_tokens), prompt.dtype)], axis=1
    ).T  # [n_steps, b]
    (_, _), toks = jax.lax.scan(
        step, (cache, prompt[:, 0]), (keys, forced, forced_tok),
    )
    # toks[i] = token fed at step i+1; the generated continuation is the
    # last max_new_tokens of them
    return toks[prompt_len - 1:].T  # [b, max_new_tokens]


# Bounded LRU of compiled decode programs, keyed by the generate signature
# (model config, batch, prompt_len, max_new_tokens) — the same discipline
# as the tp cache below: long-lived serving processes that vary batch
# shapes or budgets must not accumulate executables forever, and a bare
# `jax.jit` module global could never free them.  Evictions just recompile.
_GEN_CACHE_MAX = 8
_GEN_CACHE: "dict" = {}  # insertion-ordered; move-to-end on hit

# Same policy for the tensor-parallel decode programs (these additionally
# pin their mesh/device objects).  8 distinct (model, mesh, budget,
# sharding) signatures cover realistic serving; evictions just recompile.
_TP_GEN_CACHE_MAX = 8
_TP_GEN_CACHE: "dict" = {}  # insertion-ordered; move-to-end on hit


def clear_generate_cache() -> None:
    """Drop every compiled single-host decode program (frees the
    executables); the next :func:`generate` call recompiles."""
    _GEN_CACHE.clear()


def clear_tp_generate_cache() -> None:
    """Drop every compiled tensor-parallel decode program (frees the
    executables and releases their mesh references)."""
    _TP_GEN_CACHE.clear()


def generate(
    model,
    params,
    prompt: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
):
    """Generate ``max_new_tokens`` continuations of ``prompt``.

    Args:
        model: a ``TransformerLM`` in decode mode (``decode_model(m)``), or
            a training-mode model (converted automatically).
        params: the trained params (training and decode modes share them).
        prompt: int32 ``[batch, prompt_len]``, ``prompt_len >= 1``;
            ``prompt_len + max_new_tokens`` must fit ``cfg.max_seq_len``.
        temperature: 0 = greedy, else categorical at the given temperature.
        rng: PRNG key (required only for temperature > 0).

    Returns:
        int32 ``[batch, max_new_tokens]``.
    """
    if not model.cfg.decode:
        model = decode_model(model)
    b, prompt_len = prompt.shape
    total = prompt_len + max_new_tokens
    if total > model.cfg.max_seq_len:
        raise ValueError(
            f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
            f"exceeds max_seq_len {model.cfg.max_seq_len}"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    # memoized per (model, batch, prompt_len, max_new) signature, exactly
    # like generate_tp: repeated calls reuse the compiled scan instead of
    # re-dispatching through a fresh trace, and the LRU bounds the
    # executables a long-lived serving process can accumulate
    from ..utils import lru_get_or_build

    n = int(max_new_tokens)

    def build():
        def run(params, prompt, rng, temperature, _model=model, _n=n):
            return _generate_core(_model, params, prompt, _n, rng,
                                  temperature)

        return jax.jit(run)

    fn = lru_get_or_build(_GEN_CACHE, _GEN_CACHE_MAX,
                          (model, b, prompt_len, n), build)
    return fn(params, prompt, rng, jnp.float32(temperature))


def generate_tp(
    model,
    params,
    prompt: jax.Array,
    max_new_tokens: int,
    mesh,
    tp_axis: str = "tp",
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    tp_param_dim=None,
):
    """Tensor-parallel generation: the decode loop runs under ``shard_map``
    over ``tp_axis``, with attention heads / FFN width sharded exactly as in
    training (the model's conjugate collectives reduce the per-shard
    partials, so logits — and therefore samples — are identical on every
    shard).  ``params`` are the GLOBAL arrays (as held by a
    ``BaguaTrainer(tp_axis=...)`` state); ``tp_param_dim`` maps param name →
    sharded dim (default: the transformer family's table).

    ``mesh`` may carry extra (replication) axes besides ``tp_axis`` — on
    the CPU-simulation platform prefer a mesh spanning ALL devices (e.g.
    ``build_mesh({"rep": 4, "tp": 2})``): XLA's in-process communicator can
    wedge on collectives over a device subset after full-device work ran
    in the same process.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..tensor import _name_of_path

    if not model.cfg.decode:
        model = decode_model(model)
    if model.cfg.tp_axis != tp_axis or model.cfg.tp_size <= 1:
        raise ValueError(
            f"model config must carry tp_axis={tp_axis!r} with tp_size > 1 "
            f"(got tp_axis={model.cfg.tp_axis!r}, tp_size={model.cfg.tp_size})"
        )
    b, prompt_len = prompt.shape
    if prompt_len + max_new_tokens > model.cfg.max_seq_len:
        raise ValueError(
            f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
            f"exceeds max_seq_len {model.cfg.max_seq_len}"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if mesh.shape[tp_axis] != model.cfg.tp_size:
        raise ValueError(
            f"mesh axis {tp_axis!r} has size {mesh.shape[tp_axis]} but the "
            f"model config says tp_size={model.cfg.tp_size}"
        )
    if tp_param_dim is None:
        from .transformer import tp_param_dim as _default_dim

        tp_param_dim = _default_dim

    def leaf_spec(path, leaf):
        d = tp_param_dim(_name_of_path(path))
        return P() if d is None else P(*([None] * d + [tp_axis]))

    pspecs = jax.tree_util.tree_map_with_path(leaf_spec, params)
    # params may live on a different (e.g. training dp) mesh — lay them out
    # on THIS mesh with their tp shardings before entering the jit
    from jax.sharding import NamedSharding

    params = jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        params, pspecs,
    )
    replicated = NamedSharding(mesh, P())
    prompt = jax.device_put(prompt, replicated)
    rng = jax.device_put(rng, replicated)
    n = int(max_new_tokens)

    # one compiled fn per (model, mesh, axis, budget, param structure) —
    # rebuilding jit(shard_map(...)) per call would re-trace the whole
    # decode scan every request (the _EAGER_CACHE lesson, communication.py)
    # key includes the spec VALUES, not just the tree structure — a custom
    # tp_param_dim mapping the same params to different dims must recompile
    from ..utils import lru_get_or_build

    flat_specs, spec_tree = jax.tree_util.tree_flatten(pspecs)

    def build():
        def per_shard(p, toks, key, temp):
            return _generate_core(model, p, toks, n, key, temp)

        return jax.jit(shard_map(
            per_shard, mesh=mesh, in_specs=(pspecs, P(), P(), P()),
            out_specs=P(), check_vma=False,
        ))

    fn = lru_get_or_build(
        _TP_GEN_CACHE, _TP_GEN_CACHE_MAX,
        (model, mesh, tp_axis, n, spec_tree, tuple(flat_specs)), build,
    )
    return fn(params, prompt, rng, jnp.float32(temperature))
