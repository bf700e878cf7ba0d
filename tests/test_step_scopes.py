"""The step seen from inside: phase scopes in the compiled program, kernel
names, and the program's spans on the profiler's clock.

Everything here reads what an operator (or the benchmark) reads: the
optimized HLO text of ``BaguaTrainer.compiled_step`` — where every
instruction's ``op_name`` carries the ``bagua.*`` scope it was traced under,
wrapped by JAX's own ``jvp(...)`` / ``transpose(...)`` / ``rematted_computation``
— and a ``jax.profiler`` capture read back with ``ProfileData``.
"""

import ast
import contextlib
import glob
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import golden
from bagua_tpu.algorithms import (
    ByteGradAlgorithm, GradientAllReduceAlgorithm, ZeroOptimizerAlgorithm,
)
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.parallel.mesh import build_mesh
from bagua_tpu.telemetry import counters

N_DEVICES = 8
ROOT = pathlib.Path(__file__).resolve().parents[1]

_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
                "collective-permute")

ALGORITHMS = {
    "gradient_allreduce": lambda: GradientAllReduceAlgorithm(),
    "zero": lambda: ZeroOptimizerAlgorithm(optax.sgd(0.1)),
    "bytegrad": lambda: ByteGradAlgorithm(hierarchical=False),
}


@pytest.fixture
def obs_on():
    obs_spans.set_enabled(True)
    obs_spans.recorder.clear()
    yield
    obs_spans.set_enabled(None)
    obs_spans.recorder.clear()


def golden_trainer(algorithm="gradient_allreduce", **kw):
    loss_fn, params, batch = golden.golden_task()
    # 600-byte buckets: the golden MLP's 1024-byte kernel stands alone in
    # its own shape, the three smaller leaves share a 1-D flat (at 256 every
    # leaf would be its own bucket and nothing would run under bagua.layout)
    trainer = BaguaTrainer(
        loss_fn, optax.sgd(0.1), ALGORITHMS[algorithm](),
        mesh=build_mesh({"dp": N_DEVICES}), autotune=False, bucket_bytes=600,
        **kw)
    state = trainer.init(params)
    return trainer, state, trainer.shard_batch(batch)


def op_names(text):
    """[(instruction, opcode, op_name)] of an optimized HLO text."""
    out = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            path = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), path.group(1) if path else ""))
    return out


def has(paths, *needles, without=()):
    return any(all(n in p for n in needles)
               and not any(w in p for w in without) for p in paths)


# ---- A: phase scopes inside the compiled step -------------------------------


@pytest.mark.parametrize("accum_steps", [1, 2])
@pytest.mark.parametrize("overlap", ["off", "on"])
@pytest.mark.parametrize("algorithm", sorted(ALGORITHMS))
def test_compiled_step_names_its_phases(algorithm, overlap, accum_steps):
    trainer, state, batch = golden_trainer(algorithm, overlap=overlap,
                                           accum_steps=accum_steps)
    compiled = trainer.compiled_step(state, batch)
    assert isinstance(compiled, jax.stages.Compiled)
    instructions = op_names(compiled.as_text())
    paths = [p for _, _, p in instructions]
    # forward and backward come from ONE scope and JAX's transform wrappers
    assert has(paths, "jvp(bagua.loss)", without=("transpose(",))
    assert has(paths, "transpose(jvp(bagua.loss))")
    assert has(paths, "bagua.optimizer")
    assert has(paths, "bagua.layout")
    assert not has(paths, "rematted_computation")
    collectives = [(name, path) for name, opcode, path in instructions
                   if opcode.startswith(_COLLECTIVES)]
    assert collectives
    for name, path in collectives:
        assert "bagua.comm/" in path, (name, path)
    assert has([p for _, p in collectives], "bagua.comm/bucket_")
    # the gauge says what the plan asks of the wire; XLA may combine
    assert len(trainer._plan.buckets) > 1
    assert counters.get("comm/buckets_per_step") == len(trainer._plan.buckets)


def test_remat_replay_is_named_by_jax_itself():
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn,
    )

    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=16, remat=True))
    tokens = jnp.zeros((N_DEVICES, 9), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])["params"]
    trainer = BaguaTrainer(lm_loss_fn(model), optax.sgd(0.1),
                           GradientAllReduceAlgorithm(),
                           mesh=build_mesh({"dp": N_DEVICES}), autotune=False)
    state = trainer.init(params)
    batch = trainer.shard_batch({"tokens": tokens})
    paths = [p for _, _, p in
             op_names(trainer.compiled_step(state, batch).as_text())]
    assert has(paths, "bagua.loss", "rematted_computation")
    # flax's own module names stay inside the scope
    assert has(paths, "jvp(bagua.loss)", "TransformerLM")


def test_one_chip_world_exchanges_no_bucket():
    loss_fn, params, batch = golden.golden_task(batch_size=8)
    trainer = BaguaTrainer(
        loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 1}, jax.devices()[:1]), autotune=False)
    state = trainer.init(params)
    text = trainer.compiled_step(state, trainer.shard_batch(batch)).as_text()
    # (XLA:CPU keeps a one-member all-reduce; the TPU compiler drops it)
    assert counters.get("comm/buckets_per_step") == 0
    assert has([p for _, _, p in op_names(text)], "bagua.optimizer")


# ---- A2: the model's areas in the compiled step -----------------------------

AREA_PATHS = [
    # forward, backward and replay of an area read alike
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_3/attn/q/"
     "dot_general", "attn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_3/"
     "attn/flash_bwd_dq/pallas_call", "attn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/"
     "jvp(bagua.loss)/TransformerLM/checkpoint/rematted_computation/"
     "block_0/attn_norm/rsqrt", "attn"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/mlp/wi_gate/"
     "dot_general", "mlp"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/mlp_norm/mul",
     "mlp"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/embed/jit(_take)/gather",
     "embed"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/pos_embed/add", "embed"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/final_norm/mul", "head"),
    ("transpose(jvp(bagua.loss))/TransformerLM/lm_head/dot_general", "head"),
    ("jit(bagua_step)/jvp(bagua.loss)/loss_tail/reduce_max", "head"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/loss_tail/"
     "jit(take_along_axis)/scatter-add", "head"),
    ("jit(bagua_step)/while/body/closed_call/grad_accum/add", "accum"),
    # a looped model: the pass's scope names no area and hides none; the
    # norm that closes a pass, the heads and the exits lie outside it
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/while/body/loop_body/"
     "block_3/attn/q/dot_general", "attn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/while/body/"
     "loop_body/checkpoint/rematted_computation/block_3/mlp_post_norm/mul",
     "mlp"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/while/body/loop_body/"
     "block_0/attn_post_norm/rsqrt", "attn"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/while/body/loop_body/"
     "block_0/add", None),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/while/body/final_norm/"
     "mul", "head"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/exit_gate/dot_general",
     "exit"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/exit_dist/jit(log_sigmoid)/"
     "logistic", "exit"),
    # the rope kernel (ops/rope.py): forward, replay and backward of a
    # looped, rematted step as the TPU compiler names them (Ouro's), and
    # of a plain one (OLMoE's)
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/while/body/closed_call/"
     "TransformerLM.one_pass/loop_body/block_3/attn/jit(_rotate)/rope/"
     "pallas_call", "attn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/while/body/"
     "closed_call/TransformerLM.one_pass/loop_body/TransformerLM.one_pass/"
     "loop_body/checkpoint/rematted_computation/block_3/attn/jit(_rotate)/"
     "rope/pallas_call", "attn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/while/body/"
     "closed_call/TransformerLM.one_pass/loop_body/TransformerLM.one_pass/"
     "loop_body/checkpoint/block_3/attn/jit(_rotate)/rope/pallas_call",
     "attn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_0/attn/"
     "jit(_rotate)/rope/pallas_call", "attn"),
    # a block that norms its sub-layers' OUTPUT (Olmo-Hybrid's): each norm
    # in its sub-layer's area, the linear mixer's under its own name
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/"
     "linear_attn_post_norm/rsqrt", "linattn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_0/"
     "checkpoint/rematted_computation/linear_attn_post_norm/mul", "linattn"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_3/attn_post_norm/"
     "mul", "attn"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_3/"
     "mlp_post_norm/reduce_sum", "mlp"),
    # an expert layer's scope decides, whatever module it sits under
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_1/mlp/bagua.moe/"
     "experts/gmm_fwd/pallas_call", "moe/experts"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_1/attn/bagua.moe/"
     "route/router/dot_general", "moe/route"),
    ("jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_1/mlp/"
     "bagua.moe/dispatch/checkpoint/rematted_computation/sort",
     "moe/dispatch"),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_1/mlp/bagua.moe/"
     "combine/mul", "moe/combine"),
    # no area: the optimizer, the layout, a bare block, nothing
    ("jit(bagua_step)/bagua.optimizer/mul", None),
    ("jit(bagua_step)/jvp(bagua.loss)/bagua.layout/slice", None),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/add", None),
    ("jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_1/mlp/bagua.moe",
     None),
    ("", None),
    (None, None),
]


@pytest.mark.parametrize("path, area", AREA_PATHS)
def test_area_of(path, area):
    assert obs_spans.area_of(path) == area
    assert area is None or area in obs_spans.AREAS


def lm_trainer(kind, **trainer_kw):
    """A two-layer LM of ``kind`` under a trainer on the 8-device mesh."""
    from bagua_tpu.model_parallel.moe import MoEMLP, moe_lm_loss_fn
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn,
    )

    cfg = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
               max_seq_len=16)
    if kind == "remat":
        cfg.update(remat=True, remat_policy="dots_no_batch")
    if kind == "looped":
        from bagua_tpu.models.transformer import looped_lm_loss_fn

        cfg.update(rope_theta=1e6, n_passes=3, post_norms=True,
                   exit_gate=True, remat=True)
        model = TransformerLM(TransformerConfig(**cfg))
        loss_fn = looped_lm_loss_fn(model)
    elif kind == "output-norm hybrid":
        # Olmo-Hybrid's block: one linear-attention layer, one full one, a
        # norm behind each sub-layer and none in front, no positions
        cfg.update(rope_theta=1e6, rope_layers=(0,), qk_norm=True,
                   pre_norms=False, post_norms=True, mixer_layers=(1, 0),
                   linear_key_heads=2, linear_value_heads=2,
                   linear_key_dim=16, linear_value_dim=32,
                   linear_neg_eigval=True)
        model = TransformerLM(TransformerConfig(**cfg))
        loss_fn = lm_loss_fn(model)
    elif kind == "moe":
        cfg.update(rope_theta=10000.0)
        moe = lambda: MoEMLP(n_experts=4, d_ff=32, k=2, dropless=True,
                             gated=True, name="mlp")
        model = TransformerLM(TransformerConfig(**cfg),
                              mlp_factory=lambda _i: moe)
        loss_fn = moe_lm_loss_fn(model)
    else:
        model = TransformerLM(TransformerConfig(**cfg))
        loss_fn = lm_loss_fn(model)
    rows = N_DEVICES * trainer_kw.get("accum_steps", 1)
    tokens = jnp.zeros((rows, 9), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])["params"]
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.1),
                           GradientAllReduceAlgorithm(),
                           mesh=build_mesh({"dp": N_DEVICES}), autotune=False,
                           **trainer_kw)
    state = trainer.init(params)
    return trainer, state, trainer.shard_batch({"tokens": tokens})


DENSE_AREAS = {"embed", "attn", "mlp", "head"}
MOE_AREAS = {"moe/route", "moe/dispatch", "moe/experts", "moe/combine"}


@pytest.mark.parametrize("kind, trainer_kw, areas", [
    ("dense", {}, DENSE_AREAS),
    ("remat", {}, DENSE_AREAS),
    ("moe", {}, DENSE_AREAS | MOE_AREAS),
    ("dense", {"accum_steps": 4, "overlap": "on"}, DENSE_AREAS | {"accum"}),
    ("dense", {"accum_steps": 4, "overlap": "off"}, DENSE_AREAS | {"accum"}),
    ("looped", {}, DENSE_AREAS | {"exit"}),
    ("output-norm hybrid", {}, DENSE_AREAS | {"linattn"}),
], ids=["dense", "remat", "moe", "accum4-overlap", "accum4-serial",
        "looped", "output-norm-hybrid"])
def test_compiled_step_names_its_areas(kind, trainer_kw, areas):
    trainer, state, batch = lm_trainer(kind, **trainer_kw)
    paths = [p for _, _, p in
             op_names(trainer.compiled_step(state, batch).as_text())]
    # every area of the table that this model has, and no other
    assert {obs_spans.area_of(p) for p in paths} - {None} == areas
    if kind == "remat":
        assert has(paths, "rematted_computation", "/attn/")
    if kind == "dense":
        # the learned position table is no module's: its own plain scope
        assert has(paths, f"/{obs_spans.POS_EMBED_SCOPE}/")
    if kind == "output-norm hybrid":
        # each output norm is there under its sub-layer's name, forward and
        # backward, and no norm stands in front of a sub-layer
        for name, area in (("linear_attn_post_norm", "linattn"),
                           ("attn_post_norm", "attn"),
                           ("mlp_post_norm", "mlp")):
            named = [p for p in paths if f"/{name}/" in p]
            assert has(named, "jvp(bagua.loss)", without=("transpose(",))
            assert has(named, "transpose(jvp(bagua.loss))")
            assert {obs_spans.area_of(p) for p in named} == {area}
        assert not has(paths, "/linear_attn_norm/")
        assert not has(paths, "/attn_norm/") and not has(paths, "/mlp_norm/")
    inside = [p for p in paths if obs_spans.in_loop(p)]
    assert bool(inside) == (kind == "looped")
    if kind == "looped":
        # the passes are one body: forward in one loop, replay and backward
        # in another; what is inside is the trunk and only the trunk
        assert has(inside, "jvp(bagua.loss)", without=("transpose(",))
        assert has(inside, "transpose(jvp(bagua.loss))",
                   "rematted_computation")
        assert {obs_spans.area_of(p) for p in inside} == {"attn", "mlp",
                                                          None}
        exits = [p for p in paths if obs_spans.area_of(p) == "exit"]
        assert has(exits, "/exit_gate/") and has(
            exits, f"/{obs_spans.EXIT_SCOPE}/")
        assert {m for p in inside + exits
                for m in re.findall(r"bagua\.\w+", p)} == {"bagua.loss"}
    # the float32 cast of the logits is the head's, not bare TransformerLM's
    assert has(paths, "/lm_head/convert_element_type")
    assert not has(paths, "TransformerLM/convert_element_type")
    # the loss tail reads forward and backward, inside the one loss scope
    tail = [p for p in paths
            if obs_spans.LOSS_TAIL_SCOPE in p.split("/")]
    assert has(tail, "jvp(bagua.loss)", without=("transpose(",))
    assert has(tail, "transpose(jvp(bagua.loss))")
    accum = [p for p in paths if obs_spans.ACCUM_SCOPE in p.split("/")]
    assert bool(accum) == ("accum_steps" in trainer_kw)
    # neither plain scope is a phase to a reader of ``bagua.*`` components:
    # the tail stays in the loss, the accumulation stays unattributed
    assert {m for p in tail for m in re.findall(r"bagua\.\w+", p)} == {
        "bagua.loss"}
    assert not any(re.search(r"bagua\.\w+", p) for p in accum)


def test_the_plain_scopes_are_no_phase_scopes():
    for scope in (obs_spans.LOSS_TAIL_SCOPE, obs_spans.ACCUM_SCOPE,
                  obs_spans.POS_EMBED_SCOPE, obs_spans.EXIT_SCOPE):
        assert not re.search(r"bagua\.\w+", scope)
        assert obs_spans.AREA_COMPONENTS[scope] in obs_spans.AREAS
    # a pass's scope is no phase and no area: the modules inside it name one
    assert not re.search(r"bagua\.\w+", obs_spans.LOOP_SCOPE)
    assert obs_spans.LOOP_SCOPE not in obs_spans.AREA_COMPONENTS


def parent_tail(logits, targets):
    """The two lines each loss function wrote out before ``loss_tail``."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, targets).mean()


def assert_bit_equal(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and bool(jnp.array_equal(g, w))


# the last two: batch 1 and a vocabulary that is no multiple of 128, rows that
# fill no sublane tile (the loss tail's compare-and-sum, PR 38)
@pytest.mark.parametrize("kind,vocab,rows", [
    ("lm", 64, 4), ("moe", 64, 4), ("sp", 64, 4),
    ("lm", 1187, 1), ("lm", 130, 2)])
def test_loss_functions_are_bit_equal_to_their_written_out_form(kind, vocab,
                                                                rows):
    from jax.sharding import PartitionSpec as P

    from bagua_tpu.model_parallel.moe import MoEMLP, moe_lm_loss_fn
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn, sp_lm_loss_fn,
    )

    cfg = TransformerConfig(vocab_size=vocab, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=16,
                            rope_theta=10000.0)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (rows, 17), 0, vocab)
    batch = {"tokens": tokens}
    if kind == "moe":
        moe = lambda: MoEMLP(n_experts=4, d_ff=32, k=2, dropless=True,
                             gated=True, name="mlp")
        model = TransformerLM(cfg, mlp_factory=lambda _i: moe)

        def written_out(params, batch):
            logits, mutated = model.apply(
                {"params": params}, batch["tokens"][:, :-1],
                mutable=["intermediates"])
            aux = jnp.zeros((), jnp.float32)
            for leaf in jax.tree.leaves(mutated.get("intermediates", {})):
                aux = aux + jnp.sum(leaf)
            return parent_tail(logits, batch["tokens"][:, 1:]) + 0.01 * aux

        loss_fn = moe_lm_loss_fn(model)
    else:
        model = TransformerLM(cfg)

        def written_out(params, batch):
            logits = model.apply({"params": params}, batch["tokens"][:, :-1])
            return parent_tail(logits, batch["tokens"][:, 1:])

        loss_fn = lm_loss_fn(model)
    params = model.init(jax.random.PRNGKey(4), tokens[:1, :8])["params"]
    if kind == "sp":
        sp = 2

        def written_out(params, batch):
            start = jax.lax.axis_index("sp") * 8
            tokens = batch["tokens"]
            logits = model.apply(
                {"params": params},
                jax.lax.dynamic_slice_in_dim(tokens, start, 8, axis=1))
            return parent_tail(
                logits,
                jax.lax.dynamic_slice_in_dim(tokens, start + 1, 8, axis=1))

        mesh = build_mesh({"sp": sp}, jax.devices()[:sp])

        def sharded(fn):
            def per_shard(params, batch):
                loss, grads = jax.value_and_grad(fn)(params, batch)
                return loss[None], jax.tree.map(lambda g: g[None], grads)
            return jax.jit(jax.shard_map(
                per_shard, mesh=mesh, in_specs=(P(), P()),
                out_specs=(P("sp"), P("sp")), check_vma=False))

        got = sharded(sp_lm_loss_fn(model, sp_size=sp))(params, batch)
        want = sharded(written_out)(params, batch)
    else:
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(written_out))(params, batch)
    assert_bit_equal(got, want)
    assert float(jnp.ravel(got[0])[0]) > 0


# the loss tail picks the target's logit by compare-and-sum (PR 38): batch 1
# with a vocabulary that is no multiple of 128 (the SmallThinker share's kind
# of slice), a lane-aligned one, and rows that fill no sublane tile
TAIL_SHAPES = [(1, 64, 1187), (2, 16, 256), (8, 12, 130)]


def tail_case(shape, seed=5):
    """bf16-rounded float32 logits (what the head hands the tail), targets."""
    k_logits, k_targets = jax.random.split(jax.random.PRNGKey(seed))
    logits = 4.0 * jax.random.normal(k_logits, shape, jnp.float32)
    logits = logits.astype(jnp.bfloat16).astype(jnp.float32)
    targets = jax.random.randint(k_targets, shape[:-1], 0, shape[-1])
    return logits, targets


@pytest.mark.parametrize("shape", TAIL_SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("how", ["op_by_op", "jit"])
def test_loss_tail_is_bit_equal_to_optax(shape, how):
    from bagua_tpu.models.transformer import loss_tail

    # ``jax.jit`` is the identity under ``disable_jit``: one rounding an
    # operation, on both sides
    mode = jax.disable_jit() if how == "op_by_op" else contextlib.nullcontext()
    ours = jax.jit(jax.value_and_grad(loss_tail))
    theirs = jax.jit(jax.value_and_grad(parent_tail))
    cases = [tail_case(shape, seed) for seed in range(5, 17)]
    with mode:
        results = [(ours(*case), theirs(*case)) for case in cases]
    for (_, targets), ((loss, grad), (want_loss, want_grad)) in zip(
            cases, results):
        # the sum of one logit and zeros is that logit
        assert_bit_equal(loss, want_loss)
        assert grad.shape == shape and float(loss) > 0
        if how == "op_by_op":
            # the same two terms (softmax, minus one-hot), and two-term
            # sums commute
            assert_bit_equal(grad, want_grad)
            continue
        # under jit the compiler is free to round the two-term sum once
        # (softmax * g - g as one fused multiply-add) on either side: equal
        # to the bit wherever no target sits, within one unit in the last
        # place in a target's own column
        hit = np.arange(shape[-1]) == np.asarray(targets)[..., None]
        got, want = np.asarray(grad), np.asarray(want_grad)
        assert np.array_equal(got[~hit], want[~hit])
        np.testing.assert_array_max_ulp(got[hit], want[hit], maxulp=1)


@pytest.mark.parametrize("shape", TAIL_SHAPES[:2],
                         ids=lambda s: "x".join(map(str, s)))
def test_loss_tail_holds_no_gather_and_no_scatter(shape):
    # the operand of a gather must exist in HBM (the float32 logits) and its
    # transpose is a scatter (a float32 d-logits flat at batch 1): neither
    # may come back, in the trace or in what is handed to the compiler
    from bagua_tpu.models.transformer import loss_tail

    logits, targets = tail_case(shape)
    grad = jax.value_and_grad(loss_tail)
    for text in (str(jax.make_jaxpr(grad)(logits, targets)),
                 jax.jit(grad).lower(logits, targets).as_text()):
        assert "gather" not in text and "scatter" not in text
    # the lens sees them where they are: optax's form holds both
    parent = jax.jit(jax.value_and_grad(parent_tail)).lower(
        logits, targets).as_text()
    assert "gather" in parent and "scatter" in parent


def test_a_target_outside_the_vocabulary_reads_a_label_logit_of_zero():
    from bagua_tpu.models.transformer import loss_tail

    logits, targets = tail_case((2, 16, 256))
    outside = targets.at[0, 3].set(256).at[1, 5].set(-1)
    loss, grad = jax.value_and_grad(loss_tail)(logits, outside)
    # no column matches: the row's loss is its log-normaliser alone and its
    # gradient the softmax with no one-hot taken off
    labels = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    rows = jax.nn.logsumexp(logits, axis=-1) - jnp.where(
        outside == targets, labels, 0.0)
    assert bool(jnp.isfinite(loss)) and bool(jnp.isfinite(grad).all())
    assert jnp.allclose(loss, rows.mean(), rtol=1e-6)
    assert jnp.allclose(grad[0, 3].sum() * targets.size, 1.0, rtol=1e-5)
    assert jnp.allclose(grad[0, 4].sum(), 0.0, atol=1e-7)
    # optax's gather fills a NaN past the end and wraps a negative target
    # around to the last columns; the docstring says what differs
    assert bool(jnp.isnan(parent_tail(logits, targets.at[0, 3].set(256))))
    assert_bit_equal(parent_tail(logits, targets.at[1, 5].set(-1)),
                     parent_tail(logits, targets.at[1, 5].set(255)))
    doc = " ".join(loss_tail.__doc__.split())
    assert "outside ``[0, vocab)``" in doc and "label logit is 0" in doc
    assert "gather" in doc and "scatter" in doc


# ---- B: program spans on the profiler's clock --------------------------------


def host_events(trace_dir):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "the capture wrote no .xplane.pb"
    events = []
    for plane in ProfileData.from_file(max(files, key=os.path.getmtime)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bagua/", "bagua_train")):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats), line.name))
    return events


def capture_two_steps(tmp_path):
    trainer, state, batch = golden_trainer()
    state, loss = trainer.train_step(state, batch)   # compile outside
    float(loss)
    first = trainer._step_counter + 1
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            state, loss = trainer.train_step(state, batch)
        float(loss)
    finally:
        jax.profiler.stop_trace()
    return first, host_events(str(tmp_path))


def test_profile_holds_the_step_and_its_spans(obs_on, tmp_path):
    first, events = capture_two_steps(tmp_path)
    steps = sorted((e for e in events if e[0] == "bagua_train"),
                   key=lambda e: e[1])
    assert [e[3].get("step_num") for e in steps] == [first, first + 1]
    roots = [e for e in events if e[0] == "bagua/step/train_step"]
    dispatches = [e for e in events if e[0] == "bagua/step/dispatch"]
    assert len(roots) == len(dispatches) == 2
    for _, lo, hi, _, line in dispatches:
        # each dispatch sits inside one root span of the same thread, and
        # each root inside one step annotation: one clock for all of them
        assert sum(r[1] <= lo and hi <= r[2] and r[4] == line
                   for r in roots) == 1
    for _, lo, hi, _, _ in roots:
        assert sum(s[1] <= lo and hi <= s[2] for s in steps) == 1
    names = {e[0] for e in events}
    assert {"bagua/step/hooks", "bagua/step/watchdog_handoff"} <= names
    # the ring tells the same story: dispatch is the root span's child
    ring = obs_spans.span_ring.snapshot()
    dispatch = [s for s in ring if s["name"] == "step/dispatch"][-1]
    root = [s for s in ring if s["name"] == "step/train_step"][-1]
    assert (dispatch["parent"], dispatch["depth"]) == ("step/train_step", 1)
    assert (root["parent"], root["depth"]) == (None, 0)
    assert root["step"] == dispatch["step"] == first + 1
    assert root["dur_s"] >= dispatch["dur_s"]


def test_obs_off_writes_no_annotation_and_keeps_the_scopes(tmp_path):
    obs_spans.set_enabled(False)
    obs_spans.recorder.clear()
    try:
        _, events = capture_two_steps(tmp_path)
        assert events == []
        trainer, state, batch = golden_trainer()
        paths = [p for _, _, p in
                 op_names(trainer.compiled_step(state, batch).as_text())]
        assert has(paths, "jvp(bagua.loss)") and has(paths, "bagua.optimizer")
        assert obs_spans.span_ring.snapshot() == []
    finally:
        obs_spans.set_enabled(None)


def test_prefetch_divides_the_wait_for_input(obs_on):
    from bagua_tpu.contrib.prefetch import prefetch_to_device

    trainer, _, _ = golden_trainer()
    _, _, batch = golden.golden_task()
    batches = prefetch_to_device(iter([batch] * 3), trainer=trainer, size=1)
    obs_spans.set_current_step(None)
    assert len(list(batches)) == 3
    names = [s["name"] for s in obs_spans.span_ring.snapshot()
             if s["name"].startswith("input/")]
    # the fourth pull finds the iterator exhausted: a source span, no place
    assert names.count("input/place") == 3
    assert names.count("input/source") == 4


def test_a_span_without_jax_mirrors_nothing(obs_on, monkeypatch):
    """spans.py imports no jax; a process that has none (the launcher) pays
    for no annotation."""
    import sys

    monkeypatch.setitem(sys.modules, "jax", None)
    with obs_spans.trace_span("launcher/tick") as span:
        assert span.annotations == ()
    assert obs_spans.span_ring.snapshot()[-1]["name"] == "launcher/tick"


# ---- kernel names -------------------------------------------------------------

KERNEL_FILES = ("bagua_tpu/ops/flash_attention.py", "bagua_tpu/ops/gmm.py",
                "bagua_tpu/compression/pallas_codec.py",
                "bagua_tpu/ops/embed_grad.py", "bagua_tpu/ops/rope.py")


def pallas_call_names(path):
    names = []
    for node in ast.walk(ast.parse((ROOT / path).read_text())):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            name = next((k.value for k in node.keywords if k.arg == "name"),
                        None)
            # a literal, or a choice between two literals (a windowed
            # flash call's own name): every name is there to grep for
            choices = ([name.body, name.orelse]
                       if isinstance(name, ast.IfExp) else [name])
            for choice in choices:
                assert isinstance(choice, ast.Constant) and isinstance(
                    choice.value, str), (
                        f"{path}:{node.lineno} has no literal name=")
                names.append(choice.value)
    return names


@pytest.mark.parametrize("path, count", zip(KERNEL_FILES, (9, 2, 9, 1, 2)))
def test_every_pallas_call_has_a_literal_name(path, count):
    names = pallas_call_names(path)
    assert len(names) == count
    assert all(re.fullmatch(r"[a-z][a-z0-9_]*", n) for n in names)
    everywhere = [n for p in KERNEL_FILES for n in pallas_call_names(p)]
    # one name is shared on purpose: ``norm_rope``'s call is the rotation's
    # pass with a norm operand, and the readers that count or time ``rope``
    # calls take both (ops/rope.py)
    assert sorted(everywhere) == sorted([*set(everywhere), "rope"])
    if path.endswith("rope.py"):
        assert names == ["rope", "rope"]
    if path.endswith("flash_attention.py"):
        # the three names the gpt2 cell's readers key on, each beside
        # its windowed twin; then the block-diffusion kernels' own
        assert names == ["flash_fwd", "flash_win_fwd", "flash_bwd_dkv",
                         "flash_win_bwd_dkv", "flash_bwd_dq",
                         "flash_win_bwd_dq", "flash_bd_fwd",
                         "flash_bd_bwd_dkv", "flash_bd_bwd_dq"]


#: configuration -> rotary layers of its step on the ``rope`` kernel: the
#: four architectures of the benchmark's cells at tiny widths, heads of 128
ROPE_KERNEL_LAYERS = [
    ("ouro", dict(n_layers=8, rope_theta=1e6, n_passes=4, post_norms=True,
                  exit_gate=True, remat=True), 8),
    ("smallthinker", dict(n_layers=4, rope_theta=1.5e6, n_kv_heads=1,
                          window=64, window_layers=(0, 1, 1, 1),
                          rope_layers=(0, 1, 1, 1), remat=True,
                          remat_policy="dots_no_batch"), 3),
    ("olmoe", dict(n_layers=1, rope_theta=1e4, qk_norm=True), 1),
    ("gpt2", dict(n_layers=2), 0),
]


def rotation_gauges(kw, forced, monkeypatch, seq=128):
    """``(attn/rope_kernel_layers, attn/head_norm_kernel_layers)`` as a
    tiny ``TransformerLM(**kw)`` sets them where its step is traced (not
    run: ``eval_shape``); ``forced``: the gate's platform test says yes,
    steered here in the test."""
    import importlib

    from bagua_tpu.models.transformer import TransformerConfig, TransformerLM

    if forced:
        flash = importlib.import_module("bagua_tpu.ops.flash_attention")
        monkeypatch.setattr(flash, "flash_supported", lambda *a, **kw: True)
    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=128, n_heads=2, d_head=128, d_ff=128,
        max_seq_len=seq, **kw))
    tokens = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    names = ("attn/rope_kernel_layers", "attn/head_norm_kernel_layers")
    for name in names:
        counters.set_gauge(name, -1)
    jax.eval_shape(model.apply, params, tokens)
    return tuple(counters.get(name) for name in names)


@pytest.mark.parametrize("forced", [True, False], ids=["forced", "off-tpu"])
@pytest.mark.parametrize("name, kw, layers", ROPE_KERNEL_LAYERS,
                         ids=[c[0] for c in ROPE_KERNEL_LAYERS])
def test_the_gauge_counts_the_layers_on_the_rope_kernel(name, kw, layers,
                                                        forced, monkeypatch):
    """``attn/rope_kernel_layers``, set where the step is traced: a looped
    model's scanned body counts once, a NoPE layer not at all, and off the
    TPU no layer reaches the kernel; none of the four normalises each head."""
    assert rotation_gauges(kw, forced, monkeypatch) == (layers * forced, 0)


#: configuration -> (rotary layers on the ``rope`` kernel, those of them
#: whose per-head norm of q and k rides the same pass)
HEAD_NORM_KERNEL_LAYERS = [
    ("sdar", dict(n_layers=3, rope_theta=1e6, qk_norm="head", n_kv_heads=1,
                  attention="block_diffusion", diffusion_block=4, remat=True,
                  remat_policy="dots_no_batch"), 3, 3),
    ("qwen3-next", dict(n_layers=4, rope_theta=1e7, qk_norm="head",
                        norm_zero_centered=True, rotary_dim=32,
                        attn_gate=True), 0, 0),
    ("head-norm-some-layers-nope", dict(
        n_layers=4, rope_theta=1e6, qk_norm="head",
        rope_layers=(0, 1, 1, 1)), 3, 3),
    ("olmoe", dict(n_layers=2, rope_theta=1e4, qk_norm=True), 2, 0),
]


@pytest.mark.parametrize("forced", [True, False], ids=["forced", "off-tpu"])
@pytest.mark.parametrize("name, kw, rotary, normed", HEAD_NORM_KERNEL_LAYERS,
                         ids=[c[0] for c in HEAD_NORM_KERNEL_LAYERS])
def test_the_gauge_counts_the_layers_whose_head_norm_rides_the_pass(
        name, kw, rotary, normed, forced, monkeypatch):
    """``attn/head_norm_kernel_layers`` beside ``attn/rope_kernel_layers``:
    SDAR's every layer, none of a model whose rotation is partial
    (Qwen3-Next's softmax layers) or whose norm is over all heads (OLMoE),
    none off the TPU."""
    assert rotation_gauges(kw, forced, monkeypatch, seq=256) == (
        rotary * forced, normed * forced)


#: the chunked scans and the row passes around them: file -> its calls
SCAN_FILES = {
    "bagua_tpu/ops/gated_delta.py": ["gdn_fwd", "gdn_bwd"],
    "bagua_tpu/ops/gated_delta_rows.py": ["gdn_mix", "gdn_mix_bwd",
                                          "gdn_gate", "gdn_gate_bwd"],
    "bagua_tpu/ops/ssd.py": ["ssd_fwd", "ssd_bwd"],
    "bagua_tpu/ops/ssd_rows.py": ["ssd_mix", "ssd_mix_bwd", "ssd_gate",
                                  "ssd_gate_bwd"],
}


@pytest.mark.parametrize("path", list(SCAN_FILES))
def test_the_scans_and_their_row_passes_are_named_apart(path):
    """Literal names, and each of them once across every file of the
    package that holds a ``pallas_call``: ``perfbench/scopes.py::kernel_of``
    matches a call's exact last path element, so ``ssd_mix`` must not be
    ``ssd_fwd`` and no second file may write an ``ssd_fwd``."""
    names = pallas_call_names(path)
    assert names == SCAN_FILES[path]
    everywhere = [
        n for p in (ROOT / "bagua_tpu").rglob("*.py")
        if "pallas_call(" in p.read_text()
        for n in pallas_call_names(str(p.relative_to(ROOT)))]
    assert [everywhere.count(n) for n in names] == [1] * len(names)


def test_no_pallas_call_outside_the_named_files():
    for path in (ROOT / "bagua_tpu").rglob("*.py"):
        rel = str(path.relative_to(ROOT))
        if "pallas_call(" in path.read_text() and rel not in KERNEL_FILES:
            pallas_call_names(rel)  # must be named as well
