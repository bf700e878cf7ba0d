"""The token table's lookup and its gradient kernel (``ops/embed_grad.py``):
``embed_grad`` in interpret mode against the float32 scatter-add, and through
``TransformerLM`` against the ``nn.Embed`` model — the forward to the bit, the
table's gradient within one bf16 rounding of the float32 sum, every other
leaf to the bit, the parameter tree unchanged.  ``correct`` on the chip does
not watch this leaf (the first-gradient comparison reads the attention
matrices): these tests carry its correctness."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models import transformer
from bagua_tpu.models.transformer import (
    TokenEmbed, TransformerConfig, TransformerLM, lm_loss_fn,
)
from bagua_tpu.ops import embed_grad as E
from bagua_tpu.telemetry import counters

R = 256   # the block height the kernel takes at these widths
C = 128   # sorted rows a visit


def scatter_add(ids, rows, vocab):
    """The float32 sum the kernel has to round once."""
    return jnp.zeros((vocab, rows.shape[1]), jnp.float32).at[ids].add(
        rows.astype(jnp.float32), mode="drop")


def ids_of(kind, vocab, t, key):
    if kind == "distinct":
        return jax.random.permutation(key, vocab)[:t].astype(jnp.int32)
    if kind == "equal":
        return jnp.full((t,), vocab // 2 + 5, jnp.int32)
    if kind == "block_ends":   # first and last row of every block, and of V
        ends = [r for b in range(0, vocab, R)
                for r in (b, min(b + R, vocab) - 1)]
        return jnp.resize(jnp.array(ends, jnp.int32), (t,))
    if kind == "uniform":
        return jax.random.randint(key, (t,), 0, vocab, jnp.int32)
    raise ValueError(kind)


CASES = [
    # kind, vocab, tokens, d
    ("distinct", 1024, 512, 128),
    ("equal", 1024, 512, 128),
    ("block_ends", 1024, 512, 128),
    ("uniform", 1024, 512, 256),
    ("uniform", 992, 300, 128),      # 32 * odd rows; T not a multiple of C
    ("block_ends", 992, 300, 128),
    ("distinct", 992, 200, 128),
    ("uniform", 608, 256, 2560),     # 20 lane tiles (SmallThinker's rows)
    ("uniform", 640, 256, 2048),     # 16 lane tiles (OLMoE's, Ouro's)
    ("equal", 608, 130, 2560),
]


@pytest.mark.parametrize("kind,vocab,t,d", CASES)
def test_embed_grad_is_the_float32_sum_rounded_once(kind, vocab, t, d):
    k1, k2 = jax.random.split(jax.random.PRNGKey(vocab + t + d))
    ids = ids_of(kind, vocab, t, k1)
    rows = jax.random.normal(k2, (t, d), jnp.bfloat16)
    want = scatter_add(ids, rows, vocab)
    got32 = E.embed_grad(ids, rows, vocab=vocab, out_dtype=jnp.float32,
                         interpret=True)
    got = E.embed_grad(ids, rows, vocab=vocab, interpret=True)
    assert got.shape == (vocab, d) and got.dtype == jnp.bfloat16
    if kind == "distinct":
        # one row a token: nothing is summed, nothing rounded
        np.testing.assert_array_equal(got32, want)
        np.testing.assert_array_equal(got.astype(jnp.float32), want)
        return
    # the float32 sums agree to their order of summation ...
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got32, want, atol=4e-6 * scale, rtol=0)
    # ... and the bf16 result is one rounding of them (half an ulp: 2^-9)
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=2.0 ** -8,
                               atol=1e-5 * scale)


def test_ids_outside_the_table_add_nothing_and_negative_ones_are_not_rows():
    vocab, t, d = 600, 256, 128
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    ids = jax.random.randint(k1, (t,), 0, vocab, jnp.int32)
    ids = ids.at[::7].set(vocab + 3).at[1::11].set(2 ** 31 - 1)
    ids = ids.at[2::13].set(-4)
    rows = jax.random.normal(k2, (t, d), jnp.bfloat16)
    inside = (ids >= 0) & (ids < vocab)
    want = scatter_add(jnp.where(inside, ids, vocab), rows, vocab)
    got = E.embed_grad(ids, rows, vocab=vocab, out_dtype=jnp.float32,
                       interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("kind", ["equal", "distinct", "uniform",
                                  "block_ends"])
@pytest.mark.parametrize("vocab,t", [(37984, 8192), (50304, 8192),
                                     (30528, 3072), (992, 384)])
def test_the_walk_has_at_most_blocks_plus_chunks_visits(kind, vocab, t):
    """Whatever the batch — all tokens equal, all distinct — the plan has at
    most ``V / R + T / C - 1`` visits, which is the grid's static length:
    every block once, one more for each chunk boundary inside a block."""
    ids = jnp.sort(ids_of(kind, vocab, t, jax.random.PRNGKey(0)))
    block, chunk, total = jax.jit(
        functools.partial(E.visit_plan, vocab=vocab, rows_per_block=R))(ids)
    n_blocks, n_chunks = -(-vocab // R), t // C
    assert block.shape == chunk.shape == (n_blocks + n_chunks - 1,)
    total = int(total[0])
    assert n_blocks <= total <= n_blocks + n_chunks - 1
    block, chunk = np.asarray(block), np.asarray(chunk)
    # every block is visited, in order; a block's chunks ascend by one
    assert (np.diff(block) >= 0).all() and set(block[:total]) == set(
        range(n_blocks))
    same = np.diff(block[:total]) == 0
    assert (np.diff(chunk[:total])[same] == 1).all()
    assert 0 <= chunk.min() and chunk.max() < n_chunks
    # each token's chunk is visited by its block
    visited = set(zip(block[:total].tolist(), chunk[:total].tolist()))
    ids = np.asarray(ids)
    assert {(int(v) // R, p // C) for p, v in enumerate(ids)} <= visited
    # past the plan's end the grid repeats its last block and chunk
    assert (block[total:] == block[total - 1]).all()
    assert (chunk[total:] == chunk[total - 1]).all()
    if kind == "equal":       # one block takes every chunk
        assert total == n_blocks + n_chunks - 1


def test_block_height_follows_the_width_and_the_vmem_limit(monkeypatch):
    assert E.block_rows(2560, 2) == E.block_rows(1024, 2) == 256
    # a core with the compiler's default 16 MiB: a 2,560-wide block of 256
    # rows (accumulator, product, output and chunk buffers: 9.2 MB of 12) fits,
    # an 8,192-wide one does not
    monkeypatch.setattr(E, "_vmem_limit", lambda: 16 << 20)
    assert E.block_rows(2560, 2) == 256
    assert E.block_rows(8192, 2) == 128


# -- through the model ------------------------------------------------------

CFG = TransformerConfig(vocab_size=640, d_model=128, n_heads=2, n_layers=1,
                        d_ff=256, max_seq_len=64)


def batch_of(seed=0, b=2, s=64):
    # a fifth of the table: most tokens share their id with another
    return {"tokens": jax.random.randint(jax.random.PRNGKey(seed),
                                         (b, s + 1), 0, 128, jnp.int32)}


@pytest.fixture
def kernel_path(monkeypatch):
    """The path the chip takes, with the kernel in interpret mode: steered
    here, not by an option of the program."""
    monkeypatch.setattr(E, "grad_kernel_supported", lambda d: True)
    monkeypatch.setattr(E, "token_lookup", functools.partial(
        E.token_lookup, interpret=True, force=True))


@pytest.fixture
def embed_model(monkeypatch):
    """``TransformerLM`` as the parent built it: ``nn.Embed`` under the name
    ``embed``."""
    def build():
        monkeypatch.setattr(transformer, "TokenEmbed", nn.Embed)
        return TransformerLM(CFG)
    return build


def test_the_parameter_tree_is_nn_embeds(embed_model):
    tokens = batch_of()["tokens"][:, :-1]
    ours = TransformerLM(CFG).init(jax.random.PRNGKey(0), tokens)["params"]
    theirs = embed_model().init(jax.random.PRNGKey(0), tokens)["params"]
    assert (jax.tree_util.tree_structure(ours)
            == jax.tree_util.tree_structure(theirs))
    assert ours["embed"]["embedding"].shape == (CFG.vocab_size, CFG.d_model)
    assert ours["embed"]["embedding"].dtype == jnp.float32
    # the same initialiser under the same key: a checkpoint of either loads
    # into the other, and a seed gives the weights it gave
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("path", ["kernel", "fallback"])
def test_the_forward_is_nn_embeds_to_the_bit(path, dtype, request):
    if path == "kernel":
        request.getfixturevalue("kernel_path")
    tokens = jnp.array([[0, 5, 639, -1, 5], [17, 17, -640, 300, 1]])
    table = jax.random.normal(jax.random.PRNGKey(1), (640, 128)) * 3.0
    ours = TokenEmbed(640, 128, dtype=dtype).apply(
        {"params": {"embedding": table}}, tokens)
    theirs = nn.Embed(640, 128, dtype=dtype).apply(
        {"params": {"embedding": table}}, tokens)
    assert ours.dtype == theirs.dtype == dtype
    np.testing.assert_array_equal(ours, theirs)
    with pytest.raises(ValueError, match="integers"):
        TokenEmbed(640, 128).apply({"params": {"embedding": table}},
                                   tokens.astype(jnp.float32))


def grads(model, params, batch):
    return jax.jit(jax.grad(lm_loss_fn(model)))(params, batch)


@pytest.fixture
def three_gradients(kernel_path, embed_model):
    batch = batch_of()
    params = TransformerLM(CFG).init(jax.random.PRNGKey(0),
                                     batch["tokens"][:, :-1])["params"]
    ours = grads(TransformerLM(CFG), params, batch)
    theirs = grads(embed_model(), params, batch)
    return batch, ours, theirs


def test_every_other_leaf_is_nn_embeds_to_the_bit(three_gradients):
    _, ours, theirs = three_gradients
    ours, theirs = dict(ours), dict(theirs)
    ours.pop("embed"), theirs.pop("embed")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours),
                            jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(a, b, err_msg=str(path))


def test_the_tables_gradient_is_the_sum_of_the_same_rows(three_gradients):
    batch, ours, theirs = three_gradients
    ours, theirs = ours["embed"]["embedding"], theirs["embed"]["embedding"]
    assert ours.dtype == theirs.dtype == jnp.float32
    counts = np.bincount(np.asarray(batch["tokens"][:, :-1]).ravel(),
                         minlength=CFG.vocab_size)
    assert (counts > 1).sum() > 20 and (counts == 1).sum() > 5
    # a token seen once, or never: nothing is summed, the row is the parent's
    np.testing.assert_array_equal(ours[counts <= 1], theirs[counts <= 1])
    assert not ours[counts == 0].any()
    # seen n times: the parent's row is n - 1 bf16 additions, each rounded,
    # ours the float32 sum rounded once; both within n roundings of it
    scale = np.abs(np.asarray(theirs)).max(axis=1, keepdims=True)
    err = np.abs(np.asarray(ours) - np.asarray(theirs))
    assert (err <= counts[:, None] * 2.0 ** -8 * scale).all()


def table_ops(fn, *args):
    """(primitive, operand shape, operand dtype, result dtype) of every
    equation of ``fn``'s jaxpr that reads a table-shaped operand; a
    ``pallas_call`` by its kernel's name, whatever it reads."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                found.append((eqn.params["name"], None, None, None))
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if getattr(aval, "shape", None) == (CFG.vocab_size,
                                                    CFG.d_model):
                    found.append((name, aval.shape, aval.dtype,
                                  eqn.outvars[0].aval.dtype))
            for value in eqn.params.values():
                subs = value if isinstance(value, (list, tuple)) else [value]
                for sub in subs:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def step_ops(model, batch):
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           batch["tokens"][:, :-1]))["params"]
    counters.set_gauge("embed/grad_kernel", -1)
    ops = table_ops(jax.grad(lm_loss_fn(model)), params, batch)
    return ops, counters.get("embed/grad_kernel")


def whole_table_casts(ops):
    return [op for op in ops if op[0] == "convert_element_type"
            and op[2] == jnp.float32 and op[3] == jnp.bfloat16]


def test_the_kernel_paths_step_holds_embed_grad_and_no_scatter(monkeypatch):
    # nothing runs: the jaxpr alone, as a compile for a described chip sees it
    monkeypatch.setattr(E.jax, "default_backend", lambda: "tpu")
    ops, gauge = step_ops(TransformerLM(CFG), batch_of())
    names = [op[0] for op in ops]
    assert gauge == 1
    assert names.count("embed_grad") == 1
    assert not [n for n in names if n.startswith("scatter")]
    # the table is read by the row gather alone: never cast whole
    assert whole_table_casts(ops) == []
    assert "gather" in names


def test_the_fallbacks_step_is_the_gathers_own_transpose():
    ops, gauge = step_ops(TransformerLM(CFG), batch_of())
    names = [op[0] for op in ops]
    assert gauge == 0
    assert "embed_grad" not in names and "scatter-add" in names
    assert whole_table_casts(ops) == []


def test_nn_embed_cast_the_whole_table(embed_model):
    """What the parent's step held, so that the two tests above test
    something: a float32 -> bf16 cast of the whole table and a scatter-add."""
    ops, _ = step_ops(embed_model(), batch_of())
    assert len(whole_table_casts(ops)) == 1
    assert "scatter-add" in [op[0] for op in ops]


def test_a_narrow_table_keeps_the_fallback_on_the_tpu(monkeypatch):
    monkeypatch.setattr(E.jax, "default_backend", lambda: "tpu")
    assert E.grad_kernel_supported(2560) and E.grad_kernel_supported(128)
    assert not E.grad_kernel_supported(64)
    monkeypatch.setattr(E.jax, "default_backend", lambda: "cpu")
    assert not E.grad_kernel_supported(2560)


def test_a_data_parallel_step_runs_the_kernel_on_each_chips_own_tokens(
        kernel_path, monkeypatch):
    """``BaguaTrainer``'s step over four devices (``shard_map``, gradients
    all-reduced) with the kernel on each device's quarter of the batch
    against the same step on the fallback: the losses agree and the table
    moves by the same update to a bf16 rounding of its gradient."""
    import optax

    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    batch = batch_of(seed=3, b=8)
    params = TransformerLM(CFG).init(jax.random.PRNGKey(0),
                                     batch["tokens"][:, :-1])["params"]

    def one_step():
        trainer = BaguaTrainer(
            lm_loss_fn(TransformerLM(CFG)), optax.sgd(1.0),
            GradientAllReduceAlgorithm(),
            mesh=build_mesh({"dp": 4}, jax.devices()[:4]),
            autotune=False)
        state = trainer.init(params)
        counters.set_gauge("embed/grad_kernel", -1)
        state, loss = trainer.train_step(state, trainer.shard_batch(batch))
        table = trainer.unstack_params(state)["embed"]["embedding"]
        return float(loss), np.asarray(table), counters.get(
            "embed/grad_kernel")

    traced, kernel = [], E.embed_grad
    monkeypatch.setattr(E, "embed_grad", lambda ids, rows, **kw: (
        traced.append(ids.shape), kernel(ids, rows, **kw))[1])
    loss_k, table_k, gauge_k = one_step()
    assert traced == [(2 * 64,)]       # a quarter of the batch's tokens
    with pytest.MonkeyPatch.context() as patch:     # back on the fallback
        patch.setattr(E, "grad_kernel_supported", lambda d: False)
        patch.setattr(E, "token_lookup", E.token_lookup.func)
        loss_f, table_f, gauge_f = one_step()
    assert (gauge_k, gauge_f) == (1, 0)
    assert loss_k == loss_f
    start = np.asarray(params["embed"]["embedding"])
    moved_k, moved_f = table_k - start, table_f - start
    assert np.abs(moved_f).max() > 0
    scale = np.abs(moved_f).max(axis=1, keepdims=True)
    counts = np.bincount(np.asarray(batch["tokens"][:, :-1]).ravel(),
                         minlength=CFG.vocab_size)
    assert (np.abs(moved_k - moved_f)
            <= np.maximum(counts, 1)[:, None] * 2.0 ** -7 * scale + 1e-12).all()
