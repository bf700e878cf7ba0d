"""Bucketization unit tests (reference: bucket flattening bucket.py:95-123 and
autotune split autotune_task_manager.py:86-119)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu import BucketPlan, TensorDtype, build_params, split_bucket_by_bucket_size
from bagua_tpu.bucket import relayout_flats
from bagua_tpu.define import TensorDeclaration


def _params():
    k = jax.random.PRNGKey(0)
    return {
        "a": jax.random.normal(k, (4, 5)),
        "b": jax.random.normal(k, (7,)),
        "c": jax.random.normal(k, (3, 3)),
    }


def test_build_params_reversed_dedup():
    named = build_params(_params())
    assert [p.name for p in named] == ["c", "b", "a"]
    assert named[0].numel == 9


def test_split_by_bucket_size():
    decls = [
        TensorDeclaration(name=f"t{i}", num_elements=100, dtype=TensorDtype.F32)
        for i in range(10)
    ]
    buckets = split_bucket_by_bucket_size(decls, 400)  # 400 bytes = 1 tensor each
    assert all(len(b) == 1 for b in buckets)
    buckets = split_bucket_by_bucket_size(decls, 800)
    assert len(buckets) == 5
    # everything lands somewhere exactly once
    names = [t.name for b in buckets for t in b]
    assert sorted(names) == sorted(d.name for d in decls)


def test_plan_flatten_roundtrip():
    params = _params()
    named = build_params(params)
    plan = BucketPlan.build(named, bucket_bytes=64, alignment=8)
    flats = plan.flatten_tree(params)
    assert all(f.shape[0] % 8 == 0 for f in flats)
    back = plan.unflatten_tree(flats, params)
    for k in params:
        np.testing.assert_allclose(np.asarray(back[k]), np.asarray(params[k]), rtol=1e-6)


def test_plan_signature_changes_with_bucketing():
    params = _params()
    named = build_params(params)
    p1 = BucketPlan.build(named, bucket_bytes=64)
    p2 = BucketPlan.build(named, bucket_bytes=10 ** 9)
    assert p1.signature() != p2.signature()
    assert len(p2.buckets) == 1


# ---- a tensor as large as a bucket is its own bucket, in its own shape ------


def _decl(name, nbytes, dtype=TensorDtype.F32):
    itemsize = 2 if dtype == TensorDtype.BF16 else 4
    return TensorDeclaration(name=name, num_elements=nbytes // itemsize,
                             dtype=dtype)


@pytest.mark.parametrize("sizes, bucket_size, want", [
    # a tensor >= bucket_size closes the open bucket first and stands alone
    ([("s0", 40), ("big", 400), ("s1", 40)], 400,
     [["s0"], ["big"], ["s1"]]),
    # ... also when it comes first, and when two of them are neighbours
    ([("big0", 800), ("big1", 400), ("s0", 40)], 400,
     [["big0"], ["big1"], ["s0"]]),
    # tensors below it are bucketed as before: appended, THEN the bucket
    # closes once it holds bucket_size bytes
    ([("a", 120), ("b", 120), ("c", 120), ("d", 120), ("e", 120)], 300,
     [["a", "b", "c"], ["d", "e"]]),
    # a small tensor may still tip a bucket over bucket_size (the old rule)
    ([("a", 200), ("b", 396), ("c", 8)], 400, [["a", "b"], ["c"]]),
    # nothing is large: one bucket, as before
    ([("a", 40), ("b", 40)], 10 ** 6, [["a", "b"]]),
])
def test_split_gives_a_bucket_sized_tensor_its_own_bucket(sizes, bucket_size,
                                                          want):
    decls = [_decl(n, b) for n, b in sizes]
    got = split_bucket_by_bucket_size(decls, bucket_size)
    assert [[t.name for t in b] for b in got] == want


def test_split_keeps_dtype_grouping_and_group_order():
    decls = [_decl("f0", 40), _decl("h0", 40, TensorDtype.BF16),
             _decl("fbig", 400), _decl("hbig", 400, TensorDtype.BF16),
             _decl("f1", 40)]
    got = split_bucket_by_bucket_size(
        decls, 400, param_group_info={"f1": 0, "f0": 1})
    names = [[t.name for t in b] for b in got]
    # dtypes in sorted order, a bucket never spans two, order kept inside
    by_dtype = {n: TensorDtype(t.dtype).value for b in got for t in b
                for n in [t.name]}
    assert all(len({by_dtype[n] for n in b}) == 1 for b in names)
    assert sorted(names) == sorted([["f0"], ["fbig"], ["f1"], ["h0"],
                                    ["hbig"]])
    # param_group_info still orders the tensors INSIDE a bucket
    got = split_bucket_by_bucket_size(
        [_decl("f0", 40), _decl("f1", 40)], 400,
        param_group_info={"f1": 0, "f0": 1})
    assert [[t.name for t in b] for b in got] == [["f1", "f0"]]


def _big_small_params():
    k = jax.random.PRNGKey(1)
    return {
        "emb": jax.random.normal(k, (16, 8)),        # 512 B: a bucket's worth
        "norm": jax.random.normal(k, (8,)),
        "w": jax.random.normal(k, (4, 8, 4)),        # 512 B
        "bias": jax.random.normal(k, (5,)),
        "scalar": jax.random.normal(k, ()),
    }


@pytest.mark.parametrize("alignment, shaped", [
    # (a lone small tensor between two big ones is its own bucket too)
    (1, {"w": (4, 8, 4), "emb": (16, 8), "bias": (5,)}),
    # 128 elements divide by 8: still no padding, still shaped
    (8, {"w": (4, 8, 4), "emb": (16, 8)}),
    # ... but not by 3: the padded bucket is a 1-D flat again
    (3, {}),
])
def test_shaped_buckets_roundtrip_bit_equal(alignment, shaped):
    params = _big_small_params()
    plan = BucketPlan.build(build_params(params), bucket_bytes=512,
                            alignment=alignment)
    got = {b.tensors[0].name: b.buffer_shape for b in plan.buckets
           if b.shaped}
    assert got == shaped
    flats = plan.flatten_tree(params)
    for b, f in zip(plan.buckets, flats):
        assert f.shape == b.buffer_shape
        if b.shaped:  # the buffer IS the tensor: nothing was computed
            assert f is params[b.tensors[0].name]
        else:
            assert f.shape == (b.padded_numel,)
    named = plan.unflatten_to_named(flats)
    back = plan.unflatten_tree(flats, params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))
        if k in shaped:
            assert named[k] is params[k]


def test_a_scalar_alone_in_its_bucket_stays_a_flat():
    plan = BucketPlan.build(build_params({"s": jnp.float32(3.0)}), 4)
    (b,) = plan.buckets
    assert not b.shaped and b.buffer_shape == (1,)
    (f,) = plan.flatten_tree({"s": jnp.float32(3.0)})
    assert f.shape == (1,)


@pytest.mark.parametrize("old_bytes, new_bytes", [
    (512, 10 ** 6),   # shaped -> one 1-D flat of everything
    (10 ** 6, 512),   # one 1-D flat -> shaped
    (512, 512),       # shaped on both sides
    (512, 64),        # shaped -> shaped, the small ones re-split
])
@pytest.mark.parametrize("stack", [0, 3])
def test_relayout_with_shaped_buckets_on_either_side(old_bytes, new_bytes,
                                                     stack):
    params = _big_small_params()
    named = build_params(params)
    old = BucketPlan.build(named, old_bytes, alignment=1)
    new = BucketPlan.build(named, new_bytes, alignment=4)
    flats = old.flatten_tree(params)
    want = new.flatten_tree(params)
    if stack:  # gossip state: a leading rank axis, row r = r + 1 times row 0
        lift = lambda f: jnp.stack([f * (r + 1) for r in range(stack)])
        flats, want = [lift(f) for f in flats], [lift(f) for f in want]
    got = relayout_flats(old, new, flats)
    assert len(got) == len(new.buckets)
    for g, w, b in zip(got, want, new.buckets):
        assert g.shape[g.ndim - len(b.buffer_shape):] == b.buffer_shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # a tensor that is its own bucket on both sides moves as it is
    own_old = {b.tensors[0].name: f for b, f in zip(old.buckets, flats)
               if b.shaped}
    for b, g in zip(new.buckets, got):
        if b.shaped and b.tensors[0].name in own_old:
            assert g is own_old[b.tensors[0].name]
    # and back again, bit for bit (padding re-zeroed)
    back = relayout_flats(new, old, got)
    for g, w in zip(back, flats):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("old_sidecar", [False, True])
def test_layout_descriptor_records_the_buffers_shapes(old_sidecar):
    from bagua_tpu.bucket import conform_flats

    params = _big_small_params()
    plan = BucketPlan.build(build_params(params), 512)
    desc = plan.layout_descriptor()
    if old_sidecar:  # as written before the shaped buckets: every buffer 1-D
        desc = [{k: v for k, v in d.items() if k != "buffer_shape"}
                for d in desc]
    rebuilt = BucketPlan.from_layout_descriptor(desc)
    assert rebuilt.signature() == plan.signature()
    saved = BucketPlan.saved_buffer_shapes(desc)
    if old_sidecar:
        assert saved == [(b.padded_numel,) for b in plan.buckets]
    else:
        assert saved == [b.buffer_shape for b in plan.buckets]
    # buffers as they were written -> the plan's own shapes: one reshape,
    # and the leaves come back bit for bit
    flats = plan.flatten_tree(params)
    written = [f.reshape(s) for f, s in zip(flats, saved)]
    for got, want in zip(conform_flats(rebuilt, written, saved), flats):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # unflatten_to_named takes a shaped bucket's buffer in either form
    named = rebuilt.unflatten_to_named(written)
    for k in params:
        np.testing.assert_array_equal(np.asarray(named[k]),
                                      np.asarray(params[k]))


# ---- a tensor of a megabyte stands alone whatever bucket_size says ---------

MIB = 1 << 20


def _split_by_the_rule_before_the_floor(tensor_list, bucket_size,
                                        param_group_info=None):
    """A frozen copy of ``split_bucket_by_bucket_size`` as it was until the
    floor (PR 33's rule: a tensor stands alone at ``nbytes >= bucket_size``),
    to hold the new rule to it wherever the floor does not reach."""
    param_group_info = param_group_info or {}
    dtypes = sorted({TensorDtype(t.dtype).value for t in tensor_list})
    buckets = []
    for dtype in dtypes:
        tmp, tmp_bytes = [], 0
        for td in [t for t in tensor_list
                   if TensorDtype(t.dtype).value == dtype]:
            if td.nbytes >= bucket_size:
                if tmp:
                    buckets.append(tmp)
                buckets.append([td])
                tmp, tmp_bytes = [], 0
                continue
            tmp_bytes += td.nbytes
            tmp.append(td)
            if tmp_bytes >= bucket_size:
                buckets.append(tmp)
                tmp, tmp_bytes = [], 0
        if tmp:
            buckets.append(tmp)
    return [sorted(b, key=lambda p: param_group_info.get(p.name, -1))
            for b in buckets]


def _plan_by_the_rule_before_the_floor(named, bucket_bytes, alignment=1):
    decls = [p.declaration() for p in named]
    return BucketPlan.from_declaration_buckets(
        _split_by_the_rule_before_the_floor(decls, bucket_bytes), named,
        alignment)


def test_the_floor_is_a_mebibyte():
    from bagua_tpu import bucket

    assert bucket.LONE_TENSOR_BYTES == MIB


@pytest.mark.parametrize("sizes, bucket_size, want", [
    # a bert layer under the cells' 10 MiB: each 4 MiB attention matrix
    # closes the open bucket and stands alone; the 4 KiB norm scale between
    # two of them is left a bucket of its own
    ([("n0", 4096), ("q", 4 * MIB), ("k", 4 * MIB), ("n1", 4096),
      ("o", 4 * MIB), ("n2", 4096)], 10 * MIB,
     [["n0"], ["q"], ["k"], ["n1"], ["o"], ["n2"]]),
    # exactly the floor stands alone; one element under it does not, and
    # keeps packing with what follows
    ([("a", MIB), ("b", MIB - 4), ("c", 4096)], 10 * MIB,
     [["a"], ["b", "c"]]),
    # tensors under the floor still fill a bucket up to bucket_size
    ([("a", MIB - 4), ("b", MIB - 4), ("c", MIB - 4), ("d", 8)], 2 * MIB,
     [["a", "b", "c"], ["d"]]),
    # a bfloat16 tensor: the rule reads bytes, not elements
    ([("h", MIB, TensorDtype.BF16), ("g", MIB - 2, TensorDtype.BF16),
      ("s", 64, TensorDtype.BF16)], 10 * MIB, [["h"], ["g", "s"]]),
    # bucket_size at the floor: the two thresholds are one
    ([("a", 40), ("b", MIB), ("c", 40)], MIB, [["a"], ["b"], ["c"]]),
])
def test_split_lets_a_tensor_of_a_megabyte_stand_alone(sizes, bucket_size,
                                                       want):
    decls = [_decl(*s) for s in sizes]
    got = split_bucket_by_bucket_size(decls, bucket_size)
    assert [[t.name for t in b] for b in got] == want


@pytest.mark.parametrize("bucket_size", [64, 400, 4096, MIB - 4, MIB])
def test_a_bucket_size_under_the_floor_gives_the_old_rules_split(bucket_size):
    """``min(bucket_size, floor)`` is ``bucket_size`` there: every plan built
    from kilobyte tensors, and every trial size of the autotuner under a
    megabyte, is what it was."""
    sizes = [("s0", 40), ("m0", 3 * MIB), ("s1", 400), ("s2", 396),
             ("m1", MIB), ("s3", 4096), ("m2", MIB - 4), ("s4", 8),
             ("h0", 2 * MIB, TensorDtype.BF16), ("h1", 64, TensorDtype.BF16)]
    decls = [_decl(*s) for s in sizes]
    group = {"s2": 0, "s1": 1}
    got = split_bucket_by_bucket_size(decls, bucket_size, group)
    want = _split_by_the_rule_before_the_floor(decls, bucket_size, group)
    assert [[t.name for t in b] for b in got] == [
        [t.name for t in b] for b in want]
    # ... and to the signature on a tree of kilobyte tensors
    named = build_params(_big_small_params())
    for alignment in (1, 8):
        assert (BucketPlan.build(named, bucket_size, alignment).signature()
                == _plan_by_the_rule_before_the_floor(
                    named, bucket_size, alignment).signature())


def test_lifting_the_floor_out_of_reach_is_the_old_rule(monkeypatch):
    """How the trainer tests build "the plan the old rule built" (they
    cannot hand the trainer a frozen copy): held to the copy here, at a
    ``bucket_size`` over the floor."""
    from bagua_tpu import bucket

    decls = [_decl(*s) for s in [
        ("n0", 4096), ("q", 4 * MIB), ("n1", 4096), ("o", 4 * MIB),
        ("big", 16 * MIB), ("n2", 4096)]]
    want = _split_by_the_rule_before_the_floor(decls, 10 * MIB)
    assert [[t.name for t in b] for b in want] == [
        ["n0", "q", "n1", "o"], ["big"], ["n2"]]
    assert split_bucket_by_bucket_size(decls, 10 * MIB) != want
    monkeypatch.setattr(bucket, "LONE_TENSOR_BYTES", 1 << 62)
    assert split_bucket_by_bucket_size(decls, 10 * MIB) == want


def _cell_plans(workload):
    """(plan, plan by the old rule) of a benchmark cell, from shapes alone:
    the cell's own builder makes model and trainer, ``jax.eval_shape`` the
    parameters — no weights."""
    from perfbench import cells

    cell = cells.resolve(workload)
    builder = cells.load_plugin("builders", cell.config["builder"])
    model, trainer = builder.make_trainer(
        cell, cell.traffic, jax.devices()[:cell.chips])
    shapes = jax.eval_shape(lambda: builder.make_params(model, 0))
    named = build_params(shapes)
    bucket_bytes = int(trainer.bucket_bytes)
    assert bucket_bytes == 10 * MIB  # every cell runs the default
    return (BucketPlan.build(named, bucket_bytes),
            _plan_by_the_rule_before_the_floor(named, bucket_bytes))


def _shaped_bytes_share(plan):
    nbytes = [b.padded_numel * np.dtype(b.dtype).itemsize
              for b in plan.buckets]
    return sum(n for n, b in zip(nbytes, plan.buckets) if b.shaped) / sum(
        nbytes)


@pytest.mark.parametrize("workload, buckets, shaped, share, old", [
    # 71 q / k / v kernels [1024, 16, 64], 24 out-projections
    # [16, 64, 1024] and 47 norm scales sat in 47 flats until the floor
    ("bert-large.squad384-dp1", 220, 220, 0.999, (125, 78, 0.79)),
    ("bert-large.squad384-dp4", 220, 220, 0.999, (125, 78, 0.79)),
    ("bert-large.squad384-accum4-dp1", 220, 220, 0.999, (125, 78, 0.79)),
    ("gpt2-medium.pretrain1024-dp1", 220, 220, 0.999, (125, 78, 0.81)),
    # its k / v kernels [2560, 4, 128] of 5.2 MB leave their flats
    ("smallthinker-21b-a3b.pretrain8192-dp1", 39, 35, 0.998,
     (32, 24, 0.99)),
])
def test_the_dense_cells_hold_every_megabyte_tensor_shaped(
        workload, buckets, shaped, share, old):
    plan, before = _cell_plans(workload)
    assert (len(before.buckets), sum(b.shaped for b in before.buckets)) == (
        old[:2])
    assert _shaped_bytes_share(before) < old[2]
    assert (len(plan.buckets), sum(b.shaped for b in plan.buckets)) == (
        buckets, shaped)
    for b in plan.buckets:
        for t in b.tensors:
            if t.numel * np.dtype(t.dtype).itemsize >= MIB:
                assert b.shaped and b.buffer_shape == tuple(t.shape), t.name
    assert _shaped_bytes_share(plan) >= share
    # the same tensors in the same order: only the boundaries moved
    assert plan.tensor_names == before.tensor_names


@pytest.mark.parametrize("workload, buckets, shaped", [
    ("olmoe-1b-7b.pretrain4096-dp1", 14, 13),
    ("ouro-2.6b.pretrain4096-b1-dp1", 75, 58),
])
def test_cells_with_no_tensor_between_floor_and_bucket_keep_their_plan(
        workload, buckets, shaped):
    """No tensor of theirs lies between 1 and 10 MiB (OLMoE's router is
    512 KiB), so the plan — and with it the compiled step — is the old
    rule's to the signature: the cells the traffic takes around the
    mechanism."""
    plan, before = _cell_plans(workload)
    assert plan.signature() == before.signature()
    assert (len(plan.buckets), sum(b.shaped for b in plan.buckets)) == (
        buckets, shaped)
    sizes = [t.numel * np.dtype(t.dtype).itemsize
             for b in plan.buckets for t in b.tensors]
    assert not [s for s in sizes if MIB <= s < 10 * MIB]


@pytest.mark.parametrize("jit", [False, True])
def test_flatten_unflatten_is_the_identity_under_the_lone_tensor_plan(jit):
    """One bert-wide layer with real ``[d, h, 64]`` kernels under the cells'
    10 MiB: the four attention matrices (4 MiB) and the three FFN matrices
    (exactly 1 MiB at d_ff 256) are buffers in their own shapes, the norm
    scales 1-D flats; leaves -> buffers -> leaves is the identity, bit for
    bit, and so is buffers -> leaves -> buffers."""
    from bagua_tpu.models.transformer import TransformerConfig, TransformerLM

    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=1024, n_heads=16, n_layers=1, d_ff=256,
        max_seq_len=16))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    named = build_params(params)
    plan = BucketPlan.build(named, 10 * MIB)
    by_name = {t.name: b for b in plan.buckets for t in b.tensors}
    kernels = {n: b for n, b in by_name.items() if "attn" in n
               and n.endswith("kernel")}
    assert sorted(b.buffer_shape for b in kernels.values()) == [
        (16, 64, 1024), (1024, 16, 64), (1024, 16, 64), (1024, 16, 64)]
    assert all(b.shaped for n, b in by_name.items()
               if n.endswith("kernel") and "mlp" in n)
    assert any(not b.shaped or len(b.buffer_shape) == 1
               for b in plan.buckets)

    flatten = jax.jit(plan.flatten_tree) if jit else plan.flatten_tree
    unflatten = (jax.jit(lambda f: plan.unflatten_tree(f, params)) if jit
                 else lambda f: plan.unflatten_tree(f, params))
    flats = flatten(params)
    assert [f.shape for f in flats] == [b.buffer_shape for b in plan.buckets]
    back = unflatten(flats)
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(flatten(back), flats):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the old rule's buffers hold the same elements: one relayout away
    before = _plan_by_the_rule_before_the_floor(named, 10 * MIB)
    assert len(before.buckets) < len(plan.buckets)
    moved = relayout_flats(before, plan, before.flatten_tree(params))
    for got, want in zip(moved, flats):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
