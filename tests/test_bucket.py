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
