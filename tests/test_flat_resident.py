"""Flat-resident training state (ISSUE 4).

Pinned contracts:

* flat-resident and leaf layouts train the IDENTICAL trajectory — exact
  for elementwise optimizers (the leaf view is pure slicing, autodiff's
  scatter-add is the gradient flatten, elementwise updates commute with
  the relayout), within quantization tolerance for bytegrad;
* ``fuse_optimizer`` is unwrapped onto the resident bucket flats (no
  per-dtype concat traces) and matches the unfused optimizer exactly;
* autotune/overlap re-bucketing migrates resident state flat->flat
  (``relayout_flats``) without perturbing the trajectory;
* checkpoints round-trip across layouts AND plans:
  save-flat -> restore-leaf -> restore-flat continuity against
  ``golden.golden_task()``;
* ``flat_resident="off"`` reproduces the leaf construction exactly
  (leaf-pytree state, no flat containers anywhere in the step's HLO).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu import BaguaTrainer
from bagua_tpu.algorithms import (
    ByteGradAlgorithm,
    DecentralizedAlgorithm,
    GradientAllReduceAlgorithm,
    LowPrecisionDecentralizedAlgorithm,
    QAdamAlgorithm,
    ZeroOptimizerAlgorithm,
)
from bagua_tpu.bucket import BucketPlan, relayout_flats, split_bucket_by_bucket_size
from bagua_tpu.checkpoint import BaguaCheckpointManager
from bagua_tpu.contrib import fuse_optimizer
from bagua_tpu.models import MLP

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = 8
DIM = 12
NCLASS = 10
MODEL = MLP(features=(16, NCLASS))


def _loss_fn(params, batch):
    logits = MODEL.apply({"params": params}, batch["x"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["y"]
    ).mean()


def _params():
    return MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]


def _batches(steps, accum=1, seed=3):
    rng = np.random.default_rng(seed)
    return [
        {
            "x": rng.normal(size=(N * 2 * accum, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2 * accum,)).astype(
                np.int32
            ),
        }
        for _ in range(steps)
    ]


def _train(algo_factory, optimizer, mode, accum=1, steps=4, **kw):
    trainer = BaguaTrainer(
        _loss_fn, optimizer, algo_factory(), bucket_bytes=256,
        accum_steps=accum, autotune=False, flat_resident=mode, **kw,
    )
    state = trainer.init(_params())
    losses = []
    for batch in _batches(steps, accum):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    return np.array(losses), state, trainer


def _leaf_allclose(ta, sa, tb, sb, **kw):
    for a, b in zip(jax.tree.leaves(ta.unstack_params(sa)),
                    jax.tree.leaves(tb.unstack_params(sb))):
        if kw:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **kw)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- step equality: flat-resident vs leaf -----------------------------


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize(
    "algo_factory,optimizer,exact",
    [
        (GradientAllReduceAlgorithm, optax.sgd(0.1, momentum=0.9), True),
        (lambda: QAdamAlgorithm(warmup_steps=2), None, True),
        # the codec consumes identical flat buckets either way, but its
        # quantization levels may differ across platforms' fusion choices
        (lambda: ByteGradAlgorithm(hierarchical=False), optax.sgd(0.1),
         False),
    ],
    ids=["gradient_allreduce", "qadam", "bytegrad"],
)
def test_flat_matches_leaf(algo_factory, optimizer, exact, accum):
    l_leaf, st_leaf, tr_leaf = _train(algo_factory, optimizer, "off", accum)
    l_flat, st_flat, tr_flat = _train(algo_factory, optimizer, "on", accum)
    assert tr_flat._flat_resident and not tr_leaf._flat_resident
    # the resident state really is bucket-flat, and the leaf state is leaves
    assert set(st_flat.params.keys()) == {"flats", "local"}
    assert jax.tree_util.tree_structure(st_leaf.params) == (
        jax.tree_util.tree_structure(_params())
    )
    if exact:
        # two differently fused programs: the flat step sums each bucket's
        # gradient (and, for momentum, its update) as one 1-D buffer, the
        # leaf step per leaf, so XLA:CPU may associate the same sums in
        # another order — a few float32 ulp, never more
        np.testing.assert_allclose(l_flat, l_leaf, rtol=1e-6, atol=0)
        _leaf_allclose(tr_flat, st_flat, tr_leaf, st_leaf,
                       rtol=1e-6, atol=1e-8)
    else:
        np.testing.assert_allclose(l_flat, l_leaf, rtol=0.05, atol=0.02)


@pytest.mark.parametrize(
    "algo_factory,optimizer",
    [
        (lambda: DecentralizedAlgorithm(hierarchical=False), optax.sgd(0.1)),
        (lambda: LowPrecisionDecentralizedAlgorithm(hierarchical=False),
         optax.sgd(0.1)),
        (lambda: ZeroOptimizerAlgorithm(optax.adam(1e-2)), None),
    ],
    ids=["decentralized", "low_precision_decentralized", "zero"],
)
def test_flat_matches_leaf_gossip_and_zero(algo_factory, optimizer):
    """Gossip families carry the flat container under their stacked
    per-rank protocol; ZeRO's flat layout (previously unconditional on
    pure-dp) is now the ``auto`` resolution of the same knob."""
    l_leaf, st_leaf, tr_leaf = _train(algo_factory, optimizer, "off")
    l_flat, st_flat, tr_flat = _train(algo_factory, optimizer, "on")
    assert tr_flat._flat_resident and not tr_leaf._flat_resident
    np.testing.assert_array_equal(l_flat, l_leaf)
    # params: the gossip weight average fuses differently over flats vs
    # leaves on XLA:CPU — ~1-ulp jitter; losses above stay bit-equal
    _leaf_allclose(tr_flat, st_flat, tr_leaf, st_leaf,
                   rtol=1e-6, atol=1e-8)


def test_auto_engages_on_pure_dp_and_off_reproduces_leaf():
    _, st_auto, tr_auto = _train(GradientAllReduceAlgorithm, optax.sgd(0.1),
                                 "auto", steps=1)
    assert tr_auto._flat_resident
    # off: the exact leaf construction — leaf params/opt state, and the
    # compiled step contains none of the flat-container plumbing
    _, st_off, tr_off = _train(GradientAllReduceAlgorithm, optax.sgd(0.1),
                               "off", steps=1)
    assert not tr_off._flat_resident
    assert jax.tree_util.tree_structure(st_off.params) == (
        jax.tree_util.tree_structure(_params())
    )


def test_explicit_on_rejects_model_parallel_axes():
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn,
    )
    from jax.sharding import Mesh

    devs = np.array(jax.devices()).reshape(4, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    kw = dict(vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=32,
              max_seq_len=8)
    model = TransformerLM(TransformerConfig(tp_axis="tp", tp_size=2, **kw))
    with pytest.raises(ValueError, match="flat_resident='on'"):
        BaguaTrainer(
            lm_loss_fn(model), optax.sgd(0.1), GradientAllReduceAlgorithm(),
            mesh=mesh, dp_axes=("dp",), tp_axis="tp", bucket_bytes=4096,
            flat_resident="on",
        )


def test_auto_falls_back_to_leaf_for_shape_aware_optimizer():
    """`auto` must not silently change the math of shape-aware transforms
    (factored second moments read matrix shapes): the flat-safety probe
    fails them, auto keeps the leaf layout, and explicit `on` raises."""
    shape_aware = optax.adafactor(1e-3)
    trainer = BaguaTrainer(
        _loss_fn, shape_aware, GradientAllReduceAlgorithm(),
        bucket_bytes=256, autotune=False, flat_resident="auto",
    )
    state = trainer.init(_params())
    assert not trainer._flat_resident
    assert jax.tree_util.tree_structure(state.params) == (
        jax.tree_util.tree_structure(_params())
    )
    on = BaguaTrainer(
        _loss_fn, shape_aware, GradientAllReduceAlgorithm(),
        bucket_bytes=256, autotune=False, flat_resident="on",
    )
    with pytest.raises(ValueError, match="commute with flattening"):
        on.init(_params())


def test_checkpoint_fused_cross_layout_raises_actionably(tmp_path):
    """A fuse_optimizer wrapper's leaf-layout state has no leaf/flat
    mirror: the cross-layout restore must raise the actionable error, not
    an opaque orbax structure mismatch."""
    l, st, tr = _train(GradientAllReduceAlgorithm,
                       fuse_optimizer(optax.adam(1e-2)), "on", steps=1)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert tr.save_checkpoint(mgr, 1, st)
    mgr.wait()
    leaf = BaguaTrainer(
        _loss_fn, fuse_optimizer(optax.adam(1e-2)),
        GradientAllReduceAlgorithm(), bucket_bytes=256, autotune=False,
        flat_resident="off",
    )
    with pytest.raises(ValueError, match="fuse_optimizer"):
        leaf.restore_checkpoint(mgr, leaf.init(_params()))
    mgr.close()


def test_checkpoint_stacked_world_size_still_checked():
    """Gossip flat state carries a world-sized rank axis: an identical plan
    signature must NOT waive the world-size comparison for stacked
    checkpoints (unstacked alignment-1 state legitimately waives it)."""
    from bagua_tpu.checkpoint import BaguaCheckpointManager as M

    base = {"layout": "flat", "plan_signature": "abc", "world_size": 4,
            "bucket_bytes": 256, "plan_dependent": True}
    other = dict(base, world_size=8, bucket_bytes=128)
    # unstacked: same signature -> knob-only diffs pass
    M._check_layout(dict(base), dict(other))
    # stacked: the rank axis is world-sized -> must raise
    with pytest.raises(ValueError, match="checkpoint layout mismatch"):
        M._check_layout(dict(base, stacked=True), dict(other, stacked=True))


def test_env_registry_carries_flat_resident():
    from bagua_tpu import env

    assert "BAGUA_FLAT_RESIDENT" in env.ENV_REGISTRY
    os.environ["BAGUA_FLAT_RESIDENT"] = "off"
    try:
        assert env.get_flat_resident_mode() == "off"
        trainer = BaguaTrainer(_loss_fn, optax.sgd(0.1),
                               GradientAllReduceAlgorithm(),
                               bucket_bytes=256, autotune=False)
        trainer.init(_params())
        assert not trainer._flat_resident
    finally:
        del os.environ["BAGUA_FLAT_RESIDENT"]


# ---- fused optimizer on bucket flats ----------------------------------


def test_fused_on_flats_matches_unfused_adam():
    """Under flat residency the trainer unwraps ``fuse_optimizer`` and runs
    the inner transform on the resident bucket flats — exact step equality
    with the unfused optimizer, and no per-dtype repack in the program."""
    l_fused, st_fused, tr_fused = _train(
        GradientAllReduceAlgorithm, fuse_optimizer(optax.adam(1e-2)), "on"
    )
    l_plain, st_plain, tr_plain = _train(
        GradientAllReduceAlgorithm, optax.adam(1e-2), "on"
    )
    l_leaf, st_leaf, tr_leaf = _train(
        GradientAllReduceAlgorithm, optax.adam(1e-2), "off"
    )
    assert tr_fused._opt is tr_fused.optimizer.fused_inner
    assert tr_plain._opt is tr_plain.optimizer
    np.testing.assert_array_equal(l_fused, l_plain)
    np.testing.assert_allclose(l_fused, l_leaf, rtol=1e-6, atol=1e-7)
    # grouping the elementwise update per-bucket vs per-leaf leaves ~1-ulp
    # fusion jitter on XLA:CPU — same bound the leaf fused wrapper carries
    _leaf_allclose(tr_fused, st_fused, tr_leaf, st_leaf,
                   rtol=1e-6, atol=1e-8)
    # the fused wrapper's own state never appears: the opt state is the
    # inner transform's, laid out over the bucket flats
    from bagua_tpu.contrib.fused_optimizer import _FusedState

    assert not any(
        isinstance(x, _FusedState)
        for x in jax.tree_util.tree_leaves(
            st_fused.opt_state,
            is_leaf=lambda x: isinstance(x, _FusedState),
        )
    )


def test_fused_leaf_layout_still_wraps():
    """In the leaf layout the wrapper's per-dtype flatten still runs (and
    still matches plain adam) — the unwrap is a flat-residency-only move."""
    l_fused, st_fused, tr_fused = _train(
        GradientAllReduceAlgorithm, fuse_optimizer(optax.adam(1e-2)), "off"
    )
    assert tr_fused._opt is tr_fused.optimizer
    l_plain, st_plain, tr_plain = _train(
        GradientAllReduceAlgorithm, optax.adam(1e-2), "off"
    )
    _leaf_allclose(tr_fused, st_fused, tr_plain, st_plain,
                   rtol=1e-6, atol=1e-8)


# ---- re-bucket migration ----------------------------------------------


def test_rebucket_migrates_resident_state():
    """An autotune-style rebucket mid-run relays the resident params AND
    optimizer state flat->flat; the trajectory is unperturbed (elementwise
    state relayouts exactly; padding stays zero)."""
    base, _, _ = _train(GradientAllReduceAlgorithm, optax.adam(1e-2), "on",
                        steps=6)

    trainer = BaguaTrainer(
        _loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
        bucket_bytes=256, autotune=False, flat_resident="on",
    )
    state = trainer.init(_params())
    n_before = len(trainer._plan.buckets)
    losses = []
    for i, batch in enumerate(_batches(6)):
        if i == 3:
            decls = [t.declaration() for b in trainer._plan.buckets
                     for t in b.tensors]
            old_sig = trainer._plan.signature()
            trainer.rebucket(split_bucket_by_bucket_size(decls, 1024))
            assert trainer._plan.signature() != old_sig
            assert trainer._pending_state_migration is not None
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert len(trainer._plan.buckets) != n_before
    assert trainer._pending_state_migration is None
    np.testing.assert_array_equal(np.array(losses), base)


@pytest.mark.parametrize("donate", [True, False])
def test_rebucket_migration_consumes_the_old_flats(donate):
    """The migration in front of a donating step frees the old plan's
    buffers itself: a caller that still names the old state (a driver's
    ``job.state``) must not keep a second copy of it on the device while
    the recompiled step loads.  Without donation the old state stays."""
    trainer = BaguaTrainer(
        _loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
        bucket_bytes=256, autotune=False, flat_resident="on", donate=donate,
    )
    state = trainer.init(_params())
    state, _ = trainer.train_step(state, _batches(1)[0])
    held = state  # what a caller may still hold across the step
    decls = [t.declaration() for b in trainer._plan.buckets
             for t in b.tensors]
    trainer.rebucket(split_bucket_by_bucket_size(decls, 1024))
    migrated = trainer._pending_state_migration(held)

    def flats_of(st):
        return [f for tree in (st.params, st.opt_state)
                for x in jax.tree.leaves(tree,
                                         is_leaf=trainer._is_flat_container)
                if trainer._is_flat_container(x) for f in x["flats"]]

    old, new = flats_of(held), flats_of(migrated)
    # a tensor that is its own bucket under both plans (dense_0.bias) is
    # MOVED: the new state holds the very buffer, so it is never freed
    moved = [f for f in old if any(f is g for g in new)]
    assert len(moved) == 3 and not any(f.is_deleted() for f in moved)
    rest = [f for f in old if not any(f is g for g in moved)]
    assert rest and all(f.is_deleted() == donate for f in rest)
    trainer._pending_state_migration = None
    state, loss = trainer.train_step(migrated, _batches(1)[0])
    assert np.isfinite(float(loss))


def test_rebucket_migrates_gossip_peer_state():
    """Plan-keyed algorithm state (tracked peer weights) migrates through
    the Algorithm.relayout_algo_state hook — stacked rank axis included."""
    fac = lambda: DecentralizedAlgorithm(
        hierarchical=False, track_peer_weights=True, communication_interval=2
    )
    base, _, _ = _train(fac, optax.sgd(0.1), "on", steps=6)
    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1), fac(), bucket_bytes=256, autotune=False,
        flat_resident="on",
    )
    state = trainer.init(_params())
    losses = []
    for i, batch in enumerate(_batches(6)):
        if i == 3:
            decls = [t.declaration() for b in trainer._plan.buckets
                     for t in b.tensors]
            old_sig = trainer._plan.signature()
            trainer.rebucket(split_bucket_by_bucket_size(decls, 1024))
            assert trainer._plan.signature() != old_sig
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    np.testing.assert_array_equal(np.array(losses), base)


def test_save_checkpoint_refuses_pending_migration(tmp_path):
    """Between rebucket() and the next train_step the state still holds the
    OLD plan's buffers; a sidecar written then would describe the wrong
    layout — the save must refuse actionably."""
    _, state, trainer = _train(GradientAllReduceAlgorithm, optax.sgd(0.1),
                               "on", steps=1)
    decls = [t.declaration() for b in trainer._plan.buckets
             for t in b.tensors]
    trainer.rebucket(split_bucket_by_bucket_size(decls, 1024))
    assert trainer._pending_state_migration is not None
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    with pytest.raises(RuntimeError, match="migration pending"):
        trainer.save_checkpoint(mgr, 1, state)
    # stale state against the new plan is also detected at the leaf view
    with pytest.raises(ValueError, match="different bucket plan"):
        trainer.unstack_params(state)
    # one train_step applies the migration; everything works again
    state, _ = trainer.train_step(state, _batches(1)[0])
    assert trainer.save_checkpoint(mgr, 1, state)
    mgr.close()


def test_relayout_flats_rejects_resized_tensors():
    """A same-name tensor whose size changed between plans must raise, not
    silently shift every later offset."""
    from bagua_tpu.tensor import NamedParam

    a1 = NamedParam("a", (), (3,), np.dtype("float32"))
    a2 = NamedParam("a", (), (4,), np.dtype("float32"))
    b = NamedParam("b", (), (2,), np.dtype("float32"))
    one = BucketPlan.build([a1, b], bucket_bytes=1024)
    two = BucketPlan.build([a2, b], bucket_bytes=1024)
    flats = one.flatten_tree({"a": jnp.arange(3.0), "b": jnp.arange(2.0)})
    with pytest.raises(ValueError, match="sizes differ"):
        relayout_flats(one, two, flats)


def test_relayout_flats_unit():
    """flat->flat relayout: segments move by name, old padding dropped,
    new padding zero-filled, stacked leading axes preserved."""
    from bagua_tpu.tensor import NamedParam

    a = NamedParam("a", (), (3,), np.dtype("float32"))
    b = NamedParam("b", (), (2, 2), np.dtype("float32"))
    one = BucketPlan.build([a, b], bucket_bytes=1024, alignment=8)
    two = BucketPlan.build([a, b], bucket_bytes=4, alignment=4)
    assert len(one.buckets) == 1 and len(two.buckets) == 2

    tree = {"a": jnp.arange(3.0), "b": jnp.arange(4.0).reshape(2, 2) + 10}
    flats_one = one.flatten_tree(tree)
    flats_two = relayout_flats(one, two, flats_one)
    for got, want in zip(flats_two, two.flatten_tree(tree)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # round trip restores the original (padding re-zeroed)
    back = relayout_flats(two, one, flats_two)
    np.testing.assert_array_equal(np.asarray(back[0]),
                                  np.asarray(flats_one[0]))
    # stacked leading axis (gossip state): relayout slices the LAST axis
    stacked = [jnp.stack([f, f * 2]) for f in flats_one]
    out = relayout_flats(one, two, stacked)
    for got, want in zip(out, flats_two):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want) * 2)


# ---- checkpoint continuity across layouts ------------------------------


def test_checkpoint_flat_leaf_flat_continuity(tmp_path):
    """save-flat -> restore-leaf -> restore-flat (different plan) against
    the uninterrupted golden-task trajectory: bit-equal while both sides
    run the same compiled program, within a few float32 ulp once the
    layout (and with it the program) differs."""
    import golden

    loss_fn, params, batch = golden.golden_task()

    def make(mode, bucket_bytes=256):
        return BaguaTrainer(
            loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
            bucket_bytes=bucket_bytes, autotune=False, flat_resident=mode,
        )

    def run(trainer, state, n):
        losses = []
        for _ in range(n):
            state, loss = trainer.train_step(state, batch)
            losses.append(float(loss))
        return state, losses

    # uninterrupted reference: 9 steps
    t_ref = make("on")
    s_ref, base = run(t_ref, t_ref.init(params), 9)

    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)

    # 3 flat steps, save in FLAT layout
    t1 = make("on")
    s1, l1 = run(t1, t1.init(params), 3)
    assert t1.save_checkpoint(mgr, 3, s1)
    mgr.wait()

    # restore into a LEAF trainer (canonical-leaf fallback), 3 more steps
    t2 = make("off")
    step, s2 = t2.restore_checkpoint(mgr, t2.init(params))
    assert step == 3
    assert jax.tree_util.tree_structure(s2.params) == (
        jax.tree_util.tree_structure(params)
    )
    s2, l2 = run(t2, s2, 3)
    assert t2.save_checkpoint(mgr, 6, s2)
    mgr.wait()

    # restore the LEAF checkpoint into a FLAT trainer under a DIFFERENT
    # bucket plan, 3 more steps
    t3 = make("on", bucket_bytes=4096)
    s3_init = t3.init(params)
    assert t3._plan.signature() != t1._plan.signature()
    step, s3 = t3.restore_checkpoint(mgr, s3_init, step=6)
    assert step == 6
    assert set(s3.params.keys()) == {"flats", "local"}
    s3, l3 = run(t3, s3, 3)

    # same layout, same plan, same program: the bits
    np.testing.assert_array_equal(np.array(l1), np.array(base[:3]))
    # the leaf step and the re-planned flat step are other programs than
    # the reference's: their bucket sums may associate differently
    np.testing.assert_allclose(np.array(l2 + l3), np.array(base[3:]),
                               rtol=1e-6, atol=0)
    mgr.close()


def test_checkpoint_flat_to_flat_replan(tmp_path):
    """A flat checkpoint restores into a flat trainer with ANOTHER plan via
    flat->flat relayout — no leaf materialization on either side."""
    import golden

    loss_fn, params, batch = golden.golden_task()

    def make(bucket_bytes):
        return BaguaTrainer(
            loss_fn, optax.sgd(0.1, momentum=0.9),
            GradientAllReduceAlgorithm(), bucket_bytes=bucket_bytes,
            autotune=False, flat_resident="on",
        )

    t_ref = make(256)
    s_ref = t_ref.init(params)
    base = []
    for _ in range(6):
        s_ref, loss = t_ref.train_step(s_ref, batch)
        base.append(float(loss))

    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    t1 = make(256)
    s1 = t1.init(params)
    for _ in range(3):
        s1, _ = t1.train_step(s1, batch)
    assert t1.save_checkpoint(mgr, 3, s1)
    mgr.wait()

    t2 = make(4096)
    s2_init = t2.init(params)
    assert t2._plan.signature() != t1._plan.signature()
    step, s2 = t2.restore_checkpoint(mgr, s2_init)
    tail = []
    for _ in range(3):
        s2, loss = t2.train_step(s2, batch)
        tail.append(float(loss))
    np.testing.assert_array_equal(np.array(tail), np.array(base[3:]))
    mgr.close()


def test_checkpoint_zero_cross_plan_still_blocked(tmp_path):
    """Sharded-opt-state ZeRO keeps the actionable cross-plan error: its
    per-chunk optimizer states have no host-side conversion."""
    import golden

    loss_fn, params, batch = golden.golden_task()

    def make(bucket_bytes):
        return BaguaTrainer(
            loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
            bucket_bytes=bucket_bytes, autotune=False,
        )

    t1 = make(256)
    s1 = t1.init(params)
    s1, _ = t1.train_step(s1, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert t1.save_checkpoint(mgr, 1, s1)
    mgr.wait()
    t2 = make(4096)
    s2_init = t2.init(params)
    assert t2._plan.signature() != t1._plan.signature()
    with pytest.raises(ValueError, match="checkpoint layout mismatch"):
        t2.restore_checkpoint(mgr, s2_init)
    mgr.close()


# ---- eval + leaf views -------------------------------------------------


def test_eval_and_unstack_under_flat_residency():
    _, state, trainer = _train(GradientAllReduceAlgorithm, optax.sgd(0.1),
                               "on", steps=2)
    batch = _batches(1)[0]
    e = float(trainer.eval_step(state, trainer.shard_batch(batch)))
    assert np.isfinite(e)
    leaves = trainer.unstack_params(state)
    assert jax.tree_util.tree_structure(leaves) == (
        jax.tree_util.tree_structure(_params())
    )
    # the leaf view round-trips through the plan exactly
    reflat = trainer._plan.flatten_tree(leaves)
    for a, b in zip(reflat, state.params["flats"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- a tensor as large as a bucket is its own bucket, in its own shape ------


def _shaped(trainer):
    return {b.tensors[0].name: i for i, b in enumerate(trainer._plan.buckets)
            if b.shaped}


@pytest.mark.parametrize("accum", [1, 4])
def test_shaped_flat_trajectory_is_the_leaf_layouts_bit_for_bit(accum):
    """At 256-byte buckets both kernels of the MLP are as large as a bucket:
    each stands alone and the resident state holds it — parameter, moments,
    gradient — in its own shape.  The step then does to every leaf exactly
    what the leaf layout's step does, so the trajectories agree to the bit.
    ``accum=4`` goes through the overlap scheduler's one-time readiness
    re-bucketing (same split rule, flat->flat migration) on the way."""
    opt = optax.adam(1e-2)
    l_leaf, st_leaf, tr_leaf = _train(GradientAllReduceAlgorithm, opt, "off",
                                      accum, steps=5)
    l_flat, st_flat, tr_flat = _train(GradientAllReduceAlgorithm, opt, "on",
                                      accum, steps=5)
    assert tr_flat._flat_resident and not tr_leaf._flat_resident
    assert tr_flat._overlap_ordered == (accum > 1)
    shaped = _shaped(tr_flat)
    assert {"dense_0.kernel", "dense_1.kernel"} <= set(shaped)
    leaves = tr_flat.unstack_params(st_flat)
    mu = st_flat.opt_state[0].mu
    for name, i in shaped.items():
        layer, leaf = name.split(".")
        want = leaves[layer][leaf].shape
        assert st_flat.params["flats"][i].shape == want
        assert mu["flats"][i].shape == want
    np.testing.assert_array_equal(l_flat, l_leaf)
    _leaf_allclose(tr_flat, st_flat, tr_leaf, st_leaf)


def _as_written_before_shaped_buckets(trainer, state):
    """``state`` and its sidecar as a trainer older than the shaped buckets
    wrote them: every bucket buffer 1-D, no ``buffer_shape`` in the layout."""
    is_zp = trainer._is_flat_container

    def ravel(x):
        if is_zp(x):
            return {"flats": tuple(f.reshape(-1) for f in x["flats"]),
                    "local": x["local"]}
        return x

    meta = trainer.checkpoint_layout_metadata()
    meta["flat_layout"] = [
        {k: v for k, v in b.items() if k != "buffer_shape"}
        for b in meta["flat_layout"]
    ]
    old = state._replace(
        params=jax.tree.map(ravel, state.params, is_leaf=is_zp),
        opt_state=jax.tree.map(ravel, state.opt_state, is_leaf=is_zp))
    return old, meta


@pytest.mark.parametrize("target", [
    "same_plan", "other_plan", "leaf", "zero_same_plan",
])
def test_restore_of_a_checkpoint_written_all_1d(tmp_path, target):
    """A sidecar that describes the old all-1-D layout still restores: the
    buffers hold the same elements in the same order, so the restore asks
    the checkpoint for the shapes it was written in and reshapes once —
    under the identical plan (ZeRO's plan-locked chunk states included),
    across plans, and into the leaf layout."""
    zero = target.startswith("zero")

    def make(mode="on", bucket_bytes=256):
        if zero:
            return BaguaTrainer(
                _loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
                bucket_bytes=bucket_bytes, autotune=False)
        return BaguaTrainer(
            _loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
            bucket_bytes=bucket_bytes, autotune=False, flat_resident=mode)

    batches = _batches(6)
    t_ref = make()
    s_ref = t_ref.init(_params())
    base = []
    for b in batches:
        s_ref, loss = t_ref.train_step(s_ref, b)
        base.append(float(loss))

    t1 = make()
    s1 = t1.init(_params())
    for b in batches[:3]:
        s1, _ = t1.train_step(s1, b)
    assert _shaped(t1)  # the live state does hold shaped buffers
    old_state, old_meta = _as_written_before_shaped_buckets(t1, s1)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(3, old_state, metadata=old_meta)
    mgr.wait()

    t2 = {"same_plan": make, "zero_same_plan": make,
          "other_plan": lambda: make(bucket_bytes=4096),
          "leaf": lambda: make("off")}[target]()
    step, s2 = t2.restore_checkpoint(mgr, t2.init(_params()))
    assert step == 3
    if target != "leaf":
        assert [f.shape for f in s2.params["flats"]] == [
            b.buffer_shape for b in t2._plan.buckets]
    tail = []
    for b in batches[3:]:
        s2, loss = t2.train_step(s2, b)
        tail.append(float(loss))
    if target in ("same_plan", "zero_same_plan"):
        np.testing.assert_array_equal(np.array(tail), np.array(base[3:]))
    else:  # another program: bucket sums may associate differently
        np.testing.assert_allclose(np.array(tail), np.array(base[3:]),
                                   rtol=1e-6, atol=0)
    mgr.close()


def test_shaped_bytes_share_gauge_is_set_with_the_step_program():
    from bagua_tpu.obs import export
    from bagua_tpu.telemetry import counters

    assert export.is_registered("comm/shaped_bytes_share")
    _, _, trainer = _train(GradientAllReduceAlgorithm, optax.sgd(0.1), "on",
                           steps=1)
    nbytes = {b.tensors[0].name: b.numel * 4 for b in trainer._plan.buckets}
    plan_bytes = sum(b.padded_numel * 4 for b in trainer._plan.buckets)
    want = sum(nbytes[n] for n in _shaped(trainer)) / plan_bytes
    assert 0.9 < want <= 1.0
    assert counters.get("comm/shaped_bytes_share") == pytest.approx(want)
    # a plan with nothing as large as a bucket holds nothing in its own shape
    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        bucket_bytes=10 ** 6, autotune=False, flat_resident="on")
    state = trainer.init(_params())
    trainer.train_step(state, _batches(1)[0])
    assert not _shaped(trainer)
    assert counters.get("comm/shaped_bytes_share") == 0


# ---- a tensor over the floor stands alone under any bucket_bytes -----------
# (bucket.LONE_TENSOR_BYTES is a megabyte; the toys' matrices are hundreds of
# bytes, so the tests lower the floor under them — and lift it out of reach
# for "the plan the old rule built")

OLD_RULE = 1 << 62   # min(bucket_size, floor) == bucket_size: PR 33's rule


def _set_floor(monkeypatch, nbytes):
    from bagua_tpu import bucket

    monkeypatch.setattr(bucket, "LONE_TENSOR_BYTES", nbytes)


def _named_state(trainer, state):
    """Parameters and both moments as named tensors, whatever the plan."""
    plan = trainer._plan
    named = {}
    for kind, tree in (("p", state.params), ("mu", state.opt_state[0].mu),
                       ("nu", state.opt_state[0].nu)):
        for name, x in plan.unflatten_to_named(tree["flats"]).items():
            named[kind + ":" + name] = np.asarray(x)
    return named


@pytest.mark.parametrize("saved_under", ["old_rule", "new_rule"])
def test_restore_across_the_lone_tensor_floor(tmp_path, monkeypatch,
                                              saved_under):
    """A flat-resident state saved under a plan the old rule built (both MLP
    kernels inside one 1-D flat with the biases) restores under the new
    plan (each kernel a buffer in its own shape) to the same named tensors
    — parameters and both moments — through the changed-plan path the
    sidecar's layout drives; and a state saved now restores now, into the
    identical plan, to the same program's bits."""

    def make(floor):
        _set_floor(monkeypatch, floor)
        trainer = BaguaTrainer(
            _loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
            bucket_bytes=4096, autotune=False, flat_resident="on")
        return trainer, trainer.init(_params())

    batches = _batches(6)
    t_ref, s_ref = make(256)
    base = []
    for b in batches:
        s_ref, loss = t_ref.train_step(s_ref, b)
        base.append(float(loss))

    t1, s1 = make(OLD_RULE if saved_under == "old_rule" else 256)
    for b in batches[:3]:
        s1, _ = t1.train_step(s1, b)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert t1.save_checkpoint(mgr, 3, s1)
    mgr.wait()
    saved = _named_state(t1, s1)

    t2, s2_init = make(256)
    # (a bias left alone between two lone kernels is its own 1-D buffer)
    assert {"dense_0.kernel", "dense_1.kernel"} <= set(_shaped(t2))
    if saved_under == "old_rule":
        assert not _shaped(t1) and len(t1._plan.buckets) == 1
        assert t1._plan.signature() != t2._plan.signature()
    else:
        assert t1._plan.signature() == t2._plan.signature()
    step, s2 = t2.restore_checkpoint(mgr, s2_init)
    assert step == 3
    assert [f.shape for f in s2.params["flats"]] == [
        b.buffer_shape for b in t2._plan.buckets]
    restored = _named_state(t2, s2)
    assert sorted(restored) == sorted(saved)
    for name, want in saved.items():
        np.testing.assert_array_equal(restored[name], want, err_msg=name)
    tail = []
    for b in batches[3:]:
        s2, loss = t2.train_step(s2, b)
        tail.append(float(loss))
    if saved_under == "new_rule":
        np.testing.assert_array_equal(np.array(tail), np.array(base[3:]))
    else:  # the first three steps ran another program
        np.testing.assert_allclose(np.array(tail), np.array(base[3:]),
                                   rtol=1e-6, atol=0)
    mgr.close()


def _attention_toy():
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn,
    )

    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=16, dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return lm_loss_fn(model), params


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("family", ["allreduce", "zero"])
def test_the_lone_tensor_plan_trains_the_old_rules_trajectory(
        monkeypatch, family, accum):
    """A two-layer LM whose ``[32, 2, 16]`` attention kernels (4 KiB) cross
    a floor lowered to 4 KiB: under 64 KiB buckets the old rule packs them
    into flats with the norm scales, the new plan holds each in its own
    shape.  Three steps under either plan are the same training run to
    float32 round-off — through the plain step and the ``lax.scan``
    accumulation (with its one-time readiness re-bucketing), for the fused
    all-reduce and for ZeRO, which cuts every bucket into per-rank chunks
    and ravels a shaped buffer where it cuts."""
    loss_fn, params = _attention_toy()
    rng = np.random.default_rng(5)
    batches = [{"tokens": rng.integers(0, 64, size=(N * accum, 9)).astype(
        np.int32)} for _ in range(3)]

    def run(floor):
        _set_floor(monkeypatch, floor)
        if family == "zero":
            trainer = BaguaTrainer(
                loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
                bucket_bytes=1 << 16, accum_steps=accum, autotune=False)
        else:
            trainer = BaguaTrainer(
                loss_fn, optax.adam(1e-2), GradientAllReduceAlgorithm(),
                bucket_bytes=1 << 16, accum_steps=accum, autotune=False,
                flat_resident="on")
        state = trainer.init(params)
        losses = []
        for b in batches:
            state, loss = trainer.train_step(state, b)
            losses.append(float(loss))
        return np.array(losses), state, trainer

    l_old, s_old, t_old = run(OLD_RULE)
    l_new, s_new, t_new = run(4096)
    kernels = {n for n in _shaped(t_new) if ".attn." in n}
    assert len(kernels) == 8  # q, k, v, o of both layers
    assert not any(".attn." in n for n in _shaped(t_old))
    assert len(t_new._plan.buckets) > len(t_old._plan.buckets)
    assert t_new._overlap_ordered == (accum > 1 and family == "allreduce")
    np.testing.assert_allclose(l_new, l_old, rtol=1e-6, atol=0)
    _leaf_allclose(t_new, s_new, t_old, s_old, rtol=1e-5, atol=1e-7)
