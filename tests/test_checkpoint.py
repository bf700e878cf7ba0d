"""Checkpoint/resume tests (SURVEY.md §5.4: the TPU build needs a real
orbax-style checkpoint subsystem; reference only hand-rolled torch.save)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.checkpoint import BaguaCheckpointManager
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.models.mlp import MLP
from bagua_tpu.parallel.mesh import build_mesh

N_DEVICES = 8


def _setup():
    model = MLP(features=(16, 8))
    mesh = build_mesh({"dp": N_DEVICES})
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jnp.argmax(x @ jax.random.normal(jax.random.PRNGKey(1), (4, 8)), -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    def new_trainer(**kw):
        return BaguaTrainer(loss_fn, optax.sgd(0.1),
                            GradientAllReduceAlgorithm(), mesh=mesh, **kw)

    return new_trainer, params, {"x": x, "y": y}


def test_save_restore_resume_equals_uninterrupted(tmp_path):
    new_trainer, params, batch = _setup()

    # uninterrupted reference run: 6 steps
    t0 = new_trainer()
    s = t0.init(params)
    ref_losses = []
    for _ in range(6):
        s, loss = t0.train_step(s, batch)
        ref_losses.append(float(loss))

    # interrupted run: 3 steps, save, "restart", restore, 3 more steps
    t1 = new_trainer()
    s1 = t1.init(params)
    for _ in range(3):
        s1, _ = t1.train_step(s1, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(3, s1)
    mgr.wait()

    t2 = new_trainer()
    s2 = t2.init(params)  # fresh (wrong) state, then restored over
    step, s2 = mgr.restore(s2)
    assert step == 3
    resumed = []
    for _ in range(3):
        s2, loss = t2.train_step(s2, batch)
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, ref_losses[3:], rtol=1e-6)
    mgr.close()


def test_retention_pruning(tmp_path):
    new_trainer, params, batch = _setup()
    t = new_trainer()
    s = t.init(params)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2,
                                 async_save=False)
    for step in range(5):
        s, _ = t.train_step(s, batch)
        mgr.save(step, s)
    mgr.wait()
    assert mgr.latest_step() == 4
    assert len(mgr._mgr.all_steps()) <= 2
    mgr.close()


def test_try_restore_empty_dir(tmp_path):
    new_trainer, params, _ = _setup()
    t = new_trainer()
    s = t.init(params)
    mgr = BaguaCheckpointManager(str(tmp_path / "none"), async_save=False)
    step, s2 = mgr.try_restore(s)
    assert step is None and s2 is s
    mgr.close()


def test_save_restore_tp_sharded_state(tmp_path):
    """Checkpoint round-trip with tensor-parallel (per-dim sharded) state:
    restore must land tp leaves back on their NamedShardings so the jitted
    step accepts them (SURVEY.md §5.4 + the tp axis added this round)."""
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn, tp_param_dim,
    )
    from bagua_tpu.parallel.tensor_parallel import globalize_tp_params

    TP = 4
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32,
                            tp_axis="tp", tp_size=TP)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 9), 0, 64)

    def new_trainer():
        return BaguaTrainer(
            lm_loss_fn(model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
            mesh=build_mesh({"dp": 2, "tp": TP}), tp_axis="tp",
            autotune=False,
        )

    params = globalize_tp_params(
        model.init(jax.random.PRNGKey(1), tokens[:2, :-1])["params"],
        jax.random.PRNGKey(2), TP, tp_param_dim,
    )
    batch_maker = new_trainer()
    batch = batch_maker.shard_batch({"tokens": tokens})

    t0 = new_trainer()
    s = t0.init(params)
    ref = []
    for _ in range(4):
        s, loss = t0.train_step(s, batch)
        ref.append(float(loss))

    t1 = new_trainer()
    s1 = t1.init(params)
    for _ in range(2):
        s1, _ = t1.train_step(s1, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(2, s1)
    mgr.wait()

    t2 = new_trainer()
    s2 = t2.init(params)
    step, s2 = mgr.restore(s2)
    assert step == 2
    resumed = []
    for _ in range(2):
        s2, loss = t2.train_step(s2, batch)
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, ref[2:], rtol=1e-6)
    mgr.close()


def test_save_restore_zero_sharded_opt_state(tmp_path):
    """Checkpoint round-trip with ZeRO-1 (per-rank chunk) optimizer state:
    restore must land each rank's opt-state slice back on its shard so the
    jitted step accepts the resumed state."""
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm

    model = MLP(features=(16, 8))
    mesh = build_mesh({"dp": N_DEVICES})
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jnp.argmax(x @ jax.random.normal(jax.random.PRNGKey(1), (4, 8)), -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    def new_trainer():
        return BaguaTrainer(
            loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
            mesh=mesh, bucket_bytes=256,
        )

    batch = {"x": x, "y": y}
    t0 = new_trainer()
    s = t0.init(params)
    ref = []
    for _ in range(6):
        s, loss = t0.train_step(s, batch)
        ref.append(float(loss))

    t1 = new_trainer()
    s1 = t1.init(params)
    for _ in range(3):
        s1, _ = t1.train_step(s1, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(3, s1)
    mgr.wait()

    t2 = new_trainer()
    s2 = t2.init(params)
    step, s2 = mgr.restore(s2)
    assert step == 3
    resumed = []
    for _ in range(3):
        s2, loss = t2.train_step(s2, batch)
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, ref[3:], rtol=1e-6)
    mgr.close()


def test_save_restore_pp_sharded_state(tmp_path):
    """Checkpoint round-trip with pipeline-parallel (stage-stacked) state."""
    from bagua_tpu.models.transformer import TransformerConfig
    from bagua_tpu.parallel.pipeline import (
        PipelinedTransformerLM, globalize_pp_params, pp_lm_loss_fn,
    )

    PP = 4
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=4,
                            d_ff=64, max_seq_len=8, dtype=jnp.float32)
    model = PipelinedTransformerLM(cfg, pp_size=PP, n_microbatches=2)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 9), 0, 64)

    def new_trainer():
        return BaguaTrainer(
            pp_lm_loss_fn(model), optax.adam(1e-2),
            GradientAllReduceAlgorithm(),
            mesh=build_mesh({"dp": 2, "pp": PP}), pp_axis="pp",
            autotune=False,
        )

    params = globalize_pp_params(
        model.init(jax.random.PRNGKey(1), tokens[:2])["params"],
        jax.random.PRNGKey(2), PP,
    )
    batch = new_trainer().shard_batch({"tokens": tokens})

    t0 = new_trainer()
    s = t0.init(params)
    ref = []
    for _ in range(4):
        s, loss = t0.train_step(s, batch)
        ref.append(float(loss))

    t1 = new_trainer()
    s1 = t1.init(params)
    for _ in range(2):
        s1, _ = t1.train_step(s1, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(2, s1)
    mgr.wait()

    t2 = new_trainer()
    s2 = t2.init(params)
    step, s2 = mgr.restore(s2)
    assert step == 2
    resumed = []
    for _ in range(2):
        s2, loss = t2.train_step(s2, batch)
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, ref[2:], rtol=1e-6)
    mgr.close()


def test_flush_all_checkpoints_drains_async_saves(tmp_path):
    """The watchdog's pre-exit flush (os._exit skips atexit) must make
    queued async saves durable — bounded, so a wedged flush can't block the
    exit path (ADVICE r3: watchdog default-on exit loses async saves)."""
    import jax.numpy as jnp

    from bagua_tpu.checkpoint import BaguaCheckpointManager, flush_all_checkpoints

    mgr = BaguaCheckpointManager(str(tmp_path / "ck"), async_save=True)
    state = {"w": jnp.arange(8.0)}
    assert mgr.save(0, state)
    flush_all_checkpoints(timeout_s=30.0)
    assert mgr.latest_step() == 0
    step, restored = mgr.restore({"w": jnp.zeros(8)})
    assert step == 0
    import numpy as np

    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8.0))
    mgr.close()


def test_layout_metadata_roundtrip_and_mismatch(tmp_path):
    """Flat-resident ZeRO checkpoints are bucket-plan/world-size dependent
    (ADVICE r4, medium): the layout metadata saved alongside the state must
    round-trip when the plan matches and fail with an ACTIONABLE error —
    before orbax's opaque shape mismatch — when it doesn't."""
    import pytest

    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm

    model = MLP(features=(16, 8))
    mesh = build_mesh({"dp": N_DEVICES})
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jnp.argmax(x @ jax.random.normal(jax.random.PRNGKey(1), (4, 8)), -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    def new_trainer(bucket_bytes=256):
        return BaguaTrainer(
            loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
            mesh=mesh, bucket_bytes=bucket_bytes,
        )

    t1 = new_trainer()
    s1 = t1.init(params)
    s1, _ = t1.train_step(s1, {"x": x, "y": y})
    meta = t1.checkpoint_layout_metadata()
    assert meta["layout"] == "flat" and meta["plan_dependent"]
    assert meta["flat_layout"]  # full bucket descriptor rides the sidecar
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(1, s1, metadata=meta)
    mgr.wait()

    # matching plan: restores fine, metadata validated
    t2 = new_trainer()
    s2 = t2.init(params)
    step, s2 = mgr.restore(s2, expect_metadata=t2.checkpoint_layout_metadata())
    assert step == 1
    s2, loss = t2.train_step(s2, {"x": x, "y": y})
    assert np.isfinite(float(loss))

    # different bucket plan: actionable layout error, not an orbax shape
    # error (4096B buckets genuinely re-split this model: one bucket of
    # everything; at 32 every leaf would stand alone, as at 256)
    t3 = new_trainer(bucket_bytes=4096)
    s3 = t3.init(params)
    assert t3._plan.signature() != t1._plan.signature()
    with pytest.raises(ValueError, match="checkpoint layout mismatch"):
        mgr.restore(s3, expect_metadata=t3.checkpoint_layout_metadata())
    mgr.close()


def test_checkpoint_without_metadata_still_restores(tmp_path):
    """metadata= is optional: plain saves keep the old on-disk layout and
    restore exactly as before (backward compatibility)."""
    new_trainer, params, batch = _setup()
    t = new_trainer()
    s = t.init(params)
    s, _ = t.train_step(s, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(1, s)
    mgr.wait()
    t2 = new_trainer()
    s2 = t2.init(params)
    # expect_metadata against a metadata-less checkpoint: warns, proceeds
    step, s2 = mgr.restore(s2, expect_metadata=t2.checkpoint_layout_metadata())
    assert step == 1
    s2, loss = t2.train_step(s2, batch)
    assert np.isfinite(float(loss))
    mgr.close()


def test_mixed_metadata_and_plain_saves_one_manager(tmp_path):
    """metadata= and plain saves must coexist on ONE manager (the sidecar
    design: orbax locks a manager to one item structure on first use, so a
    composite item would make this an opaque error), and leaf-layout
    metadata differences must NOT block a restore (plan-independent).
    Leaf layout is forced: the default flat-resident layout is
    plan-DEPENDENT, whose strict metadata path is covered above."""
    new_trainer, params, batch = _setup()
    new_trainer = partial(new_trainer, flat_resident="off")
    t = new_trainer()
    s = t.init(params)
    s, _ = t.train_step(s, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(1, s)                                        # plain
    assert mgr.save(2, s, metadata=t.checkpoint_layout_metadata())  # sidecar
    assert mgr.save(3, s)                                        # plain again
    mgr.wait()

    t2 = new_trainer()
    s2_init = t2.init(params)
    # leaf layout: a metadata difference only logs, never raises
    other = dict(t2.checkpoint_layout_metadata())
    other["world_size"] = other["world_size"] + 1
    step, s2 = mgr.restore(s2_init, step=2, expect_metadata=other)
    assert step == 2
    s2, loss = t2.train_step(s2, batch)
    assert np.isfinite(float(loss))
    # resume path: try_restore picks latest (plain) with expect_metadata
    step, _ = mgr.try_restore(t2.init(params),
                              expect_metadata=t2.checkpoint_layout_metadata())
    assert step == 3
    mgr.close()


def test_legacy_sidecar_missing_new_keys_still_restores(tmp_path):
    """A sidecar written before a metadata field existed (e.g. opt_shards,
    r5) must WARN and restore at the same topology — not hard-fail claiming
    a layout mismatch (r5 review finding)."""
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm

    model = MLP(features=(16, 8))
    mesh = build_mesh({"dp": N_DEVICES})
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jnp.argmax(x @ jax.random.normal(jax.random.PRNGKey(1), (4, 8)), -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    def new_trainer():
        return BaguaTrainer(loss_fn, None,
                            ZeroOptimizerAlgorithm(optax.adam(1e-2)),
                            mesh=mesh, bucket_bytes=256)

    t = new_trainer()
    s = t.init(params)
    s, _ = t.train_step(s, {"x": x, "y": y})
    legacy = dict(t.checkpoint_layout_metadata())
    legacy.pop("opt_shards")  # simulate a pre-r5 sidecar
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(1, s, metadata=legacy)
    mgr.wait()
    t2 = new_trainer()
    s2 = t2.init(params)
    step, s2 = mgr.restore(s2, expect_metadata=t2.checkpoint_layout_metadata())
    assert step == 1
    s2, loss = t2.train_step(s2, {"x": x, "y": y})
    assert np.isfinite(float(loss))
    mgr.close()


def test_save_restore_hierarchical_zero_state(tmp_path):
    """Checkpoint round-trip for the STAGED (hierarchical) ZeRO layout:
    intra-stacked chunk states (replicated across inter) must land back on
    their P('intra') shardings so the jitted step accepts the resumed
    state, and resumed training must equal the uninterrupted run."""
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm
    from bagua_tpu.parallel.mesh import hierarchical_mesh

    model = MLP(features=(16, 8))
    mesh = hierarchical_mesh(intra_size=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jnp.argmax(x @ jax.random.normal(jax.random.PRNGKey(1), (4, 8)), -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    def new_trainer():
        return BaguaTrainer(
            loss_fn, None,
            ZeroOptimizerAlgorithm(optax.adam(1e-2), hierarchical=True),
            mesh=mesh, bucket_bytes=256,
        )

    batch = {"x": x, "y": y}
    t0 = new_trainer()
    s = t0.init(params)
    ref = []
    for _ in range(6):
        s, loss = t0.train_step(s, batch)
        ref.append(float(loss))

    t1 = new_trainer()
    s1 = t1.init(params)
    for _ in range(3):
        s1, _ = t1.train_step(s1, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    meta = t1.checkpoint_layout_metadata()
    assert meta["opt_shards"] == 4
    assert mgr.save(3, s1, metadata=meta)
    mgr.wait()

    t2 = new_trainer()
    s2 = t2.init(params)
    step, s2 = mgr.restore(s2, expect_metadata=t2.checkpoint_layout_metadata())
    assert step == 3
    resumed = []
    for _ in range(3):
        s2, loss = t2.train_step(s2, batch)
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, ref[3:], rtol=1e-6)
    mgr.close()


# ---- the exact family's sharded update: the same moments, cut over the
# ranks (gradient_allreduce.py's header) ------------------------------------


def _adam_setup(monkeypatch=None):
    """``new_trainer(sharded)``: adam over a plan with one shaped bucket and
    packed 1-D flats at bucket_bytes=300.  The replicated update over the
    same 8 ranks is had by steering what the trainer observes (its
    elementwise probe): nothing selects it."""
    from bagua_tpu.core import backend

    model = MLP(features=(16, 8))
    mesh = build_mesh({"dp": N_DEVICES})
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jnp.argmax(x @ jax.random.normal(jax.random.PRNGKey(1), (4, 8)), -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    def new_trainer(sharded, **kw):
        with monkeypatch.context() as m:
            if not sharded:
                m.setattr(backend, "is_elementwise", lambda optimizer: False)
            trainer = BaguaTrainer(loss_fn, optax.adam(1e-2),
                                   GradientAllReduceAlgorithm(), mesh=mesh,
                                   bucket_bytes=300, **kw)
            state = trainer.init(params)
        assert trainer._update_sharded() == sharded
        return trainer, state

    return new_trainer, {"x": x, "y": y}


@pytest.mark.parametrize("saved_sharded", [False, True],
                         ids=["replicated_to_sharded", "sharded_to_replicated"])
def test_moments_restore_across_the_sharded_and_replicated_layouts(
        tmp_path, monkeypatch, saved_sharded):
    """A checkpoint holds the moments whole whichever way a trainer lays
    them out on its ranks, under one plan (nothing is padded to the world
    for the sharded update): written by one layout it restores into the
    other and training goes on as if never interrupted."""
    from bagua_tpu.obs.memory import tree_device_bytes

    new_trainer, batch = _adam_setup(monkeypatch)
    ref, s = new_trainer(saved_sharded)
    want = []
    for _ in range(6):
        s, loss = ref.train_step(s, batch)
        want.append(float(loss))

    t1, s1 = new_trainer(saved_sharded)
    for _ in range(3):
        s1, _ = t1.train_step(s1, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert t1.save_checkpoint(mgr, 3, s1)
    mgr.wait()

    t2, s2 = new_trainer(not saved_sharded)
    assert t1._plan.signature() == t2._plan.signature()
    fresh_bytes = tree_device_bytes(s2.opt_state)
    step, s2 = t2.restore_checkpoint(mgr, s2)
    assert step == 3
    # placed as this trainer's step takes it: a rank's share of the moments
    # is what a fresh state's is
    assert tree_device_bytes(s2.opt_state) == fresh_bytes
    got = []
    for _ in range(3):
        s2, loss = t2.train_step(s2, batch)
        got.append(float(loss))
    np.testing.assert_allclose(got, want[3:], rtol=1e-5)
    mgr.close()


def test_sharded_moments_round_trip_in_place(tmp_path, monkeypatch):
    new_trainer, batch = _adam_setup(monkeypatch)
    t1, s1 = new_trainer(True)
    for _ in range(2):
        s1, _ = t1.train_step(s1, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert t1.save_checkpoint(mgr, 2, s1)
    mgr.wait()
    t2, s2 = new_trainer(True)
    step, s2 = t2.restore_checkpoint(mgr, s2)
    for a, b in zip(jax.tree.leaves(s1.opt_state),
                    jax.tree.leaves(s2.opt_state)):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(t1.unstack_params(s1)),
                    jax.tree.leaves(t2.unstack_params(s2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()
