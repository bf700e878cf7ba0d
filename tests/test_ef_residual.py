"""Error-feedback residual lifecycle (ISSUE 17).

The 1-bit / top-k ring codecs only converge with a per-bucket residual
accumulating ``grad - decode(encode(grad + residual))`` in algo_state.
That residual is plan- AND world-keyed state, so every lifecycle edge the
repo already guarantees for resident state must hold for it too:

* it exists exactly when a stateful codec is resolved on the family's
  wire (and ``BAGUA_EF_RESIDUAL=off`` is the escape hatch);
* autotune-style rebuckets migrate it across bucket boundaries
  (``relayout_algo_state``) instead of orphaning it;
* checkpoints carry it through the layout sidecar: same-plan restores
  are bit-exact, cross-plan restores relayout, world resizes zero-reset
  LOUDLY, pre-EF checkpoints zero-init loudly, and restoring into a
  trainer without the codec drops it loudly;
* the grad-guard skip rewinds it bit-exactly with the rest of the step
  (a poisoned bucket's residual must not leak into the next step);
* codec-knob flips (the autopilot ladder) add/drop it as a queued state
  migration, never mid-compiled-step.
"""

import contextlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu import BaguaTrainer
from bagua_tpu.algorithms import (
    ByteGradAlgorithm,
    GradientAllReduceAlgorithm,
)
from bagua_tpu.bucket import split_bucket_by_bucket_size
from bagua_tpu.checkpoint import BaguaCheckpointManager
from bagua_tpu.define import BaguaHyperparameter
from bagua_tpu.faults import inject
from bagua_tpu.faults.inject import FaultSpec, fault_scope
from bagua_tpu.models import MLP
from bagua_tpu.parallel.mesh import build_mesh

N = 8
INTRA = 4
INTER = 2
DIM = 12
NCLASS = 10
MODEL = MLP(features=(16, NCLASS))


@pytest.fixture(autouse=True)
def _clean_faults():
    inject.clear_plan()
    bagua_tpu.reset_abort()
    yield
    inject.clear_plan()
    bagua_tpu.reset_abort()


def _loss_fn(params, batch):
    logits = MODEL.apply({"params": params}, batch["x"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["y"]
    ).mean()


def _params():
    return MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]


def _batches(steps, seed=3):
    rng = np.random.default_rng(seed)
    return [
        {
            "x": rng.normal(size=(N * 2, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2,)).astype(np.int32),
        }
        for _ in range(steps)
    ]


def _make(codec="onebit_ef", bucket_bytes=256, **kw):
    """A two-level trainer with the stateful codec on the DCN tier."""
    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1),
        GradientAllReduceAlgorithm(hierarchical=True),
        mesh=build_mesh({"inter": INTER, "intra": INTRA}),
        bucket_bytes=bucket_bytes, autotune=False,
        **({} if codec is None else {"compress_inter": codec}), **kw,
    )
    state = trainer.init(_params())
    return trainer, state


def _ef(state):
    return state.algo_state["ef"]["buckets"]


def _residual_norm(state):
    return sum(float(jnp.abs(b).sum()) for b in _ef(state))


def _residual_by_tensor(trainer, state):
    """Per-tensor [world, numel] views of the residual — the plan-invariant
    representation (bucket padding excluded)."""
    out = {}
    for b, flat in zip(trainer._plan.buckets, _ef(state)):
        # (a shaped bucket's residual keeps the tensor's own shape)
        flat = np.asarray(flat).reshape(flat.shape[0], -1)
        for t, off in zip(b.tensors, b.offsets()):
            out[t.name] = np.asarray(flat[:, off:off + t.numel])
    return out


# ---- existence + convergence -------------------------------------------


@pytest.mark.parametrize("codec", ["onebit_ef", "topk"])
def test_ef_state_created_and_trains(codec):
    trainer, state = _make(codec)
    assert trainer._ef_active()
    assert set(state.algo_state) == {"ef"}
    assert [tuple(b.shape) for b in _ef(state)] == [
        (N,) + b.buffer_shape for b in trainer._plan.buckets
    ]
    assert _residual_norm(state) == 0.0  # EF inits at zero
    batch = _batches(1)[0]  # fixed batch: per-step losses are comparable
    losses = []
    for _ in range(8):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # the compensated sign/sparse SGD learns
    # residual is live and finite
    assert _residual_norm(state) > 0.0
    assert all(bool(jnp.isfinite(b).all()) for b in _ef(state))


@pytest.mark.parametrize("codec", ["onebit_ef", "topk"])
def test_residual_closes_the_gap_to_the_uncompressed_run(codec, monkeypatch):
    """What the residual is for: over 60 steps of the exact-loss gate's
    task (tests/golden.py) the compensated run ends nearer the uncompressed
    run's loss than the stateless control (``BAGUA_EF_RESIDUAL=off``), whose
    gap is the quantization bias the residual cancels."""
    import golden

    loss_fn, params, batch = golden.golden_task()

    def final_loss(compress_inter):
        trainer = BaguaTrainer(
            loss_fn, optax.sgd(0.1),
            GradientAllReduceAlgorithm(hierarchical=True),
            mesh=build_mesh({"inter": INTER, "intra": INTRA}),
            bucket_bytes=65536, autotune=False, overlap="off",
            **({} if compress_inter is None
               else {"compress_inter": compress_inter}),
        )
        state = trainer.init(params)
        data = trainer.shard_batch(batch)
        for _ in range(60):
            state, loss = trainer.train_step(state, data)
            # fenced: with several steps in flight on the 8-device virtual
            # mesh XLA:CPU's rendezvous deadlocks on a loaded host (tier-1's
            # -n 6: the process aborts; PR 31 saw the same in an example)
            loss.block_until_ready()
        return float(loss)

    uncompressed = final_loss(None)
    compensated = final_loss(codec)
    monkeypatch.setenv("BAGUA_EF_RESIDUAL", "off")
    stateless = final_loss(codec)
    assert abs(compensated - uncompressed) < abs(stateless - uncompressed), (
        uncompressed, compensated, stateless)
    assert compensated < stateless


def test_no_codec_keeps_algo_state_none():
    trainer, state = _make(None)
    assert not trainer._ef_active()
    assert state.algo_state is None


def test_stateless_codec_keeps_algo_state_none():
    trainer, state = _make("minmax_uint8")
    assert not trainer._ef_active()
    assert state.algo_state is None


def test_bytegrad_flat_path_never_carries_ef():
    """ByteGrad's non-hierarchical scatter-gather pipeline has ONE wire
    format (minmax) — a forced stateful codec NAME keeps that pipeline, so
    engaging EF there would compensate for error that never hits the
    wire."""
    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1), ByteGradAlgorithm(hierarchical=False),
        mesh=build_mesh({"dp": N}), bucket_bytes=256, autotune=False,
        compress_intra="onebit_ef",
    )
    state = trainer.init(_params())
    assert not trainer._ef_active()
    assert state.algo_state is None


def test_env_escape_hatch_disables_ef(monkeypatch):
    """``BAGUA_EF_RESIDUAL=off`` runs the codec stateless (the documented
    debug-a-divergence escape hatch) — no residual anywhere."""
    monkeypatch.setenv("BAGUA_EF_RESIDUAL", "off")
    from bagua_tpu.algorithms import base as algo_base

    algo_base._EF_STATELESS_WARNED.clear()
    trainer, state = _make("onebit_ef")
    assert not trainer._ef_active()
    assert state.algo_state is None
    state, loss = trainer.train_step(state, _batches(1)[0])
    assert np.isfinite(float(loss))


# ---- rebucket migration -------------------------------------------------


def test_rebucket_migrates_ef_residual():
    """An autotune-style rebucket with EF active is a state migration even
    under the LEAF layout: the residual crosses the new bucket boundaries
    via relayout_algo_state instead of being orphaned at the old shapes."""
    trainer, state = _make("onebit_ef")
    for batch in _batches(3):
        state, _ = trainer.train_step(state, batch)
    norm_before = _residual_norm(state)
    assert norm_before > 0.0
    decls = [t.declaration() for b in trainer._plan.buckets
             for t in b.tensors]
    old_sig = trainer._plan.signature()
    trainer.rebucket(split_bucket_by_bucket_size(decls, 2048))
    assert trainer._plan.signature() != old_sig
    assert trainer._pending_state_migration is not None
    state, loss = trainer.train_step(state, _batches(1, seed=5)[0])
    assert trainer._pending_state_migration is None
    assert np.isfinite(float(loss))
    # residual now laid out on the NEW plan
    assert [tuple(b.shape) for b in _ef(state)] == [
        (N,) + b.buffer_shape for b in trainer._plan.buckets
    ]
    assert all(bool(jnp.isfinite(b).all()) for b in _ef(state))


# ---- checkpoint lifecycle -----------------------------------------------


def test_checkpoint_same_plan_roundtrip_bit_exact(tmp_path):
    """Save -> restore into the identical layout: the residual (and the
    whole trajectory) continues bit-exactly."""
    trainer, state = _make("onebit_ef")
    batches = _batches(6)
    for batch in batches[:3]:
        state, _ = trainer.train_step(state, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert trainer.save_checkpoint(mgr, 3, state)

    other, state_like = _make("onebit_ef")
    step, restored = other.restore_checkpoint(mgr, state_like)
    assert step == 3
    for a, b in zip(_ef(state), _ef(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # uninterrupted vs restored continuation: bit-identical losses
    tail_direct, tail_restored = [], []
    for batch in batches[3:]:
        state, l1 = trainer.train_step(state, batch)
        restored, l2 = other.train_step(restored, batch)
        tail_direct.append(float(l1))
        tail_restored.append(float(l2))
    assert tail_direct == tail_restored
    mgr.close()


def test_checkpoint_cross_plan_relayouts_residual(tmp_path, caplog):
    trainer, state = _make("onebit_ef", bucket_bytes=256)
    for batch in _batches(3):
        state, _ = trainer.train_step(state, batch)
    assert len(trainer._plan.buckets) > 1
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert trainer.save_checkpoint(mgr, 3, state)

    other, state_like = _make("onebit_ef", bucket_bytes=4096)
    assert len(other._plan.buckets) != len(trainer._plan.buckets)
    with caplog.at_level(logging.INFO, logger="bagua_tpu.core.backend"):
        _, restored = other.restore_checkpoint(mgr, state_like)
    assert any("relaying out the error-feedback residual" in r.getMessage()
               for r in caplog.records)
    # the accumulated error survived the relayout (not zero-reset) ...
    assert _residual_norm(restored) > 0.0
    assert [tuple(b.shape) for b in _ef(restored)] == [
        (N,) + b.buffer_shape for b in other._plan.buckets
    ]
    # ... element-for-element: relayout is slice+concat, so every tensor's
    # residual rows cross the boundary change bit-exactly (only old bucket
    # PADDING is dropped — sign codecs do accumulate residual there, but
    # it is layout noise, not gradient error)
    saved_rt = _residual_by_tensor(trainer, state)
    restored_rt = _residual_by_tensor(other, restored)
    assert set(saved_rt) == set(restored_rt)
    for name, rows in saved_rt.items():
        np.testing.assert_array_equal(rows, restored_rt[name])
    restored, loss = other.train_step(restored, _batches(1, seed=9)[0])
    assert np.isfinite(float(loss))
    mgr.close()


def test_checkpoint_pre_ef_zero_inits_loudly(tmp_path, caplog):
    """A checkpoint saved before the codec flip has no residual: restore
    into an EF trainer zero-inits it with the actionable warning."""
    plain, state = _make(None)
    for batch in _batches(2):
        state, _ = plain.train_step(state, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert plain.save_checkpoint(mgr, 2, state)

    ef_trainer, state_like = _make("onebit_ef")
    with caplog.at_level(logging.WARNING, logger="bagua_tpu.core.backend"):
        _, restored = ef_trainer.restore_checkpoint(mgr, state_like)
    assert any("starting from ZERO residuals" in r.getMessage()
               for r in caplog.records)
    assert _residual_norm(restored) == 0.0
    restored, loss = ef_trainer.train_step(restored, _batches(1)[0])
    assert np.isfinite(float(loss))
    mgr.close()


def test_restore_into_non_ef_trainer_drops_loudly(tmp_path, caplog):
    ef_trainer, state = _make("onebit_ef")
    for batch in _batches(2):
        state, _ = ef_trainer.train_step(state, batch)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert ef_trainer.save_checkpoint(mgr, 2, state)

    plain, state_like = _make(None)
    with caplog.at_level(logging.WARNING, logger="bagua_tpu.core.backend"):
        _, restored = plain.restore_checkpoint(mgr, state_like)
    assert any("discarding the checkpoint's error-feedback residual"
               in r.getMessage() for r in caplog.records)
    assert restored.algo_state is None
    # params still restored faithfully
    for a, b in zip(jax.tree.leaves(plain.unstack_params(restored)),
                    jax.tree.leaves(ef_trainer.unstack_params(state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_world_resize_zero_resets_residual(caplog):
    """Elastic resize: the saved residual's rank rows are meaningless in
    the new world — the adapter zero-resets with the loud warning instead
    of dying on an orbax shape mismatch.  (Unit-level: a real resize needs
    a second device topology; the adapter/fixup pair is the whole
    seam.)"""
    trainer, state = _make("onebit_ef")
    saved_world = N // 2
    tampered = dict(trainer.checkpoint_layout_metadata())
    tampered["ef"] = dict(tampered["ef"], world=saved_world)
    adapted, fixup = trainer._ef_restore_adapter(state, tampered)
    # the restore targets the SAVED shape ...
    assert all(b.shape[0] == saved_world
               for b in adapted.algo_state["ef"]["buckets"])
    # ... and the fixup converts a (simulated) restored state back to the
    # live world as zeros
    fake_restored = state._replace(algo_state={"ef": {"buckets": tuple(
        jnp.ones((saved_world,) + b.buffer_shape, jnp.float32)
        for b in trainer._plan.buckets
    )}})
    with caplog.at_level(logging.WARNING, logger="bagua_tpu.core.backend"):
        fixed = fixup(fake_restored)
    assert any("elastic resize" in r.getMessage() for r in caplog.records)
    assert [tuple(b.shape) for b in _ef(fixed)] == [
        (N,) + b.buffer_shape for b in trainer._plan.buckets
    ]
    assert _residual_norm(fixed) == 0.0


# ---- grad-guard skip ----------------------------------------------------


def test_guard_skip_rewinds_residual_bit_exact():
    """A poisoned step under ``grad_guard="skip"`` must rewind the
    residual WITH the params: a run poisoned at step 3 (rewound) equals a
    clean run of one fewer step bitwise — params AND residual.  A leaked
    poisoned residual would re-inject the NaN on the next step."""
    def run(poison_step, n_steps):
        cm = (fault_scope(FaultSpec("grad.poison", step=poison_step))
              if poison_step is not None else contextlib.nullcontext())
        with cm:
            trainer, state = _make("onebit_ef", grad_guard="skip")
            batch = _batches(1)[0]  # fixed batch: skip == one fewer step
            for _ in range(n_steps):
                state, _ = trainer.train_step(state, batch)
        return trainer, state

    t_clean, s_clean = run(None, 5)
    t_skip, s_skip = run(3, 6)
    for a, b in zip(jax.tree.leaves(t_clean.unstack_params(s_clean)),
                    jax.tree.leaves(t_skip.unstack_params(s_skip))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(_ef(s_clean), _ef(s_skip)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(bool(jnp.isfinite(b).all()) for b in _ef(s_skip))
    assert int(s_skip.step) == 6


# ---- codec-knob flips (the autopilot ladder's actuation path) -----------


def test_knob_flip_adds_then_drops_ef_state():
    trainer, state = _make(None)
    assert state.algo_state is None
    batches = _batches(6)
    state, _ = trainer.train_step(state, batches[0])

    # escalate onto the stateful rung: residual appears (from zero) at the
    # next step boundary, as a queued migration
    trainer._apply_recommendation(BaguaHyperparameter(
        compress_inter="onebit_ef", is_hierarchical_reduce=True))
    assert trainer._ef_active()
    assert trainer._pending_state_migration is not None
    state, loss = trainer.train_step(state, batches[1])
    assert np.isfinite(float(loss))
    assert set(state.algo_state) == {"ef"}
    state, _ = trainer.train_step(state, batches[2])
    assert _residual_norm(state) > 0.0

    # de-escalate back to a stateless codec: residual dropped
    trainer._apply_recommendation(BaguaHyperparameter(
        compress_inter="minmax_uint8", is_hierarchical_reduce=True))
    assert not trainer._ef_active()
    state, loss = trainer.train_step(state, batches[3])
    assert np.isfinite(float(loss))
    assert state.algo_state is None
