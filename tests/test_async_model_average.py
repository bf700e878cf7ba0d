"""Async model averaging: convergence + abort/resume behavior
(mirrors /root/reference/tests/torch_api/test_async_model_average.py:86-110),
plus the bounded-staleness / robustness-integration layer (ISSUE 6):
staleness invariant, partition → catch-up, grad-guard veto of in-flight
rounds, and schedule reset across checkpoint restores (elastic resizes
included)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu import BaguaTrainer, telemetry
from bagua_tpu.algorithms import AsyncModelAverageAlgorithm
from bagua_tpu.faults import inject
from bagua_tpu.faults.inject import FaultSpec, fault_scope
from bagua_tpu.models import MLP

N = 8
DIM, NCLASS = 10, 5


@pytest.fixture(autouse=True)
def _clean_faults():
    inject.clear_plan()
    yield
    inject.clear_plan()


def _setup(seed=0):
    model = MLP(features=(12, NCLASS))
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, DIM)))["params"]

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"]).mean()

    return model, params, loss_fn


def _batch(rng, W, rows=N * 4):
    x = rng.normal(size=(rows, DIM)).astype(np.float32)
    y = np.argmax(x @ W, 1).astype(np.int32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def _rows_bitident(tree) -> bool:
    """Every leaf's per-rank rows (leading axis) bit-identical to row 0."""
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        if a.ndim == 0:
            continue
        if any(a[0].tobytes() != a[r].tobytes() for r in range(1, a.shape[0])):
            return False
    return True


def test_convergence_with_background_averaging():
    model, params, loss_fn = _setup()
    algo = AsyncModelAverageAlgorithm(sync_interval_ms=0, warmup_steps=2)
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.05), algo)
    st = trainer.init(params)
    rng = np.random.default_rng(0)
    W = rng.normal(size=(DIM, NCLASS))
    losses = []
    for _ in range(20):
        x = rng.normal(size=(N * 4, DIM)).astype(np.float32)
        y = np.argmax(x @ W, 1).astype(np.int32)
        st, loss = trainer.train_step(st, {"x": jnp.asarray(x), "y": jnp.asarray(y)})
        losses.append(float(loss))
    st = algo.barrier(trainer, st)
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


def test_abort_resume():
    model, params, loss_fn = _setup(1)
    algo = AsyncModelAverageAlgorithm(sync_interval_ms=0)
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.05), algo)
    st = trainer.init(params)
    rng = np.random.default_rng(1)
    W = rng.normal(size=(DIM, NCLASS))

    def run(st, k):
        for _ in range(k):
            x = rng.normal(size=(N * 4, DIM)).astype(np.float32)
            y = np.argmax(x @ W, 1).astype(np.int32)
            st, loss = trainer.train_step(st, {"x": jnp.asarray(x), "y": jnp.asarray(y)})
        return st, float(loss)

    st, _ = run(st, 5)
    algo.abort()
    st, l1 = run(st, 5)   # trains without averaging
    algo.resume()
    st, l2 = run(st, 5)
    st = algo.barrier(trainer, st)
    assert np.isfinite(l1) and np.isfinite(l2)

def test_pinned_period_schedules_exact_rounds():
    """period_steps pins the cadence with no wall-clock dependence: the
    round count over a fixed step budget is exact and deterministic."""
    model, params, loss_fn = _setup(2)
    algo = AsyncModelAverageAlgorithm(warmup_steps=2, period_steps=3)
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.05), algo)
    st = trainer.init(params)
    rng = np.random.default_rng(2)
    W = rng.normal(size=(DIM, NCLASS))
    launches = []
    for i in range(14):
        x = rng.normal(size=(N * 4, DIM)).astype(np.float32)
        y = np.argmax(x @ W, 1).astype(np.int32)
        before = algo._pending
        st, _ = trainer.train_step(st, {"x": jnp.asarray(x), "y": jnp.asarray(y)})
        if algo._pending is not None and algo._pending is not before:
            launches.append(trainer._step_counter)
    st = algo.barrier(trainer, st)
    assert algo._period == 3
    # anchor is the first post-warmup step; rounds then every 3rd step
    diffs = np.diff(launches)
    assert len(launches) >= 3 and all(d == 3 for d in diffs), (launches, diffs)


def test_single_rank_comm_world_skips_rounds():
    """On a 1-rank comm world the averaging collective is an identity: no
    snapshot/avg/combine work may be scheduled at all (round-4 measured ~10%
    single-chip overhead from these hops; the reference async CI floor is
    the highest of all families)."""
    from jax.sharding import Mesh

    model, params, loss_fn = _setup(3)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    algo = AsyncModelAverageAlgorithm(sync_interval_ms=0, warmup_steps=1)
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.05), algo, mesh=mesh)
    st = trainer.init(params)
    rng = np.random.default_rng(3)
    W = rng.normal(size=(DIM, NCLASS))
    for _ in range(10):
        x = rng.normal(size=(N, DIM)).astype(np.float32)
        y = np.argmax(x @ W, 1).astype(np.int32)
        st, loss = trainer.train_step(st, {"x": jnp.asarray(x), "y": jnp.asarray(y)})
    assert algo._pending is None and algo._avg_fn is None and algo._period is None
    assert np.isfinite(float(loss))


def test_periodic_recalibration_rederives_period():
    """After recalibrate_rounds rounds the period resets and re-derives from
    current measured step time (ADVICE r4: a one-shot calibration diverges
    arbitrarily from sync_interval_ms after any sustained step-time change)."""
    model, params, loss_fn = _setup(4)
    algo = AsyncModelAverageAlgorithm(
        sync_interval_ms=0, warmup_steps=1, calibration_steps=1,
        recalibrate_rounds=3,
    )
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.05), algo)
    st = trainer.init(params)
    rng = np.random.default_rng(4)
    W = rng.normal(size=(DIM, NCLASS))
    saw_reset = False
    had_period = False
    for _ in range(30):
        x = rng.normal(size=(N, DIM)).astype(np.float32)
        y = np.argmax(x @ W, 1).astype(np.int32)
        st, _ = trainer.train_step(st, {"x": jnp.asarray(x), "y": jnp.asarray(y)})
        if algo._period is not None:
            had_period = True
        elif had_period:
            saw_reset = True  # period was agreed, then reset for recalibration
    st = algo.barrier(trainer, st)
    assert saw_reset
    assert algo._period is not None  # re-derived after the reset


# ---- bounded staleness / robustness integration (ISSUE 6) -----------------


def test_bounded_staleness_invariant_and_catchup_bitident():
    """The acceptance invariant: with ``max_staleness_rounds=k`` armed
    against a persistent ``async.partition``, the applied-round counter
    NEVER lags the launched count by more than k, and every forced
    catch-up leaves the per-rank replicas bit-identical at the sync
    point (sampled inside ``_catchup_sync``, before the next train step
    re-diverges the gossip rows)."""
    k = 2
    model, params, loss_fn = _setup(10)
    algo = AsyncModelAverageAlgorithm(
        warmup_steps=2, period_steps=2, max_staleness_rounds=k
    )
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.05), algo)
    st = trainer.init(params)

    synced_params = []
    orig = algo._catchup_sync

    def spy(tr, state, watchdog, step, reason):
        out = orig(tr, state, watchdog, step, reason)
        # host copy NOW: the next train step donates (deletes) the buffers
        synced_params.append(jax.tree.map(np.asarray, out.params))
        return out

    algo._catchup_sync = spy
    rng = np.random.default_rng(10)
    W = rng.normal(size=(DIM, NCLASS))
    before = telemetry.counters.snapshot()
    lags = []
    with fault_scope(FaultSpec("async.partition", count=-1)):
        for _ in range(24):
            st, loss = trainer.train_step(st, _batch(rng, W))
            lags.append(algo._rounds_launched - algo._rounds_applied)
    after = telemetry.counters.snapshot()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert max(lags) <= k, lags
    assert delta("async/catchup_syncs") >= 1
    assert delta("async/rounds_dropped") >= 1
    assert delta("async/missed_boundaries") >= 1
    assert delta("async/rounds_launched") >= delta("async/catchup_syncs")
    # detection + recovery attributed to the injected partition
    assert delta("faults/async.partition/fired") >= 1
    assert delta("faults/async.partition/recovered") >= 1
    # every sync point left the stacked replicas bit-identical
    assert synced_params and all(_rows_bitident(p) for p in synced_params)
    assert np.isfinite(float(loss))


def test_staleness_cap_zero_disables_catchup():
    """``max_staleness_rounds=0`` = purely asynchronous: a persistent
    partition grows the lag without bound and no catch-up ever fires."""
    model, params, loss_fn = _setup(11)
    algo = AsyncModelAverageAlgorithm(
        warmup_steps=2, period_steps=2, max_staleness_rounds=0
    )
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.05), algo)
    st = trainer.init(params)
    rng = np.random.default_rng(11)
    W = rng.normal(size=(DIM, NCLASS))
    before = telemetry.counters.snapshot()
    with fault_scope(FaultSpec("async.partition", count=-1)):
        for _ in range(20):
            st, loss = trainer.train_step(st, _batch(rng, W))
    catchups = (telemetry.counters.get("async/catchup_syncs")
                - before.get("async/catchup_syncs", 0))
    assert catchups == 0
    assert algo._rounds_launched - algo._rounds_applied > 2
    assert np.isfinite(float(loss))


def test_staleness_knob_validation(monkeypatch):
    with pytest.raises(ValueError, match="max_staleness_rounds"):
        AsyncModelAverageAlgorithm(max_staleness_rounds=-1)
    # None reads the env-registry knob
    monkeypatch.setenv("BAGUA_ASYNC_MAX_STALENESS", "7")
    assert AsyncModelAverageAlgorithm().max_staleness_rounds == 7
    monkeypatch.delenv("BAGUA_ASYNC_MAX_STALENESS")
    assert AsyncModelAverageAlgorithm().max_staleness_rounds == 4  # default


def test_grad_guard_rewind_vetoes_inflight_round():
    """A poisoned (rewound) step while a round is in flight must NOT apply
    the round's delta on top of the rewound state — the boundary drops the
    round instead, and the staleness machinery later re-syncs."""
    model, params, loss_fn = _setup(12)
    algo = AsyncModelAverageAlgorithm(
        warmup_steps=1, period_steps=3, max_staleness_rounds=0
    )
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.05), algo,
                           grad_guard="skip")
    st = trainer.init(params)
    rng = np.random.default_rng(12)
    W = rng.normal(size=(DIM, NCLASS))
    before = telemetry.counters.snapshot()
    # boundary schedule: anchor=2 (first post-warmup step), boundaries at
    # 2, 5, 8, ...; the round launched at host-step 2 is in flight when
    # the poison fires (traced state.step == 3 ⇒ host step 4) and must be
    # dropped at the step-5 boundary
    with fault_scope(FaultSpec("grad.poison", step=3)):
        for _ in range(8):
            st, loss = trainer.train_step(st, _batch(rng, W))
        trainer.flush_grad_health()

    def delta(name):
        return telemetry.counters.get(name) - before.get(name, 0)

    assert trainer._guard_rewinds_total >= 1
    assert delta("grad_guard/skipped_steps") == 1
    assert delta("async/rounds_dropped") >= 1
    assert delta("async/missed_boundaries") >= 1
    assert np.isfinite(float(loss))
    st = algo.barrier(trainer, st)


def test_restore_resets_async_schedule(tmp_path):
    """Checkpoint restore (same world) runs the algorithm's ``on_restore``
    hook: no stale ``_pending``/``_anchor``/period crosses the restore —
    the resumed run opens a fresh calibration window."""
    import golden
    from bagua_tpu.checkpoint import BaguaCheckpointManager

    loss_fn, params, batch = golden.golden_task()
    algo = AsyncModelAverageAlgorithm(warmup_steps=1, period_steps=3)
    trainer = BaguaTrainer(loss_fn, optax.sgd(0.1), algo, autotune=False)
    st = trainer.init(params)
    data = trainer.shard_batch(batch)
    for _ in range(7):
        st, _ = trainer.train_step(st, data)
    st = algo.sync_for_checkpoint(trainer, st)
    assert algo._period is not None and algo._anchor is not None
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert trainer.save_checkpoint(mgr, 7, st)
    mgr.wait()

    # second trainer with its own live schedule state, then restore into it
    algo2 = AsyncModelAverageAlgorithm(warmup_steps=1, period_steps=3)
    tr2 = BaguaTrainer(loss_fn, optax.sgd(0.1), algo2, autotune=False)
    st2 = tr2.init(params)
    for _ in range(5):
        st2, _ = tr2.train_step(st2, data)
    assert algo2._period is not None
    step, restored = tr2.restore_checkpoint(mgr, st2)
    assert step == 7
    # on_restore wiped the negotiated schedule and any in-flight round
    assert algo2._pending is None
    assert algo2._period is None and algo2._anchor is None
    assert algo2._rounds_launched == 0 and algo2._rounds_applied == 0
    # and training resumes (a fresh window re-derives the schedule)
    for _ in range(7):
        restored, loss = tr2.train_step(restored, data)
    assert algo2._period is not None
    assert np.isfinite(float(loss))
    mgr.close()


def test_async_elastic_world_resize_restore(tmp_path):
    """Elastic continuity across a WORLD RESIZE: ``sync_for_checkpoint``
    makes the stacked per-rank rows bit-identical, the dp8 save re-tiles
    onto a dp4 trainer through the stacked-resize restore path, and the
    resumed run opens a fresh calibration window."""
    import golden
    from bagua_tpu.checkpoint import BaguaCheckpointManager
    from bagua_tpu.parallel.mesh import build_mesh

    loss_fn, params, batch = golden.golden_task()
    algo = AsyncModelAverageAlgorithm(warmup_steps=1, period_steps=3)
    tr8 = BaguaTrainer(loss_fn, optax.sgd(0.1), algo,
                       mesh=build_mesh({"dp": 8}), autotune=False)
    st = tr8.init(params)
    data = tr8.shard_batch(batch)
    for _ in range(7):
        st, _ = tr8.train_step(st, data)
    st = algo.sync_for_checkpoint(tr8, st)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert tr8.save_checkpoint(mgr, 7, st)
    mgr.wait()

    before = telemetry.counters.snapshot()
    algo4 = AsyncModelAverageAlgorithm(warmup_steps=1, period_steps=3)
    tr4 = BaguaTrainer(
        loss_fn, optax.sgd(0.1), algo4,
        mesh=build_mesh({"dp": 4}, devices=jax.devices()[:4]),
        autotune=False,
    )
    st4 = tr4.init(params)
    step, restored = tr4.restore_checkpoint(mgr, st4)
    assert step == 7
    assert (telemetry.counters.get("ckpt/stacked_resize_restores")
            - before.get("ckpt/stacked_resize_restores", 0)) == 1
    # re-tiled rows are the saved (synced) row, on the new world size
    assert _rows_bitident(restored.params)
    lead = {np.asarray(x).shape[0] for x in jax.tree.leaves(restored.params)}
    assert lead == {4}
    # fresh schedule; resumes and re-derives the period on the new world
    assert algo4._pending is None and algo4._period is None
    data4 = tr4.shard_batch(batch)
    loss = None
    for _ in range(7):
        restored, loss = tr4.train_step(restored, data4)
    assert algo4._period is not None
    assert np.isfinite(float(loss))
    mgr.close()


def test_async_resize_restore_divergent_rows_raise(tmp_path):
    """A stacked checkpoint saved WITHOUT the pre-save sync (divergent
    per-rank rows) must refuse a cross-world restore actionably rather
    than silently picking one rank's replica."""
    import golden
    from bagua_tpu.checkpoint import BaguaCheckpointManager
    from bagua_tpu.parallel.mesh import build_mesh

    loss_fn, params, batch = golden.golden_task()
    algo = AsyncModelAverageAlgorithm(warmup_steps=0, period_steps=100)
    tr8 = BaguaTrainer(loss_fn, optax.sgd(0.1), algo,
                       mesh=build_mesh({"dp": 8}), autotune=False)
    st = tr8.init(params)
    data = tr8.shard_batch(batch)
    for _ in range(4):  # per-rank shards diverge the gossip rows
        st, _ = tr8.train_step(st, data)
    mgr = BaguaCheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert tr8.save_checkpoint(mgr, 4, st)
    mgr.wait()

    tr4 = BaguaTrainer(
        loss_fn, optax.sgd(0.1),
        AsyncModelAverageAlgorithm(warmup_steps=0, period_steps=100),
        mesh=build_mesh({"dp": 4}, devices=jax.devices()[:4]),
        autotune=False,
    )
    st4 = tr4.init(params)
    with pytest.raises(ValueError, match="DIVERGENT per-rank rows"):
        tr4.restore_checkpoint(mgr, st4)
    mgr.close()


def test_drop_pending_publishes_health_beacon(tmp_path, monkeypatch):
    """A dropped round is a fenceable health event: _drop_pending must
    publish the beacon file itself — grad-guard is the only other writer,
    so a rank dropping rounds with finite gradients would otherwise never
    reach the coordinator's fence."""
    import json
    import os

    path = str(tmp_path / "beacon.json")
    monkeypatch.setenv("BAGUA_ELASTIC_HEALTH_FILE", path)
    algo = AsyncModelAverageAlgorithm(warmup_steps=0, period_steps=100)
    algo._pending = object()
    algo._drop_pending("test drop")
    assert algo._pending is None
    assert os.path.exists(path)
    with open(path) as f:
        snap = json.load(f)
    assert snap.get("async_missed", 0) >= 1


def test_gated_straggle_reports_stall_to_trainer(monkeypatch):
    """Boundary straggle sleeps must be reported to the trainer's cadence
    tracker: an unreported sleep lands in the next measured_step_dt sample
    and becomes the base of the next stall (compounding dilation)."""

    class FakeTrainer:
        def __init__(self):
            self.noted = []

        def measured_step_dt(self):
            return 0.01

        def note_injected_stall(self, seconds):
            self.noted.append(seconds)

    algo = AsyncModelAverageAlgorithm(warmup_steps=0, period_steps=100)
    monkeypatch.setattr(inject, "maybe_straggle",
                        lambda sync_point, base_dt=None, gated=True: 0.25)
    tr = FakeTrainer()
    algo._gated_straggle(tr, "async.negotiate")
    assert tr.noted == [0.25]
    # no sleep -> nothing reported
    monkeypatch.setattr(inject, "maybe_straggle",
                        lambda sync_point, base_dt=None, gated=True: 0.0)
    algo._gated_straggle(tr, "async.catchup")
    assert tr.noted == [0.25]
