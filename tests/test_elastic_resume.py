"""Cross-topology elastic resume against the golden-gate harness: save at
world size 4, elastic-restore at 2 and at 8, and require loss-trajectory
continuity — the resumed run must land on the same final loss as the
uninterrupted run of ``golden.golden_task()`` (the exact-loss gate's task,
tests/test_loss_goldens.py).

"World size" here is the dp mesh extent inside the single 8-virtual-device
test process (conftest) — exactly the quantity the flat/plan layouts care
about — so the restore math is the multi-process one without subprocess
cost.  Tier-1 fast: pure CPU, no ports, no subprocesses.
"""

import jax
import numpy as np
import optax
import pytest

import golden
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.checkpoint import BaguaCheckpointManager
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.elastic.resize import elastic_restore
from bagua_tpu.parallel.mesh import build_mesh

# reduction orders differ between dp extents; continuity means "same
# trajectory up to collective reassociation", not bit-equality
ATOL = 5e-5
SAVE_AT, TOTAL = 15, 30


def _trainer(loss_fn, dp: int) -> BaguaTrainer:
    mesh = build_mesh({"dp": dp}, devices=jax.devices()[:dp])
    return BaguaTrainer(
        loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        mesh=mesh, autotune=False,
    )


def _run(trainer, state, batch, steps: int):
    loss = None
    for _ in range(steps):
        state, loss = trainer.train_step(state, batch)
    return state, float(loss)


@pytest.fixture(scope="module")
def task():
    loss_fn, params, batch = golden.golden_task()
    # the uninterrupted 30-step trajectory this platform's golden gate
    # certifies (goldens are platform-specific; recompute, don't hardcode)
    trainer = _trainer(loss_fn, 4)
    state = trainer.init(params)
    _, final = _run(trainer, state, batch, TOTAL)
    return loss_fn, params, batch, final


@pytest.mark.parametrize("dp_restore", [2, 8])
def test_cross_topology_resume_matches_golden_trajectory(
    tmp_path, task, dp_restore
):
    loss_fn, params, batch, golden_final = task
    # ---- phase 1: train at world size 4, checkpoint at step SAVE_AT ----
    tr4 = _trainer(loss_fn, 4)
    state = tr4.init(params)
    state, _ = _run(tr4, state, batch, SAVE_AT)
    mgr = BaguaCheckpointManager(
        str(tmp_path / "ckpt"), max_to_keep=2, async_save=False,
    )
    assert mgr.save(SAVE_AT, state, metadata=tr4.checkpoint_layout_metadata())
    mgr.wait()

    # ---- phase 2: "restart" at a different world size and resume --------
    tr_new = _trainer(loss_fn, dp_restore)
    state_like = tr_new.init(params)
    mgr2 = BaguaCheckpointManager(str(tmp_path / "ckpt"))
    step, restored = elastic_restore(
        mgr2, state_like,
        expect_metadata=tr_new.checkpoint_layout_metadata(),
        mesh=tr_new.mesh,
    )
    assert step == SAVE_AT
    _, resumed_final = _run(tr_new, restored, batch, TOTAL - SAVE_AT)

    np.testing.assert_allclose(resumed_final, golden_final, rtol=0, atol=ATOL)


def test_elastic_restore_empty_dir_passes_through(tmp_path, task):
    loss_fn, params, _, _ = task
    tr = _trainer(loss_fn, 2)
    state = tr.init(params)
    mgr = BaguaCheckpointManager(str(tmp_path / "none"))
    step, out = elastic_restore(mgr, state)
    assert step is None and out is state


def test_plan_dependent_layout_still_blocked_across_topologies(
    tmp_path, task
):
    """elastic_restore relaxes ONLY the plan-independent case: a
    flat-resident ZeRO checkpoint saved at dp=4 must still refuse to
    restore at dp=2 with the actionable layout error."""
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm

    loss_fn, params, batch, _ = task

    def zero_trainer(dp):
        mesh = build_mesh({"dp": dp}, devices=jax.devices()[:dp])
        return BaguaTrainer(
            loss_fn, None,
            ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9)),
            mesh=mesh, autotune=False,
        )

    tr4 = zero_trainer(4)
    meta4 = None
    state = tr4.init(params)
    meta4 = tr4.checkpoint_layout_metadata()
    if not meta4.get("plan_dependent"):
        pytest.skip("zero layout is not flat-resident on this config")
    state, _ = _run(tr4, state, batch, 2)
    mgr = BaguaCheckpointManager(
        str(tmp_path / "zckpt"), async_save=False)
    mgr.save(2, state, metadata=meta4)
    mgr.wait()

    tr2 = zero_trainer(2)
    state_like = tr2.init(params)
    with pytest.raises(ValueError, match="layout mismatch"):
        elastic_restore(
            BaguaCheckpointManager(str(tmp_path / "zckpt")),
            state_like,
            expect_metadata=tr2.checkpoint_layout_metadata(),
        )
