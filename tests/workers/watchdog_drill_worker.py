"""Worker for the watchdog end-to-end drill (VERDICT r4 #8).

The FULL failure chain in one scripted run, through the production paths:

1. train normally with orbax checkpoints every step;
2. at ``BAGUA_TEST_WEDGE_AT_STEP`` (first attempt only, marker-gated) the
   batch carries a huge ``spin`` count and the loss's ``fori_loop`` wedges
   the DEVICE program inside ``trainer.train_step`` — a genuine on-device
   hang, not a host sleep;
3. the hang watchdog's waiter thread blocks on that step's readback, times
   out (``BAGUA_COMM_TIMEOUT_S``), dumps stacks, sets the abort flag,
   flushes queued async checkpoint saves, and ``os._exit(3)``;
4. the launcher sees the nonzero exit and restarts the gang;
5. the restarted worker resumes from the checkpoint and completes.

Run on the real TPU by ``scripts/watchdog_drill.py``; the CPU twin runs in CI
(tests/test_launcher.py::test_watchdog_hang_restart_resume).  The wedge is
a dynamic-trip-count ``fori_loop`` so the same compiled step serves both
the normal (spin=0) and wedged paths — no recompile masks the hang.
"""

import os
import sys

import jax

if os.environ.get("BAGUA_TEST_FORCE_CPU") == "1":
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import bagua_tpu  # noqa: E402
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm  # noqa: E402
from bagua_tpu.checkpoint import BaguaCheckpointManager  # noqa: E402
from bagua_tpu.models.mlp import MLP  # noqa: E402


def main():
    out_dir = os.environ["BAGUA_TEST_OUT"]
    steps = int(os.environ.get("BAGUA_TEST_STEPS", "10"))
    wedge_at = int(os.environ.get("BAGUA_TEST_WEDGE_AT_STEP", "-1"))
    mesh = bagua_tpu.init_process_group()
    print(f"drill: platform={jax.devices()[0].platform} "
          f"timeout={os.environ.get('BAGUA_COMM_TIMEOUT_S')}s", flush=True)

    model = MLP(features=(64, 8))
    teacher = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    y = jnp.argmax(x @ teacher, -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()
        # the wedge: a dynamic-trip-count device loop.  spin=0 -> identity;
        # spin=huge -> the device program runs ~forever and the watchdog
        # must fire.  Same compiled step either way.
        def body(i, a):
            return a + jnp.sin(a) * 1e-9

        wedge = jax.lax.fori_loop(
            0, b["spin"][0], body, jnp.zeros((512, 512), jnp.float32)
        ).mean()
        # stop_gradient: reverse-mode AD cannot differentiate a dynamic
        # trip count (and the wedge must not change the gradients anyway);
        # 1e-20, not 0.0: XLA may simplify mul-by-zero and delete the loop
        return loss + jax.lax.stop_gradient(wedge) * 1e-20

    trainer = bagua_tpu.BaguaTrainer(
        loss_fn, optax.sgd(0.2), GradientAllReduceAlgorithm(), mesh=mesh,
        autotune=False,
    )
    state = trainer.init(params)
    mgr = BaguaCheckpointManager(os.path.join(out_dir, "ckpt"),
                                 async_save=True)
    start, state = mgr.try_restore(
        state, expect_metadata=trainer.checkpoint_layout_metadata()
    )
    if start is not None:
        print(f"resumed from checkpoint step {start}", flush=True)
        start += 1
    else:
        start = 0

    marker = os.path.join(out_dir, "wedged.marker")
    for step in range(start, steps):
        spin = np.int32(0)
        if step == wedge_at and not os.path.exists(marker):
            open(marker, "w").close()
            # huge dynamic trip count: ~hours of device time if left alone
            spin = np.int32(2**31 - 1)
            print(f"injecting device wedge at step {step}", flush=True)
        batch = trainer.shard_batch({
            "x": np.asarray(x), "y": np.asarray(y),
            "spin": np.full((x.shape[0],), spin, np.int32),
        })
        state, loss = trainer.train_step(state, batch)
        # fence each step: the drill wants the hang to surface AT the
        # wedged step, and the per-step save below needs real values
        lval = float(loss)
        mgr.save(step, state, metadata=trainer.checkpoint_layout_metadata())
        print(f"step {step} loss {lval:.6f}", flush=True)
    mgr.wait()

    with open(os.path.join(out_dir, "final.txt"), "w") as f:
        f.write(f"{lval:.6f}")
    print(f"drill complete: final_loss {lval:.6f}", flush=True)


if __name__ == "__main__":
    main()
