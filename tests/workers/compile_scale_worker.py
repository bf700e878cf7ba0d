"""Worker for tests/test_compile_scale.py: trace + compile (never execute)
the ``shift_one`` decentralized train step on an ``argv[1]``-device virtual
CPU mesh and print one JSON line with the compile seconds.

One process per device count: the virtual device count is fixed at backend
init, so it is set here before jax is imported.
"""

import json
import os
import sys
import time

N = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N}"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

import bagua_tpu  # noqa: E402
from bagua_tpu.algorithms import DecentralizedAlgorithm  # noqa: E402
from bagua_tpu.models.mlp import MLP  # noqa: E402

model = MLP(features=(64, 8))


def loss_fn(p, b):
    logits = model.apply({"params": p}, b["x"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, b["y"]
    ).mean()


trainer = bagua_tpu.BaguaTrainer(
    loss_fn, optax.sgd(0.1),
    DecentralizedAlgorithm(peer_selection_mode="shift_one"),
    mesh=Mesh(np.array(jax.devices()[:N]), ("dp",)), bucket_bytes=4096,
)
state = trainer.init(
    model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32)))["params"])
batch = trainer.shard_batch({
    "x": jnp.zeros((N * 2, 32), jnp.float32),
    "y": jnp.zeros((N * 2,), jnp.int32),
})
lowered = trainer._get_step_fn().lower(state, batch)
t0 = time.time()
lowered.compile()
print(json.dumps({"n_devices": N, "compile_s": round(time.time() - t0, 3)}),
      flush=True)
