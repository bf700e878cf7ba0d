"""Train a tiny LM, then decode with the KV cache — single-device and
tensor-parallel.

Runs on a virtual 8-device CPU mesh by default (same mechanism as the test
suite):

    python examples/generate_lm.py

The script trains the LM to memorize a fixed token sequence through the
prefetching input pipeline, then generates the continuation back two ways
(plain `generate` and `generate_tp` over a tp=2 mesh) and checks they agree
with the memorized sequence.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("BAGUA_ZOO_REAL_DEVICES", "0") != "1":
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import dataclasses  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import bagua_tpu  # noqa: E402
from bagua_tpu.algorithms import GradientAllReduceAlgorithm  # noqa: E402
from bagua_tpu.contrib import prefetch_to_device  # noqa: E402
from bagua_tpu.models.generate import generate, generate_tp  # noqa: E402
from bagua_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
    lm_loss_fn,
)
from bagua_tpu.parallel.mesh import build_mesh  # noqa: E402


def main():
    bagua_tpu.init_process_group()
    n = len(jax.devices())

    cfg = TransformerConfig(vocab_size=32, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_seq_len=24, dtype=jnp.float32)
    model = TransformerLM(cfg)
    seq = np.array([3, 14, 15, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 31, 8],
                   np.int32)
    tokens = np.tile(seq, (8 * n, 1))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(tokens[:2, :-1]))["params"]

    trainer = bagua_tpu.BaguaTrainer(
        lm_loss_fn(model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        donate=False, autotune=False,
    )
    state = trainer.init(params)
    n_steps = int(os.environ.get("BAGUA_EXAMPLE_STEPS", "80"))
    for batch in prefetch_to_device(
        ({"tokens": tokens} for _ in range(n_steps)), trainer=trainer, size=2
    ):
        state, loss = trainer.train_step(state, batch)
        # one step in flight: on the virtual CPU mesh the eight device
        # threads of a collective share a pool of as many threads as the
        # host has cores, and with several steps queued behind each other
        # a loaded 8-core host deadlocks in XLA:CPU's rendezvous (seven
        # arrive, the eighth is never scheduled).  Reading the loss fences
        # the step; on a TPU leave it out.
        loss = float(loss)
    print(f"final train loss: {loss:.5f}")

    trained = trainer.unstack_params(state)
    prompt = jnp.asarray(tokens[:2, :4])
    expect = np.tile(seq[4:-1], (2, 1))

    out = np.asarray(generate(model, trained, prompt, seq.size - 5))
    print("generated (1 device):", out[0].tolist())
    assert (out == expect).all(), (out[0], expect[0])

    if n >= 2 and (os.cpu_count() or 1) >= 2:
        # (single-core hosts skip: 8 virtual devices time-slicing one core
        # can trip XLA's collective stuck-detector mid-scan; the tp decode
        # path itself is covered by tests/test_generate.py)
        # the SAME replicated params drive tensor-parallel decode: tp=1
        # training params are valid tp slices only when re-laid-out, so
        # here we demo the API on a tp-configured model trained densely —
        # heads split 2 ways, logits reduced with the conjugate psum
        cfg_tp = dataclasses.replace(cfg, tp_axis="tp", tp_size=2)
        # NOTE: dense kernels ARE the global tp kernels; generate_tp shards
        # them along the head/width dims per tp_param_dim
        # mesh spans ALL devices (extra axes replicate): XLA's in-process
        # CPU communicator can wedge on collectives over a device SUBSET
        # when the process previously ran full-device work
        out_tp = np.asarray(generate_tp(
            TransformerLM(cfg_tp), trained, prompt, seq.size - 5,
            build_mesh({"rep": n // 2, "tp": 2}),
        ))
        print("generated (tp=2):    ", out_tp[0].tolist())
        assert (out_tp == expect).all(), (out_tp[0], expect[0])

    print("generate_lm OK")


if __name__ == "__main__":
    main()
