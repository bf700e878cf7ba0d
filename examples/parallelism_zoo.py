"""Every parallelism axis in one script: dp, hierarchical, tp, pp, sp, ep.

Runs on a virtual 8-device CPU mesh by default (same mechanism as the test
suite) so it works on any machine:

    python examples/parallelism_zoo.py

On a real TPU pod slice, drop the env overrides and size the meshes to
``len(jax.devices())``.  The reference framework covers only the dp rows
(SURVEY.md §2.3); tp/pp/sp are additive capabilities of this rebuild.
"""

import os
import sys

# runnable from a plain checkout: `python examples/parallelism_zoo.py`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("BAGUA_ZOO_REAL_DEVICES", "0") != "1":
    # demo default: a virtual 8-device CPU mesh (works everywhere); set
    # BAGUA_ZOO_REAL_DEVICES=1 on a pod slice with >= 8 real chips.
    # last-occurrence-wins, so appending overrides any inherited count
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

import bagua_tpu  # noqa: E402
from bagua_tpu.algorithms.gradient_allreduce import (  # noqa: E402
    GradientAllReduceAlgorithm,
)
from bagua_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
    lm_loss_fn,
    sp_lm_loss_fn,
    tp_param_dim,
)
from bagua_tpu.parallel.mesh import build_mesh  # noqa: E402

VOCAB, SEQ = 64, 16


def _data(batch, seq=SEQ):
    return jax.random.randint(jax.random.PRNGKey(0), (batch, seq + 1), 0, VOCAB)


def _cfg(**kw):
    return TransformerConfig(vocab_size=VOCAB, d_model=32, n_heads=4,
                             n_layers=4, d_ff=64, max_seq_len=SEQ,
                             dtype=jnp.float32, **kw)


def run(name, trainer, params, tokens, steps=5):
    state = trainer.init(params)
    batch = trainer.shard_batch({"tokens": tokens})
    for _ in range(steps):
        state, loss = trainer.train_step(state, batch)
    print(f"{name:32s} loss after {steps} steps: {float(loss):.4f}")


def main():
    bagua_tpu.init_process_group()
    n = len(jax.devices())
    assert n >= 8, f"need 8 devices, found {n}"

    # --- data parallel (flat) --------------------------------------------
    model = TransformerLM(_cfg())
    tokens = _data(16)
    params = model.init(jax.random.PRNGKey(1), tokens[:2, :-1])["params"]
    run("dp=8", bagua_tpu.BaguaTrainer(
        lm_loss_fn(model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 8}), autotune=False), params, tokens)

    # --- hierarchical (inter x intra, the reference's Leader/Worker) -----
    run("hierarchical inter=2 x intra=4", bagua_tpu.BaguaTrainer(
        lm_loss_fn(model), optax.adam(1e-2),
        GradientAllReduceAlgorithm(hierarchical=True),
        mesh=build_mesh({"inter": 2, "intra": 4}), autotune=False),
        params, tokens)

    # --- tensor parallel (Megatron-style) --------------------------------
    from bagua_tpu.parallel.tensor_parallel import globalize_tp_params

    tp_model = TransformerLM(_cfg(tp_axis="tp", tp_size=4))
    tp_params = globalize_tp_params(
        tp_model.init(jax.random.PRNGKey(2), tokens[:2, :-1])["params"],
        jax.random.PRNGKey(3), 4, tp_param_dim)
    run("dp=2 x tp=4", bagua_tpu.BaguaTrainer(
        lm_loss_fn(tp_model), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 2, "tp": 4}), tp_axis="tp", autotune=False),
        tp_params, tokens)

    # --- pipeline parallel (GPipe microbatches) --------------------------
    from bagua_tpu.parallel.pipeline import (
        PipelinedTransformerLM, globalize_pp_params, pp_lm_loss_fn,
    )

    pp_model = PipelinedTransformerLM(_cfg(), pp_size=4, n_microbatches=2)
    pp_params = globalize_pp_params(
        pp_model.init(jax.random.PRNGKey(4), tokens[:2])["params"],
        jax.random.PRNGKey(5), 4)
    run("dp=2 x pp=4 (2 microbatches)", bagua_tpu.BaguaTrainer(
        pp_lm_loss_fn(pp_model), optax.adam(1e-2),
        GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 2, "pp": 4}), pp_axis="pp", autotune=False),
        pp_params, tokens)

    # --- sequence parallel (ring attention) ------------------------------
    from bagua_tpu.parallel.ring_attention import make_ring_attention

    sp_cfg = _cfg(sp_axis="sp")
    sp_model = TransformerLM(sp_cfg, attn_fn=make_ring_attention(4))
    sp_params = sp_model.init(
        jax.random.PRNGKey(6), tokens[:2, : SEQ // 4])["params"]
    run("dp=2 x sp=4 (ring attention)", bagua_tpu.BaguaTrainer(
        sp_lm_loss_fn(sp_model, sp_size=4), optax.adam(1e-2),
        GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 2, "sp": 4}), seq_axis="sp", autotune=False),
        sp_params, tokens)

    # --- expert parallel (dropless MoE) ----------------------------------
    from bagua_tpu.model_parallel.moe import MoEMLP, moe_lm_loss_fn
    from bagua_tpu.model_parallel.moe.layer import globalize_expert_params

    moe_model = TransformerLM(_cfg(), mlp_factory=lambda i: (
        lambda: MoEMLP(n_experts=8, d_ff=64, k=2, ep_size=4, dropless=True,
                       dtype=jnp.float32)
    ) if i == 1 else None)
    moe_params = globalize_expert_params(
        moe_model.init(jax.random.PRNGKey(7), tokens[:2, :-1])["params"],
        jax.random.PRNGKey(8), ep_size=4)
    run("dp=2 x ep=4 (dropless MoE)", bagua_tpu.BaguaTrainer(
        moe_lm_loss_fn(moe_model), optax.adam(1e-2),
        GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 2, "ep": 4}), expert_axis="ep",
        autotune=False), moe_params, tokens)

    # --- 3-D: dp x pp x tp in one step -----------------------------------
    m3_cfg = _cfg(tp_axis="tp", tp_size=2)
    m3 = PipelinedTransformerLM(m3_cfg, pp_size=2, n_microbatches=2)
    m3_params = globalize_pp_params(
        m3.init(jax.random.PRNGKey(9), tokens[:2])["params"],
        jax.random.PRNGKey(10), 2, tp_size=2)
    run("dp=2 x pp=2 x tp=2 (3-D)", bagua_tpu.BaguaTrainer(
        pp_lm_loss_fn(m3), optax.adam(1e-2), GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 2, "pp": 2, "tp": 2}), pp_axis="pp",
        tp_axis="tp", autotune=False), m3_params, tokens)

    # --- ZeRO-1 (sharded optimizer state) + grad accumulation ------------
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm

    run("dp=8 ZeRO-1 + accum=2", bagua_tpu.BaguaTrainer(
        lm_loss_fn(model), None,
        ZeroOptimizerAlgorithm(optax.adam(1e-2), clip_global_norm=1.0),
        mesh=build_mesh({"dp": 8}), accum_steps=2, autotune=False),
        params, tokens)

    print("all parallelism axes ran")


if __name__ == "__main__":
    main()
