"""The ``olmoe`` builder and what came with it: the cell end to end through
the ``train`` driver at tiny widths on the CPU (``--rehearse`` swaps in
``_tiny.json`` and its dense builder, so it never reaches this builder), the
hand counts behind ``mfu`` and the ``gmm_*_roofline`` metrics, the refusal of
a program that lacks the architecture's fields, and the real step compiled
for the described v5e (nothing runs there; no time comes out of it)."""

import argparse
import dataclasses
import importlib
import json
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench import cells, kernel_costs_gmm

CELL = "olmoe-1b-7b.pretrain4096-dp1"
#: what the TPU compiler itself reports as usable on a v5e
V5E_HBM_BYTES = 15.75 * 2 ** 30

TINY = {
    "name": "olmoe-tiny", "builder": "olmoe",
    "hidden_size": 64, "intermediate_size": 32, "max_position_embeddings": 64,
    "norm_topk_prob": False, "num_attention_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 4, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "vocab_size": 250,
    "assumed": {"router_aux_loss_coef": 0.01},
    "traffic_overrides": {"seq_len": 32, "batch_per_chip": 2,
                          "warmup_steps": 2, "trace_steps": 3,
                          "reference_micro_batch": 2},
}


@pytest.fixture(scope="module")
def builder():
    return cells.load_plugin("builders", "olmoe")


def tiny_cell():
    return dataclasses.replace(cells.resolve(CELL), config=TINY)


def test_the_cell_runs_end_to_end_through_the_train_driver(builder):
    driver = cells.load_plugin("drivers", "train")
    args = argparse.Namespace(seed=2 ** 31 + 5, seconds=5.0, trace=1,
                              rehearse=True, keep_trace=None)
    result = driver.run(tiny_cell(), args, time.perf_counter())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["device"]["platform"] == "cpu"
    # a CPU run prints counts only; the MoE gauges are set on this path.
    # 2 x 32 tokens x 4 experts a token = 256 rows; the dense fallback of
    # gmm pads nothing off the TPU, so the share is 0, not None
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert metrics["moe_padding_share"] == 0.0
    from bagua_tpu.telemetry import counters

    gauges = counters.snapshot()
    assert gauges["moe/rows_per_step"] == 256
    assert gauges["moe/padded_rows_per_step"] == 256
    assert gauges["moe/experts"] == 8


def test_the_compiled_step_carries_the_moe_scopes(builder):
    """What ``moe_ms`` keys on, in the text the driver hands the readers."""
    from perfbench import scopes

    model, trainer = builder.make_trainer(tiny_cell(), {
        **cells.resolve(CELL).traffic, **TINY["traffic_overrides"]},
        jax.devices()[:1])
    state = trainer.init(builder.make_params(model, 0))
    batch = trainer.shard_batch({"tokens": jnp.zeros((2, 33), jnp.int32)})
    paths = scopes.instruction_scopes(
        trainer.compiled_step(state, batch).as_text()).values()
    inside = [p for p in paths if "bagua.moe" in p.split("/")]
    for part in ("route", "dispatch", "experts", "combine"):
        assert any(f"bagua.moe/{part}" in p for p in inside), part
    assert any(p.startswith("jit(bagua_step)/jvp(bagua.loss)") for p in inside)
    assert any("transpose(jvp(bagua.loss))" in p for p in inside)


def test_flops_per_token_is_the_hand_count_over_active_parameters(builder):
    config = cells.resolve(CELL).config
    # per token, multiply-accumulates of one forward pass at seq 4096:
    attention_matrices = 4 * 2048 * 2048            # q k v o
    scores_and_values = 2 * 4096 * 2048             # q k^T and p v, full s x s
    experts = 8 * 3 * 2048 * 1024                   # 8 of 64, gate up down
    router = 2048 * 64
    head = 2048 * 50304
    mac = attention_matrices + scores_and_values + experts + router + head
    assert mac == 187_039_744
    assert builder.flops_per_token(config, 4096) == 6 * mac == 1_122_238_464
    # and the parameters the optimizer really moves: all 64 experts
    assert builder.parameters(config) == config["parameters_as_built"] == (
        2 * 50304 * 2048 + 2048
        + 4 * 2048 * 2048 + 4 * 2048 + 2048 * 64 + 64 * 3 * 2048 * 1024)


def test_the_builder_counts_what_the_model_holds(builder):
    model = builder.make_model(TINY, {"moe": {"dropless": True}})
    shapes = jax.eval_shape(lambda: builder.make_params(model, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == builder.parameters(TINY)


def test_gmm_costs_are_the_hand_count():
    # 2 x 4096 tokens x 8 experts = 65,536 rows; each of 64 groups may waste
    # 127: 65,536 + 8,128 = 73,664 -> 73,728 rows of 128-row blocks
    rows = -(-(65536 + 64 * 127) // 128) * 128
    assert rows == 73728
    flop, moved = kernel_costs_gmm.gmm_fwd(rows, 2048, 1024, 64, 2)
    assert flop == 2 * 73728 * 2048 * 1024 == 309_237_645_312
    assert moved == 2 * (73728 * 2048 + 64 * 2048 * 1024 + 73728 * 1024)
    flop_t, moved_t = kernel_costs_gmm.gmm_fwd(rows, 1024, 2048, 64, 2)
    assert (flop_t, moved_t) == (flop, moved)      # the transposed call
    flop_r, moved_r = kernel_costs_gmm.gmm_bwd_drhs(rows, 2048, 1024, 64, 2)
    assert flop_r == flop
    assert moved_r == 2 * 73728 * (2048 + 1024) + 4 * 64 * 2048 * 1024
    # both compute-bound on a v5e: more FLOP a byte than 197e12 / 819e9
    assert flop / moved > 240 and flop_r / moved_r > 240


GMM_LINES = """
  %gmm_fwd.7 = bf16[73728,1024]{1,0:T(8,128)(2,1)} custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[576]{0}, bf16[73728,2048]{1,0}, bf16[64,2048,1024]{2,1,0}}, metadata={op_name="jit(bagua_step)/jvp(bagua.loss)/mlp/bagua.moe/experts/gmm_fwd/pallas_call"}
  %gmm_bwd_drhs.3 = f32[64,1024,2048]{2,1,0:T(8,128)} custom-call(%a, %d, %e), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[576]{0}, bf16[73728,1024]{1,0}, bf16[73728,2048]{1,0}}, metadata={op_name="x/gmm_bwd_drhs/pallas_call"}
  %flash.1 = (bf16[32,4096,128]{2,1,0}, f32[32,8,4096]{2,1,0}) custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0}}, metadata={op_name="x/flash_fwd/pallas_call"}
"""


def test_gmm_call_shapes_are_read_from_the_compiled_text():
    assert kernel_costs_gmm.call_shapes(GMM_LINES) == {
        "gmm_fwd.7": (73728, 2048, 1024, 64, 2),
        "gmm_bwd_drhs.3": (73728, 1024, 2048, 64, 2),
    }


def test_a_program_without_the_fields_is_refused_at_once(builder, monkeypatch):
    """The parent commit with these files: a ``CellError`` before any weight
    is made (the driver runs every new cell on the parent first)."""
    from bagua_tpu.models.transformer import TransformerConfig

    monkeypatch.setitem(builder.NEEDED_FIELDS, TransformerConfig,
                        ("rope_theta", "no_such_field"))
    with pytest.raises(cells.CellError, match="no field no_such_field"):
        builder.make_trainer(tiny_cell(), cells.resolve(CELL).traffic,
                             jax.devices()[:1])


def test_the_readers_return_nothing_where_the_program_has_nothing():
    """A parent run, a dense cell, an untraced context: None, no raise."""
    train = cells.load_plugin("drivers", "train")
    ctx = train.ReaderContext(chips=1, spans={}, counters={},
                              rate_per_chip=None, flops_per_unit=1.0,
                              peak=None)
    for name in ("moe_ms", "moe_permute_ms", "gmm_fwd_ms", "gmm_bwd_drhs_ms",
                 "gmm_fwd_roofline", "gmm_bwd_drhs_roofline"):
        assert cells.load_plugin("layer_metrics", name).reduce(ctx) is None


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to describe
        pytest.skip(f"the v5e:2x2 topology cannot be described here: {e}")


def test_the_real_step_compiles_for_the_described_v5e(builder, topology,
                                                      monkeypatch):
    """The cell's flat-resident step at the published widths: it fits, it
    leaves at least 1 GiB, the next batch size does not, and the twelve
    Mosaic calls are there — the numbers the traffic file's ``assumed``
    quotes."""
    # flash_supported and gmm ask jax.default_backend(), still the CPU here
    flash = importlib.import_module("bagua_tpu.ops.flash_attention")
    monkeypatch.setattr(flash.jax, "default_backend", lambda: "tpu")
    from bagua_tpu.core import backend

    cell = cells.resolve(CELL)
    model, trainer = builder.make_trainer(cell, cell.traffic,
                                          list(topology.devices)[:1])
    # the flat-safety probe cannot run under eval_shape (PERF.md §7)
    assert backend._optimizer_flattens_safely(trainer._flat_opt())
    params = jax.eval_shape(lambda: builder.make_params(model, 0))
    replicated = NamedSharding(trainer.mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(trainer.init, params))
    assert trainer._flat_resident
    batch = {"tokens": jax.ShapeDtypeStruct(
        (int(cell.traffic["batch_per_chip"]),
         int(cell.traffic["seq_len"]) + 1), jnp.int32,
        sharding=NamedSharding(trainer.mesh, P("dp")))}
    compiled = trainer.compiled_step(state, batch)

    memory = compiled.memory_analysis()
    needed = (memory.argument_size_in_bytes + memory.output_size_in_bytes
              - memory.alias_size_in_bytes + memory.temp_size_in_bytes
              + memory.generated_code_size_in_bytes)
    assert needed <= V5E_HBM_BYTES - 2 ** 30
    assert needed >= 12 * 2 ** 30          # the chip is full
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * cell.config["parameters_as_built"], rel=0.01)
    print(json.dumps({"needed_gib": needed / 2 ** 30,
                      "state_gib": memory.argument_size_in_bytes / 2 ** 30,
                      "temp_gib": memory.temp_size_in_bytes / 2 ** 30}))

    text = compiled.as_text()
    shapes = kernel_costs_gmm.call_shapes(text)
    kinds = sorted(name.split(".")[0] for name in shapes)
    assert kinds == ["gmm_bwd_drhs"] * 3 + ["gmm_fwd"] * 6
    assert {s[0] for s in shapes.values()} == {73728}
    assert text.count('custom_call_target="tpu_custom_call"') == 12
