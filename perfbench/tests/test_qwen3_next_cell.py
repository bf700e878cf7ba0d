"""The ``qwen3_next`` builder and what came with it: the cell resolves to
the source's widths, ``--rehearse`` runs it, it runs end to end through the
``train`` driver at tiny widths on the CPU, the hand counts behind ``mfu``
and the ``gdn_*_roofline`` metrics, the new readers on a hand-made timeline,
the comparison's refusal of a fault, the refusal of a program that lacks the
architecture's fields, and the real step compiled for the described v5e
(nothing runs there; no time comes out of it)."""

import argparse
import dataclasses
import importlib
import json
import subprocess
import sys
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench import cells, kernel_costs_gdn
from perfbench.trace_reduce import Chip, Trace
from perfbench.trace_reduce import parse_op as Op

CELL = "qwen3-next-80b-a3b.pretrain4096-b2-dp1"
ROOT = Path(__file__).resolve().parents[2]
#: what the TPU compiler itself reports as usable on a v5e
V5E_HBM_BYTES = 15.75 * 2 ** 30

TINY = {
    "name": "qwen3-next-tiny", "builder": "qwen3_next",
    "full_attention_interval": 2, "head_dim": 32, "hidden_act": "silu",
    "hidden_size": 48, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 16, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 16,
    "max_position_embeddings": 64, "moe_intermediate_size": 24,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 2,
    "num_experts_per_tok": 3, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 24, "vocab_size": 250,
    "reduced_from": {"num_experts": 8},
    "deployment": {"expert_rank": 1},
    "assumed": {"chunk_length": 64},
    # float32 products: at 48 lanes and 8 experts bfloat16 flips a winner
    # of the router here and there, which the published widths' limits are
    # not made for; what this size rehearses is the plumbing
    "traffic_overrides": {"seq_len": 40, "batch_per_chip": 2,
                          "warmup_steps": 2, "trace_steps": 3,
                          "model": {"dtype": "float32"},
                          "moe": {"dropless": True, "dtype": "float32"}},
}

NEW_METRICS = ("linattn_ms", "gdn_fwd_ms", "gdn_bwd_ms", "gdn_fwd_roofline",
               "gdn_bwd_roofline", "moe_shared_ms")


@pytest.fixture(scope="module")
def builder():
    return cells.load_plugin("builders", "qwen3_next")


def tiny_cell():
    cell = cells.resolve(CELL)
    return dataclasses.replace(
        cell, config=TINY,
        traffic={**cell.traffic, **TINY["traffic_overrides"]})


def reader(name):
    return cells.load_plugin("layer_metrics", name)


def test_the_cell_resolves_to_the_sources_widths():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.traffic_name == "pretrain4096-b2-dp1"
    config = cell.config
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["reduced_from"] == {"num_hidden_layers": 48,
                                      "num_experts": 512,
                                      "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 151936 // 8)
    deployment = config["deployment"]
    assert (deployment["chips"], deployment["chips_per_layer"],
            deployment["expert_parallel"], deployment["vocabulary_slices"],
            deployment["pipeline_stages"], deployment["layers_per_stage"],
            deployment["expert_rank"]) == (192, 16, 16, 8, 12, 4, 0)
    traffic = cell.traffic
    assert (traffic["seq_len"], traffic["batch_per_chip"], traffic["mesh"],
            traffic["prefetch"], traffic["max_in_flight"],
            traffic["replay_steps"], traffic["warmup_steps"],
            traffic["trace_steps"], traffic["reference_micro_batch"]) == (
        4096, 2, {"dp": 1}, 2, 2, 3, 5, 12, 2)
    assert traffic["optimizer"] == {"name": "adamw",
                                    "kwargs": {"learning_rate": 1e-4}}
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the six new readers apply here and nowhere else
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    for metric in cells.load_benchmark()["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
            module = reader(metric["name"])
            assert (module.LAYER, module.UNIT, module.MOVES,
                    module.SOURCE) == (metric["layer"], metric["unit"],
                                       metric["moves"], metric["source"])


def test_the_rehearsal_runs():
    done = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 11), "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"


def test_the_cell_runs_end_to_end_through_the_train_driver(builder):
    driver = cells.load_plugin("drivers", "train")
    args = argparse.Namespace(seed=2 ** 31 + 5, seconds=4.0, trace=1,
                              rehearse=True, keep_trace=None)
    result = driver.run(tiny_cell(), args, time.perf_counter())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    from bagua_tpu.telemetry import counters

    gauges = counters.snapshot()
    # 2 x 40 rows x 3 experts a row = 240 routed pairs, 2 of 8 experts
    assert gauges["moe/rows_per_step"] == 240
    assert (gauges["moe/experts"], gauges["moe/experts_total"]) == (2, 8)
    assert gauges["moe/shared_width"] == 24
    assert (gauges["linattn/layers"], gauges["linattn/chunk"],
            gauges["linattn/key_heads"], gauges["linattn/value_heads"]) == (
        1, 64, 2, 4)
    assert (gauges["attn/kv_heads"], gauges["attn/rotary_dim"],
            gauges["attn/full_layers"]) == (2, 8, 1)


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_reference_check",
        ROOT / "perfbench" / "tools" / "qwen3_next_reference_check.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_comparison_refuses_a_fault(builder, tool):
    """The tool's readings are ``correct``'s own comparison through the
    builder's job: the sound reference agrees, one without the output gate
    is refused."""
    reference = cells.load_plugin("reference", "qwen3_next")
    seed = 2 ** 31 + 5
    args = argparse.Namespace(seed=[seed, 7], fault_seeds=[seed],
                              faults=["clean", "no_output_gate"])
    out = tool.faults(tiny_cell(), builder, reference, args)
    assert set(out["seeds"]) == {seed, 7}
    assert "no_output_gate" not in out["seeds"][7]
    out = out["seeds"][seed]
    assert out["clean"]["agrees"] is True
    assert out["clean"]["largest_gradient_distance"][1] <= (
        reference.GRADIENT_TOLERANCE)
    assert out["clean"]["largest_change_distance"][1] <= (
        reference.CHANGE_TOLERANCE)
    assert out["no_output_gate"]["agrees"] is False
    assert set(tool.FAULTS) == {
        "bf16_scan", "alpha_is_one", "beta_is_one", "no_l2_norm",
        "whole_head_rotation", "no_output_gate", "no_shared_expert_gate",
        "plain_norm_scale"}


def test_flops_per_token_counts_what_is_computed(builder):
    config = cells.resolve(CELL).config
    seq = 4096
    # multiply-accumulates of one forward pass, a token:
    linear = (2048 * 12288 + 2048 * 64 + 4 * 8192   # in-projections, taps
              + 32 * 3 * 128 * 128                  # the recurrence
              + 4096 * 2048)                        # out-projection
    full = (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048   # q+gate, k v, o
            + 2 * 16 * 256 * (seq + 1) / 2)              # the causal half
    experts = (2048 * 512 + 10 * 32 / 512 * 3 * 2048 * 512   # 0.625 held
               + 3 * 2048 * 512 + 2048)                      # shared + gate
    mac = 3 * linear + full + 4 * experts + 2048 * 18992
    assert builder.flops_per_token(config, seq) == pytest.approx(6 * mac)
    # ~ 10.5 TFLOP a step of 8,192 tokens
    assert 10.4e12 < builder.flops_per_token(config, seq) * 8192 < 10.6e12
    layer = 2 * 2048 + 2048 * 512 + 32 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048
    assert builder.parameters(config) == config["parameters_as_built"] == (
        2 * 18992 * 2048 + 2048
        + 3 * (2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128
               + 4096 * 2048 + layer)
        + (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256 + layer)
    ) == 625_667_136


def test_the_builder_counts_what_the_model_holds(builder):
    model = builder.make_model(TINY, {"moe": {"dropless": True}})
    shapes = jax.eval_shape(lambda: builder.make_params(model, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == builder.parameters(TINY)
    assert model.cfg.mixer_layers == (1, 0) and model.cfg.rotary_dim == 8
    assert shapes["block_0"]["mlp"]["expert_wi"].shape == (2, 48, 24)
    assert shapes["block_0"]["mlp"]["router"]["kernel"].shape == (48, 8)
    assert shapes["block_0"]["linear_attn"]["conv"].shape == (4, 128)
    assert shapes["block_1"]["attn"]["q"]["kernel"].shape == (48, 4, 64)


def test_gdn_kernel_costs_are_the_hand_count():
    b, seq, hk, hv, d = 2, 4096, 16, 32, 128
    flop, moved = kernel_costs_gdn.COSTS["gdn_fwd"](b, seq, hk, hv, d, d, 2)
    # 3 d_k d_v multiply-accumulates a position and value head
    assert flop == 2 * 3 * 128 * 128 * b * seq * hv == 25_769_803_776
    # q k at 16 heads, v o at 32, two float32 scalars a value head
    assert moved == b * seq * (2 * 16 * 128 * 2 + 2 * 32 * 128 * 2
                               + 2 * 32 * 4) == 203_423_744
    flop_b, moved_b = kernel_costs_gdn.COSTS["gdn_bwd"](b, seq, hk, hv, d, d,
                                                        2)
    assert flop_b == 2 * flop
    # q k dq dk; v dO dv; g beta dg dbeta
    assert moved_b == b * seq * (4 * 16 * 128 * 2 + 3 * 32 * 128 * 2
                                 + 4 * 32 * 4) == 339_738_624
    # the chunked form's own products, a chunk of 64 and value head:
    # K K^T, Q K^T, the two with the inverse, P U inside the chunk and four
    # with the state — about twice the count, so a share cannot pass 100 %
    # unless work is left out
    inside = 64 * 64 * (128 + 128 + 128 + 128 + 128)
    with_state = 4 * 64 * 128 * 128
    assert 1.8 < (inside + with_state) / (64 * 3 * 128 * 128) < 2.2


HLO = """HloModule jit_bagua_step

ENTRY %main (w: f32[8]) -> f32[8] {
  %w = f32[8]{0} parameter(0)
  %gdn.1 = (bf16[2,4096,4096]{2,1,0}, bf16[2,32,64,128,128]{4,3,2,1,0}) custom-call(%q, %k, %v, %g, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[2,4096,2048]{2,1,0}, bf16[2,4096,2048]{2,1,0}, bf16[2,4096,4096]{2,1,0}, f32[2,32,64,64]{3,2,1,0}, f32[2,32,64,64]{3,2,1,0}}, metadata={op_name="jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/linear_attn/jit(_kernel_fwd)/gdn_fwd/pallas_call"}
  %gdn.2 = (bf16[2,4096,2048]{2,1,0}, bf16[2,4096,2048]{2,1,0}) custom-call(%q, %k, %v, %g, %b, %s, %do), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[2,4096,2048]{2,1,0}, bf16[2,4096,2048]{2,1,0}, bf16[2,4096,4096]{2,1,0}, f32[2,32,64,64]{3,2,1,0}, f32[2,32,64,64]{3,2,1,0}, bf16[2,32,64,128,128]{4,3,2,1,0}, bf16[2,4096,4096]{2,1,0}}, metadata={op_name="jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_0/linear_attn/jit(_kernel_bwd)/gdn_bwd/pallas_call"}
  %flash.1 = (bf16[2,4096,4096]{2,1,0}, f32[32,8,4096]{2,1,0}) custom-call(%fq, %fk, %fv), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[2,4096,4096]{2,1,0}, bf16[2,4096,512]{2,1,0}, bf16[2,4096,512]{2,1,0}}, metadata={op_name="jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_3/attn/jit(_fwd)/flash_fwd/pallas_call"}
  %fusion.1 = bf16[8192,2048]{1,0} fusion(%x), kind=kOutput, calls=%fused.1, metadata={op_name="jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/linear_attn/in_proj_qkvz/dot_general"}
  %fusion.2 = bf16[8192,2048]{1,0} fusion(%x), kind=kOutput, calls=%fused.2, metadata={op_name="jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/mlp/bagua.moe/shared/shared_wi/dot_general"}
}
"""
MS = 1_000_000  # ns
MOSAIC = 'custom_call_target="tpu_custom_call"'


def step(t0):
    """One step of 20 ms from ``t0`` (times are nanoseconds): gdn_fwd 4 ms,
    gdn_bwd 10 ms, a flash call, the in-projection 1 ms and the shared
    expert 0.5 ms."""
    kernel = f"%{{}} = x[] custom-call(), {MOSAIC}"
    fusion = "%{} = x[] fusion(), kind=kOutput"
    spans = [(kernel.format("gdn.1"), 0, 4 * MS),
             (kernel.format("gdn.2"), 4 * MS, 14 * MS),
             (kernel.format("flash.1"), 14 * MS, 15 * MS),
             (fusion.format("fusion.1"), 15 * MS, 16 * MS),
             (fusion.format("fusion.2"), 16 * MS, 16 * MS + MS // 2)]
    return [Op(text, t0 + lo, t0 + hi) for text, lo, hi in spans]


@pytest.fixture
def ctx():
    starts = (0, 20 * MS, 40 * MS)
    ops = [op for t in starts for op in step(t)]
    modules = [Op("jit_bagua_step", t, t + 20 * MS) for t in starts]
    trace = Trace({0: Chip(ops, modules)}, [])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(trace=trace, hlo_text=HLO, chips=1, peak=peak)


@pytest.mark.parametrize("metric,ms", [
    ("gdn_fwd_ms", 4.0), ("gdn_bwd_ms", 10.0), ("linattn_ms", 15.0),
    ("moe_shared_ms", 0.5)])
def test_the_time_readers_on_a_hand_made_timeline(ctx, metric, ms):
    assert reader(metric).reduce(ctx) == pytest.approx(ms)


@pytest.mark.parametrize("metric,kernel,ms", [
    ("gdn_fwd_roofline", "gdn_fwd", 4.0),
    ("gdn_bwd_roofline", "gdn_bwd", 10.0)])
def test_the_roofline_readers_on_a_hand_made_timeline(ctx, monkeypatch,
                                                      metric, kernel, ms):
    from perfbench import scopes

    gauges = {"linattn/key_heads": 16, "linattn/value_heads": 32}
    monkeypatch.setattr(scopes, "program_gauge", gauges.get)
    assert kernel_costs_gdn.call_shapes(HLO) == {
        "gdn.1": (2, 4096, 2048, 4096, 2), "gdn.2": (2, 4096, 2048, 4096, 2)}
    flop, moved = kernel_costs_gdn.COSTS[kernel](2, 4096, 16, 32, 128, 128, 2)
    least_s = max(flop / 197e12, moved / 819e9)
    assert reader(metric).reduce(ctx) == pytest.approx(
        100 * least_s / (ms * 1e-3))
    assert reader(metric).reduce(ctx) < 10
    # without the program's gauges: nothing, and no raise
    monkeypatch.setattr(scopes, "program_gauge", lambda name: None)
    assert reader(metric).reduce(ctx) is None


def test_the_readers_return_nothing_where_the_program_has_nothing(
        monkeypatch):
    """An untraced context on a program without the gauges: None, no
    raise."""
    from perfbench import scopes

    monkeypatch.setattr(scopes, "program_gauge", lambda name: None)
    train = cells.load_plugin("drivers", "train")
    bare = train.ReaderContext(chips=1, spans={}, counters={},
                               rate_per_chip=None, flops_per_unit=1.0,
                               peak=None)
    for name in NEW_METRICS:
        assert reader(name).reduce(bare) is None


def test_a_program_without_the_fields_is_refused_at_once(builder, monkeypatch):
    """The parent commit with these files: a ``CellError`` before any weight
    is made (the driver runs every new cell on the parent first)."""
    from bagua_tpu.models.transformer import TransformerConfig

    monkeypatch.setitem(builder.NEEDED_FIELDS, TransformerConfig,
                        ("n_kv_heads", "no_such_field"))
    with pytest.raises(cells.CellError, match="no field no_such_field"):
        builder.make_trainer(tiny_cell(), cells.resolve(CELL).traffic,
                             jax.devices()[:1])


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
    """The cell's flat-resident step at the published widths under the
    traffic file's remat choice: it fits with at least 1 GiB free by the
    buffer assignment's total; three ``gdn_fwd`` and three ``gdn_bwd`` calls
    (the tags keep what the forward call made: no replay of it), the flash
    kernels once each at head_dim 256 under 16 / 2 heads."""
    # the kernels' gates ask jax.default_backend(), still the CPU here
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
    assert needed >= 0.25 * 16e9           # not cell_too_small
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * cell.config["parameters_as_built"], rel=0.01)
    print(json.dumps({"needed_gib": needed / 2 ** 30,
                      "state_gib": memory.argument_size_in_bytes / 2 ** 30,
                      "temp_gib": memory.temp_size_in_bytes / 2 ** 30}))

    text = compiled.as_text()
    shapes = kernel_costs_gdn.call_shapes(text)
    assert len(shapes) == 6
    assert set(shapes.values()) == {(2, 4096, 16 * 128, 32 * 128, 2)}
    kernels = [line.split("/pallas_call")[0].rsplit("/", 1)[1]
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    count = {name: kernels.count(name) for name in set(kernels)}
    assert count == {
        "gdn_fwd": 3, "gdn_bwd": 3,
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
        # per layer: gate, up, down forward and again in the replay, three
        # d_lhs; three d_rhs
        "gmm_fwd": 4 * 9, "gmm_bwd_drhs": 4 * 3,
        "moe_rows_sum": 4 * 2, "moe_rows_in": 4, "embed_grad": 1}
