"""The ``smallthinker`` builder and what came with it: the cell resolves and
holds its source's widths, ``--rehearse`` runs, the cell runs end to end
through the ``train`` driver at tiny widths on the CPU, the hand counts
behind ``mfu`` and the ``flash_win_*_roofline`` metrics, the new readers on
a hand-made timeline, the refusal of a program that lacks the
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

from perfbench import cells, kernel_costs_window
from perfbench.trace_reduce import Chip, Trace
from perfbench.trace_reduce import parse_op as Op

CELL = "smallthinker-21b-a3b.pretrain8192-dp1"
ROOT = Path(__file__).resolve().parents[2]
#: what the TPU compiler itself reports as usable on a v5e
V5E_HBM_BYTES = 15.75 * 2 ** 30

TINY = {
    "name": "smallthinker-tiny", "builder": "smallthinker",
    "head_dim": 16, "hidden_size": 48, "max_position_embeddings": 64,
    "moe_ffn_hidden_size": 24, "moe_num_active_primary_experts": 3,
    "moe_num_primary_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1], "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "vocab_size": 250,
    "reduced_from": {"moe_num_primary_experts": 8},
    "deployment": {"expert_rank": 1},
    "assumed": {"expert_activation": "relu"},
    "traffic_overrides": {"seq_len": 32, "batch_per_chip": 2,
                          "warmup_steps": 2, "trace_steps": 3,
                          "reference_micro_batch": 2},
}


@pytest.fixture(scope="module")
def builder():
    return cells.load_plugin("builders", "smallthinker")


def tiny_cell():
    return dataclasses.replace(cells.resolve(CELL), config=TINY)


def reader(name):
    return cells.load_plugin("layer_metrics", name)


NEW_METRICS = ("flash_win_fwd_ms", "flash_win_dq_ms", "flash_win_dkv_ms",
               "flash_win_fwd_roofline", "flash_win_dq_roofline",
               "flash_win_dkv_roofline", "attn_full_ms", "gmm_held_ms")


def test_the_cell_resolves_to_the_sources_widths():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.traffic_name == "pretrain8192-dp1"
    config = cell.config
    published = {
        "hidden_size": 2560, "num_attention_heads": 28,
        "num_key_value_heads": 4, "head_dim": 128,
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "sliding_window_size": 4096, "rope_theta": 1500000,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "max_position_embeddings": 16384, "norm_topk_prob": True,
        "moe_primary_router_apply_softmax": True}
    assert {k: config[k] for k in published} == published
    assert config["rope_layout"] == config["sliding_window_layout"] == (
        [0, 1, 1, 1] * 13)
    # the cut: depth, experts held, vocabulary; the published counts beside
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    assert config["reduced_from"] == {"num_hidden_layers": 52,
                                      "moe_num_primary_experts": 64,
                                      "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 151936 // 4)
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the eight new readers apply here and nowhere else; the expert
    # layers' time is OLMoE's ``moe_ms``, this cell appended to its list
    here = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) | {"moe_ms"} <= here
    for metric in cells.load_benchmark()["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
        if metric["name"] == "moe_ms":
            assert metric["workloads"] == ["olmoe-1b-7b.pretrain4096-dp1",
                                           CELL]


def test_the_rehearsal_runs():
    """``--rehearse`` swaps in ``_tiny.json`` and its dense builder: the
    traffic file's keys must be ones that builder knows."""
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
    # 2 x 32 tokens x 3 experts a token = 192 routed pairs, 2 of 8 experts
    assert gauges["moe/rows_per_step"] == 192
    assert (gauges["moe/experts"], gauges["moe/experts_total"]) == (2, 8)
    assert (gauges["attn/kv_heads"], gauges["attn/window"]) == (2, 8)
    assert (gauges["attn/window_layers"], gauges["attn/full_layers"]) == (3, 1)


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "smallthinker_reference_check",
        ROOT / "perfbench" / "tools" / "smallthinker_reference_check.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny_cell_with_its_traffic():
    cell = tiny_cell()
    return dataclasses.replace(
        cell, traffic={**cell.traffic, **TINY["traffic_overrides"]})


def test_the_comparison_refuses_an_attention_fault(builder, tool):
    """``faults`` is the comparison that decides ``correct`` through the
    builder's own job: the trainer's losses and the first gradient of the
    model as built against the sound reference (agrees) and against one
    whose layer 1 is not windowed (refused by the gradient whatever the
    losses say)."""
    reference = cells.load_plugin("reference", "smallthinker")
    args = argparse.Namespace(seed=2 ** 31 + 5,
                              faults=["clean", "full_on_a_window_layer"])
    out = tool.faults(tiny_cell_with_its_traffic(), builder, reference, args)
    assert out["clean"]["agrees"] is True
    assert out["clean"]["largest_gradient_distance"][1] <= (
        reference.GRADIENT_TOLERANCE)
    fault = out["full_on_a_window_layer"]
    assert fault["gradients_agree"] is False and fault["agrees"] is False
    assert fault["gradient_distance"]["block_1/attn/q/kernel"] > (
        2 * reference.GRADIENT_TOLERANCE)


def test_the_drift_reading_runs(builder, tool):
    reference = cells.load_plugin("reference", "smallthinker")
    args = argparse.Namespace(seed=11, steps=2, every=1)
    out = tool.drift(tiny_cell_with_its_traffic(), builder, reference, args)
    # before the first step, after the replay, then every step of the window
    assert [r["step"] for r in out["readings"]] == [0, 3, 5, 6, 7]
    for reading in out["readings"]:
        assert len(reading["held_share"]) == 4
        assert all(0 <= x <= 1 for x in reading["held_share"])
        assert all(0.5 <= x <= 1 for x in reading["busiest_expert"])  # of 2


def test_flops_per_token_counts_what_is_computed(builder):
    config = cells.resolve(CELL).config
    seq = 8192
    # per token, multiply-accumulates of one forward pass at seq 8192:
    projections = 2 * 2560 * 3584 + 2 * 2560 * 512   # q o; k v at 4 heads
    router = 2560 * 64
    experts = 6 * 16 / 64 * 3 * 2560 * 768           # 1.5 held experts
    full = seq * (seq + 1) // 2                      # pairs of one head
    band = 4096 * 4097 // 2 + (seq - 4096) * 4096
    assert (full, band) == (33_558_528, 25_167_872)
    assert builder.visible_pairs(seq, None) == full
    assert builder.visible_pairs(seq, 4096) == band
    assert builder.visible_pairs(2048, 4096) == 2048 * 2049 // 2
    attention = 2 * 28 * 128 * (full + 3 * band) / seq
    head = 2560 * 37984
    mac = 4 * (projections + router + experts) + attention + head
    assert builder.flops_per_token(config, seq) == pytest.approx(6 * mac)
    # ~ 15.4 TFLOP a step; the full s x s convention would count 2.2 x the
    # attention that is computed
    assert 15.3e12 < builder.flops_per_token(config, seq) * seq < 15.4e12
    assert builder.parameters(config) == config["parameters_as_built"] == (
        2 * 37984 * 2560 + 2560 + 4 * (
            2 * 2560 * 3584 + 2 * 2560 * 512 + 2 * 2560 + 2560 * 64
            + 16 * 3 * 2560 * 768)) == 656_529_920


def test_the_builder_counts_what_the_model_holds(builder):
    model = builder.make_model(TINY, {"moe": {"dropless": True}})
    shapes = jax.eval_shape(lambda: builder.make_params(model, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == builder.parameters(TINY)
    assert shapes["block_0"]["mlp"]["expert_wi"].shape == (2, 48, 24)
    assert shapes["block_0"]["mlp"]["router"]["kernel"].shape == (48, 8)


def test_window_kernel_costs_are_the_hand_count():
    b, seq, h, kv, d, w = 1, 8192, 28, 4, 128, 4096
    band = 25_167_872
    assert kernel_costs_window.band_pairs(seq, w) == band
    assert kernel_costs_window.band_pairs(seq, 2 * seq) == seq * (seq + 1) // 2
    q_tensor, kv_tensor = seq * h * d * 2, seq * kv * d * 2
    row = h * seq * 4
    assert kernel_costs_window.COSTS["flash_win_fwd"](b, seq, h, kv, d, w, 2) == (
        h * band * 2 * 2 * d, 2 * q_tensor + 2 * kv_tensor + 8 * row)
    assert kernel_costs_window.COSTS["flash_win_bwd_dq"](
        b, seq, h, kv, d, w, 2) == (
        h * band * 3 * 2 * d, 3 * q_tensor + 2 * kv_tensor + 2 * row)
    flop, moved = kernel_costs_window.COSTS["flash_win_bwd_dkv"](
        b, seq, h, kv, d, w, 2)
    assert (flop, moved) == (h * band * 4 * 2 * d,
                             2 * q_tensor + 4 * kv_tensor + 2 * row)
    assert flop / moved > 240            # compute-bound on a v5e


LOSS = "jit(bagua_step)/jvp(bagua.loss)"
BACK = "jit(bagua_step)/transpose(jvp(bagua.loss))"
MOSAIC = 'custom_call_target="tpu_custom_call"'
QKV = ("operand_layout_constraints={bf16[1,8192,3584]{2,1,0}, "
       "bf16[1,8192,512]{2,1,0}, bf16[1,8192,512]{2,1,0}")
STATS = ", bf16[1,8192,3584]{2,1,0}, f32[28,1,8192]{2,1,0}, f32[28,1,8192]{2,1,0}"
GMM = ("operand_layout_constraints={s32[400]{0}, bf16[51200,2560]{1,0}, "
       "bf16[16,2560,768]{2,1,0}}")
#: what the optimized HLO of the cell's step looks like, cut to what is read
HLO = f"""
HloModule jit_bagua_step

ENTRY %main (w: f32[8]) -> f32[8] {{
  %w = f32[8]{{0}} parameter(0)
  %flash_fwd.1 = (bf16[1,8192,3584]{{2,1,0}}, f32[28,8,8192]{{2,1,0}}) custom-call(%w), {MOSAIC}, {QKV}}}, metadata={{op_name="{LOSS}/block_0/attn/jit(_fwd)/flash_fwd/pallas_call"}}
  %flash_win_fwd.1 = (bf16[1,8192,3584]{{2,1,0}}, f32[28,8,8192]{{2,1,0}}) custom-call(%w), {MOSAIC}, {QKV}}}, metadata={{op_name="{LOSS}/block_1/attn/jit(_fwd)/flash_win_fwd/pallas_call"}}
  %fusion.route = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.1, metadata={{op_name="{LOSS}/block_1/mlp/bagua.moe/route/dot_general"}}
  %gmm_fwd.1 = bf16[51200,768]{{1,0}} custom-call(%w), {MOSAIC}, {GMM}, metadata={{op_name="{LOSS}/block_1/mlp/bagua.moe/experts/gmm_fwd/pallas_call"}}
  %gmm_bwd_drhs.1 = f32[16,2560,768]{{2,1,0}} custom-call(%w), {MOSAIC}, operand_layout_constraints={{s32[400]{{0}}, bf16[51200,2560]{{1,0}}, bf16[51200,768]{{1,0}}}}, metadata={{op_name="{BACK}/block_1/mlp/bagua.moe/experts/gmm_bwd_drhs/pallas_call"}}
  %flash_win_bwd_dkv.1 = (bf16[1,8192,512]{{2,1,0}}, bf16[1,8192,512]{{2,1,0}}) custom-call(%w), {MOSAIC}, {QKV}{STATS}}}, metadata={{op_name="{BACK}/block_1/attn/jit(_bwd)/flash_win_bwd_dkv/pallas_call"}}
  %flash_win_bwd_dq.1 = bf16[1,8192,3584]{{2,1,0}} custom-call(%w), {MOSAIC}, {QKV}{STATS}}}, metadata={{op_name="{BACK}/block_1/attn/jit(_bwd)/flash_win_bwd_dq/pallas_call"}}
  %flash_bwd_dkv.1 = (bf16[1,8192,512]{{2,1,0}}, bf16[1,8192,512]{{2,1,0}}) custom-call(%w), {MOSAIC}, {QKV}{STATS}}}, metadata={{op_name="{BACK}/block_0/attn/jit(_bwd)/flash_bwd_dkv/pallas_call"}}
  %flash_bwd_dq.1 = bf16[1,8192,3584]{{2,1,0}} custom-call(%w), {MOSAIC}, {QKV}{STATS}}}, metadata={{op_name="{BACK}/block_0/attn/jit(_bwd)/flash_bwd_dq/pallas_call"}}
  ROOT %tuple = (f32[8]) tuple(%w)
}}
"""


def step(t0):
    """One step of 1,000 ns from ``t0`` (times are nanoseconds)."""
    kernel = f"%{{}} = x[] custom-call(), {MOSAIC}"
    spans = [
        (kernel.format("flash_fwd.1"), 0, 100),
        (kernel.format("flash_win_fwd.1"), 100, 160),
        ("%fusion.route = x[] fusion(), kind=kLoop", 160, 170),
        (kernel.format("gmm_fwd.1"), 170, 200),
        (kernel.format("gmm_bwd_drhs.1"), 200, 220),
        (kernel.format("flash_win_bwd_dkv.1"), 220, 340),
        (kernel.format("flash_win_bwd_dq.1"), 340, 430),
        (kernel.format("flash_bwd_dkv.1"), 430, 630),
        (kernel.format("flash_bwd_dq.1"), 630, 780),
    ]
    return [Op(text, t0 + lo, t0 + hi) for text, lo, hi in spans]


@pytest.fixture
def ctx():
    ops = step(0) + step(1000) + step(2000)
    modules = [Op("jit_bagua_step", t, t + 1000) for t in (0, 1000, 2000)]
    trace = Trace({0: Chip(ops, modules)}, [])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(trace=trace, hlo_text=HLO, chips=1, peak=peak)


@pytest.mark.parametrize("metric,ns", [
    ("flash_win_fwd_ms", 60), ("flash_win_dq_ms", 90),
    ("flash_win_dkv_ms", 120), ("attn_full_ms", 100 + 200 + 150),
    ("moe_ms", 10 + 30 + 20), ("gmm_held_ms", 30 + 20)])
def test_the_time_readers_on_a_hand_made_timeline(ctx, metric, ns):
    assert reader(metric).reduce(ctx) == pytest.approx(ns * 1e-6)


def test_call_shapes_are_read_from_the_compiled_text():
    shapes = kernel_costs_window.call_shapes(HLO)
    flash = {name for name in shapes if name.startswith("flash")}
    assert len(flash) == 6
    assert {shapes[name] for name in flash} == {(1, 8192, 3584, 512, 2)}
    assert not any(name.startswith("gmm") for name in shapes)


@pytest.mark.parametrize("metric,kernel,ns", [
    ("flash_win_fwd_roofline", "flash_win_fwd", 60),
    ("flash_win_dq_roofline", "flash_win_bwd_dq", 90),
    ("flash_win_dkv_roofline", "flash_win_bwd_dkv", 120)])
def test_the_roofline_readers_on_a_hand_made_timeline(ctx, monkeypatch,
                                                      metric, kernel, ns):
    from perfbench import scopes

    gauges = {"attn/window": 4096, "attn/kv_heads": 4}
    monkeypatch.setattr(scopes, "program_gauge", gauges.get)
    flop, moved = kernel_costs_window.COSTS[kernel](1, 8192, 28, 4, 128,
                                                    4096, 2)
    assert flop / moved > 197e12 / 819e9       # compute-bound: the peak
    assert reader(metric).reduce(ctx) == pytest.approx(
        100 * flop / (ns * 1e-9) / 197e12)
    # a program that sets no such gauges (the parent): nothing, no raise
    monkeypatch.setattr(scopes, "program_gauge", lambda name: None)
    assert reader(metric).reduce(ctx) is None


def test_the_readers_return_nothing_where_the_program_has_nothing():
    """An untraced context: None, no raise."""
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
    traffic file's remat choice: it fits with at least 1 GiB free, the
    kernels of both kinds of layer are there, and no key or value tensor
    is repeated to the 28 query heads."""
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
    assert needed >= 0.25 * 16e9           # not cell_too_small
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * cell.config["parameters_as_built"], rel=0.01)
    print(json.dumps({"needed_gib": needed / 2 ** 30,
                      "state_gib": memory.argument_size_in_bytes / 2 ** 30,
                      "temp_gib": memory.temp_size_in_bytes / 2 ** 30}))

    text = compiled.as_text()
    shapes = kernel_costs_window.call_shapes(text)
    # q at 28 heads, k / v at 4, in every one of the 12 flash calls (one
    # full layer, three window layers; fwd, dq, dkv each)
    assert len(shapes) == 12
    assert set(shapes.values()) == {(1, 8192, 28 * 128, 4 * 128, 2)}
    kernels = [line.split("/pallas_call")[0].rsplit("/", 1)[1]
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    count = {name: kernels.count(name) for name in set(kernels)}
    assert count == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
        "flash_win_fwd": 3, "flash_win_bwd_dq": 3, "flash_win_bwd_dkv": 3,
        # per layer: gate, up, down forward and again in the replay, three
        # d_lhs; three d_rhs
        "gmm_fwd": 4 * 9, "gmm_bwd_drhs": 4 * 3}
