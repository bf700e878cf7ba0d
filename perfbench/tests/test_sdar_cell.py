"""The ``sdar`` builder and what came with it: both new cells resolve,
``--rehearse`` runs them, the SDAR cell runs end to end through the ``train``
driver at tiny widths on the CPU, the hand counts behind ``mfu`` and the
``flash_bd_*_roofline`` metrics, the new readers on a hand-made timeline,
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

from perfbench import cells, kernel_costs_blockdiff, kernel_costs_window
from perfbench.trace_reduce import Chip, Trace
from perfbench.trace_reduce import parse_op as Op

CELL = "sdar-30b-a3b.blockdiff4096-b1-dp1"
COMM_CELL = "bert-large.squad384-bf16comm-dp4"
ROOT = Path(__file__).resolve().parents[2]
#: what the TPU compiler itself reports as usable on a v5e
V5E_HBM_BYTES = 15.75 * 2 ** 30

TINY = {
    "name": "sdar-tiny", "builder": "sdar",
    "head_dim": 16, "hidden_size": 48, "max_position_embeddings": 64,
    "moe_intermediate_size": 24, "num_experts_per_tok": 3, "num_experts": 2,
    "norm_topk_prob": True, "hidden_act": "silu",
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "vocab_size": 250,
    "reduced_from": {"num_experts": 8},
    "deployment": {"expert_rank": 1},
    "assumed": {"block_length": 4, "mask_token_id": 249},
    "traffic_overrides": {"seq_len": 32, "batch_per_chip": 2,
                          "warmup_steps": 2, "trace_steps": 3},
}

NEW_METRICS = ("flash_bd_fwd_ms", "flash_bd_dq_ms", "flash_bd_dkv_ms",
               "flash_bd_fwd_roofline", "flash_bd_dq_roofline",
               "flash_bd_dkv_roofline", "diffusion_masked_share")


@pytest.fixture(scope="module")
def builder():
    return cells.load_plugin("builders", "sdar")


def tiny_cell():
    return dataclasses.replace(cells.resolve(CELL), config=TINY)


def reader(name):
    return cells.load_plugin("layer_metrics", name)


def test_the_cell_resolves_to_the_sources_widths():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.traffic_name == "blockdiff4096-b1-dp1"
    config = cell.config
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "intermediate_size": 6144, "rope_theta": 1000000,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "max_position_embeddings": 32768, "norm_topk_prob": True,
        "hidden_act": "silu", "decoder_sparse_step": 1,
        "mlp_only_layers": [], "model_type": "sdar_moe"}
    assert {k: config[k] for k in published} == published
    # the cut: depth, experts held, vocabulary; the published counts beside
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["reduced_from"] == {"num_hidden_layers": 48,
                                      "num_experts": 128,
                                      "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 151936 // 8)
    deployment = config["deployment"]
    assert (deployment["chips_per_layer"], deployment["expert_parallel"],
            deployment["vocabulary_slices"], deployment["pipeline_stages"],
            deployment["layers_per_stage"], deployment["expert_rank"]) == (
        8, 8, 8, 8, 6, 0)
    assert config["assumed"]["mask_token_id"] == config["vocab_size"] - 1
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the seven new readers apply here and nowhere else
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    for metric in cells.load_benchmark()["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
            module = reader(metric["name"])
            assert (module.LAYER, module.UNIT, module.MOVES,
                    module.SOURCE) == (metric["layer"], metric["unit"],
                                       metric["moves"], metric["source"])


def test_the_comm_cell_is_the_dp4_cell_but_for_the_wire():
    cell, dp4 = cells.resolve(COMM_CELL), cells.resolve(
        "bert-large.squad384-dp4")
    assert cell.chips == 4 and cell.config == dp4.config
    prose = ("who", "assumed")
    ours = {k: v for k, v in cell.traffic.items() if k not in prose}
    theirs = {k: v for k, v in dp4.traffic.items() if k not in prose}
    assert ours.pop("algorithm") == {
        "class": theirs["algorithm"]["class"],
        "kwargs": {"hierarchical": False, "comm_dtype": "bfloat16"}}
    theirs.pop("algorithm")
    assert ours == theirs
    # at most a quarter of the cells, rounded down, ask for four chips
    chips = [w["chips"] for w in cells.load_benchmark()["workloads"]]
    assert chips.count(4) == 2 <= len(chips) // 4


@pytest.mark.parametrize("cell", [CELL, COMM_CELL])
def test_the_rehearsal_runs(cell):
    """``--rehearse`` swaps in ``_tiny.json`` and its dense builder: the
    traffic file's keys must be ones that builder knows."""
    done = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         cell, "--seed", str(2 ** 31 + 11), "--seconds", "2", "--trace", "1",
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
    # about half of the 64 clean positions carry loss
    assert 25 < result["metrics"]["diffusion_masked_share"]["value"] < 75
    from bagua_tpu.telemetry import counters

    gauges = counters.snapshot()
    # 2 x (2 x 32) rows x 3 experts a row = 384 routed pairs, 2 of 8 experts
    assert gauges["moe/rows_per_step"] == 384
    assert (gauges["moe/experts"], gauges["moe/experts_total"]) == (2, 8)
    assert (gauges["attn/kv_heads"], gauges["attn/diffusion_block"]) == (2, 4)
    assert gauges["attn/block_diffusion_layers"] == 2
    assert gauges["diffusion/tokens_per_step"] == 64


def test_a_batch_is_tokens_and_noise_from_the_seed(builder):
    import numpy as np

    cell = tiny_cell()
    traffic = {**cell.traffic, **TINY["traffic_overrides"]}
    draw = lambda seed: builder.draw_batch(np.random.default_rng([seed, 0]),
                                           TINY, traffic, 2)
    one, again, other = draw(7), draw(7), draw(8)
    assert one["tokens"].shape == one["masked"].shape == (2, 32)
    assert one["t"].shape == (2, 8) and one["t"].dtype == np.float32
    assert one["tokens"].max() < 249           # never the mask id
    assert 1e-3 <= one["t"].min() and one["t"].max() <= 1.0
    for name in one:
        np.testing.assert_array_equal(one[name], again[name])
    assert (one["tokens"] != other["tokens"]).any()


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "sdar_reference_check",
        ROOT / "perfbench" / "tools" / "sdar_reference_check.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny_cell_with_its_traffic():
    cell = tiny_cell()
    return dataclasses.replace(
        cell, traffic={**cell.traffic, **TINY["traffic_overrides"]})


def test_the_comparison_refuses_a_mask_fault(builder, tool):
    """``faults`` is the comparison that decides ``correct`` through the
    builder's own job: the sound reference agrees, one whose noised rows see
    their own clean block is refused."""
    reference = cells.load_plugin("reference", "sdar")
    seed = 2 ** 31 + 5
    args = argparse.Namespace(
        seed=[seed, 7], fault_seeds=[seed],
        faults=["clean", "noised_sees_own_clean_block"])
    out = tool.faults(tiny_cell_with_its_traffic(), builder, reference, args)
    assert set(out["seeds"]) == {seed, 7}
    assert set(out["seeds"][7]) >= {"clean", "mean_square_weight"}
    assert "noised_sees_own_clean_block" not in out["seeds"][7]
    out = out["seeds"][seed]
    assert out["clean"]["agrees"] is True
    assert out["clean"]["largest_gradient_distance"][1] <= (
        reference.GRADIENT_TOLERANCE)
    assert out["clean"]["largest_change_distance"][1] <= (
        reference.CHANGE_TOLERANCE)
    assert out["noised_sees_own_clean_block"]["agrees"] is False
    assert set(tool.FAULTS) == {
        "causal_over_2L", "noised_sees_own_clean_block", "clean_sees_noised",
        "positions_not_restarted", "loss_with_a_shift", "no_one_over_t"}


def test_the_drift_reading_runs(builder, tool):
    reference = cells.load_plugin("reference", "sdar")
    args = argparse.Namespace(seed=[11], steps=2, every=1)
    out = tool.drift(tiny_cell_with_its_traffic(), builder, reference, args)
    assert [r["step"] for r in out["readings"]] == [0, 3, 5, 6, 7]
    for reading in out["readings"]:
        assert len(reading["held_share"]) == 2
        assert all(0 <= x <= 1 for x in reading["held_share"])
        assert all(0.5 <= x <= 1 for x in reading["busiest_expert"])  # of 2


def test_flops_per_token_counts_what_is_computed(builder):
    config = cells.resolve(CELL).config
    length = 4096
    # per ROW, multiply-accumulates of one forward pass:
    projections = 2 * 2048 * 4096 + 2 * 2048 * 512   # q o; k v at 4 heads
    router = 2048 * 128
    experts = 8 * 16 / 128 * 3 * 2048 * 768          # 1 held expert
    # per clean token: two rows, L + B visible keys a head, one head row
    attention = 2 * 32 * 128 * (length + 4)
    head = 2048 * 18992
    mac = 6 * (2 * (projections + router + experts) + attention) + head
    assert builder.flops_per_token(config, length) == pytest.approx(6 * mac)
    # ~ 12.9 TFLOP a step; the dense (2 L)^2 would count 4 x the attention
    assert 12.9e12 < builder.flops_per_token(config, length) * length < 13e12
    assert builder.parameters(config) == config["parameters_as_built"] == (
        2 * 18992 * 2048 + 2048 + 6 * (
            2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2 * 2048
            + 2048 * 128 + 16 * 3 * 2048 * 768)) == 645_623_296


def test_the_builder_counts_what_the_model_holds(builder):
    model = builder.make_model(TINY, {"moe": {"dropless": True}})
    shapes = jax.eval_shape(lambda: builder.make_params(model, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == builder.parameters(TINY)
    assert shapes["block_0"]["mlp"]["expert_wi"].shape == (2, 48, 24)
    assert shapes["block_0"]["mlp"]["router"]["kernel"].shape == (48, 8)
    assert shapes["block_0"]["attn"]["q_norm"]["scale"].shape == (16,)


def test_block_diffusion_kernel_costs_are_the_hand_count():
    # L 8, B 4, by hand: clean-clean 4 x 4 + 4 x 8 = 48, noised-clean
    # 4 x 0 + 4 x 4 = 16, noised-noised 8 x 4 = 32: 96 = L (L + B)
    assert kernel_costs_blockdiff.visible_pairs(8, 4) == 48 + 16 + 32
    b, rows, h, kv, d, blk = 1, 16, 2, 1, 128, 4
    q_tensor, kv_tensor = rows * h * d * 2, rows * kv * d * 2
    row = h * rows * 4
    costs = kernel_costs_blockdiff.COSTS
    assert costs["flash_bd_fwd"](b, rows, h, kv, d, blk, 2) == (
        h * 96 * 2 * 2 * d, 2 * q_tensor + 2 * kv_tensor + 8 * row)
    assert costs["flash_bd_bwd_dq"](b, rows, h, kv, d, blk, 2) == (
        h * 96 * 3 * 2 * d, 3 * q_tensor + 2 * kv_tensor + 2 * row)
    assert costs["flash_bd_bwd_dkv"](b, rows, h, kv, d, blk, 2) == (
        h * 96 * 4 * 2 * d, 2 * q_tensor + 4 * kv_tensor + 2 * row)
    # the cell's shape: 16,793,600 pairs a head, compute-bound on a v5e
    assert kernel_costs_blockdiff.visible_pairs(4096, 4) == 16_793_600
    flop, moved = costs["flash_bd_bwd_dkv"](1, 8192, 32, 4, 128, 4, 2)
    assert flop / moved > 240


LOSS = "jit(bagua_step)/jvp(bagua.loss)"
BACK = "jit(bagua_step)/transpose(jvp(bagua.loss))"
MOSAIC = 'custom_call_target="tpu_custom_call"'
QKV = ("operand_layout_constraints={bf16[1,8192,4096]{2,1,0}, "
       "bf16[1,8192,512]{2,1,0}, bf16[1,8192,512]{2,1,0}")
STATS = ", bf16[1,8192,4096]{2,1,0}, f32[32,1,8192]{2,1,0}, f32[32,1,8192]{2,1,0}"
#: what the optimized HLO of the cell's step looks like, cut to what is read
HLO = f"""
HloModule jit_bagua_step

ENTRY %main (w: f32[8]) -> f32[8] {{
  %w = f32[8]{{0}} parameter(0)
  %flash_bd_fwd.1 = (bf16[1,8192,4096]{{2,1,0}}, f32[32,8,8192]{{2,1,0}}) custom-call(%w), {MOSAIC}, {QKV}}}, metadata={{op_name="{LOSS}/block_0/attn/jit(_bd_fwd)/flash_bd_fwd/pallas_call"}}
  %gmm_fwd.1 = bf16[67584,768]{{1,0}} custom-call(%w), {MOSAIC}, operand_layout_constraints={{s32[528]{{0}}, bf16[67584,2048]{{1,0}}, bf16[16,2048,768]{{2,1,0}}}}, metadata={{op_name="{LOSS}/block_0/mlp/bagua.moe/experts/gmm_fwd/pallas_call"}}
  %flash_bd_bwd_dkv.1 = (bf16[1,8192,512]{{2,1,0}}, bf16[1,8192,512]{{2,1,0}}) custom-call(%w), {MOSAIC}, {QKV}{STATS}}}, metadata={{op_name="{BACK}/block_0/attn/jit(_bd_bwd)/flash_bd_bwd_dkv/pallas_call"}}
  %flash_bd_bwd_dq.1 = bf16[1,8192,4096]{{2,1,0}} custom-call(%w), {MOSAIC}, {QKV}{STATS}}}, metadata={{op_name="{BACK}/block_0/attn/jit(_bd_bwd)/flash_bd_bwd_dq/pallas_call"}}
  ROOT %tuple = (f32[8]) tuple(%w)
}}
"""


def step(t0):
    """One step of 1,000 ns from ``t0`` (times are nanoseconds)."""
    kernel = f"%{{}} = x[] custom-call(), {MOSAIC}"
    spans = [(kernel.format("flash_bd_fwd.1"), 0, 100),
             (kernel.format("gmm_fwd.1"), 100, 200),
             (kernel.format("flash_bd_bwd_dkv.1"), 200, 500),
             (kernel.format("flash_bd_bwd_dq.1"), 500, 700)]
    return [Op(text, t0 + lo, t0 + hi) for text, lo, hi in spans]


@pytest.fixture
def ctx():
    ops = step(0) + step(1000) + step(2000)
    modules = [Op("jit_bagua_step", t, t + 1000) for t in (0, 1000, 2000)]
    trace = Trace({0: Chip(ops, modules)}, [])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(trace=trace, hlo_text=HLO, chips=1, peak=peak)


@pytest.mark.parametrize("metric,ns", [
    ("flash_bd_fwd_ms", 100), ("flash_bd_dq_ms", 200),
    ("flash_bd_dkv_ms", 300)])
def test_the_time_readers_on_a_hand_made_timeline(ctx, metric, ns):
    assert reader(metric).reduce(ctx) == pytest.approx(ns * 1e-6)


@pytest.mark.parametrize("metric,kernel,ns", [
    ("flash_bd_fwd_roofline", "flash_bd_fwd", 100),
    ("flash_bd_dq_roofline", "flash_bd_bwd_dq", 200),
    ("flash_bd_dkv_roofline", "flash_bd_bwd_dkv", 300)])
def test_the_roofline_readers_on_a_hand_made_timeline(ctx, monkeypatch,
                                                      metric, kernel, ns):
    from perfbench import scopes

    shapes = kernel_costs_window.call_shapes(HLO)
    assert {shapes[name] for name in shapes if name.startswith("flash")} == {
        (1, 8192, 4096, 512, 2)}
    gauges = {"attn/diffusion_block": 4, "attn/kv_heads": 4}
    monkeypatch.setattr(scopes, "program_gauge", gauges.get)
    flop, moved = kernel_costs_blockdiff.COSTS[kernel](1, 8192, 32, 4, 128,
                                                       4, 2)
    assert flop / moved > 197e12 / 819e9       # compute-bound: the peak
    assert reader(metric).reduce(ctx) == pytest.approx(
        100 * flop / (ns * 1e-9) / 197e12)
    # a program that sets no such gauges (the parent): nothing, no raise
    monkeypatch.setattr(scopes, "program_gauge", lambda name: None)
    assert reader(metric).reduce(ctx) is None


def test_the_masked_share_reads_the_programs_gauges(monkeypatch):
    from perfbench import scopes

    gauges = {"diffusion/masked_tokens_per_step": 2100,
              "diffusion/tokens_per_step": 4096}
    monkeypatch.setattr(scopes, "program_gauge", gauges.get)
    assert reader("diffusion_masked_share").reduce(None) == pytest.approx(
        100 * 2100 / 4096)
    monkeypatch.setattr(scopes, "program_gauge", lambda name: None)
    assert reader("diffusion_masked_share").reduce(None) is None


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
    monkeypatch.undo()
    monkeypatch.setattr(builder, "NEEDED_FUNCTIONS", ("no_such_loss",))
    with pytest.raises(cells.CellError, match="no no_such_loss"):
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
    buffer assignment's total, all attention is the ``flash_bd_*`` kernels
    (no ``flash_fwd``), and no key or value tensor is repeated to the 32
    query heads."""
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
    rows = (int(cell.traffic["batch_per_chip"]), int(cell.traffic["seq_len"]))
    sharded = NamedSharding(trainer.mesh, P("dp"))
    batch = {
        "tokens": jax.ShapeDtypeStruct(rows, jnp.int32, sharding=sharded),
        "masked": jax.ShapeDtypeStruct(rows, jnp.bool_, sharding=sharded),
        "t": jax.ShapeDtypeStruct((rows[0], rows[1] // 4), jnp.float32,
                                  sharding=sharded)}
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
    # q at 32 heads, k / v at 4, over the 8,192 rows of [x ; x~], in every
    # one of the 18 flash calls (six layers; fwd, dq, dkv each)
    assert len(shapes) == 18
    assert set(shapes.values()) == {(1, 8192, 32 * 128, 4 * 128, 2)}
    kernels = [line.split("/pallas_call")[0].rsplit("/", 1)[1]
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    count = {name: kernels.count(name) for name in set(kernels)}
    assert count == {
        "flash_bd_fwd": 6, "flash_bd_bwd_dq": 6, "flash_bd_bwd_dkv": 6,
        # per layer: gate, up, down forward and again in the replay, three
        # d_lhs; three d_rhs
        "gmm_fwd": 6 * 9, "gmm_bwd_drhs": 6 * 3,
        # q and k of both halves in one call each: forward, replay, backward
        "rope": 6 * 6, "embed_grad": 1}
    # nothing of [2 L, 2 L] anywhere in the step
    assert "8192,8192]" not in text
