"""The ``ouro`` builder and what came with it: the cell resolves and holds
its source's widths, ``--rehearse`` runs, the cell runs end to end through
the ``train`` driver at tiny widths on the CPU, the comparison that decides
``correct`` refuses a fault, the hand counts behind ``mfu``, the new readers
on a hand-made timeline, the refusal of a program that lacks the
architecture's fields, and the real step compiled for the described v5e
(nothing runs there; no time comes out of it)."""

import argparse
import dataclasses
import importlib
import json
import re
import subprocess
import sys
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench import cells
from perfbench.trace_reduce import Chip, Trace
from perfbench.trace_reduce import parse_op as Op

CELL = "ouro-2.6b.pretrain4096-b1-dp1"
ROOT = Path(__file__).resolve().parents[2]
#: what the TPU compiler itself reports as usable on a v5e
V5E_HBM_BYTES = 15.75 * 2 ** 30

TINY = {
    "name": "ouro-tiny", "builder": "ouro",
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 96,
    "max_position_embeddings": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "total_ut_steps": 4, "vocab_size": 250,
    "assumed": {"beta": 0.05},
    "traffic_overrides": {"seq_len": 32, "batch_per_chip": 2,
                          "warmup_steps": 2, "trace_steps": 3,
                          "reference_micro_batch": 2},
}
NEW_METRICS = ("loop_trunk_ms", "loop_exit_ms", "loop_grad_sum_unfused_ms")


@pytest.fixture(scope="module")
def builder():
    return cells.load_plugin("builders", "ouro")


def tiny_cell():
    return dataclasses.replace(cells.resolve(CELL), config=TINY)


def reader(name):
    return cells.load_plugin("layer_metrics", name)


def test_the_cell_resolves_to_the_sources_widths():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.traffic_name == "pretrain4096-b1-dp1"
    config = cell.config
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if json.loads(line)["name"] == "Ouro-2.6B")
        assert config["source"] == row["source_url"]
        changed = {k for k, v in row["config"].items() if config.get(k) != v}
        assert changed == {"num_hidden_layers"}
    published = {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 16, "head_dim": 128,
        "intermediate_size": 5632, "hidden_act": "silu",
        "rope_theta": 1000000, "rms_norm_eps": 1e-6, "vocab_size": 49152,
        "tie_word_embeddings": False, "max_position_embeddings": 65536,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "sliding_window": None}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["reduced_from"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 8
    assert config["assumed"]["beta"] == 0.05
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    traffic = cell.traffic
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["mesh"]) == (4096, 1, {"dp": 1})
    assert (traffic["prefetch"], traffic["max_in_flight"],
            traffic["warmup_steps"], traffic["replay_steps"],
            traffic["trace_steps"], traffic["reference_micro_batch"]) == (
                2, 2, 5, 3, 12, 1)
    # the new readers apply here and nowhere else
    assert set(NEW_METRICS) | {"head_ms", "attn_ms", "mlp_ms", "pallas_ms",
                               "mfu"} <= {m["name"] for m in cell.per_layer}
    for metric in cells.load_benchmark()["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "tokens_per_s_per_chip"
    bench = cells.load_benchmark()
    assert len(bench["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


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
    assert (gauges["loop/passes"], gauges["loop/shared_layers"]) == (4, 2)


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "ouro_reference_check",
        ROOT / "perfbench" / "tools" / "ouro_reference_check.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny_cell_with_its_traffic():
    cell = tiny_cell()
    return dataclasses.replace(
        cell, traffic={**cell.traffic, **TINY["traffic_overrides"]})


def test_the_comparison_refuses_the_faults(builder, tool):
    """``faults`` is the comparison that decides ``correct`` through the
    builder's own job: the trainer's losses and the first gradient of the
    model as built against the sound reference (agrees) and against ones
    with a mechanism left out (refused)."""
    reference = cells.load_plugin("reference", "ouro")
    wrong = ["three_passes", "uniform_weights", "last_exit_gated",
             "no_entropy", "no_post_norms", "unnormed_fed_on"]
    args = argparse.Namespace(seed=2 ** 31 + 5, faults=["clean", *wrong])
    out = tool.faults(tiny_cell_with_its_traffic(), builder, reference, args)
    assert out["clean"]["agrees"] is True
    assert out["clean"]["largest_gradient_distance"][1] <= (
        reference.GRADIENT_TOLERANCE)
    assert set(out["clean"]["gradient_distance"]) == set(
        reference.watched_names(2))
    for name in wrong:
        assert out[name]["agrees"] is False, (name, out[name])
    # the two that the mean loss cannot see are the gradient's to refuse
    for name in ("three_passes", "unnormed_fed_on"):
        assert out[name]["gradients_agree"] is False, (name, out[name])
    assert set(tool.fault_hypers(reference.hyperparameters(
        {**TINY}))) == set(wrong) | {"bf16_parts"}


def test_flops_per_token_counts_every_pass(builder):
    config = cells.resolve(CELL).config
    seq, layers = 4096, config["num_hidden_layers"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 2 * seq * 2048
    head = 2048 * 49152
    mac = 4 * (layers * layer + head)
    assert builder.flops_per_token(config, seq) == pytest.approx(6 * mac)
    # one pass of the same stack: a quarter
    once = {**config, "total_ut_steps": 1}
    assert builder.flops_per_token(once, seq) == pytest.approx(6 * mac / 4)
    assert builder.parameters(config) == config["parameters_as_built"] == (
        2 * 49152 * 2048 + 2048 + 2048 + 1 + layers * (
            4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048))
    # the parameters do not know the number of passes
    assert builder.parameters(once) == builder.parameters(config)


def test_the_builder_counts_what_the_model_holds(builder):
    model = builder.make_model(TINY, {})
    shapes = jax.eval_shape(lambda: builder.make_params(model, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == builder.parameters(TINY)
    assert sorted(k for k in shapes if k.startswith("block_")) == [
        "block_0", "block_1"]
    assert shapes["exit_gate"]["kernel"].shape == (64, 1)


LOSS = "jit(bagua_step)/jvp(bagua.loss)"
BACK = "jit(bagua_step)/transpose(jvp(bagua.loss))"
#: what the optimized HLO of the cell's step looks like, cut to what is read
HLO = f"""
HloModule jit_bagua_step

ENTRY %main (w: f32[8]) -> f32[8] {{
  %w = f32[8]{{0}} parameter(0)
  %fusion.embed = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.0, metadata={{op_name="{LOSS}/TransformerLM/embed/jit(_take)/gather"}}
  %fusion.q = f32[8]{{0}} fusion(%w), kind=kOutput, calls=%fc.1, metadata={{op_name="{LOSS}/TransformerLM/while/body/loop_body/block_0/attn/q/dot_general"}}
  %fusion.add = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.2, metadata={{op_name="{LOSS}/TransformerLM/while/body/loop_body/block_0/add"}}
  %fusion.norm = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.3, metadata={{op_name="{LOSS}/TransformerLM/while/body/final_norm/mul"}}
  %fusion.head = f32[8]{{0}} fusion(%w), kind=kOutput, calls=%fc.4, metadata={{op_name="{LOSS}/TransformerLM/lm_head/dot_general"}}
  %fusion.gate = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.5, metadata={{op_name="{LOSS}/TransformerLM/exit_gate/dot_general"}}
  %fusion.dist = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.6, metadata={{op_name="{BACK}/exit_dist/mul"}}
  %fusion.wo = f32[8]{{0}} fusion(%w), kind=kOutput, calls=%fc.7, metadata={{op_name="{BACK}/TransformerLM/while/body/loop_body/checkpoint/rematted_computation/block_1/mlp/wo/dot_general"}}
  %fusion.acc = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.9, metadata={{op_name="{BACK}/TransformerLM/while/body/closed_call/TransformerLM.one_pass/loop_body/add_any"}}
  %fusion.update = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.8, metadata={{op_name="jit(bagua_step)/bagua.optimizer/loop_body/mul"}}
  ROOT %tuple = (f32[8]) tuple(%w)
}}
"""


def step(t0):
    """One step of 1,000 ns from ``t0`` (times are nanoseconds): two passes
    of the forward body, one of the backward's."""
    fusion = "%{} = x[] fusion(), kind=kLoop"
    spans = [
        ("fusion.embed", 0, 10),
        ("fusion.q", 10, 110), ("fusion.add", 110, 120),
        ("fusion.norm", 120, 130),
        ("fusion.q", 130, 230), ("fusion.add", 230, 240),
        ("fusion.norm", 240, 250),
        ("fusion.head", 250, 450), ("fusion.gate", 450, 460),
        ("fusion.dist", 460, 480), ("fusion.wo", 480, 760),
        ("fusion.acc", 760, 780), ("fusion.update", 780, 900),
    ]
    return [Op(fusion.format(name), t0 + lo, t0 + hi)
            for name, lo, hi in spans]


@pytest.fixture
def ctx():
    ops = step(0) + step(1000) + step(2000)
    modules = [Op("jit_bagua_step", t, t + 1000) for t in (0, 1000, 2000)]
    trace = Trace({0: Chip(ops, modules)}, [])
    return types.SimpleNamespace(trace=trace, hlo_text=HLO, chips=1,
                                 peak=None)


@pytest.mark.parametrize("metric,ns", [
    # both passes' q and bare add, the replayed wo and the gradient sum; not
    # the norm that closes a pass, not the update that carries the name
    ("loop_trunk_ms", 2 * (100 + 10) + 280 + 20),
    ("loop_exit_ms", 10 + 20), ("loop_grad_sum_unfused_ms", 20),
    ("head_ms", 2 * 10 + 200), ("attn_ms", 2 * 100), ("mlp_ms", 280),
    ("area_other_ms", 2 * 10 + 20)])
def test_the_readers_on_a_hand_made_timeline(ctx, metric, ns):
    assert reader(metric).reduce(ctx) == pytest.approx(ns * 1e-6)


def test_the_readers_return_nothing_where_the_program_has_nothing(
        ctx, monkeypatch):
    """An untraced context, a program that names no pass (the parent), a
    step that has none: None, no raise."""
    from perfbench import loops

    train = cells.load_plugin("drivers", "train")
    bare = train.ReaderContext(chips=1, spans={}, counters={},
                               rate_per_chip=None, flops_per_unit=1.0,
                               peak=None)
    for name in NEW_METRICS:
        assert reader(name).reduce(bare) is None
    unlooped = types.SimpleNamespace(
        trace=ctx.trace, hlo_text=HLO.replace("loop_body/", ""), chips=1)
    assert reader("loop_trunk_ms").reduce(unlooped) is None
    monkeypatch.setattr(loops, "program_in_loop", lambda: None)
    assert reader("loop_trunk_ms").reduce(ctx) is None


def test_a_program_without_the_fields_is_refused_at_once(builder, monkeypatch):
    """The parent commit with these files: a ``CellError`` before any weight
    is made (the driver runs every new cell on the parent first)."""
    monkeypatch.setattr(builder, "NEEDED_FIELDS", ("n_passes", "no_such"))
    with pytest.raises(cells.CellError, match="no field no_such"):
        builder.make_trainer(tiny_cell(), cells.resolve(CELL).traffic,
                             jax.devices()[:1])
    monkeypatch.undo()
    monkeypatch.setattr(builder, "NEEDED_LOSS", "no_such_loss")
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
    traffic file's remat choice: it fits, the three flash kernels are there once a layer of the ONE scanned body
    (the forward's again in the replay), and no float32 array of the
    logits' size is written."""
    flash = importlib.import_module("bagua_tpu.ops.flash_attention")
    monkeypatch.setattr(flash.jax, "default_backend", lambda: "tpu")
    from bagua_tpu.core import backend

    cell = cells.resolve(CELL)
    layers = cell.config["num_hidden_layers"]
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
    print(json.dumps({"needed_gib": needed / 2 ** 30,
                      "state_gib": memory.argument_size_in_bytes / 2 ** 30,
                      "temp_gib": memory.temp_size_in_bytes / 2 ** 30,
                      "code_mib": memory.generated_code_size_in_bytes
                      / 2 ** 20}))
    # ``memory_analysis()`` counts what the two loops over the passes carry
    # (the shared weights' float32 gradient sums, the kept block inputs)
    # twice: 15.23 GiB here where the executable's own buffer assignment,
    # which the chip's reservation equals to 15 KB, holds 12.66 (PERF.md
    # §6, PR 39).  By the larger count the step still fits the chip
    assert needed <= V5E_HBM_BYTES
    assert needed >= 0.25 * 16e9           # not cell_too_small
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * cell.config["parameters_as_built"], rel=0.01)

    text = compiled.as_text()
    kernels = [line.split("/pallas_call")[0].rsplit("/", 1)[1]
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    count = {name: kernels.count(name) for name in set(kernels)}
    assert count == {"flash_fwd": 2 * layers, "flash_bwd_dq": layers,
                     "flash_bwd_dkv": layers}
    # the four passes' logits exist once, in bfloat16, and never in float32
    entry = text[text.index("\nENTRY "):]
    results = re.findall(r"= \(?(\w+)\[([\d,]+)\]", entry)
    assert {dtype for dtype, shape in results
            if shape.endswith("4096,49152")} == {"bf16"}
