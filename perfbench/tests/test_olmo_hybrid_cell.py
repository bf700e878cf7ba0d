"""The ``olmo_hybrid`` builder and what came with it: the cell resolves to
the source's widths, it runs end to end through the ``train`` driver at small
widths on four CPU devices, the hand counts behind ``mfu`` and the
``gdn96_*_roofline`` metrics, the new readers on a hand-made timeline, the
comparison's refusal of each wrong mechanism through the builder's own job
and of a step that leaves half its batch or the exchange out, the refusal of
a program that lacks the architecture's fields, and the real
four-chip step compiled for the described v5e (nothing runs there; no time
comes out of it)."""

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

CELL = "olmo-hybrid-7b.pretrain8192-b1-dp4"
ROOT = Path(__file__).resolve().parents[2]
#: what the TPU compiler itself reports as usable on a v5e, and what a
#: step's temporaries may take of it and still LOAD (PERF.md section 6, PR 54)
V5E_HBM_BYTES = 15.75 * 2 ** 30
LOADS_UNDER_BYTES = 5.8 * 2 ** 30

#: the published shapes' kind at a small size: six heads of 96 / 192
TINY = {
    "name": "olmo-hybrid-tiny", "builder": "olmo_hybrid", "hidden_size": 64,
    "intermediate_size": 96, "num_attention_heads": 2,
    "num_key_value_heads": 2,
    "layer_types": ["linear_attention", "full_attention"],
    "num_hidden_layers": 2, "linear_num_key_heads": 6,
    "linear_num_value_heads": 6, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6, "rope_theta": None, "vocab_size": 250,
    "assumed": {"chunk_length": 64},
    # float32 products: the published widths' limits are not made for 64
    # lanes of model in bfloat16; what this size rehearses is the plumbing
    "traffic_overrides": {"seq_len": 128, "batch_per_chip": 1,
                          "warmup_steps": 2, "trace_steps": 3,
                          "model": {"dtype": "float32", "remat": True,
                                    "remat_policy": "dots_no_batch"}},
}

NEW_METRICS = ("linattn96_ms", "gdn96_fwd_ms", "gdn96_bwd_ms",
               "gdn96_fwd_roofline", "gdn96_bwd_roofline")


@pytest.fixture(scope="module")
def builder():
    return cells.load_plugin("builders", "olmo_hybrid")


def tiny_cell():
    cell = cells.resolve(CELL)
    return dataclasses.replace(
        cell, config=TINY,
        traffic={**cell.traffic, **TINY["traffic_overrides"]})


def reader(name):
    return cells.load_plugin("layer_metrics", name)


def test_the_cell_resolves_to_the_sources_widths():
    cell = cells.resolve(CELL)
    assert cell.chips == 4 and cell.traffic_name == "pretrain8192-b1-dp4"
    config = cell.config
    published = {
        "model_type": "olmo_hybrid", "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert {k: config[k] for k in published} == published
    assert config["rope_theta"] is None
    assert config["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    assert config["reduced_from"]["num_hidden_layers"] == 32
    assert config["reduced_from"]["vocab_size"] == 100352
    assert (config["num_hidden_layers"], config["vocab_size"]) == (
        4, 100352 // 8)
    deployment = config["deployment"]
    assert (deployment["chips"], deployment["chips_per_layer"],
            deployment["data_parallel"], deployment["optimizer_state_shards"],
            deployment["vocabulary_slices"], deployment["pipeline_stages"],
            deployment["layers_per_stage"]) == (32, 4, 4, 4, 8, 8, 4)
    for key in ("norm_placement", "rotation", "projection_order",
                "initialization", "chunk_length"):
        assert key in config["assumed"]
    traffic = cell.traffic
    assert (traffic["seq_len"], traffic["batch_per_chip"], traffic["mesh"],
            traffic["prefetch"], traffic["max_in_flight"],
            traffic["replay_steps"], traffic["warmup_steps"],
            traffic["trace_steps"]) == (8192, 1, {"dp": 4}, 2, 2, 3, 5, 12)
    # the keys of squad384-dp4: the exact family shards the moments by itself
    dp4 = cells.resolve("bert-large.squad384-dp4").traffic
    for key in ("algorithm", "optimizer", "trainer"):
        assert traffic[key] == dp4[key]
    assert traffic["model"] == {"remat": True,
                                "remat_policy": "dots_no_batch"}
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the new readers apply here and nowhere else; the accepted gdn readers
    # stay Qwen3-Next's
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert not {"gdn_fwd_ms", "gdn_fwd_roofline", "linattn_ms"} & names
    for metric in cells.load_benchmark()["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
            module = reader(metric["name"])
            assert (module.LAYER, module.UNIT, module.MOVES,
                    module.SOURCE) == (metric["layer"], metric["unit"],
                                       metric["moves"], metric["source"])
    # three of twelve cells on four chips: the quota
    bench = cells.load_benchmark()
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 3 and len(bench["workloads"]) == 12


def test_the_rehearsal_runs():
    done = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 11), "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 4


@pytest.fixture(scope="module")
def driven():
    """The small cell once through the driver on four CPU devices."""
    driver = cells.load_plugin("drivers", "train")
    args = argparse.Namespace(seed=2 ** 31 + 5, seconds=4.0, trace=1,
                              rehearse=True, keep_trace=None)
    cell = tiny_cell()
    captured = {}
    load = cells.load_plugin

    def keeping(kind, name, *rest):
        """The driver's own loader, the builder's job kept for the tests."""
        module = load(kind, name, *rest)
        if (kind, name) == ("builders", "olmo_hybrid"):
            build = module.build

            def keep(*a, **kw):
                captured["job"] = build(*a, **kw)
                return captured["job"]

            module.build = keep
        return module

    cells.load_plugin = keeping
    try:
        result = driver.run(cell, args, time.perf_counter())
    finally:
        cells.load_plugin = load
    # (kept here: the tests of a wrong reference overwrite the job's)
    result["trainer_losses"] = list(captured["job"].wanted["trainer_losses"])
    return result, captured["job"]


def test_the_cell_runs_end_to_end_through_the_train_driver(driven):
    result, job = driven
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert job._replayer._update_sharded()        # the moments over dp = 4
    from bagua_tpu.telemetry import counters

    gauges = counters.snapshot()
    assert (gauges["linattn/layers"], gauges["linattn/chunk"],
            gauges["linattn/key_heads"], gauges["linattn/value_heads"],
            gauges["linattn/key_dim"], gauges["linattn/value_dim"],
            gauges["linattn/neg_eigval"]) == (1, 64, 6, 6, 96, 192, 1)
    assert gauges["attn/full_layers"] == 1
    # what the four comparisons read (the gates' projection by halves too)
    assert len(job.gradient_distance) == 10 + 9 + 2
    assert max(job.gradient_distance.values()) < 0.01
    assert set(job.rule_distance) == {
        f"{name}/{kind}" for name in job._reference.RULE_QUANTITIES
        for kind in ("weak", "strong")}
    # what the sound replay left for a planted fault to be read against
    assert set(job.wanted) == {"gradient", "change", "losses",
                               "trainer_losses"}


@pytest.mark.parametrize("fault", ["beta_is_sigmoid", "norm_in_front",
                                   "bfloat16_state"])
def test_the_comparison_refuses_each_wrong_mechanism(driven, fault):
    """``correct``'s own comparison through the builder's job: against a
    reference with the mechanism wrong, the system that has it right is
    refused — by the first gradient, or, for the state's precision, by the
    rule's probe alone."""
    _, job = driven
    reference = job._reference
    hyper = {**reference.hyperparameters(job._config), **{
        "beta_is_sigmoid": {"neg_eigval": False},
        "norm_in_front": {"output_norm": False},
        "bfloat16_state": {"scan_dtype": "bfloat16"}}[fault]}
    if fault == "bfloat16_state":
        # (a probe of 128 positions is too short for a weak head to learn
        # anything: the timed length's kind)
        job = dataclasses.replace(job, replay_batch={
            "tokens": jnp.zeros((4, 1025), jnp.int32)})
        got = cells.load_plugin("builders", "olmo_hybrid").system_rule(
            reference, job._seed, 1024, hyper, jnp.bfloat16)
        want = reference.rule_by_scan(
            *reference.rule_probe(job._seed, 1024, hyper),
            scan_dtype="bfloat16")
        distance = reference.rule_distance(got, want)
        assert not reference.rule_agrees(distance)
        # by the output and, the state's precision in the backward pass, by
        # the cotangents alone
        assert not reference.rule_agrees(distance, {
            k: v for k, v in reference.RULE_TOLERANCE.items()
            if not k.startswith("o/")})
        return
    trainer_losses = [5.5, 5.5, 5.5]
    losses = job.reference_losses(3, hyper=hyper)
    assert not job.losses_agree(trainer_losses, losses)
    # the small size's own limit: in float32 the sound system reads under
    # 0.01 here; the cell's 0.5 is made for bfloat16 at the published
    # widths, where these faults read 0.34-1.17 and 1.00-6.67 on every leaf
    assert not reference.gradients_agree(job.gradient_distance, 0.1)
    assert max(job.gradient_distance.values()) > 0.3


@pytest.fixture(scope="module")
def sound(driven):
    """What a sound replay of the reference leaves on the job (the tests of
    a wrong reference overwrite it): copies."""
    result, job = driven
    trainer_losses = result["trainer_losses"]
    losses = job.reference_losses(3)
    assert job.losses_agree(trainer_losses, losses)
    return dict(job.wanted), dict(job.change_distance)


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange"])
def test_the_comparison_refuses_a_step_that_leaves_its_work_out(driven, sound,
                                                                fault):
    """The faults a dp = 4 step can have, planted in the SYSTEM and read
    against the sound reference by the comparisons that come from the
    trainer's own step (the first gradient is the builder's own program and
    sees neither): the step fed half of its batch twice, and the step with
    every chip keeping its own gradient (no reduce-scatter).  The parameters'
    change refuses both — here as at the published widths (PERF.md section
    6 has the chips' readings) — and so do the replayed losses."""
    _, job = driven
    wanted, sound_change = sound
    reference = job._reference
    tool = cells.load_plugin("tools", "olmo_hybrid_reference_check")
    change, losses = tool.planted(
        fault, job, tiny_cell(),
        cells.load_plugin("builders", "olmo_hybrid"), reference, 3)
    distance = job.distances(change, wanted["change"])
    assert not reference.changes_agree(distance)
    held = [d for name, d in distance.items()
            if not name.endswith(reference.CHANGE_SKIPPED)]
    assert min(held) > 0.3 and max(held) > 1.0
    assert not reference.agree(losses, wanted["losses"],
                               reference.LOSS_TOLERANCE)
    # the sound step on the same replay: within every limit
    assert reference.changes_agree(sound_change)
    assert reference.agree(wanted["trainer_losses"], wanted["losses"],
                           reference.LOSS_TOLERANCE)
    if fault == "no_exchange":
        # a chip's first loss is computed before any exchange
        assert losses[0] == pytest.approx(wanted["losses"][0], abs=1e-5)


def test_flops_per_token_counts_what_is_computed(builder):
    config = cells.resolve(CELL).config
    seq = 8192
    # multiply-accumulates of one forward pass, a token:
    linear = (3840 * 17280 + 3840 * 60 + 4 * 11520   # in-projections, taps
              + 30 * 3 * 96 * 192                    # the recurrence
              + 5760 * 3840)                         # out-projection
    full = 4 * 3840 * 3840 + 2 * 30 * 128 * (seq + 1) / 2
    mlp = 3 * 3840 * 11008
    mac = 3 * linear + full + 4 * mlp + 3840 * 12544
    assert builder.flops_per_token(config, seq) == pytest.approx(6 * mac)
    # ~ 5.5 GFLOP a token, 45 TFLOP a chip's sequence
    assert 5.4e9 < builder.flops_per_token(config, seq) < 5.6e9
    assert builder.parameters(config) == config["parameters_as_built"] == (
        2 * 12544 * 3840 + 3840
        + 3 * (3840 * 17280 + 3840 * 60 + 4 * 11520 + 30 + 30 + 192
               + 5760 * 3840 + mlp + 2 * 3840)
        + (4 * 3840 * 3840 + 2 * 3840 + mlp + 2 * 3840)) == 928_862_196


def test_the_builder_counts_what_the_model_holds(builder):
    model = builder.make_model(TINY, {})
    shapes = jax.eval_shape(lambda: builder.make_params(model, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == builder.parameters(TINY)
    cfg = model.cfg
    assert cfg.mixer_layers == (1, 0) and cfg.rope_layers == (0,)
    assert (cfg.pre_norms, cfg.post_norms, cfg.qk_norm,
            cfg.linear_neg_eigval) == (False, True, True, True)
    assert "pos_embed" not in shapes
    # the one-line switch: a number under rope_theta rotates the full layers
    rotating = builder.make_model({**TINY, "rope_theta": 500000.0}, {})
    assert rotating.cfg.rope_layers == (1,)
    assert rotating.cfg.rope_theta == 500000.0


def test_gdn_kernel_costs_at_96_and_192_lanes():
    b, seq, heads, d_k, d_v = 1, 8192, 30, 96, 192
    flop, moved = kernel_costs_gdn.COSTS["gdn_fwd"](b, seq, heads, heads,
                                                    d_k, d_v, 2)
    # 3 d_k d_v multiply-accumulates a position and head, whatever the
    # kernel does
    assert flop == 2 * 3 * 96 * 192 * seq * heads == 27_179_089_920
    assert moved == seq * (2 * 2880 * 2 + 2 * 5760 * 2 + 2 * 30 * 4)
    flop_b, moved_b = kernel_costs_gdn.COSTS["gdn_bwd"](b, seq, heads, heads,
                                                        d_k, d_v, 2)
    assert flop_b == 2 * flop
    assert moved_b == seq * (4 * 2880 * 2 + 3 * 5760 * 2 + 4 * 30 * 4)


HLO = """HloModule jit_bagua_step

ENTRY %main (w: f32[8]) -> f32[8] {
  %w = f32[8]{0} parameter(0)
  %gdn.1 = (bf16[1,8192,5760]{2,1,0}, bf16[1,30,128,96,192]{4,3,2,1,0}) custom-call(%q, %k, %v, %g, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[1,8192,2880]{2,1,0}, bf16[1,8192,2880]{2,1,0}, bf16[1,8192,5760]{2,1,0}, f32[1,30,128,64]{3,2,1,0}, f32[1,30,128,64]{3,2,1,0}}, metadata={op_name="jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/linear_attn/jit(_kernel_fwd)/gdn_fwd/pallas_call"}
  %gdn.2 = (bf16[1,8192,2880]{2,1,0}, bf16[1,8192,2880]{2,1,0}) custom-call(%q, %k, %v, %g, %b, %s, %do), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[1,8192,2880]{2,1,0}, bf16[1,8192,2880]{2,1,0}, bf16[1,8192,5760]{2,1,0}, f32[1,30,128,64]{3,2,1,0}, f32[1,30,128,64]{3,2,1,0}, bf16[1,30,128,96,192]{4,3,2,1,0}, bf16[1,8192,5760]{2,1,0}}, metadata={op_name="jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM/block_0/linear_attn/jit(_kernel_bwd)/gdn_bwd/pallas_call"}
  %fusion.1 = bf16[8192,3840]{1,0} fusion(%x), kind=kOutput, calls=%fused.1, metadata={op_name="jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/linear_attn_post_norm/mul"}
  %fusion.2 = bf16[8192,3840]{1,0} fusion(%x), kind=kOutput, calls=%fused.2, metadata={op_name="jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_0/mlp_post_norm/mul"}
}
"""
MS = 1_000_000  # ns
MOSAIC = 'custom_call_target="tpu_custom_call"'


def step(t0):
    """One step of 30 ms from ``t0`` (times are nanoseconds): gdn_fwd 7 ms,
    gdn_bwd 11 ms, the linear layer's output norm 1 ms and the MLP's
    0.5 ms."""
    kernel = f"%{{}} = x[] custom-call(), {MOSAIC}"
    fusion = "%{} = x[] fusion(), kind=kOutput"
    spans = [(kernel.format("gdn.1"), 0, 7 * MS),
             (kernel.format("gdn.2"), 7 * MS, 18 * MS),
             (fusion.format("fusion.1"), 18 * MS, 19 * MS),
             (fusion.format("fusion.2"), 19 * MS, 19 * MS + MS // 2)]
    return [Op(text, t0 + lo, t0 + hi) for text, lo, hi in spans]


@pytest.fixture
def ctx():
    starts = (0, 30 * MS, 60 * MS)
    ops = [op for t in starts for op in step(t)]
    modules = [Op("jit_bagua_step", t, t + 30 * MS) for t in starts]
    trace = Trace({0: Chip(ops, modules)}, [])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(trace=trace, hlo_text=HLO, chips=4, peak=peak)


@pytest.mark.parametrize("metric,ms", [
    ("gdn96_fwd_ms", 7.0), ("gdn96_bwd_ms", 11.0),
    # the rule's kernels and the output norm behind the mixer, not the MLP's
    ("linattn96_ms", 19.0), ("mlp_ms", 0.5)])
def test_the_time_readers_on_a_hand_made_timeline(ctx, metric, ms):
    assert reader(metric).reduce(ctx) == pytest.approx(ms)


@pytest.mark.parametrize("metric,kernel,ms", [
    ("gdn96_fwd_roofline", "gdn_fwd", 7.0),
    ("gdn96_bwd_roofline", "gdn_bwd", 11.0)])
def test_the_roofline_readers_on_a_hand_made_timeline(ctx, monkeypatch,
                                                      metric, kernel, ms):
    from perfbench import scopes

    gauges = {"linattn/key_heads": 30, "linattn/value_heads": 30}
    monkeypatch.setattr(scopes, "program_gauge", gauges.get)
    assert kernel_costs_gdn.call_shapes(HLO) == {
        "gdn.1": (1, 8192, 2880, 5760, 2), "gdn.2": (1, 8192, 2880, 5760, 2)}
    flop, moved = kernel_costs_gdn.COSTS[kernel](1, 8192, 30, 30, 96, 192, 2)
    least_s = max(flop / 197e12, moved / 819e9)
    share = reader(metric).reduce(ctx)
    assert share == pytest.approx(100 * least_s / (ms * 1e-3))
    assert 0 < share < 10
    # without the program's gauges (the parent commit): nothing, no raise
    monkeypatch.setattr(scopes, "program_gauge", lambda name: None)
    assert reader(metric).reduce(ctx) is None


def test_the_readers_return_nothing_where_the_program_has_nothing(
        monkeypatch):
    from perfbench import scopes

    monkeypatch.setattr(scopes, "program_gauge", lambda name: None)
    train = cells.load_plugin("drivers", "train")
    bare = train.ReaderContext(chips=4, spans={}, counters={},
                               rate_per_chip=None, flops_per_unit=1.0,
                               peak=None)
    for name in NEW_METRICS:
        assert reader(name).reduce(bare) is None


def test_a_program_without_the_fields_is_refused_at_once(builder, monkeypatch):
    """The parent commit with these files: a ``CellError`` before any weight
    is made (the driver runs every new cell on the parent first)."""
    from bagua_tpu.models.transformer import TransformerConfig

    monkeypatch.setitem(builder.NEEDED_FIELDS, TransformerConfig,
                        ("qk_norm", "no_such_field"))
    with pytest.raises(cells.CellError, match="no field no_such_field"):
        builder.make_trainer(tiny_cell(), cells.resolve(CELL).traffic,
                             jax.devices()[:4])


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
    """The cell's four-chip step at the published widths under the traffic
    file's remat choice, the moments sharded over the four chips: it fits by
    the rule the traffic file states (1 GiB free, temporaries that load);
    three ``gdn_fwd`` and three ``gdn_bwd`` calls at 30 heads of 96 / 192
    (the tags keep what the forward call made: no replay of it), the row
    passes around them, the flash kernels once each at 30 heads of 128; the
    gradient is reduce-scattered and the parameters gathered."""
    # the kernels' gates ask jax.default_backend(), still the CPU here
    flash = importlib.import_module("bagua_tpu.ops.flash_attention")
    monkeypatch.setattr(flash.jax, "default_backend", lambda: "tpu")
    from bagua_tpu.core import backend

    cell = cells.resolve(CELL)
    model, trainer = builder.make_trainer(cell, cell.traffic,
                                          list(topology.devices))
    # the flat-safety probe cannot run under eval_shape (PERF.md §7)
    assert backend._optimizer_flattens_safely(trainer._flat_opt())
    params = jax.eval_shape(lambda: builder.make_params(model, 0))
    replicated = NamedSharding(trainer.mesh, P())
    shapes = jax.eval_shape(trainer.init, params)
    assert trainer._flat_resident and trainer._update_sharded()
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        shapes)
    state = state._replace(opt_state=jax.tree.map(
        lambda x, sharding: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                 sharding=sharding),
        shapes.opt_state,
        trainer._opt_state_shardings(trainer._plan, replicated)))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (4 * int(cell.traffic["batch_per_chip"]),
         int(cell.traffic["seq_len"]) + 1), jnp.int32,
        sharding=NamedSharding(trainer.mesh, P("dp")))}
    compiled = trainer.compiled_step(state, batch)

    memory = compiled.memory_analysis()
    needed = (memory.argument_size_in_bytes + memory.output_size_in_bytes
              - memory.alias_size_in_bytes + memory.temp_size_in_bytes
              + memory.generated_code_size_in_bytes)
    assert needed <= V5E_HBM_BYTES - 2 ** 30
    assert memory.temp_size_in_bytes <= LOADS_UNDER_BYTES
    assert needed >= 0.25 * 16e9           # not cell_too_small
    # a chip holds the weights whole and a quarter of the moments
    assert memory.argument_size_in_bytes == pytest.approx(
        (4 + 8 / 4) * cell.config["parameters_as_built"], rel=0.02)
    print(json.dumps({"needed_gib": needed / 2 ** 30,
                      "state_gib": memory.argument_size_in_bytes / 2 ** 30,
                      "temp_gib": memory.temp_size_in_bytes / 2 ** 30}))

    text = compiled.as_text()
    shapes = kernel_costs_gdn.call_shapes(text)
    assert len(shapes) == 6
    assert set(shapes.values()) == {(1, 8192, 30 * 96, 30 * 192, 2)}
    kernels = [line.split("/pallas_call")[0].rsplit("/", 1)[1]
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    count = {name: kernels.count(name) for name in set(kernels)}
    assert count == {
        "gdn_fwd": 3, "gdn_bwd": 3,
        # q | k as one part and v: two calls a layer, forward, replay and
        # backward; the gate forward, replay and backward
        "gdn_mix": 3 * 2 * 2, "gdn_mix_bwd": 3 * 2,
        "gdn_gate": 3 * 2, "gdn_gate_bwd": 3,
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
        "embed_grad": 1}
    assert "reduce-scatter" in text and "all-gather" in text
