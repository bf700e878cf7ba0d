"""perfbench/scopes.py and the readers built on it, on a hand-written HLO text
and a hand-made timeline where every expected number can be checked by eye
(times are nanoseconds), and ``kernel_costs.py`` against a count by hand."""

import types

import pytest

from perfbench import cells, kernel_costs, scopes
from perfbench.trace_reduce import Chip, Trace
from perfbench.trace_reduce import parse_op as Op


def reader(name):
    return cells.load_plugin("layer_metrics", name)


LOSS = "jit(bagua_step)/jvp(bagua.loss)"
BACK = "jit(bagua_step)/transpose(jvp(bagua.loss))"
MOSAIC = 'custom_call_target="tpu_custom_call"'

#: what the optimized HLO of a step looks like, cut to what is read
HLO = f"""
HloModule jit_bagua_step

%fused_computation.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  %mul.1 = f32[8]{{0}} multiply(%p, %p), metadata={{op_name="jit(bagua_step)/bagua.optimizer/mul"}}
  ROOT %add.1 = f32[8]{{0}} add(%mul.1, %p), metadata={{op_name="jit(bagua_step)/bagua.optimizer/add"}}
}}

ENTRY %main (w: f32[8]) -> f32[8] {{
  %w = f32[8]{{0}} parameter(0)
  %slice-start.1 = ((f32[8]), f32[4], s32[]) slice-start(%w), slice={{[0:4]}}
  %slice-done.1 = f32[4]{{0}} slice-done(%slice-start.1)
  %fusion.fwd = f32[8]{{0}} fusion(%slice-done.1), kind=kOutput, calls=%fc.9, metadata={{op_name="{LOSS}/Model/dot_general"}}
  %fusion.view = f32[8]{{0}} fusion(%w), kind=kLoop, calls=%fc.8, metadata={{op_name="{LOSS}/bagua.layout/slice"}}
  %flash_fwd.1 = (bf16[16,1024,64]{{2,1,0}}, f32[16,8,1024]{{2,1,0}}) custom-call(%w), {MOSAIC}, metadata={{op_name="{LOSS}/Model/attn/flash_fwd/pallas_call"}}
  %fusion.bwd = f32[8]{{0}} fusion(%fusion.fwd), kind=kOutput, calls=%fc.7, metadata={{op_name="{BACK}/Model/{LOSS[16:]}/Model/checkpoint/transpose"}}
  %flash_fwd.2 = (bf16[16,1024,64]{{2,1,0}}, f32[16,8,1024]{{2,1,0}}) custom-call(%w), {MOSAIC}, metadata={{op_name="{BACK}/Model/{LOSS[16:]}/Model/checkpoint/rematted_computation/attn/flash_fwd/pallas_call"}}
  %flash_bwd_dq.1 = bf16[16,1024,64]{{2,1,0}} custom-call(%w), {MOSAIC}, metadata={{op_name="{BACK}/Model/attn/flash_bwd_dq/pallas_call"}}
  %fusion.scatter = f32[8]{{0}} fusion(%fusion.bwd), kind=kLoop, calls=%fc.6, metadata={{op_name="{BACK}/bagua.layout/pad"}}
  %all-reduce.1 = f32[8]{{0}} all-reduce(%fusion.scatter), replica_groups={{{{0,1}}}}, to_apply=%sum, metadata={{op_name="jit(bagua_step)/shard_map/bagua.comm/bucket_3/psum"}}
  %fusion.scale = f32[8]{{0}} fusion(%all-reduce.1), kind=kLoop, calls=%fc.5, metadata={{op_name="jit(bagua_step)/shard_map/bagua.comm/bucket_3/div"}}
  %fusion.opt = f32[8]{{0}} fusion(%fusion.scale), kind=kLoop, calls=%fused_computation.1
  %fusion.guard = f32[8]{{0}} fusion(%fusion.opt), kind=kLoop, calls=%fc.4, metadata={{op_name="jit(bagua_step)/bagua.guard/is_finite"}}
  %fusion.plain = f32[8]{{0}} fusion(%fusion.opt), kind=kLoop, calls=%fc.3
  %copy.out = f32[8]{{0}} copy(%fusion.opt)
  %c.0 = f32[8]{{0}} constant({{...}})
  %fusion.unknown = f32[8]{{0}} fusion(%c.0), kind=kLoop, calls=%fc.2
  ROOT %tuple = (f32[8]) tuple(%copy.out)
}}
"""


@pytest.mark.parametrize("op_name, phase", [
    (f"{LOSS}/Model/dot_general", scopes.FORWARD),
    (f"{BACK}/Model/transpose", scopes.BACKWARD),
    # a checkpointed block's backward names both wrappers: transposed wins
    (f"{BACK}/Model/jvp(bagua.loss)/Model/checkpoint/mul", scopes.BACKWARD),
    (f"{BACK}/Model/jvp(bagua.loss)/Model/checkpoint/rematted_computation/tanh",
     scopes.REPLAY),
    # the innermost bagua.* scope wins, whatever encloses it
    (f"{LOSS}/bagua.layout/slice", scopes.LAYOUT),
    (f"{BACK}/bagua.layout/pad", scopes.LAYOUT),
    ("jit(bagua_step)/shard_map/bagua.comm/bucket_3/div", scopes.LAYOUT),
    ("jit(bagua_step)/bagua.optimizer/bagua.comm/bucket_0/all_gather",
     scopes.LAYOUT),
    ("jit(bagua_step)/bagua.optimizer/mul", scopes.OPTIMIZER),
    ("jit(bagua_step)/bagua.guard/is_finite", scopes.GUARD),
    ("jit(bagua_step)/bagua.guard/bagua.comm/health/pmin", scopes.LAYOUT),
    # a program without scopes, an unnamed instruction, an unknown scope
    ("jit(per_shard)/jvp(Model)/dot_general", scopes.UNATTRIBUTED),
    ("", scopes.UNATTRIBUTED),
    (None, scopes.UNATTRIBUTED),
    ("jit(bagua_step)/bagua.other/mul", scopes.UNATTRIBUTED),
])
def test_phase_of(op_name, phase):
    assert scopes.phase_of(op_name) == phase


def test_instruction_scopes_resolve_what_the_compiler_made():
    found = scopes.instruction_scopes(HLO)
    phase = {name: scopes.phase_of(path) for name, path in found.items()}
    assert phase["fusion.fwd"] == scopes.FORWARD
    # a fusion without metadata: what the computation it calls agrees on
    assert phase["fusion.opt"] == scopes.OPTIMIZER
    # a prefetch belongs to its consumer, through its -done half
    assert phase["slice-start.1"] == phase["slice-done.1"] == scopes.FORWARD
    # an output copy has no named consumer: its producer's phase
    assert phase["copy.out"] == scopes.OPTIMIZER
    # a fusion without metadata still resolves through an operand ...
    assert phase["fusion.plain"] == scopes.OPTIMIZER
    # ... and with nothing to go by (unknown callee, an unnamed constant
    # for operand, no consumer) it stays unattributed
    assert "fusion.unknown" not in found
    assert scopes.phase_of(found.get("fusion.unknown")) == scopes.UNATTRIBUTED
    assert scopes.kernel_of(found["flash_fwd.2"]) == "flash_fwd"
    assert scopes.kernel_of(found["fusion.fwd"]) is None


def step(t0):
    """One step of 200 ns from ``t0``."""
    kernel = f"%{{}} = x[] custom-call(), {MOSAIC}"
    spans = [
        ("%slice-start.1 = x[] slice-start()", 0, 2),       # forward
        ("%slice-done.1 = x[] slice-done()", 2, 5),         # forward
        ("%fusion.view = x[] fusion(), kind=kLoop", 5, 10),   # layout
        ("%fusion.fwd = x[] fusion(), kind=kOutput", 10, 40),  # forward
        (kernel.format("flash_fwd.1"), 40, 50),             # forward
        ("%fusion.bwd = x[] fusion(), kind=kOutput", 50, 90),  # backward
        (kernel.format("flash_fwd.2"), 90, 102),            # replay
        (kernel.format("flash_bwd_dq.1"), 102, 120),        # backward
        ("%fusion.scatter = x[] fusion(), kind=kLoop", 120, 126),  # layout
        ("%all-reduce.1 = x[] all-reduce()", 126, 150),     # the wire: no phase
        ("%fusion.scale = x[] fusion(), kind=kLoop", 150, 153),  # layout
        ("%fusion.opt = x[] fusion(), kind=kLoop", 153, 170),  # optimizer
        ("%fusion.guard = x[] fusion(), kind=kLoop", 170, 171),  # guard
        ("%fusion.unknown = x[] fusion(), kind=kLoop", 171, 178),  # unattributed
        ("%copy.out = x[] copy()", 178, 180),               # optimizer
    ]
    return [Op(text, t0 + lo, t0 + hi) for text, lo, hi in spans]


@pytest.fixture
def ctx():
    ops = step(0) + step(200) + step(400)
    modules = [Op("jit_bagua_step", t, t + 200) for t in (0, 200, 400)]
    trace = Trace({0: Chip(ops, modules)}, [])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(trace=trace, hlo_text=HLO, chips=1, peak=peak)


EXPECTED_NS = {
    "forward_ms": 2 + 3 + 30 + 10,
    "backward_ms": 40 + 18,
    "remat_replay_ms": 12,
    "optimizer_ms": 17 + 2,
    "bucket_layout_ms": 5 + 6 + 3,
    "unattributed_ms": 7,
}
GUARD_NS = 1


@pytest.mark.parametrize("metric", sorted(EXPECTED_NS))
def test_phase_metric(ctx, metric):
    assert reader(metric).reduce(ctx) == pytest.approx(EXPECTED_NS[metric] / 1e6)


def test_phases_partition_the_non_collective_time_exactly(ctx):
    compute = reader("compute_ms").reduce(ctx)
    parts = [scopes.phase_ms(ctx, phase) for phase in scopes.PHASES]
    assert sum(parts) == pytest.approx(compute, rel=1e-12)
    assert compute == pytest.approx((sum(EXPECTED_NS.values()) + GUARD_NS) / 1e6)
    assert scopes.phase_ms(ctx, scopes.GUARD) == pytest.approx(GUARD_NS / 1e6)


def test_kernels_divide_pallas_ms(ctx):
    by_kernel = {k: reader(f"flash_{k}_ms").reduce(ctx)
                 for k in ("fwd", "dq", "dkv")}
    # the replayed forward kernel counts with its kernel
    assert by_kernel == pytest.approx({"fwd": 22e-6, "dq": 18e-6, "dkv": 0.0})
    assert sum(by_kernel.values()) == pytest.approx(reader("pallas_ms").reduce(ctx))


def test_a_program_without_scopes_reads_unattributed_and_nothing_else(ctx):
    ctx.hlo_text = HLO.replace("bagua.", "x.").replace("flash_", "attn_")
    assert reader("unattributed_ms").reduce(ctx) == reader("compute_ms").reduce(ctx)
    assert reader("forward_ms").reduce(ctx) == 0.0
    # kernels are there, but none has a name: nothing is claimed
    assert reader("flash_fwd_ms").reduce(ctx) is None
    assert reader("flash_fwd_roofline").reduce(ctx) is None


def test_without_a_trace_or_text_nothing_is_read(ctx):
    for gone in ("trace", "hlo_text"):
        held = getattr(ctx, gone)
        setattr(ctx, gone, None)
        for name in ("forward_ms", "flash_dq_ms", "flash_dq_roofline"):
            assert reader(name).reduce(ctx) is None
        setattr(ctx, gone, held)
    ctx.hlo_text = None
    assert reader("comm_calls_compiled").reduce(ctx) is None


def test_comm_calls_compiled_counts_an_async_pair_once(ctx):
    assert reader("comm_calls_compiled").reduce(ctx) == 1
    ctx.hlo_text += """
  %ag-start = (f32[4], f32[8]) all-gather-start(%w), replica_groups={{0,1}}, dimensions={0}
  %ag-done = f32[8] all-gather-done(%ag-start)
"""
    assert reader("comm_calls_compiled").reduce(ctx) == 2


# ---- kernel costs ------------------------------------------------------------


def test_causal_block_pairs_by_hand():
    # 1024 with 512-wide blocks: q block 0 sees k block 0, q block 1 both
    assert kernel_costs.causal_block_pairs(1024, 512, 512) == 3
    assert kernel_costs.causal_block_pairs(1024, 256, 512) == 1 + 1 + 2 + 2
    assert kernel_costs.causal_block_pairs(1024, 512, 256) == 2 + 4
    # one block: the whole square, masked in registers
    assert kernel_costs.causal_block_pairs(512, 512, 512) == 1


def test_flash_costs_by_hand():
    bh, seq, d, blocks = 16, 1024, 64, (512, 512)
    pair = 2 * 512 * 512 * d            # one matmul over one block pair
    tensor = bh * seq * d * 2           # one bf16 [bh, seq, d] operand
    row = bh * seq * 4
    assert kernel_costs.flash_fwd(bh, seq, d, 2, blocks) == (
        bh * 3 * 2 * pair, 4 * tensor + 8 * row)
    assert kernel_costs.flash_bwd_dq(bh, seq, d, 2, blocks) == (
        bh * 3 * 3 * pair, 5 * tensor + 2 * row)
    assert kernel_costs.flash_bwd_dkv(bh, seq, d, 2, blocks) == (
        bh * 3 * 4 * pair, 6 * tensor + 2 * row)


def test_roofline_is_flop_over_time_over_the_attainable_rate(ctx, monkeypatch):
    monkeypatch.setattr(kernel_costs, "flash_blocks", lambda seq: (512, 512))
    flop, hbm = kernel_costs.flash_bwd_dq(16, 1024, 64, 2)
    assert flop / hbm > 197e12 / 819e9          # compute-bound: the peak
    # one call of 18 ns a step
    assert reader("flash_dq_roofline").reduce(ctx) == pytest.approx(
        100 * flop / 18e-9 / 197e12)
    # two forward calls a step (one replayed), 22 ns together
    flop, _ = kernel_costs.flash_fwd(16, 1024, 64, 2)
    assert reader("flash_fwd_roofline").reduce(ctx) == pytest.approx(
        100 * 2 * flop / 22e-9 / 197e12)
    assert reader("flash_dkv_roofline").reduce(ctx) is None


# ---- the program's spans and gauges, read in-process ---------------------------


@pytest.fixture
def ring():
    from bagua_tpu.obs import spans

    spans.set_enabled(True)
    spans.span_ring.clear()
    yield spans
    spans.span_ring.clear()
    spans.set_enabled(None)


def test_trainer_overhead_is_the_root_span_less_its_dispatch(ring, monkeypatch):
    assert reader("trainer_overhead_ms").reduce(None) is None
    assert reader("input_place_ms").reduce(None) is None
    clock = iter([0.0, 1.0, 4.0, 6.0,        # step 1: root 6 ms, dispatch 3 ms
                  10.0, 10.5, 11.0, 12.0,    # step 2: root 2 ms, dispatch 0.5
                  20.0, 21.0, 29.0, 30.0,    # step 3: root 10 ms, dispatch 8
                  40.0, 47.0])               # input/place 7 ms
    monkeypatch.setattr(ring.time, "monotonic", lambda: next(clock) / 1e3)
    for step_num in (1, 2, 3):
        with ring.trace_step_span(step_num):
            with ring.trace_span("step/dispatch", step=step_num):
                pass
    with ring.trace_span("input/place"):
        pass
    # median of 3, 1.5 and 2 ms
    assert reader("trainer_overhead_ms").reduce(None) == pytest.approx(2.0)
    assert reader("input_place_ms").reduce(None) == pytest.approx(7.0)


def test_buckets_per_step_is_the_programs_gauge():
    from bagua_tpu.telemetry import counters

    held = counters.snapshot().get("comm/buckets_per_step")
    counters.reset()
    try:
        assert reader("buckets_per_step").reduce(None) is None
        counters.set_gauge("comm/buckets_per_step", 178)
        assert reader("buckets_per_step").reduce(None) == 178
    finally:
        counters.reset()
        if held is not None:
            counters.set_gauge("comm/buckets_per_step", held)
