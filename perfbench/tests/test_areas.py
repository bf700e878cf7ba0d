"""perfbench/areas.py and its eleven readers, on a hand-written HLO text and
a hand-made timeline where every expected number can be checked by eye
(times are nanoseconds): the areas with the three phases that are not the
model's and ``area_other`` add up to ``compute_ms``'s reduction exactly, the
four expert-layer parts to ``moe_ms``'s, a program without ``area_of`` reads
None everywhere, an area the cell has not reads 0.0, and one pass over the
trace serves every reader."""

import types

import pytest

from perfbench import areas, cells
from perfbench import trace_reduce as tr
from perfbench.trace_reduce import Chip, Trace
from perfbench.trace_reduce import parse_op as Op

READERS = ("embed_ms", "attn_ms", "attn_relayout_ms", "mlp_ms", "head_ms",
           "accum_ms", "moe_route_ms", "moe_dispatch_ms", "moe_experts_ms",
           "moe_combine_ms", "area_other_ms")


def reader(name):
    return cells.load_plugin("layer_metrics", name)


LOSS = "jit(bagua_step)/jvp(bagua.loss)/TransformerLM"
BACK = "jit(bagua_step)/transpose(jvp(bagua.loss))/TransformerLM"
REPLAY = f"{BACK}/jvp(bagua.loss)/TransformerLM/checkpoint/rematted_computation"
MOSAIC = 'custom_call_target="tpu_custom_call"'


def line(name, opcode, path, extra=""):
    # an instruction without a path reads an unnamed constant and feeds
    # nothing: no neighbour lends it a path
    meta = f', metadata={{op_name="{path}"}}' if path else ""
    operand = "%w" if path else "%c.0"
    return f"  %{name} = f32[8]{{0}} {opcode}({operand}){extra}{meta}\n"


#: (instruction, opcode as the HLO prints it, op_name, extra, ns, key)
STEP = [
    ("fusion.embed", "fusion", f"{LOSS}/embed/jit(_take)/gather", ", kind=kLoop", 4, "embed"),
    ("fusion.pos", "fusion", f"{LOSS}/pos_embed/add", ", kind=kLoop", 1, "embed"),
    ("fusion.norm", "fusion", f"{LOSS}/block_0/attn_norm/mul", ", kind=kLoop", 2, "attn"),
    ("fusion.q", "fusion", f"{LOSS}/block_0/attn/q/dot_general", ", kind=kOutput", 10, "attn"),
    ("copy.q", "copy", f"{LOSS}/block_0/attn/q/transpose", "", 3, "attn"),
    ("flash_fwd.1", "custom-call", f"{LOSS}/block_0/attn/flash_fwd/pallas_call", f", {MOSAIC}", 12, "attn"),
    ("fusion.res", "fusion", f"{LOSS}/block_0/add", ", kind=kLoop", 1, "other"),
    ("fusion.mlpnorm", "fusion", f"{LOSS}/block_0/mlp_norm/mul", ", kind=kLoop", 2, "mlp"),
    # the router of a layer that routes before attention: MoE, not attn
    ("fusion.route", "fusion", f"{LOSS}/block_0/attn/bagua.moe/route/router/dot_general", ", kind=kOutput", 3, "moe/route"),
    ("fusion.sort", "fusion", f"{LOSS}/block_0/mlp/bagua.moe/dispatch/sort", ", kind=kLoop", 5, "moe/dispatch"),
    ("convert.wi", "convert", f"{LOSS}/block_0/mlp/bagua.moe/experts/convert_element_type", "", 2, "moe/experts"),
    ("gmm_fwd.1", "custom-call", f"{LOSS}/block_0/mlp/bagua.moe/experts/gmm_fwd/pallas_call", f", {MOSAIC}", 20, "moe/experts"),
    ("fusion.combine", "fusion", f"{LOSS}/block_0/mlp/bagua.moe/combine/mul", ", kind=kLoop", 4, "moe/combine"),
    ("reshape.rows", "reshape", f"{LOSS}/block_0/mlp/reshape", "", 1, "mlp"),
    ("fusion.logits", "fusion", f"{LOSS}/lm_head/dot_general", ", kind=kOutput", 15, "head"),
    ("fusion.xent", "fusion", "jit(bagua_step)/jvp(bagua.loss)/loss_tail/reduce_sum", ", kind=kLoop", 3, "head"),
    ("fusion.dxent", "fusion", "jit(bagua_step)/transpose(jvp(bagua.loss))/loss_tail/mul", ", kind=kLoop", 2, "head"),
    ("fusion.dhead", "fusion", f"{BACK}/lm_head/dot_general", ", kind=kOutput", 16, "head"),
    ("fusion.fnorm", "fusion", f"{BACK}/final_norm/mul", ", kind=kLoop", 1, "head"),
    ("gmm_bwd_drhs.1", "custom-call", f"{BACK}/block_0/mlp/bagua.moe/experts/gmm_bwd_drhs/pallas_call", f", {MOSAIC}", 22, "moe/experts"),
    ("flash_fwd.2", "custom-call", f"{REPLAY}/block_0/attn/flash_fwd/pallas_call", f", {MOSAIC}", 12, "attn"),
    ("transpose.dq", "transpose", f"{BACK}/block_0/attn/q/transpose", "", 2, "attn"),
    ("fusion.dembed", "fusion", f"{BACK}/embed/jit(_take)/scatter-add", ", kind=kLoop", 5, "embed"),
    ("fusion.slice", "fusion", "jit(bagua_step)/jvp(bagua.loss)/bagua.layout/slice", ", kind=kLoop", 3, "layout"),
    ("fusion.accum", "fusion", "jit(bagua_step)/while/body/closed_call/grad_accum/add", ", kind=kLoop", 6, "accum"),
    ("all-reduce.1", "all-reduce", "jit(bagua_step)/shard_map/bagua.comm/bucket_3/psum", ", replica_groups={{0,1}}, to_apply=%sum", 30, None),
    ("fusion.opt", "fusion", "jit(bagua_step)/bagua.optimizer/mul", ", kind=kLoop", 9, "optimizer"),
    ("fusion.guard", "fusion", "jit(bagua_step)/bagua.guard/is_finite", ", kind=kLoop", 1, "guard"),
    ("fusion.unknown", "fusion", "", ", kind=kLoop, calls=%fc.2", 2, "other"),
]

HLO = ("HloModule jit_bagua_step\n\nENTRY %main (w: f32[8]) -> f32[8] {\n"
       "  %w = f32[8]{0} parameter(0)\n"
       "  %c.0 = f32[8]{0} constant({...})\n"
       + "".join(line(name, opcode, path, extra)
                 for name, opcode, path, extra, _, _ in STEP)
       + "  ROOT %tuple = (f32[8]) tuple(%w)\n}\n")
STEP_NS = sum(ns for *_, ns, _ in STEP)


def step_ops(t0):
    ops, at = [], t0
    for name, opcode, _, extra, ns, _ in STEP:
        ops.append(Op(f"%{name} = x[] {opcode}(){extra}", at, at + ns))
        at += ns
    return ops


def make_ctx(hlo_text=HLO, chips=(0,)):
    starts = (0, STEP_NS, 2 * STEP_NS)
    trace = Trace({index: Chip([op for t in starts for op in step_ops(t)],
                               [Op("jit_bagua_step", t, t + STEP_NS)
                                for t in starts])
                   for index in chips}, [])
    return types.SimpleNamespace(trace=trace, hlo_text=hlo_text,
                                 chips=len(chips), peak=None)


def expected_ns(key):
    return sum(ns for *_, ns, k in STEP if k == key)


def ms(ns):
    return ns / 1e6


def test_every_instruction_takes_the_key_written_beside_it():
    keys = areas.instruction_keys(make_ctx())
    assert keys is not None
    for name, _, _, _, _, key in STEP:
        if key is not None and name != "fusion.unknown":
            assert keys[name] == key, name
    # nothing to go by: no path of its own, no named neighbour
    assert "fusion.unknown" not in keys


@pytest.mark.parametrize("name, key", [
    ("embed_ms", "embed"), ("attn_ms", "attn"), ("mlp_ms", "mlp"),
    ("head_ms", "head"), ("accum_ms", "accum"),
    ("moe_route_ms", "moe/route"), ("moe_dispatch_ms", "moe/dispatch"),
    ("moe_experts_ms", "moe/experts"), ("moe_combine_ms", "moe/combine"),
    ("area_other_ms", "other"),
])
def test_a_reader_reads_its_area(name, key):
    assert reader(name).reduce(make_ctx()) == pytest.approx(
        ms(expected_ns(key)), abs=1e-12)


def test_attention_relayouts_are_the_bare_ops_of_the_area():
    # copy.q 3 and transpose.dq 2; not convert.wi (an expert layer's), not
    # reshape.rows (the mlp's), no fusion, no kernel
    assert reader("attn_relayout_ms").reduce(make_ctx()) == pytest.approx(
        ms(3 + 2), abs=1e-12)


def test_the_parts_add_up_to_compute_ms_exactly():
    ctx = make_ctx()
    compute = reader("compute_ms").reduce(ctx)
    assert compute == pytest.approx(ms(STEP_NS - 30), abs=1e-12)
    parts = [reader(n).reduce(ctx) for n in
             ("embed_ms", "attn_ms", "mlp_ms", "moe_ms", "head_ms",
              "accum_ms", "optimizer_ms", "bucket_layout_ms",
              "area_other_ms")]
    guard = areas.area_ms(ctx, "guard")
    assert guard == pytest.approx(ms(1), abs=1e-12)
    assert sum(parts) + guard == pytest.approx(compute, abs=1e-12)
    # and step by step on the chip, in nanoseconds, with nothing left over
    for rows in areas.table(ctx).values():
        for (lo, hi), row in zip(ctx.trace.chips[0].steps(), rows):
            whole = reader("compute_ms").compute_ns(
                Chip([op for op in ctx.trace.chips[0].ops
                      if lo <= op.start < hi], []), lo, hi)
            partition = {k: v for k, v in row.items()
                         if k != areas.ATTN_RELAYOUT}
            assert sum(partition.values()) == whole


def test_the_four_parts_add_up_to_moe_ms():
    ctx = make_ctx()
    parts = sum(reader(f"moe_{part}_ms").reduce(ctx)
                for part in ("route", "dispatch", "experts", "combine"))
    assert parts == pytest.approx(reader("moe_ms").reduce(ctx), abs=1e-12)
    assert parts == pytest.approx(ms(3 + 5 + 2 + 20 + 4 + 22), abs=1e-12)


def test_a_program_without_area_of_reads_none_everywhere(monkeypatch):
    monkeypatch.setattr(areas, "program_area_of", lambda: None)
    ctx = make_ctx()
    for name in READERS:
        assert reader(name).reduce(ctx) is None, name


def test_program_area_of_is_the_programs_or_none(monkeypatch):
    from bagua_tpu.obs import spans

    assert areas.program_area_of() is spans.area_of
    monkeypatch.delattr(spans, "area_of")
    assert areas.program_area_of() is None


def test_an_area_the_cell_has_not_reads_zero():
    dense = "\n".join(l for l in HLO.splitlines()
                      if "bagua.moe" not in l and "grad_accum" not in l)
    ctx = make_ctx(dense)
    # the trace still holds the events: with no path they fall to other
    for name in ("accum_ms", "moe_route_ms", "moe_dispatch_ms",
                 "moe_experts_ms", "moe_combine_ms"):
        assert reader(name).reduce(ctx) == 0.0, name
    assert reader("attn_ms").reduce(ctx) > 0.0


def test_no_trace_or_no_text_reads_none():
    for ctx in (types.SimpleNamespace(trace=None, hlo_text=HLO),
                types.SimpleNamespace(trace=make_ctx().trace, hlo_text=None)):
        for name in READERS:
            assert reader(name).reduce(ctx) is None, name


def test_one_pass_over_the_trace_serves_every_reader(monkeypatch):
    passes = []
    per_step_ms = tr.per_step_ms

    def counting(trace, step_ns):
        passes.append(len(trace.chips))
        return per_step_ms(trace, step_ns)

    monkeypatch.setattr(tr, "per_step_ms", counting)
    ctx = make_ctx(chips=(0, 1))
    values = [reader(name).reduce(ctx) for name in READERS]
    assert all(v is not None for v in values)
    # one cut into steps a chip, whatever the number of readers
    assert passes == [1, 1]


def test_the_worst_chip_decides():
    ctx = make_ctx(chips=(0, 1))
    slow = ctx.trace.chips[1]
    # on chip 1 the accumulation starts a nanosecond early, inside the
    # layout fusion before it: another key, so the union grows by it
    slow.ops[:] = [Op(f"%{o.name} = x[] {o.opcode}()", o.start - 1, o.end)
                   if o.name == "fusion.accum" else o for o in slow.ops]
    assert reader("accum_ms").reduce(ctx) == pytest.approx(
        ms(expected_ns("accum") + 1), abs=1e-12)
    assert reader("head_ms").reduce(ctx) == pytest.approx(
        ms(expected_ns("head")), abs=1e-12)
