"""The interval arithmetic of perfbench/trace_reduce.py and the device-trace
readers on hand-made timelines, where every expected number can be checked
by eye.  Times are nanoseconds."""

import types

import pytest

from perfbench import cells
from perfbench import trace_reduce as tr
from perfbench.trace_reduce import Chip, Trace
from perfbench.trace_reduce import parse_op as Op


def reader(name):
    return cells.load_plugin("layer_metrics", name)


def context(trace, hlo_text=None):
    return types.SimpleNamespace(trace=trace, hlo_text=hlo_text, chips=len(trace.chips))


# ---------------------------------------------------------------------------
# merge / length / clip / subtract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("intervals, merged", [
    ([], []),
    ([(0, 10)], [(0, 10)]),
    ([(5, 7), (0, 3)], [(0, 3), (5, 7)]),                 # sorted
    ([(0, 5), (5, 9)], [(0, 9)]),                         # touching
    ([(0, 10), (2, 3), (4, 12)], [(0, 12)]),              # nested + overlapping
    ([(0, 4), (4, 4), (9, 8)], [(0, 4)]),                 # empty ones dropped
])
def test_merge(intervals, merged):
    assert tr.merge(intervals) == merged
    assert tr.length(intervals) == sum(e - s for s, e in merged)


def test_clip():
    assert tr.clip([(0, 10), (20, 30), (40, 50)], 5, 45) == [
        (5, 10), (20, 30), (40, 45)]
    assert tr.clip([(0, 10)], 10, 20) == []


@pytest.mark.parametrize("a, b, rest", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10)], [(2, 4), (6, 7)], [(0, 2), (4, 6), (7, 10)]),
    ([(0, 10)], [(-5, 3), (8, 20)], [(3, 8)]),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),      # one cover, two pieces
    ([(0, 4), (2, 8)], [(5, 6)], [(0, 5), (6, 8)]),        # a is merged first
    ([(0, 2), (4, 6)], [(10, 12)], [(0, 2), (4, 6)]),
])
def test_subtract(a, b, rest):
    assert tr.subtract(a, b) == rest


# ---------------------------------------------------------------------------
# a hand-made two-chip, two-step timeline
# ---------------------------------------------------------------------------
#
# chip 0, step 1 = [0, 100):
#   fusion.1       0 ..  40   compute
#   all-reduce.1  40 ..  70   synchronous collective: 30 exposed
#   fusion.2      70 ..  90   compute
#   (idle         90 .. 100)
# chip 0, step 2 = [100, 200):
#   fusion.1     100 .. 140
#   all-reduce-start.1 140 .. 142, fusion.3 142 .. 160 runs under it,
#   all-reduce-done.1  160 .. 175: in flight 140..175 = 35, 18 hidden -> 17 exposed
#   attn.7       175 .. 195   a Pallas kernel (named so by the HLO)
#   (idle        195 .. 200)
# chip 1 is chip 0 with its step-1 collective 10 longer (a slower link).

KERNEL = ('%attn.7 = (bf16[8,64]{1,0:T(8,128)(2,1)S(1)}, f32[8]{0}) custom-call('
          'bf16[8,64]{1,0} %q), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={bf16[8,64]{1,0}}')


def chip(slow_by=0.0):
    ops = [
        Op("fusion.1", 0, 40),
        Op("all-reduce.1", 40, 70 + slow_by),
        Op("fusion.2", 70 + slow_by, 90 + slow_by),
        Op("fusion.1", 100, 140),
        Op("all-reduce-start.1", 140, 142),
        Op("fusion.3", 142, 160),
        Op("all-reduce-done.1", 160, 175),
        Op(KERNEL, 175, 195),
    ]
    modules = [Op("jit_per_shard", 0, 100), Op("jit_per_shard", 100, 200),
               Op("jit_small", 96, 97)]
    return Chip(ops, modules)


@pytest.fixture
def trace():
    host = [Op("bench/in_flight_wait", 80, 96), Op("bench/next_batch", 96, 97),
            Op("bench/train_step", 97, 101), Op("bench/in_flight_wait", 190, 260)]
    return Trace({0: chip(), 1: chip(slow_by=10.0)}, host)


def test_steps_are_the_dominant_module(trace):
    assert trace.chips[0].steps() == [(0, 100), (100, 200)]
    assert trace.chips[0].window() == (0, 200)


def test_collective_intervals_pair_async_halves(trace):
    assert tr.collective_intervals(trace.chips[0].ops) == [(40, 70), (140, 175)]


def test_comm_exposed_is_in_flight_minus_compute_worst_chip(trace):
    # chip 0: median(30, 17) = 23.5 ns; chip 1: median(40, 17) = 28.5 ns
    assert reader("comm_exposed_ms").reduce(context(trace)) == pytest.approx(28.5e-6)


def test_compute_is_the_union_of_non_collectives(trace):
    # every chip: step 1 = 40 + 20, step 2 = 40 + 18 + 20 -> median 69 ns
    assert reader("compute_ms").reduce(context(trace)) == pytest.approx(69e-6)


def test_pallas_is_the_tpu_custom_calls(trace):
    # step 1 has no kernel, step 2 has 20 ns: median 10 ns
    assert reader("pallas_ms").reduce(context(trace)) == pytest.approx(10e-6)
    for c in trace.chips.values():
        c.ops[:] = [o for o in c.ops if not tr.is_mosaic(o)]
    assert reader("pallas_ms").reduce(context(trace)) == 0.0


def test_device_idle_share_is_the_idlest_chip(trace):
    # chip 0: busy 90 + 95 of 200 -> 7.5 % idle; chip 1: 100 + 95 -> 2.5 %
    assert reader("device_idle_share").reduce(context(trace)) == pytest.approx(7.5)
    assert tr.busy_and_window(trace) == [
        (pytest.approx(185e-9), pytest.approx(200e-9)),
        (pytest.approx(195e-9), pytest.approx(200e-9))]


def test_idle_gaps_go_to_the_host_span_that_overlaps_them_most(trace):
    # chip 0's gaps: 90..100 (in_flight_wait covers 6, train_step 3) and
    # 195..200 (in_flight_wait)
    assert tr.idle_gaps_by_host_span(trace) == [
        ["bench/in_flight_wait", pytest.approx(15e-9)]]
    trace.host.clear()
    assert tr.idle_gaps_by_host_span(trace) == [
        ["(no benchmark span)", pytest.approx(15e-9)]]


def test_top_device_ops_sum_by_label_averaged_over_chips(trace):
    top = dict(tr.top_device_ops(trace, limit=3))
    assert top["fusion"] == pytest.approx(118e-9)          # 40+20+40+18
    assert top["all-reduce"] == pytest.approx(35e-9)       # (30 + 40) / 2
    assert top["custom-call tpu_custom_call"] == pytest.approx(20e-9)
    assert len(top) == 3


@pytest.mark.parametrize("text, name, opcode, label", [
    # whole instructions, as the v5e trace prints them
    ("%fusion.36 = (f32[31261696]{0:T(1024)}, f32[31261696]{0:T(1024)}) "
     "fusion(f32[31261696]{0:T(1024)} %p.1, f32[]{:T(128)S(6)} %sub.207), "
     "kind=kLoop, calls=%fused_computation.12",
     "fusion.36", "fusion", "fusion kLoop"),
    ("%psum.798 = f32[31653888]{0:T(1024)} all-reduce(f32[31653888]{0:T(1024)} "
     "%maximum_convert_fusion), channel_id=1, replica_groups={{0,1,2,3}}",
     "psum.798", "all-reduce", "all-reduce"),
    (KERNEL, "attn.7", "custom-call", "custom-call tpu_custom_call"),
    ('%custom-call.884 = f32[1024,16,64]{0,2,1:T(8,128)S(1)} custom-call('
     'f32[256,16,64]{0,2,1} %slice-done.3520), custom_call_target="ConcatBitcast"',
     "custom-call.884", "custom-call", "custom-call ConcatBitcast"),
    ("%copy-start.345 = (bf16[1024,16,64]{2,1,0:T(8,128)(2,1)}, "
     "bf16[1024,16,64]{2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start("
     "bf16[1024,16,64]{2,1,0:T(8,128)(2,1)S(1)} %reshape.539)",
     "copy-start.345", "copy-start", "copy-start"),
    # bare names: other runtimes, module events, host annotations
    ("all-reduce-start.3", "all-reduce-start.3", "all-reduce-start", "all-reduce-start"),
    ("jit_per_shard(7567715676969470261)", "jit_per_shard(7567715676969470261)",
     "jit_per_shard(7567715676969470261)", "jit_per_shard(7567715676969470261)"),
])
def test_parse_op_reads_the_instruction_the_trace_prints(text, name, opcode, label):
    op = Op(text, 1.0, 3.0)
    assert (op.name, op.opcode, op.label, op.interval) == (name, opcode, label, (1.0, 3.0))


def test_a_collective_on_the_async_line_is_in_flight_for_its_whole_event():
    # the op line has only the two short halves; the async line has the span
    ops = [Op("fusion.1", 0, 30), Op("all-gather-start.1", 30, 31),
           Op("fusion.2", 31, 50), Op("all-gather-done.1", 50, 70)]
    chip_ = Chip(ops, [Op("jit_step", 0, 70)], [Op("all-gather-start.1", 30, 70),
                                                Op("copy-start.9", 0, 5)])
    assert tr.collective_intervals(chip_.ops, chip_.async_ops) == [(30, 70), (30, 70)]
    t = Trace({0: chip_}, [])
    # in flight 30..70, fusion.2 hides 19 of it
    assert reader("comm_exposed_ms").reduce(context(t)) == pytest.approx(21e-6)


def test_a_while_loop_does_not_hide_the_collective_inside_it():
    ops = [Op("while.1", 0, 100), Op("fusion.1", 0, 60),
           Op("all-reduce.2", 60, 100)]
    t = Trace({0: Chip(ops, [Op("jit_step", 0, 100)])}, [])
    assert reader("comm_exposed_ms").reduce(context(t)) == pytest.approx(40e-6)
    assert reader("compute_ms").reduce(context(t)) == pytest.approx(60e-6)
    assert dict(tr.top_device_ops(t)) == {
        "fusion": pytest.approx(60e-9), "all-reduce": pytest.approx(40e-9)}


def test_readers_return_nothing_without_a_device_trace():
    ctx = types.SimpleNamespace(trace=None, hlo_text=None, chips=1)
    for name in ("comm_exposed_ms", "compute_ms", "pallas_ms",
                 "device_idle_share"):
        assert reader(name).reduce(ctx) is None
