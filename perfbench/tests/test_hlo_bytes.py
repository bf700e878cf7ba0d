"""perfbench/hlo_bytes.py on hand-written HLO of the forms the TPU compiler
prints (read from AOT compiles for the described v5e:2x2, PR 23)."""

import pytest

from perfbench import hlo_bytes

# the synchronous, combined form the v5e step has
SYNC = """
  %psum.798 = f32[1024]{0:T(1024)} all-reduce(%fusion.1), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_1
  %all-reduce.13 = (f32[256]{0:T(1024)}, f32[256]{0:T(1024)S(1)}, /*index=2*/f32[]{:T(128)}) all-reduce(%a, %b, %c), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_2
  %fusion.7 = f32[1024]{0} fusion(%psum.798), kind=kLoop, metadata={op_name="uses all-reduce(x)"}
  %custom-call.884 = f32[1024,16,64]{0,2,1:T(8,128)S(1)} custom-call(%s), custom_call_target="ConcatBitcast"
"""
ASYNC = """
  %all-reduce-start.1 = f32[1024]{0} all-reduce-start(%x), replica_groups=[1,8]<=[8], to_apply=%sum
  %all-reduce-done.1 = f32[1024]{0} all-reduce-done(%all-reduce-start.1)
  %all-gather-start.2 = (u8[1024]{0}, u8[8192]{0}) all-gather-start(%y), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %all-gather-done.2 = u8[8192]{0} all-gather-done(%all-gather-start.2)
  %rs = f32[128]{0} reduce-scatter(%z), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %collective-permute-start.3 = (f32[64]{0}, f32[64]{0}, u32[], u32[]) collective-permute-start(%w), source_target_pairs={{0,1},{1,0}}
  %collective-permute-done.3 = f32[64]{0} collective-permute-done(%collective-permute-start.3)
"""


def test_sync_combined_all_reduce_counts_every_array_once():
    found = hlo_bytes.collectives(SYNC, n_devices=4)
    assert [c["op"] for c in found] == ["all-reduce", "all-reduce"]
    assert [c["bytes"] for c in found] == [4096, 256 * 4 * 2 + 4]
    assert {c["group"] for c in found} == {4}
    assert hlo_bytes.wire_bytes(SYNC, 4) == pytest.approx(
        2 * 3 / 4 * (4096 + 2052))


def test_async_halves_are_counted_once_and_operands_not_at_all():
    by_op = {c["op"]: c for c in hlo_bytes.collectives(ASYNC, n_devices=8)}
    assert by_op["all-reduce"]["bytes"] == 4096
    assert by_op["all-reduce"]["group"] == 8       # iota replica groups
    assert by_op["all-gather"]["bytes"] == 8192    # the result, not the operand
    assert by_op["reduce-scatter"]["bytes"] == 512
    assert by_op["collective-permute"]["bytes"] == 256
    assert hlo_bytes.wire_bytes(ASYNC, 8) == pytest.approx(
        2 * 7 / 8 * 4096 + 7 / 8 * 8192 + 7 * 512 + 256)


def test_no_collective_is_zero_bytes():
    assert hlo_bytes.wire_bytes("  %f = f32[8]{0} fusion(%x), kind=kLoop", 1) == 0.0
