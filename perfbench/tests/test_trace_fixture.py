"""The device-trace readers against traces RECORDED on the v5e (my chip runs,
PR 23), cut to two steps by perfbench/tools/trace_dump.py --fixture:

  v5e_gpt2-medium.pretrain1024-dp1_2steps     one chip, the Pallas flash kernels
  v5e_bert-large.squad384-dp4_chips0and3_...  chips 0 and 3 of four, the all-reduces

The pinned numbers are what the readers gave on these files when they were
recorded; what makes them believable is checked beside them (the step is the
sum of its parts, the kernel count is layers x 4, exposure equals the summed
all-reduce time because the v5e's all-reduces are synchronous)."""

import gzip
import types
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from perfbench import cells
from perfbench import trace_reduce as tr

DATA = Path(__file__).parent / "data"


def load(name):
    with gzip.open(DATA / f"{name}.xspace.txt.gz", "rt", encoding="utf-8") as f:
        return tr.reduce_planes(ProfileData.from_text_proto(f.read()).planes)


def read(trace, metric):
    ctx = types.SimpleNamespace(trace=trace, hlo_text=None, chips=len(trace.chips))
    return cells.load_plugin("layer_metrics", metric).reduce(ctx)


@pytest.fixture(scope="module")
def gpt2():
    return load("v5e_gpt2-medium.pretrain1024-dp1_2steps")


@pytest.fixture(scope="module")
def dp4():
    return load("v5e_bert-large.squad384-dp4_chips0and3_2steps")


def test_gpt2_step_on_one_chip(gpt2):
    chip = gpt2.chips[0]
    steps = chip.steps()
    assert len(steps) == 2 and len(chip.ops) == 20930
    step_ms = [(hi - lo) / 1e6 for lo, hi in steps]
    assert step_ms == pytest.approx([249.724, 249.724], abs=1e-3)
    # four kernel calls per block: forward, its remat replay, dQ, dK/dV
    kernels = [o for o in chip.ops if tr.is_mosaic(o)]
    assert len(kernels) == 2 * 24 * 4
    assert {o.name.split(".")[0] for o in kernels} == {"attn"}
    assert read(gpt2, "pallas_ms") == pytest.approx(64.333274)
    assert read(gpt2, "compute_ms") == pytest.approx(249.6940955)
    # one chip: nothing to exchange
    assert read(gpt2, "comm_exposed_ms") == 0.0
    assert not any(tr.is_collective(o) for o in chip.ops)
    # the host keeps the chip fed: compute fills the step
    assert read(gpt2, "device_idle_share") == pytest.approx(0.0138092, rel=1e-4)
    assert read(gpt2, "compute_ms") == pytest.approx(step_ms[0], rel=2e-4)


def test_gpt2_breakdown(gpt2):
    top = tr.top_device_ops(gpt2)
    assert [name for name, _ in top[:4]] == [
        "fusion kOutput", "custom-call tpu_custom_call", "fusion kLoop", "copy"]
    assert dict(top)["custom-call tpu_custom_call"] == pytest.approx(0.128666548)
    assert sum(seconds for _, seconds in top) <= tr.busy_and_window(gpt2)[0][0]
    gaps = tr.idle_gaps_by_host_span(gpt2)
    assert gaps[0][0] == "bench/in_flight_wait"
    busy, window = tr.busy_and_window(gpt2)[0]
    assert sum(seconds for _, seconds in gaps) == pytest.approx(window - busy)


def test_dp4_collectives_are_synchronous_and_wholly_exposed(dp4):
    assert sorted(dp4.chips) == [0, 3]
    for chip in dp4.chips.values():
        reduces = [o for o in chip.ops if tr.is_collective(o)]
        assert {o.opcode for o in reduces} == {"all-reduce"}
        assert len(reduces) == 2 * 16          # 16 combined all-reduces a step
        assert not chip.async_ops              # nothing in flight behind compute
    assert read(dp4, "comm_exposed_ms") == pytest.approx(32.452825)
    # synchronous: exposure is the all-reduces' own time, the worse chip's
    per_step = [sum(o.end - o.start for o in c.ops if tr.is_collective(o)) / 2 / 1e6
                for c in dp4.chips.values()]
    assert max(per_step) == pytest.approx(32.452825, rel=1e-4)
    assert read(dp4, "compute_ms") == pytest.approx(87.958547)
    assert read(dp4, "pallas_ms") == 0.0       # seq 384 is below the flash gate
    # a step is its compute plus its exposed communication
    step_ms = (dp4.chips[0].steps()[0][1] - dp4.chips[0].steps()[0][0]) / 1e6
    assert read(dp4, "compute_ms") + read(dp4, "comm_exposed_ms") == pytest.approx(
        step_ms, rel=1e-3)


def test_dp4_idle_share_and_breakdown(dp4):
    assert read(dp4, "device_idle_share") == pytest.approx(0.0870865, rel=1e-4)
    top = dict(tr.top_device_ops(dp4))
    assert top["all-reduce"] == pytest.approx(0.0649042815)
    assert list(top)[:2] == ["fusion kOutput", "all-reduce"]
    assert all(busy < window for busy, window in tr.busy_and_window(dp4))
