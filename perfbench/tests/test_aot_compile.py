"""The third CPU rehearsal of the on-chip guide: every cell's real step, at its
real size, compiled here for the described ``v5e:2x2`` — BaguaTrainer's own
step program (lowered from abstract state, ``jax.eval_shape(trainer.init)``),
not a stand-in.  It shows what interpret mode cannot: that the program fits
the chip's memory, which collectives the compiler put in, and whether a
Pallas kernel is there.  Nothing runs; no time comes out of this."""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench import cells, hlo_bytes

BENCH = cells.load_benchmark()
#: what the TPU compiler itself reports as usable on a v5e (its
#: RESOURCE_EXHAUSTED message: "of 15.75G hbm")
V5E_HBM_BYTES = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to describe
        pytest.skip(f"the v5e:2x2 topology cannot be described here: {e}")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_the_cells_step_compiles_for_the_described_v5e(workload, topology,
                                                       monkeypatch):
    # flash_supported asks jax.default_backend(), which is still the CPU
    # here: steered in the test, not by an option of the program
    flash = importlib.import_module("bagua_tpu.ops.flash_attention")
    monkeypatch.setattr(flash.jax, "default_backend", lambda: "tpu")

    cell = cells.resolve(workload)
    builder = cells.load_plugin("builders", cell.config["builder"])
    devices = list(topology.devices)[:cell.chips]
    model, trainer = builder.make_trainer(cell, cell.traffic, devices)
    params = jax.eval_shape(lambda: builder.make_params(model, 0))
    replicated = NamedSharding(trainer.mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(trainer.init, params))
    rows = int(cell.traffic["batch_per_chip"]) * cell.chips
    batch = {"tokens": jax.ShapeDtypeStruct(
        (rows, int(cell.traffic["seq_len"]) + 1), jnp.int32,
        sharding=NamedSharding(trainer.mesh, P(tuple(cell.traffic["mesh"]))))}

    compiled = trainer._get_step_fn().lower(state, batch).compile()

    memory = compiled.memory_analysis()
    needed = (memory.argument_size_in_bytes + memory.output_size_in_bytes
              - memory.alias_size_in_bytes + memory.temp_size_in_bytes
              + memory.generated_code_size_in_bytes)
    assert needed <= V5E_HBM_BYTES - 2 ** 30, (
        f"{workload}: {needed / 2**30:.2f} GiB leaves under 1 GiB of the chip")
    # a cell that leaves most of the chip empty does not stand for a job
    assert needed >= 0.25 * 16e9

    text = compiled.as_text()
    # a Pallas kernel is a custom call whose target is tpu_custom_call
    assert ('custom_call_target="tpu_custom_call"' in text) == (
        cell.config_name == "gpt2-medium")
    wire = hlo_bytes.wire_bytes(text, cell.chips)
    if cell.chips == 1:
        assert wire == 0.0
    else:
        # every float32 gradient crosses the ring once: 2 (N-1)/N x 4 B x params
        ring = 2 * (cell.chips - 1) / cell.chips * 4 * cell.config["parameters_as_built"]
        assert ring <= wire <= 1.01 * ring
