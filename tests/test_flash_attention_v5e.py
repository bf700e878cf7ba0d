"""The three flash kernels compiled at the benchmark's real shapes for a
described (not attached) v5e: what interpret mode cannot show — that Mosaic
takes the ``BlockSpec``s over ``[batch, seq, heads * head_dim]``, the
two-heads-a-block bodies at head_dim 64 and the whole-sequence operands'
VMEM.  Nothing runs; no time comes out of this.  The topology is described
inside a fixture, never at import (one process at a time may load libtpu)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bagua_tpu.ops.flash_attention import flash_attention_with_lse


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to describe
        pytest.skip(f"the v5e:2x2 topology cannot be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,s,h,d", [
    (8, 1024, 16, 64),    # gpt2-medium.pretrain1024-dp1: two heads a block
    (2, 4096, 16, 128),   # olmoe-1b-7b.pretrain4096-dp1: one head a block
    (1, 8192, 8, 64),     # bench.py's long-context shape class
])
def test_the_kernels_compile_for_the_described_v5e(b, s, h, d, one_chip):
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True)
        return o.sum() + lse.sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    # each call's first result is the rank-3 [b, s, h * d] tensor (o, dq,
    # dk), row-major, as are the tensor operands: no [b * h, s, d] fold
    for line in calls:
        first = re.search(r"= \(?bf16\[([\d,]+)\]\{([\d,]+)", line)
        assert first.group(1) == f"{b},{s},{h * d}", line
        assert first.group(2) == "2,1,0", line
    assert f"bf16[{b * h},{s},{d}]" not in text
