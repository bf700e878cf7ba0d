"""Compiled-HLO audit of on-wire collective bytes per algorithm family.

Single-chip throughput can't demonstrate what the compressed families exist
for — fewer bytes on a slow link (reference op:
comm_ops/centralized_low_precision_synchronous.rs:16-74).  This audit reads
the OPTIMIZED HLO of each family's compiled train step on the 8-device mesh
and sums ring-model wire bytes over every collective instruction:

    all-reduce          2*(N-1)/N * result bytes
    reduce-scatter        (N-1)   * result bytes   (result is 1/N of input)
    all-gather          (N-1)/N   * result bytes
    all-to-all          (N-1)/N   * result bytes
    collective-permute            * result bytes

Pinned facts:
  * ByteGrad moves < 0.3x the bytes of full-precision allreduce (uint8
    payload + f32 minmax sidecar vs f32 payload — the 1/4 pitch).
  * ZeRO's reduce-scatter + all-gather equals plain allreduce's bytes
    (an allreduce IS the pair), within bucket-padding rounding.
  * bf16 comm_dtype halves the wire bytes.
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest

from bagua_tpu.algorithms import (
    ByteGradAlgorithm,
    GradientAllReduceAlgorithm,
    ZeroOptimizerAlgorithm,
)
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.models.mlp import MLP
from bagua_tpu.parallel.mesh import build_mesh

N_DEVICES = 8

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}
# result-type tokens like f32[128,64] or u8[4096]
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLLECTIVES = (
    "all-reduce", "reduce-scatter", "all-gather", "all-to-all",
    "collective-permute",
)
_WIRE_WEIGHT = {
    "all-reduce": lambda b, n: 2 * (n - 1) / n * b,
    "reduce-scatter": lambda b, n: (n - 1) * b,
    "all-gather": lambda b, n: (n - 1) / n * b,
    "all-to-all": lambda b, n: (n - 1) / n * b,
    "collective-permute": lambda b, n: b,
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        total += numel * _DTYPE_BYTES[dtype]
    return total


def wire_bytes_of_hlo(hlo_text: str, n: int = N_DEVICES) -> float:
    """Sum ring-model wire bytes over every collective instruction.  Only
    ``xxx = TYPE collective-name(...)`` instruction lines count (fusion
    *references* to collectives don't re-match: the op name must directly
    follow the result type)."""
    total = 0.0
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?[%\w.-]+ = (.*?) (" + "|".join(_COLLECTIVES)
                     + r")(?:-start|-done)?\(", line)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        if op == "all-reduce" and "-done(" in line:
            continue  # the -done half of an async pair: already counted
        total += _WIRE_WEIGHT[op](_shape_bytes(type_str), n)
    return total


def _step_hlo(algo, optimizer=None):
    """Compile one train step on the 8-device dp mesh; return optimized HLO."""
    mesh = build_mesh({"dp": N_DEVICES})
    model = MLP(features=(512, 128, 32))
    x = jax.random.normal(jax.random.PRNGKey(0), (N_DEVICES * 4, 64))
    y = jnp.zeros((N_DEVICES * 4,), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), x[:2])["params"]

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]
        ).mean()

    trainer = BaguaTrainer(
        loss_fn,
        None if algo.owns_optimizer else (optimizer or optax.sgd(0.1)),
        algo, mesh=mesh, autotune=False,
    )
    state = trainer.init(params)
    batch = trainer.shard_batch({"x": x, "y": y})
    fn = trainer._get_step_fn()
    lowered = fn.lower(state, batch)
    texts = lowered.compile().as_text()
    n_params = sum(p.size for p in jax.tree.leaves(params))
    return texts, n_params, lowered.as_text()


def test_bytegrad_wire_bytes_quarter_of_allreduce():
    ar_hlo, n_params, _ = _step_hlo(GradientAllReduceAlgorithm())
    bg_hlo, _, _ = _step_hlo(ByteGradAlgorithm())
    ar = wire_bytes_of_hlo(ar_hlo)
    bg = wire_bytes_of_hlo(bg_hlo)
    # sanity: the f32 allreduce moves at least the ring cost of the params
    assert ar >= 2 * (N_DEVICES - 1) / N_DEVICES * n_params * 4 * 0.9
    assert bg < 0.30 * ar, (
        f"bytegrad moves {bg:.0f} wire bytes vs allreduce {ar:.0f} "
        f"({bg / ar:.2f}x) — the uint8 pipeline must be ~1/4"
    )


def test_zero_wire_bytes_equal_allreduce():
    ar_hlo, _, _ = _step_hlo(GradientAllReduceAlgorithm())
    z_hlo, _, _ = _step_hlo(ZeroOptimizerAlgorithm(optax.sgd(0.1)))
    ar = wire_bytes_of_hlo(ar_hlo)
    z = wire_bytes_of_hlo(z_hlo)
    # identical modulo the loss-scalar allreduce and bucket padding
    assert ar * 0.9 < z < ar * 1.1, (
        f"zero moves {z:.0f} wire bytes vs allreduce {ar:.0f}: the "
        f"reduce-scatter + all-gather pair must cost what allreduce costs"
    )


def _requested_payloads(stablehlo, op):
    """Element types of the non-scalar payloads the program REQUESTS of one
    StableHLO collective.  ``all_reduce`` / ``reduce_scatter`` carry a
    reduction REGION; the type signature ") : (tensor<103072xbf16>) -> ..."
    follows the region's close; ``all_gather`` has none."""
    region = r".*?\}\)" if op != "all_gather" else r"[^\n]*?"
    out = []
    for m in re.finditer(
        r"stablehlo\." + op + region + r" : "
        r"\(tensor<(?:([0-9]+(?:x[0-9]+)*)x)?(bf16|f16|f32|f64)>\)",
        stablehlo, re.DOTALL,
    ):
        dims, dtype = m.groups()
        numel = 1
        for d in (dims or "").split("x"):
            if d:
                numel *= int(d)
        if numel > 1:  # skip the scalar loss allreduce
            out.append((dtype, numel))
    return out


def test_bf16_comm_dtype_requests_bf16_collectives():
    """comm_dtype=bf16 must put bf16 payloads into the gradient collectives
    the program REQUESTS — all-reduces: a wire narrower than the parameters
    keeps the all-reduce, since the sharded update's gather would carry
    the parameters' float32.  Checked on the pre-optimization StableHLO: the XLA *CPU* backend's collective
    runtime promotes narrow all-reduces to f32 during optimization (an
    artifact of this simulation platform), while the TPU backend executes
    bf16 all-reduces natively — so the optimized-HLO byte audit used
    elsewhere in this file would report the CPU promotion, not the
    program's wire request."""
    _, _, f32_st = _step_hlo(GradientAllReduceAlgorithm())
    _, _, bf_st = _step_hlo(GradientAllReduceAlgorithm(comm_dtype=jnp.bfloat16))

    def gradient_payloads(stablehlo):
        return [d for op in ("all_reduce", "reduce_scatter")
                for d, _ in _requested_payloads(stablehlo, op)]

    assert "bf16" not in gradient_payloads(f32_st)
    bf_payloads = gradient_payloads(bf_st)
    assert bf_payloads and all(d == "bf16" for d in bf_payloads), (
        f"expected every gradient collective's payload in bf16, got "
        f"{bf_payloads}"
    )
    assert not _requested_payloads(bf_st, "reduce_scatter")
    assert not _requested_payloads(bf_st, "all_gather")
    assert _requested_payloads(f32_st, "reduce_scatter")


@pytest.mark.parametrize("comm_dtype", [None, jnp.bfloat16],
                         ids=["float32", "bf16comm"])
def test_sharded_update_asks_the_wire_for_the_allreduces_bytes(
        comm_dtype, monkeypatch):
    """reduce-scatter -> update of the owned chunk -> all-gather: the ring
    carries what the all-reduce carried, (N-1)/N of the payload each way.
    With a wire narrower than the parameters only the reduce-scatter's half
    would shrink (the gather carries the parameters' own dtype: 3/4 of the
    float32 exchange where the narrow all-reduce moves 1/2), so that
    configuration keeps the all-reduce.  Counted on what the program
    requests (see the test above for why not the CPU's HLO)."""
    from bagua_tpu.core import backend

    def ring_bytes(stablehlo):
        n, width = N_DEVICES, {"bf16": 2, "f16": 2, "f32": 4, "f64": 8}
        total = 0.0
        for op, weight in (("all_reduce", 2 * (n - 1) / n),
                           ("reduce_scatter", (n - 1) / n),  # operand bytes
                           ("all_gather", n - 1)):  # operand = one chunk
            total += sum(weight * numel * width[d]
                         for d, numel in _requested_payloads(stablehlo, op))
        return total

    algo = lambda: GradientAllReduceAlgorithm(comm_dtype=comm_dtype)
    opt = optax.adamw(1e-3)
    sharded_hlo, n_params, sharded = _step_hlo(algo(), opt)
    itemsize = 4 if comm_dtype is None else 2
    ring = (N_DEVICES - 1) / N_DEVICES * n_params
    if comm_dtype is not None:
        assert not _requested_payloads(sharded, "reduce_scatter")
        assert ring_bytes(sharded) == pytest.approx(2 * ring * itemsize)
        return
    assert "reduce-scatter" in sharded_hlo and "all-gather" in sharded_hlo
    monkeypatch.setattr(backend, "is_elementwise", lambda optimizer: False)
    replicated_hlo, _, replicated = _step_hlo(algo(), opt)
    assert "reduce-scatter" not in replicated_hlo
    assert not _requested_payloads(replicated, "reduce_scatter")

    assert ring_bytes(replicated) == pytest.approx(2 * ring * itemsize)
    # (a 1-D flat is padded to the world: under 8 elements a bucket)
    assert ring_bytes(sharded) == pytest.approx(ring * (itemsize + 4),
                                                rel=1e-3)


def test_wire_parser_on_known_hlo():
    """Parser unit check on a hand-written HLO snippet."""
    hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={}
  %ag = u8[8192]{0} all-gather(u8[1024]{0} %y), dimensions={0}
  %rs = f32[128]{0} reduce-scatter(f32[1024]{0} %z), dimensions={0}
  %fused = f32[1024]{0} fusion(f32[1024]{0} %ar), kind=kLoop
"""
    n = 8
    expect = (2 * 7 / 8 * 4096) + (7 / 8 * 8192) + (7 * 512)
    assert wire_bytes_of_hlo(hlo, n) == pytest.approx(expect)
