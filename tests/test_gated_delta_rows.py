"""The row passes of a Gated DeltaNet layer (``bagua_tpu/ops/gated_delta_rows.py``:
``gdn_mix`` / ``gdn_mix_bwd`` / ``gdn_gate`` / ``gdn_gate_bwd``) on the CPU,
every ``pallas_call`` interpreted, against the layer's ``jax.numpy`` form
(``models/linear_attention.py::mix_rows`` / ``gate_rows``): the values and
every cotangent, over the shapes that cross what can go wrong — two
sequences a batch (a sequence's first rows see zeros, not the tail of the
one before), one row block and several (the convolution's reach in front of
a block and its transpose's behind it), one and several column blocks, one
and two value heads a key head, two and four taps — and the whole layer
forced onto that path beside the unforced one."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models import linear_attention as la
from bagua_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss_fn,
)
from bagua_tpu.ops import gated_delta_rows as rows
from bagua_tpu.telemetry import counters

EPS = 1e-6
#: name -> (batch, seq, (hk, hv, dk, dv), taps, dtype, (rows, lanes) caps)
CASES = {
    "one-block": (2, 128, (1, 1, 128, 128), 4, jnp.float32, None),
    "three-row-blocks": (2, 384, (1, 2, 128, 128), 4, jnp.float32,
                         (128, 128)),
    # a block of 512 rows is walked in four chunks of 128
    "two-taps-four-chunks": (2, 512, (2, 2, 128, 128), 2, jnp.float32, None),
    "wide-key-heads": (1, 256, (1, 1, 256, 128), 3, jnp.float32,
                       (128, None)),
    "bfloat16": (2, 256, (1, 2, 128, 128), 4, jnp.bfloat16, (128, 256)),
    # heads that are no whole lane tile (Olmo-Hybrid's 96-lane keys under
    # 192-lane values, a head count that is no multiple of four): q | k go
    # through as one part whose middle block of 384 lanes holds two q heads
    # and two k heads; one column block a part, and several
    "heads-96-192": (2, 256, (6, 6, 96, 192), 4, jnp.float32, (128, 384)),
    "heads-96-192-wide-blocks": (1, 384, (6, 6, 96, 192), 4, jnp.float32,
                                 None),
    "heads-96-192-bfloat16": (2, 128, (2, 2, 96, 192), 4, jnp.bfloat16,
                              None),
}
QUANTITIES = ["q", "k", "v", "dx", "d_taps", "y", "do", "dz", "d_w_n"]
#: float32 against float32 the difference is the order of the sums; in
#: bfloat16 the ``jax.numpy`` form rounds the convolution's sum and the SiLU
#: on the way and the passes round once
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -5}


def _inputs(batch, seq, dims, taps, dtype):
    hk, hv, dk, dv = dims
    kw, vw = hk * dk, hv * dv
    keys = jax.random.split(jax.random.PRNGKey(seq + taps), 8)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    return dict(
        qkvz=normal(keys[0], batch, seq, 2 * kw + 2 * vw).astype(dtype),
        taps=0.5 * normal(keys[1], taps, 2 * kw + vw),
        cotangents=tuple(normal(key, batch, seq, w).astype(dtype)
                         for key, w in zip(keys[2:5], (kw, kw, vw))),
        o=normal(keys[5], batch, seq, vw).astype(dtype),
        w_n=1 + 0.3 * normal(keys[6], dv),
        dy=normal(keys[7], batch, seq, vw).astype(dtype))


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    """``(by the passes, by jax.numpy, tolerance)``: :data:`QUANTITIES`."""
    batch, seq, dims, taps, dtype, caps = CASES[request.param]
    hk, hv, dk, dv = dims
    through = 2 * hk * dk + hv * dv          # the q | k | v columns
    x = _inputs(batch, seq, dims, taps, dtype)

    mixed, mix_vjp = jax.vjp(lambda a, t: la.mix_rows(a, t, dims),
                             x["qkvz"], x["taps"])
    dx, d_taps = mix_vjp(x["cotangents"])
    y, gate_vjp = jax.vjp(
        lambda o, a, w: la.gate_rows(o, a[..., through:], w, hv, EPS),
        x["o"], x["qkvz"], x["w_n"])
    do, dz, d_w_n = gate_vjp(x["dy"])
    want = dict(zip(QUANTITIES, (*mixed, dx[..., :through], d_taps, y, do,
                                 dz[..., through:], d_w_n)))

    mixed = rows.mix(x["qkvz"], x["taps"], dims, l2_eps=la.L2_EPS,
                     interpret=True, caps=caps)
    y = rows.gate(x["o"], x["qkvz"], x["w_n"], dims, EPS, True, caps)
    do, buffer, d_w_n = rows.gate_bwd(x["dy"], x["o"], x["qkvz"], x["w_n"],
                                      dims, EPS, True, caps)
    dz = buffer[..., through:]
    filled, d_taps = rows.mix_bwd(*x["cotangents"], x["qkvz"], x["taps"],
                                  buffer, dims, l2_eps=la.L2_EPS,
                                  interpret=True, caps=caps)
    # the three calls wrote around the z columns, not over them
    np.testing.assert_array_equal(np.asarray(filled[..., through:]),
                                  np.asarray(dz))
    got = dict(zip(QUANTITIES, (*mixed, filled[..., :through], d_taps, y, do,
                                dz, d_w_n)))
    return got, want, TOLERANCE[dtype]


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_the_passes_are_the_jnp_form(case, quantity):
    got, want, tolerance = case
    got, want = (np.asarray(t[quantity], np.float32) for t in (got, want))
    assert got.shape == want.shape
    scale = np.abs(want).max()
    assert scale > 0, "a quantity that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=tolerance * scale, rtol=0)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_a_sequence_never_reads_its_neighbour(direction):
    """Batch row 1's results do not move when batch row 0's rows do: in
    front of a sequence the convolution sees zeros, behind it its transpose
    sees none of the next sequence's cotangent."""
    dims, caps = (1, 1, 128, 128), (128, None)
    x = _inputs(2, 256, dims, 4, jnp.float32)
    other = _inputs(2, 256, dims, 3, jnp.float32)    # another draw
    swap = lambda a, b: jnp.concatenate([b[:1], a[1:]], axis=0)
    if direction == "forward":
        run = lambda qkvz: rows.mix(qkvz, x["taps"], dims, l2_eps=la.L2_EPS,
                                    interpret=True, caps=caps)
        first = run(x["qkvz"])
        second = run(swap(x["qkvz"], other["qkvz"]))
    else:
        run = lambda cotangents: rows.mix_bwd(
            *cotangents, x["qkvz"], x["taps"], jnp.zeros_like(x["qkvz"]),
            dims, l2_eps=la.L2_EPS, interpret=True, caps=caps)[:1]
        first = run(x["cotangents"])
        second = run(tuple(swap(a, b) for a, b in zip(x["cotangents"],
                                                      other["cotangents"])))
    for a, b in zip(first, second):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
        assert float(jnp.abs(a[0] - b[0]).max()) > 1e-2      # row 0 did move


# ---------------------------------------------------------------------------
# what the grids cover, and the blocks they pick
# ---------------------------------------------------------------------------

CELL = (16, 32, 128, 128)       # qwen3-next-80b-a3b's linear layers


def test_the_passes_take_whole_tiles_on_a_tpu(monkeypatch):
    assert not rows.rows_supported(4096, CELL, 4)                  # the CPU
    monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    assert rows.rows_supported(4096, CELL, 4)
    assert rows.rows_supported(128, (2, 2, 128, 256), 2, jnp.float32)
    assert rows.rows_supported(128, CELL, 9)     # eight rows' reach: a tile
    assert not rows.rows_supported(128, CELL, 10)
    assert not rows.rows_supported(4096 + 64, CELL, 4)   # no whole row block
    # heads that are whole tiles in blocks of up to four, key and value
    # heads as many: Olmo-Hybrid's 30 heads of 96 / 192; not grouped heads
    # two to a tile, not eight to a tile
    assert rows.rows_supported(8192, (30, 30, 96, 192), 4)
    assert not rows.rows_supported(4096, (16, 32, 64, 128), 4)
    assert not rows.rows_supported(4096, (16, 32, 16, 128), 4)
    # 5 heads of 96: q | k together are no whole number of 384-lane blocks
    assert not rows.rows_supported(4096, (5, 5, 96, 192), 4)
    assert not rows.rows_supported(4096, (16, 32, 128, 192), 4)
    assert not rows.rows_supported(4096, (16, 24, 128, 128), 4)
    # v would start inside a 384-lane head: no lane-block index reaches it
    assert not rows.rows_supported(4096, (1, 1, 128, 384), 4)
    assert not rows.rows_supported(4096, CELL, 4, jnp.float16)


def test_uncovered_shapes_are_refused_by_name():
    x = _inputs(1, 96, (1, 1, 128, 128), 4, jnp.float32)
    scalars = jnp.zeros((1, 96, 1), jnp.float32)
    with pytest.raises(ValueError, match="gated_delta_rows covers.*seq 96"):
        rows.gated_delta_rows(x["qkvz"], x["taps"], scalars, scalars,
                              x["w_n"], (1, 1, 128, 128), l2_eps=la.L2_EPS,
                              norm_eps=EPS)


@pytest.mark.parametrize("width,first,head,tensors,caps,want", [
    (2048, 0, 128, 2, None, (512, 2048)),          # the cell's q
    (2048, 2048, 128, 3, None, (512, 2048)),       # its k, backward
    (4096, 4096, 128, 2, None, (512, 2048)),       # its v: the lane cap
    (4096, 8192, 128, 5, None, (512, 2048)),       # its z, gate_bwd
    (4096, 8192, 128, 5, (128, 256), (128, 256)),  # a test's caps
    (512, 256, 256, 2, None, (512, 256)),          # a part starting mid-way
    (384, 768, 128, 2, None, (512, 384)),          # the widest that divides
    (512, 0, 256, 2, (None, 128), (512, 256)),     # never under a head
])
def test_the_blocks_of_a_part(width, first, head, tensors, caps, want):
    assert rows._blocks(4096, width, first, head, 2, tensors, caps) == want


def test_a_ragged_sequence_takes_the_tallest_block_that_divides():
    assert rows._blocks(384, 128, 0, 128, 4, 2, None)[0] == 384
    assert rows._blocks(640, 128, 0, 128, 4, 2, None)[0] == 128


# ---------------------------------------------------------------------------
# the layer on that path
# ---------------------------------------------------------------------------

LEAVES = ["in_proj_qkvz/kernel", "in_proj_ba/kernel", "conv", "A_log",
          "dt_bias", "norm", "out_proj/kernel"]
D_MODEL, SEQ = 64, 256


def _config(**overrides):
    return TransformerConfig(**{**dict(
        vocab_size=61, d_model=D_MODEL, n_heads=2, d_head=32, n_layers=2,
        d_ff=32, max_seq_len=SEQ, dtype=jnp.float32, norm_eps=EPS,
        mixer_layers=(1, 0), linear_key_heads=1, linear_value_heads=2,
        linear_key_dim=128, linear_value_dim=128, linear_conv=4),
        **overrides})


def force_row_passes(patch):
    """The passes' gate open and every ``pallas_call`` interpreted: steered
    here, in the test, not by an option of the program."""
    real = rows.pl.pallas_call
    patch.setattr(rows, "_on_tpu", lambda: True)
    patch.setattr(rows.pl, "pallas_call",
                  lambda *a, **kw: real(*a, **{**kw, "interpret": True}))


@pytest.fixture(scope="module")
def both_layers():
    """One layer's output, its input's gradient and every parameter's, by
    the passes (with the ``gdn_*`` kernels between them) and by the
    ``jax.numpy`` form."""
    layer = la.GatedDeltaNet(_config())
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (2, SEQ, D_MODEL))
    weigh = jax.random.normal(keys[1], (2, SEQ, D_MODEL))
    params = layer.init(keys[2], x)["params"]
    params = {**params, "norm": params["norm"] + 0.2 * jax.random.normal(
        keys[3], params["norm"].shape)}

    def quantities():
        def loss(params, x):
            out = layer.apply({"params": params}, x)
            return jnp.sum(out * weigh), out

        (_, out), (d_params, d_x) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        leaves = {"/".join(str(k.key) for k in path): leaf for path, leaf
                  in jax.tree_util.tree_leaves_with_path(d_params)}
        return {"out": out, "d_x": d_x, **leaves}

    calls = []
    fallback = quantities()
    with pytest.MonkeyPatch.context() as patch:
        force_row_passes(patch)
        real = rows.gated_delta_rows
        patch.setattr(rows, "gated_delta_rows",
                      lambda *a, **kw: calls.append(1) or real(*a, **kw))
        forced = quantities()
    assert calls, "the forced layer never reached the passes"
    return forced, fallback


@pytest.mark.parametrize("quantity", ["out", "d_x", *LEAVES])
def test_the_layer_on_the_passes_is_the_layer(both_layers, quantity):
    forced, fallback = both_layers
    assert set(forced) == set(fallback) == {"out", "d_x", *LEAVES}
    got, want = forced[quantity], fallback[quantity]
    scale = float(jnp.abs(want).max())
    assert scale > 0
    # between the passes the forced layer runs the ``gdn_*`` kernels and the
    # other the same chunks in jax.numpy: the order of a chunk's sums
    np.testing.assert_allclose(got, want, atol=5e-5 * scale, rtol=0)


def test_the_gauge_counts_the_layers_on_the_passes(monkeypatch):
    model = TransformerLM(_config(n_layers=4, mixer_layers=(1, 1, 1, 0)))
    tokens = jnp.zeros((2, SEQ + 1), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens[:, :-1])["params"]
    # a fresh function a trace: ``eval_shape`` keeps the traces it made
    trace = lambda tokens: jax.eval_shape(
        lambda params, batch: lm_loss_fn(model)(params, batch), params,
        {"tokens": tokens})
    trace(tokens)
    assert counters.get("linattn/layers") == 3
    assert counters.get("linattn/row_kernel_layers") == 0         # the CPU
    monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    trace(tokens)
    assert counters.get("linattn/row_kernel_layers") == 3
    # a sequence no row block divides falls back, and the gauge says so
    trace(tokens[:, :SEQ - 55])
    assert counters.get("linattn/row_kernel_layers") == 0


def test_a_model_without_linear_layers_never_imports_the_passes():
    script = (
        "import sys, jax, jax.numpy as jnp\n"
        "from bagua_tpu.models.transformer import (\n"
        "    TransformerConfig, TransformerLM, lm_loss_fn)\n"
        "model = TransformerLM(TransformerConfig(\n"
        "    vocab_size=61, d_model=32, n_heads=2, n_layers=2, d_ff=32,\n"
        "    max_seq_len=16))\n"
        "tokens = jnp.zeros((2, 9), jnp.int32)\n"
        "params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])['params']\n"
        "jax.grad(lm_loss_fn(model))(params, {'tokens': tokens})\n"
        "loaded = [m for m in sys.modules if m.endswith(\n"
        "    ('gated_delta_rows', 'gated_delta', 'linear_attention'))]\n"
        "assert not loaded, loaded\n"
        "print('clean')\n")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=300, cwd=str(Path(__file__).resolve().parents[1]),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and out.stdout.strip().endswith("clean"), (
        out.stdout[-2000:] + out.stderr[-2000:])
