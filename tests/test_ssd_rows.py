"""The row passes of a Mamba-2 layer (``bagua_tpu/ops/ssd_rows.py``:
``ssd_mix`` / ``ssd_mix_bwd`` / ``ssd_gate`` / ``ssd_gate_bwd``) on the CPU,
every ``pallas_call`` interpreted, against the layer's ``jax.numpy`` form
(``models/state_space.py::conv_bias_silu`` / ``gated_group_norm``): the
values and every cotangent, over the shapes that cross what can go wrong —
two sequences a batch (a sequence's first rows see zeros, not the tail of
the one before), one row block and several (the convolution's reach in front
of a block and its transpose's behind it), one and several column blocks,
two to four taps, one group and eight, heads of half a lane tile — and the
whole layer forced onto that path beside the unforced one."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models import state_space as ss
from bagua_tpu.models.transformer import TransformerConfig
from bagua_tpu.ops import ssd_rows as rows

EPS = 1e-5
#: name -> (batch, seq, (H, P, G, N), taps, dtype, (rows, lanes) caps)
CASES = {
    "one-block": (2, 128, (2, 64, 1, 128), 4, jnp.float32, None),
    "three-row-blocks": (2, 384, (4, 64, 2, 128), 4, jnp.float32,
                         (128, 128)),
    # a block of 512 rows is walked in four chunks of 128
    "two-taps-four-chunks": (2, 512, (2, 128, 2, 128), 2, jnp.float32, None),
    "three-taps-one-group": (1, 256, (8, 64, 1, 128), 3, jnp.float32,
                             (128, None)),
    "eight-groups": (2, 256, (16, 64, 8, 128), 4, jnp.float32, (128, 256)),
    "bfloat16": (2, 256, (4, 64, 2, 128), 4, jnp.bfloat16, (128, 256)),
}
QUANTITIES = ["x", "B", "C", "du", "d_taps", "d_bias", "o", "dy", "dz",
              "d_w_n"]
#: float32 against float32 the difference is the order of the sums; in
#: bfloat16 both forms round once, at the store (a sum of squares taken in
#: another order moves the last bit of a value here and there)
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -7}


def _inputs(batch, seq, dims, taps, dtype):
    inner, maps = rows._widths(dims)
    keys = jax.random.split(jax.random.PRNGKey(seq + taps), 9)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    return dict(
        zxbc=normal(keys[0], batch, seq, 2 * inner + 2 * maps).astype(dtype),
        taps=0.5 * normal(keys[1], taps, inner + 2 * maps),
        bias=0.5 * normal(keys[2], inner + 2 * maps),
        cotangents=tuple(normal(key, batch, seq, w).astype(dtype)
                         for key, w in zip(keys[3:6], (inner, maps, maps))),
        y=normal(keys[6], batch, seq, inner).astype(dtype),
        w_n=1 + 0.3 * normal(keys[7], inner),
        do=normal(keys[8], batch, seq, inner).astype(dtype))


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    """``(by the passes, by jax.numpy, tolerance)``: :data:`QUANTITIES`."""
    batch, seq, dims, taps, dtype, caps = CASES[request.param]
    inner, maps = rows._widths(dims)
    x = _inputs(batch, seq, dims, taps, dtype)

    mixed, mix_vjp = jax.vjp(
        lambda a, t, c: ss.conv_bias_silu(a[..., inner:], t, c),
        x["zxbc"], x["taps"], x["bias"])
    du, d_taps, d_bias = mix_vjp(jnp.concatenate(x["cotangents"], axis=-1))
    o, gate_vjp = jax.vjp(
        lambda y, a, w: ss.gated_group_norm(y, a[..., :inner], w, dims[2],
                                            EPS),
        x["y"], x["zxbc"], x["w_n"])
    dy, dz, d_w_n = gate_vjp(x["do"])
    want = dict(zip(QUANTITIES, (
        mixed[..., :inner], mixed[..., inner:inner + maps],
        mixed[..., inner + maps:], du[..., inner:], d_taps, d_bias, o, dy,
        dz[..., :inner], d_w_n)))

    mixed = rows.mix(x["zxbc"], x["taps"], x["bias"], dims, interpret=True,
                     caps=caps)
    o = rows.gate(x["y"], x["zxbc"], x["w_n"], dims, EPS, True, caps)
    dy, buffer, d_w_n = rows.gate_bwd(x["do"], x["y"], x["zxbc"], x["w_n"],
                                      dims, EPS, True, caps)
    dz = buffer[..., :inner]
    filled, d_taps, d_bias = rows.mix_bwd(
        *x["cotangents"], x["zxbc"], x["taps"], x["bias"], buffer, dims,
        interpret=True, caps=caps)
    # the three calls wrote beside the z columns, not over them
    np.testing.assert_array_equal(np.asarray(filled[..., :inner]),
                                  np.asarray(dz))
    got = dict(zip(QUANTITIES, (*mixed, filled[..., inner:], d_taps, d_bias,
                                o, dy, dz, d_w_n)))
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
    dims, caps = (2, 64, 1, 128), (128, None)
    x = _inputs(2, 256, dims, 4, jnp.float32)
    other = _inputs(2, 256, dims, 3, jnp.float32)    # another draw
    swap = lambda a, b: jnp.concatenate([b[:1], a[1:]], axis=0)
    if direction == "forward":
        run = lambda zxbc: rows.mix(zxbc, x["taps"], x["bias"], dims,
                                    interpret=True, caps=caps)
        first = run(x["zxbc"])
        second = run(swap(x["zxbc"], other["zxbc"]))
    else:
        run = lambda cotangents: rows.mix_bwd(
            *cotangents, x["zxbc"], x["taps"], x["bias"],
            jnp.zeros_like(x["zxbc"]), dims, interpret=True, caps=caps)[:1]
        first = run(x["cotangents"])
        second = run(tuple(swap(a, b) for a, b in zip(x["cotangents"],
                                                      other["cotangents"])))
    for a, b in zip(first, second):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
        assert float(jnp.abs(a[0] - b[0]).max()) > 1e-2      # row 0 did move


# ---------------------------------------------------------------------------
# what the grids cover, and the blocks they pick
# ---------------------------------------------------------------------------

CELL = (64, 64, 8, 128)       # nemotron-3-nano-30b-a3b's Mamba-2 layers


def test_the_passes_take_whole_tiles_on_a_tpu(monkeypatch):
    assert not rows.rows_supported(8192, CELL, 4)                  # the CPU
    monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    assert rows.rows_supported(8192, CELL, 4)
    assert rows.rows_supported(128, (2, 64, 1, 128), 2, 128, jnp.float32)
    assert rows.rows_supported(128, CELL, 9)     # eight rows' reach: a tile
    assert not rows.rows_supported(128, CELL, 10)
    assert not rows.rows_supported(8000, CELL, 4)        # no whole row block
    assert not rows.rows_supported(8192 + 512, CELL, 4)  # the scan would pad
    assert rows.rows_supported(8192 + 512, CELL, 4, 64)  # not at this chunk
    # a group of 96 lanes, a state of half a tile, heads no group divides
    assert not rows.rows_supported(8192, (12, 64, 8, 128), 4)
    assert not rows.rows_supported(8192, (64, 64, 8, 64), 4)
    assert not rows.rows_supported(8192, (64, 64, 5, 128), 4)
    assert not rows.rows_supported(8192, (0, 0, 0, 0), 4)
    assert not rows.rows_supported(8192, CELL, 4, 128, jnp.float16)


def test_uncovered_shapes_are_refused_by_name():
    dims = (2, 64, 1, 128)
    x = _inputs(1, 96, dims, 4, jnp.float32)
    scalars = jnp.zeros((1, 96, 2), jnp.float32)
    with pytest.raises(ValueError, match="ssd_rows covers.*seq 96"):
        rows.ssd_rows(x["zxbc"], x["taps"], x["bias"], scalars,
                      -jnp.ones((2,)), jnp.ones((2,)), x["w_n"], dims,
                      norm_eps=EPS)


@pytest.mark.parametrize("width,first,tensors,caps,want", [
    (4096, 4096, 2, None, (512, 2048, 2, 0)),        # the cell's x
    (1024, 8192, 2, None, (512, 1024, 8, 4)),        # its B
    (1024, 9216, 3, None, (512, 1024, 9, 5)),        # its C, backward
    (4096, 4096, 3, (128, 256), (128, 256, 16, 0)),  # a test's caps
])
def test_the_blocks_of_a_part(width, first, tensors, caps, want):
    """``(rows, lanes, the part's lane block in the buffer, in the taps)``:
    the taps' columns start at the buffer's x."""
    assert rows._part_blocks(8192, width, first, 4096, 2, tensors,
                             caps) == want


def test_the_gates_blocks_hold_whole_groups():
    assert rows._gate_blocks(8192, CELL, 2, 5, None) == (512, 2048, 512)
    assert rows._gate_blocks(8192, CELL, 2, 3, (128, 128)) == (128, 512, 512)
    # a group's lanes times a chunk's rows: sixteen registers a value
    assert rows._gate_chunk(512, 512) == 32
    assert rows._gate_chunk(128, 384) == 128
    assert rows._gate_chunk(4096, 512) == 16       # a bfloat16 tile at least
    assert rows._gate_chunk(384, 256) == 32


# ---------------------------------------------------------------------------
# the layer on that path
# ---------------------------------------------------------------------------

LEAVES = ["in_proj", "conv", "conv_bias", "A_log", "dt_bias", "D", "norm",
          "out_proj/kernel"]
D_MODEL, SEQ = 64, 256


def _config(**overrides):
    return TransformerConfig(**{**dict(
        vocab_size=61, d_model=D_MODEL, n_heads=2, d_head=32, n_layers=2,
        d_ff=32, max_seq_len=SEQ, dtype=jnp.float32, norm_eps=EPS,
        layer_kinds=("ssm", "attn"), ssm_heads=4, ssm_head_dim=64,
        ssm_groups=2, ssm_state=128, ssm_conv=4, ssm_chunk=64),
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
    the passes (with the ``ssd_*`` kernels between them) and by the
    ``jax.numpy`` form."""
    layer = ss.Mamba2(_config())
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (2, SEQ, D_MODEL))
    weigh = jax.random.normal(keys[1], (2, SEQ, D_MODEL))
    params = layer.init(keys[2], x)["params"]
    params = {**params, "dt_bias": params["dt_bias"] + 4.0, **{
        name: params[name] + 0.2 * jax.random.normal(key, params[name].shape)
        for name, key in zip(("norm", "D", "conv_bias"), keys[3:])}}

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
        real = rows.ssd_rows
        patch.setattr(rows, "ssd_rows",
                      lambda *a, **kw: calls.append(1) or real(*a, **kw))
        forced = quantities()
    assert calls, "the forced layer never reached the passes"
    return forced, fallback


@pytest.mark.parametrize("quantity", ["out", "d_x", *LEAVES])
def test_the_layer_on_the_passes_is_the_layer(both_layers, quantity):
    """Every cotangent of the layer's middle — the buffer's (``in_proj``,
    ``d_x``), the taps', the bias's, ``dt_bias``, ``A_log``, ``D``, the
    norm's — and the output."""
    forced, fallback = both_layers
    assert set(forced) == set(fallback) == {"out", "d_x", *LEAVES}
    got, want = forced[quantity], fallback[quantity]
    scale = float(jnp.abs(want).max())
    assert scale > 0
    # between the passes the forced layer runs the ``ssd_*`` kernels and the
    # other the same chunks in jax.numpy: the order of a chunk's sums
    np.testing.assert_allclose(got, want, atol=5e-5 * scale, rtol=0)


def test_under_remat_the_replay_runs_no_scan_and_no_mix(monkeypatch):
    """``dots_no_batch`` keeps the projection's buffer, the kernel's ``y``
    and the states: what the backward pass runs beside the transposes is
    ``ssd_gate`` again (the out-projection's operand) and ``ssd_mix`` once
    (x, B and C made again, a call a part) — ``ssd_fwd`` once in all."""
    from bagua_tpu.utils import remat_wrap

    layer = remat_wrap(ss.Mamba2, "dots_no_batch")(_config())
    x = jnp.zeros((1, SEQ, D_MODEL))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def loss(params, x):
        return jnp.sum(layer.apply(params, x))

    monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    calls = {name: len(re.findall(rf"name={name}\b", text))
             for name in ("ssd_fwd", "ssd_bwd", "ssd_mix", "ssd_mix_bwd",
                          "ssd_gate", "ssd_gate_bwd")}
    assert calls == {"ssd_fwd": 1, "ssd_bwd": 1, "ssd_mix": 6,
                     "ssd_mix_bwd": 3, "ssd_gate": 2, "ssd_gate_bwd": 1}


def _jaxpr_of(cfg, seq):
    layer = ss.Mamba2(cfg)
    x = jnp.zeros((1, seq, D_MODEL), cfg.dtype)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    # a fresh function a trace: jax keeps the traces it made
    return str(jax.make_jaxpr(lambda p, x: layer.apply(p, x))(params, x))


@pytest.mark.parametrize("why,seq,overrides", [
    ("off the TPU", SEQ, {}),
    ("a sequence no row block divides", SEQ - 56, {}),
    ("a group of 96 lanes", SEQ, dict(ssm_heads=6, ssm_groups=2,
                                      ssm_head_dim=32)),
])
def test_outside_the_predicate_the_layer_traces_todays_jaxpr(
        monkeypatch, why, seq, overrides):
    """Where the passes do not run the layer is ``conv_bias_silu``,
    ``ssd_scan`` and ``gated_group_norm`` as before: the same jaxpr as with
    the predicate taken away, and no ``pallas_call`` named ``ssd_mix`` or
    ``ssd_gate`` in it."""
    cfg = _config(**overrides)
    if why != "off the TPU":
        monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    assert not ss.rows_by_kernel(cfg, seq)
    got = _jaxpr_of(cfg, seq)
    monkeypatch.setattr(ss, "rows_by_kernel", lambda cfg, seq: False)
    assert got == _jaxpr_of(cfg, seq)
    assert "ssd_mix" not in got and "ssd_gate" not in got
    assert "conv_bias_silu" in got
