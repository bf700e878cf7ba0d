"""The three flash kernels compiled at the benchmark's real shapes for a
described (not attached) v5e: what interpret mode cannot show — that Mosaic
takes the ``BlockSpec``s over ``[batch, seq, heads * head_dim]``, the
two-heads-a-block bodies at head_dim 64, the whole-sequence operands'
VMEM, grouped key / value heads with the dK/dV kernel's float32 scratch and
the window's loop bounds (SmallThinker's 28 heads over 4 at 8,192 tokens),
the grouped matmuls at one rank's share of the rows, and the d_lhs product
reading the expert matrices as they are stored (contracting their last axis
inside ``gmm_fwd``).  Nothing runs; no time comes out of this.  The topology
is described inside a fixture, never at import (one process at a time may
load libtpu)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bagua_tpu.ops.flash_attention import flash_attention_with_lse
from bagua_tpu.ops.gmm import _gmm_padded, gmm_padded, padded_layout


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
    (1, 8192, 8, 64),     # a long-context shape class (s = 8192)
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


@pytest.mark.parametrize("window,prefix", [(None, "flash_"),
                                           (4096, "flash_win_")])
def test_grouped_and_windowed_kernels_compile_for_the_described_v5e(
        window, prefix, one_chip):
    """smallthinker-21b-a3b.pretrain8192-dp1: 28 query heads over 4 key /
    value heads of 128 at 8,192 tokens; K / V enter and dK / dV leave as
    [b, s, 4 * 128] — never repeated to the 28 query heads."""
    b, s, h, kv_h, d = 1, 8192, 28, 4, 128
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kv_h, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True, window=window)
        return o.sum() + lse.sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = {re.search(r"%(\w+?)\.\d+ = ", line).group(1): line
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    assert sorted(calls) == sorted(
        prefix + kernel for kernel in ("fwd", "bwd_dq", "bwd_dkv"))
    for name, line in calls.items():
        operands = re.search(
            r"operand_layout_constraints=\{([^{}]*(?:\{[^{}]*\}[^{}]*)*)\}",
            line).group(1)
        # q first, then k and v at the key / value heads' width
        assert re.findall(r"bf16\[([\d,]+)\]", operands)[:3] == [
            f"{b},{s},{h * d}", f"{b},{s},{kv_h * d}", f"{b},{s},{kv_h * d}"]
        first = re.search(r"= \(?bf16\[([\d,]+)\]", line).group(1)
        assert first == (f"{b},{s},{kv_h * d}" if name.endswith("dkv")
                         else f"{b},{s},{h * d}"), line


def test_the_grouped_matmuls_compile_at_a_ranks_share(one_chip):
    """The SmallThinker cell's expert layer: 16 held experts of [2560, 768]
    over a layout made for all 49,152 routed pairs (400 row blocks, about
    a quarter of them holding rows), forward and both gradients."""
    rows, groups, d, f = 8192 * 6, 16, 2560, 768
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    layout = jax.eval_shape(
        lambda sizes: padded_layout(sizes, rows), spec((groups,), jnp.int32))
    padded = layout.src.shape[0]
    assert padded == 400 * 128

    def loss(x_p, w, layout):
        return jnp.square(gmm_padded(x_p, w, layout).astype(jnp.float32)).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        spec((padded, d), jnp.bfloat16), spec((groups, d, f), jnp.bfloat16),
        jax.tree.map(lambda x: spec(x.shape, x.dtype), layout),
    ).compile().as_text()
    names = [re.search(r"(gmm_\w+?)\)*/pallas_call", line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(names) == ["gmm_bwd_drhs", "gmm_fwd", "gmm_fwd"]


@pytest.mark.parametrize("rows,groups,d,f", [
    (73728, 64, 2048, 1024),    # olmoe-1b-7b: d_lhs of the up / gate products
    (73728, 64, 1024, 2048),    # ... and of the down product
    (51200, 16, 2560, 768),     # smallthinker-21b-a3b, one rank's share
    (51200, 16, 768, 2560),
])
def test_d_lhs_compiles_on_the_stored_matrices(rows, groups, d, f, one_chip):
    """``gmm_fwd`` in its transposed-operand form, cotangent [R, f] times
    the stack [G, d, f] as stored -> [R, d]: Mosaic takes the product that
    contracts both last axes with the whole matrix resident, and the
    compiled text holds the kernel alone: no ``transpose`` or ``copy`` of
    the stack in front of it."""
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    text = jax.jit(
        lambda g_p, w, gid: _gmm_padded(g_p, w, gid, 128, None, False,
                                        transpose_rhs=True)
    ).lower(spec((rows, f), jnp.bfloat16), spec((groups, d, f), jnp.bfloat16),
            spec((rows // 128,), jnp.int32)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "gmm_fwd" in calls[0]
    assert re.search(rf"= bf16\[{rows},{d}\]", calls[0])
    stack = rf"bf16\[{groups},\d+,\d+\]"
    assert not [line for line in text.splitlines()
                if re.search(rf"= {stack}\S* (copy|transpose)\(", line)]


def test_the_weighed_token_tail_writes_no_float32_logits(one_chip):
    """A looped model's head and per-token loss tail at Ouro's shape
    (4,096 tokens x 49,152 rows), the cross-entropy weighed token by token
    before the mean as ``looped_lm_loss_fn`` weighs it, forward and
    backward: the compiled program holds the bf16 logits and no float32
    array of their size, no gather and no scatter."""
    from bagua_tpu.models.transformer import token_loss_tail

    b, s, d, vocab = 1, 4096, 2048, 49152
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                       sharding=one_chip)

    def loss(x, head, targets, weights):
        logits = jnp.dot(x, head.astype(jnp.bfloat16)).astype(jnp.float32)
        return jnp.mean(weights * token_loss_tail(logits, targets))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        shaped((b, s, d), jnp.bfloat16), shaped((d, vocab), jnp.float32),
        shaped((b, s), jnp.int32), shaped((b, s), jnp.float32),
    ).compile().as_text()
    entry = text[text.index("ENTRY "):]
    results = re.findall(r"= \(?(\w+)\[([\d,]+)\]", entry)
    logits_sized = {dtype for dtype, shape in results
                    if shape.replace(",", "").endswith(f"{s}{vocab}")}
    assert logits_sized == {"bf16"}, logits_sized
    assert not re.search(r" (gather|scatter)\(", text)


def _unfused(text):
    """``(name, shape, opcode, op_name)`` of the instructions outside every
    fused computation: what the chip runs as instructions of their own."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    computation, out = None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            computation = head.group(1)
        found = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if found and computation not in fused:
            op_name = re.search(r'op_name="([^"]*)"', line)
            out.append((*found.groups(), op_name.group(1) if op_name else "",
                        line))
    return out


def _compiled_layer(one_chip, monkeypatch, s, d_model, **cfg):
    """The optimized HLO text of one ``Attention`` layer's loss gradient
    (parameters and input) at ``[1, s, d_model]`` in bfloat16, compiled for
    the described chip, and the layer's abstract parameters."""
    import importlib

    from bagua_tpu.models.transformer import Attention, TransformerConfig

    # flash_supported asks jax.default_backend(), which is still the CPU
    # here: steered in the test, not by an option of the program
    flash = importlib.import_module("bagua_tpu.ops.flash_attention")
    monkeypatch.setattr(flash.jax, "default_backend", lambda: "tpu")
    layer = Attention(TransformerConfig(
        vocab_size=128, d_model=d_model, d_head=128, n_layers=1, d_ff=128,
        max_seq_len=s, rope_theta=1e6, **cfg))
    shaped = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                            sharding=one_chip)
    x = jax.ShapeDtypeStruct((1, s, d_model), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(shaped, jax.eval_shape(
        layer.init, jax.random.PRNGKey(0), x))

    def loss(params, x):
        return jnp.sum(layer.apply(params, x).astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text(), params


@pytest.mark.parametrize("s, h, kv_h, d_model", [
    (4096, 16, 16, 2048),   # ouro-2.6b.pretrain4096-b1-dp1 (and OLMoE's)
    (8192, 28, 4, 2560),    # smallthinker-21b-a3b.pretrain8192-dp1: grouped
])
def test_a_rotary_layer_rotates_q_and_k_as_the_projections_write_them(
        s, h, kv_h, d_model, one_chip, monkeypatch):
    """One rotary ``Attention`` layer, forward and backward: the rotation
    is four ``rope`` calls (q, k; each way) on the row-major ``[b, s, h *
    d]`` between the projections and the flash kernels, and the program
    holds no instruction of its own that re-lays q or k around them — the
    slice / negate / ``concatenate`` form left a dozen bare float32
    ``copy`` / ``reshape`` / ``broadcast`` a layer and made the projections
    write sequence-minor (PERF.md §6, PR 44)."""
    text, _ = _compiled_layer(one_chip, monkeypatch, s, d_model, n_heads=h,
                              n_kv_heads=kv_h)
    ops = _unfused(text)
    by_name = {name: shape for name, shape, *_ in ops}
    calls = [(shape, line) for _, shape, opcode, op_name, line in ops
             if opcode == "custom-call" and op_name.endswith(
                 "jit(_rotate)/rope/pallas_call")]
    assert len(calls) == 4
    row_major = lambda shape: re.search(r"\{([\d,]+)", shape).group(1) in (
        "2,1,0", "1,0")
    for shape, line in calls:
        lanes = int(re.match(r"bf16\[1,\d+,(\d+)\]", shape).group(1))
        assert lanes in (h * 128, kv_h * 128), shape
        assert row_major(shape), line
        # what the call reads: a projection's (or the flash backward's)
        # result as it was written, through bitcasts alone
        source = re.search(r"custom-call\(%([\w.\-]+)", line).group(1)
        assert row_major(by_name[source]), (source, by_name[source])
    # nothing of its own re-lays an activation (a tensor with the sequence
    # among its axes) around the rotation: what is left bare belongs to the
    # flash kernels' statistic rows
    bare = [(opcode, shape, op_name) for _, shape, opcode, op_name, _ in ops
            if opcode in ("copy", "reshape", "broadcast", "transpose")
            and str(s) in re.match(r"\w+\[([\d,]*)\]", shape).group(1).split(",")
            and not re.search(r"jit\(_(fwd|bwd)\)", op_name)]
    assert not bare, bare


def test_a_head_normed_layer_keeps_q_and_k_on_the_flat_rows(one_chip,
                                                            monkeypatch):
    """SDAR's ``Attention`` layer at its cell's shapes (8,192 rows of
    ``[x ; x~]``, 32 query heads over 4 of 128, ``qk_norm="head"``), forward
    and backward: norm and rotation are four ``rope`` calls — q and k, each
    way, ``ops.rope.norm_rope`` — on the row-major ``[2, 4096, h * d]``
    between the projections and the ``flash_bd_*`` kernels, and no float32
    value with a ``[heads, 128]`` tail exists between them.  As XLA ops the
    norm wrote, broadcast and re-tiled q and k in float32, 1.7 GB a layer
    and step (PERF.md §6, PR 51)."""
    s, h, kv_h = 8192, 32, 4
    text, params = _compiled_layer(
        one_chip, monkeypatch, s, 2048, n_heads=h, n_kv_heads=kv_h,
        qk_norm="head", attention="block_diffusion", diffusion_block=4)
    assert set(params["params"]) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    ops = _unfused(text)
    # the backward calls return (d_x, the scale's partial sums): a tuple
    calls = re.findall(
        r"= \(?bf16\[2,4096,(\d+)\]\{([\d,]+)[^=]* custom-call\(.*"
        r"Attention\)\)?/(\w+/jit\(\w+\))/rope/pallas_call", text)
    assert sorted(name for _, _, name in calls) == sorted(
        f"{norm}/jit({fn})" for norm in ("q_norm", "k_norm")
        for fn in ("_norm_rotate", "_norm_unrotate")), calls
    for lanes, layout, _ in calls:
        assert int(lanes) in (h * 128, kv_h * 128) and layout == "2,1,0"
    in_float32 = [(opcode, shape, op_name)
                  for _, shape, opcode, op_name, _ in ops
                  if re.match(r"\(?f32\[[\d,]*,(32|4),128\]", shape)
                  and "/attn/" in op_name + "/"
                  and not re.search(r"/(q|k|v|o)/dot_general", op_name)]
    assert not in_float32, in_float32
    # what is left bare around the passes is bfloat16: the re-layouts that
    # ``Attention``'s barrier over q, k and v costs (PERF.md §6, PR 51)
    bare = [(opcode, shape, op_name) for _, shape, opcode, op_name, _ in ops
            if opcode in ("copy", "reshape", "broadcast", "transpose")
            and re.match(r"\(?\w+\[(1,8192|8192|2,4096),", shape)
            and not re.search(r"jit\(_bd_(fwd|bwd)\)", op_name)]
    assert all(shape.startswith("bf16[") for _, shape, _ in bare), bare


@pytest.mark.parametrize("tokens,rows,k,d", [
    (8192, 73728, 8, 2048),     # olmoe-1b-7b.pretrain4096-dp1
    (8192, 51200, 6, 2560),     # smallthinker-21b-a3b.pretrain8192-dp1
    (8192, 67584, 8, 2048),     # sdar-30b-a3b.blockdiff4096-b1-dp1
])
def test_the_row_kernels_compile_at_the_cells_shapes(tokens, rows, k, d,
                                                     one_chip):
    """The three row movements of a cell's expert layer that run
    ``ops/moe_rows.py``: the dispatch's transpose and the combine
    (``rows_sum``: the whole float32 accumulator resident, 64 / 80 MiB) and
    the combine's transpose (``rows_in`` with the gates and the products:
    the whole cotangent resident) — dynamic sublane indices into the 32-bit
    view of bf16 rows, index vectors in SMEM by the block, more VMEM than
    Mosaic's default scope."""
    from bagua_tpu.ops.moe_rows import rows_in, rows_sum

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    y, g = spec((rows, d), jnp.bfloat16), spec((tokens, d), jnp.bfloat16)
    gates, reader = spec((tokens, k), jnp.float32), spec((rows,), jnp.int32)
    for name, call, args in [
        ("moe_rows_sum", lambda y, reader: rows_sum(y, reader // k, tokens),
         (y, reader)),
        ("moe_rows_sum", lambda y, gates, reader: rows_sum(
            y, reader // k, tokens, (gates, reader)), (y, gates, reader)),
        ("moe_rows_in", lambda g, y, gates, reader: rows_in(
            g, reader // k, (gates, reader), dot=y), (g, y, gates, reader)),
    ]:
        text = jax.jit(call).lower(*args).compile().as_text()
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert len(calls) == 1 and name in calls[0]


def test_the_flash_kernels_compile_at_head_dim_256_under_grouped_heads(
        one_chip):
    """qwen3-next-80b-a3b.pretrain4096-b2-dp1's full-attention layer: 16
    query heads over 2 key / value heads of 256 at 4,096 rows — one head a
    256-lane block, the first cell over 128 lanes a head."""
    from bagua_tpu.ops.flash_attention import (
        heads_per_block, kv_grouping_supported,
    )

    b, s, h, kv_h, d = 2, 4096, 16, 2, 256
    assert heads_per_block(h, d) == 1 and kv_grouping_supported(h, kv_h, d)
    # flash_supported's budget: two whole-sequence operands of a head,
    # double-buffered, in 12 MiB: 6,144 rows at 256 lanes
    assert 4 * s * d * 2 <= 12 * 2 ** 20 < 4 * 8192 * d * 2
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kv_h, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True)
        return o.sum() + lse.sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = {re.search(r"%(\w+?)\.\d+ = ", line).group(1): line
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    assert sorted(calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    for name, line in calls.items():
        first = re.search(r"= \(?bf16\[([\d,]+)\]", line).group(1)
        assert first == (f"{b},{s},{kv_h * d}" if name.endswith("dkv")
                         else f"{b},{s},{h * d}"), line


@pytest.mark.parametrize("chunk", [64, 128])
def test_the_gated_delta_kernels_compile_at_the_cells_shape(chunk, one_chip):
    """qwen3-next-80b-a3b.pretrain4096-b2-dp1's linear-attention layers: 16
    key and 32 value heads of 128 over 2 x 4,096 positions; q / k / v enter
    and o / dq / dk / dv leave as [b, T, heads * 128], the per-chunk states
    are kept once in bfloat16."""
    from bagua_tpu.ops.gated_delta import gated_delta_rule

    b, seq, hk, hv, d = 2, 4096, 16, 32, 128
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    args = (shape(b, seq, hk, d), shape(b, seq, hk, d), shape(b, seq, hv, d),
            shape(b, seq, hv, dtype=jnp.float32),
            shape(b, seq, hv, dtype=jnp.float32))

    def loss(q, k, v, g, beta):
        return gated_delta_rule(q, k, v, g, beta, chunk=chunk,
                                force=True).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    calls = {re.search(r"%(\w+?)\.\d+ = ", line).group(1): line
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    assert sorted(calls) == ["gdn_bwd", "gdn_fwd"]
    first = lambda line: re.search(r"= \(?bf16\[([\d,]+)\]", line).group(1)
    assert first(calls["gdn_fwd"]) == f"{b},{seq},{hv * d}"      # o
    assert first(calls["gdn_bwd"]) == f"{b},{seq},{hk * d}"      # dq
    # the states the backward call reads: one [d_k, d_v] a chunk and head
    assert f"bf16[{b},{hv},{seq // chunk},{d},{d}]" in calls["gdn_bwd"]


def test_a_linear_layer_runs_its_rows_as_passes_on_the_projections_buffer(
        one_chip, monkeypatch):
    """qwen3-next-80b-a3b's ``GatedDeltaNet`` layer at its cell's shape,
    forward and backward: between ``in_proj_qkvz`` and ``out_proj`` the rows
    are the passes of ``ops/gated_delta_rows.py`` around the ``gdn_*``
    kernels — one ``gdn_mix`` / ``gdn_mix_bwd`` call a part (q, k, v), one
    ``gdn_gate`` / ``gdn_gate_bwd`` — and the ``[2, 4096, 12288]`` buffer
    and its cotangent are read and written where they lie: no slice, pad,
    concatenate, add or copy of an array that wide (as XLA ops the buffer
    was sliced into q / k / v / z by copies and its cotangent padded back
    together: PERF.md §6, PR 53)."""
    import importlib

    from bagua_tpu.models.linear_attention import GatedDeltaNet
    from bagua_tpu.models.transformer import TransformerConfig

    rows = importlib.import_module("bagua_tpu.ops.gated_delta_rows")
    monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    b, s, d_model, hk, hv, d = 2, 4096, 2048, 16, 32, 128
    layer = GatedDeltaNet(TransformerConfig(
        vocab_size=128, d_model=d_model, n_heads=16, d_head=128, n_layers=1,
        d_ff=128, max_seq_len=s, mixer_layers=(1,), linear_key_heads=hk,
        linear_value_heads=hv, linear_key_dim=d, linear_value_dim=d,
        linear_conv=4))
    shaped = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                            sharding=one_chip)
    x = jax.ShapeDtypeStruct((b, s, d_model), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(shaped, jax.eval_shape(
        layer.init, jax.random.PRNGKey(0), x))

    def loss(params, x):
        return jnp.sum(layer.apply(params, x).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls = [re.search(r"/(\w+)/pallas_call", line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(calls) == sorted(
        ["gdn_fwd", "gdn_bwd", "gdn_gate", "gdn_gate_bwd"]
        + 3 * ["gdn_mix", "gdn_mix_bwd"]), calls
    wide = f"[{b},{s},{2 * hk * d + 2 * hv * d}]"
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.search(r" = \(?\w+" + re.escape(wide)
                          + r"[^=]* (slice|dynamic-slice|pad|concatenate|add"
                          r"|copy)\(", line)]
    assert not moved, moved
    # q, k and v reach the ``gdn_*`` kernels as the passes wrote them (the
    # slices left are of the float32 ``[b, s, 2 hv]`` gates)
    sliced = [line.strip()[:200] for line in text.splitlines()
              if re.search(r" = bf16\[\d+,\d+,\d+\][^=]* slice\(", line)]
    assert not sliced, sliced


@pytest.mark.parametrize("seq", [8192, 8000])
def test_the_state_space_kernels_compile_at_the_cells_shape(seq, one_chip):
    """nemotron-3-nano-30b-a3b.pretrain8192-b1-dp1's Mamba-2 layers: 64
    heads of 64 (two a lane tile) over 8 groups and a state of 128, chunks
    of 128 over 8,192 positions (and a ragged length, padded to whole blocks
    of chunks); x and y as [b, T, 4096], B and C as [b, T, 1024], the
    per-chunk states kept once in bfloat16, a group's eight heads a grid
    step."""
    from bagua_tpu.ops.ssd import ssd_scan

    b, h, p, g, n, chunk = 1, 64, 64, 8, 128, 128
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    args = (shape(b, seq, h, p), shape(b, seq, h, dtype=jnp.float32),
            shape(h, dtype=jnp.float32), shape(b, seq, g, n),
            shape(b, seq, g, n), shape(h, dtype=jnp.float32))

    def loss(x, dt, a, bm, cm, d):
        return ssd_scan(x, dt, a, bm, cm, d, chunk=chunk,
                        force=True).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *args).compile().as_text()
    calls = {re.search(r"%(\w+?)\.\d+ = ", line).group(1): line
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    assert sorted(calls) == ["ssd_bwd", "ssd_fwd"]
    padded = -(-seq // (8 * chunk)) * 8 * chunk
    first = lambda line: re.search(r"= \(?bf16\[([\d,]+)\]", line).group(1)
    assert first(calls["ssd_fwd"]) == f"{b},{padded},{h * p}"      # y
    assert first(calls["ssd_bwd"]) == f"{b},{padded},{h * p}"      # dx
    # the states the backward call reads: one [8 x 64, 128] a chunk and group
    assert f"bf16[{b},{g},{padded // chunk},{h // g * p},{n}]" in calls[
        "ssd_bwd"]


@pytest.mark.parametrize("remat", [None, "dots_no_batch"])
def test_a_state_space_layer_runs_its_rows_as_passes_on_the_projections_buffer(
        remat, one_chip, monkeypatch):
    """nemotron-3-nano-30b-a3b's ``Mamba2`` layer at its cell's shape,
    forward and backward (and under the cell's remat policy): between the
    in-projection and ``out_proj`` the rows are the passes of
    ``ops/ssd_rows.py`` around the ``ssd_*`` kernels — one ``ssd_mix`` call
    a part (x, B, C) forward and again where the backward pass reads them,
    one ``ssd_mix_bwd`` a part, ``ssd_gate`` (again in the replay: it makes
    the out-projection's operand) and ``ssd_gate_bwd`` — and the ``[1,
    8192, 10240]`` buffer and its cotangent are read and written where they
    lie: no slice, pad, concatenate, add or copy of an array that wide or as
    wide as its x | B | C part (as XLA ops: the convolution's four shifted
    float32 slices a direction, the three slices for the kernels and the
    concatenate and pads that put the cotangents back, PERF.md §6, PR 55).
    The ``ssd_*`` calls keep the operands ``perfbench/kernel_costs_ssd.py``
    recognises them by: rank-3 x, B, C of 4096 / 1024 / 1024 lanes over the
    same rows, then the rank-4 scalars."""
    import importlib

    from bagua_tpu.models.state_space import Mamba2
    from bagua_tpu.models.transformer import TransformerConfig
    from bagua_tpu.utils import remat_wrap

    rows = importlib.import_module("bagua_tpu.ops.ssd_rows")
    monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    b, s, d_model, h, p, g, n = 1, 8192, 2688, 64, 64, 8, 128
    layer = (remat_wrap(Mamba2, remat) if remat else Mamba2)(
        TransformerConfig(
            vocab_size=128, d_model=d_model, n_heads=32, d_head=128,
            n_layers=1, d_ff=128, max_seq_len=s, layer_kinds=("ssm",),
            ssm_heads=h, ssm_head_dim=p, ssm_groups=g, ssm_state=n,
            ssm_conv=4, ssm_chunk=128))
    shaped = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                            sharding=one_chip)
    x = jax.ShapeDtypeStruct((b, s, d_model), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(shaped, jax.eval_shape(
        layer.init, jax.random.PRNGKey(0), x))

    def loss(params, x):
        return jnp.sum(layer.apply(params, x).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    mosaic = [line for line in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line]
    calls = [re.search(r"/(\w+)/pallas_call", line).group(1)
             for line in mosaic]
    assert sorted(calls) == sorted(
        ["ssd_fwd", "ssd_bwd", "ssd_gate_bwd"] + 6 * ["ssd_mix"]
        + 3 * ["ssd_mix_bwd"] + (2 if remat else 1) * ["ssd_gate"]), calls
    inner, maps = h * p, g * n
    for wide in (f"[{b},{s},{2 * inner + 2 * maps}]",
                 f"[{b},{s},{inner + 2 * maps}]"):
        moved = [line.strip()[:200] for line in text.splitlines()
                 if re.search(r" = \(?\w+" + re.escape(wide)
                              + r"[^=]* (slice|dynamic-slice|pad|concatenate"
                              r"|add|copy)\(", line)]
        assert not moved, moved
    # x, B and C reach the ``ssd_*`` kernels as the passes wrote them
    sliced = [line.strip()[:200] for line in text.splitlines()
              if re.search(r" = bf16\[\d+,\d+,\d+\][^=]* slice\(", line)]
    assert not sliced, sliced
    for line, name in zip(mosaic, calls):
        if name not in ("ssd_fwd", "ssd_bwd"):
            continue
        operands = re.findall(r"\w+\[([\d,]*)\]\{", line.split(
            "operand_layout_constraints={")[1].split("frontend_attributes")[0])
        assert operands[:4] == [
            f"{b},{s},{inner}", f"{b},{s},{maps}", f"{b},{s},{maps}",
            f"{b},{h},{s // 128},128"], (name, operands)
