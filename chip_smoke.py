"""Chip smoke: the main training path, once, on the real device.

    python chip_smoke.py                # on a TPU host; exit 0 + JSON pass line
    python chip_smoke.py --cpu-dryrun   # rehearsal: tiny widths, interpret-mode
                                        # kernels, 4 virtual CPU devices

ONE process drives every visible chip through the entry points a user calls
(``bagua_tpu.init_process_group`` -> ``BaguaTrainer.init`` / ``shard_batch`` /
``train_step``).  Legs, in order — there is no ``try/except`` around any of
them, the first failing assert is the exit:

  kernels    every ``pallas_call`` entry point compiled non-interpret at a
             shape the framework uses, compared with its jnp reference
  leg A      BERT-Large (seq 384, batch 8/chip, adamw) under the default
             family (gradient allreduce): finite, decreasing loss; state and
             batch placement over every chip of the mesh
  leg B      the same model/batch under ByteGrad (the 8-bit relaxation)
  4-chip     (>= 4 chips) dp equivalence 1 vs 4 chips, two-tier hierarchical
             allreduce and staged ZeRO, the ppermute ring plain and with a
             codec, eager allreduce / ragged all-to-all against numpy

Everything printed before the last line is a *smoke observation* (versions,
device order, seconds, bytes) — not a benchmark result.  Without a TPU, or
on a device kind missing from the peak table, the script raises before any
leg runs.  ``--cpu-dryrun`` is an explicit rehearsal mode, never a fallback:
it prints ``DRYRUN`` and never the pass line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from importlib.metadata import version

import numpy as np

SEED = 22
#: the fraction of payload bytes allowed to differ by ONE level between the
#: Mosaic and the XLA lowering of the same quantizer (a value within an ulp
#: of a .5 rounding boundary may land on either side)
LEVEL_FLIP_BUDGET = 1e-3
#: relative tolerance for losses of a bf16-compute model whose reduction
#: order changed (batch split over chips, two-level vs flat sums)
BF16_LOSS_RTOL = 1e-2
STEPS_A, STEPS_B = 8, 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# persistent compile cache accounting
# ---------------------------------------------------------------------------


class CacheCounter:
    """Counts JAX persistent-compile-cache hits/misses (``jax.monitoring``
    events) so a second run on the same machine can show the full-width
    train steps came from the cache."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.hits, self.misses


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def sizes(dryrun: bool) -> dict:
    """Full width on the chip; tiny widths for the CPU rehearsal."""
    from bagua_tpu.models.transformer import TransformerConfig, bert_large_config

    if dryrun:
        tiny = dict(vocab_size=256, d_model=64, n_heads=2, d_ff=128,
                    max_seq_len=16)
        return dict(
            full=TransformerConfig(n_layers=2, **tiny),
            shallow=TransformerConfig(n_layers=1, **tiny),
            batch_per_chip=2,
            flash=dict(b=1, s=256, h=2, d=64),
            gmm=dict(rows=512, d=128, f=256, groups=4),
            embed=dict(vocab=640, tokens=300, d=128),
            rope=dict(b=1, s=256, h=2, d=128),
            gdn_rows=dict(b=2, s=256, dims=(1, 2, 128, 128), taps=4),
            # (elements per chunk) one fused-path and one tiled-path size
            codec_chunks=(4096, 2048 * 128 + 4096),
            ring_chunk_bytes=256,
        )
    return dict(
        # the bert-large.squad384 cells' shape (perfbench/configs/bert-large.json)
        full=bert_large_config(max_seq_len=384),
        # full d_model/d_ff/heads/vocab, 2 layers: bounds the compile time
        # of the six 4-chip trainers
        shallow=bert_large_config(max_seq_len=384, n_layers=2),
        batch_per_chip=8,
        flash=dict(b=2, s=4096, h=16, d=64),          # bench_longctx
        gmm=dict(rows=8192, d=512, f=2048, groups=8),  # bench_moe_dropless
        embed=dict(vocab=30528, tokens=3072, d=1024),  # BERT-Large's table
        rope=dict(b=1, s=4096, h=16, d=128),           # Ouro's q and k
        # qwen3-next's linear layers: the projection's [2, 4096, 12288]
        gdn_rows=dict(b=2, s=4096, dims=(16, 32, 128, 128), taps=4),
        # 1 MiB f32 chunks (fused, the gate's floor) and 2.5 MiB (tiled: a
        # 10 MiB bucket over 4 ranks)
        codec_chunks=(1 << 18, 5 << 17),
        ring_chunk_bytes=5 << 18,  # 1.25 MiB: ring hops stay past the gate
    )


def seeded_tokens(batch: int, cfg, seed: int = SEED) -> np.ndarray:
    """Fixed batch of RANDOM tokens (an all-zero batch makes any loss curve
    meaningless)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(batch, cfg.max_seq_len + 1),
                        dtype=np.int32)


def uses_pallas(fn, *args) -> bool:
    import jax

    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def device_memory() -> list:
    """``bytes_in_use`` and the high-water mark per device (None where the
    runtime reports no memory stats, i.e. the CPU rehearsal).  The mark is
    ``obs.memory.peak_bytes``: ``peak_bytes_in_use`` alone does not see a
    running program's temporaries on a TPU."""
    import jax

    from bagua_tpu.obs.memory import peak_bytes

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append((stats.get("bytes_in_use"), peak_bytes(stats))
                   if stats else (None, None))
    return out


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def leg_kernels(sz: dict, dryrun: bool) -> None:
    """Every pallas_call entry point, compiled for the device (interpret
    mode only in the rehearsal), against its jnp reference."""
    import jax
    import jax.numpy as jnp

    from bagua_tpu.compression import pallas_codec as pc
    from bagua_tpu.compression.minmax_uint8 import (
        compress_chunked, decompress_chunked,
    )
    from bagua_tpu.ops.flash_attention import (
        flash_attention, flash_supported, reference_attention,
    )
    from bagua_tpu.ops.gmm import gmm, gmm_reference

    interp = dryrun
    key = jax.random.PRNGKey(SEED)

    # ---- flash attention: fwd + dK/dV + dQ via jax.grad -----------------
    f = sz["flash"]
    shape = (f["b"], f["s"], f["h"], f["d"])
    kq, kk, kv, kw = jax.random.split(key, 4)
    q, k, v = (jax.random.normal(kx, shape, jnp.bfloat16)
               for kx in (kq, kk, kv))
    w = jax.random.normal(kw, shape, jnp.float32)
    if not dryrun:
        assert flash_supported(f["s"], f["h"], f["d"]), (
            "flash_supported is False at the bench_longctx shape", f)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, interpret=interp, force=dryrun)
        return (o.astype(jnp.float32) * w).sum(), o

    def ref_loss(q, k, v):
        # one batch row at a time: the materialized [h, s, s] f32 scores of
        # the full batch are gigabytes at s=4096
        o = jax.lax.map(
            lambda t: reference_attention(t[0][None], t[1][None], t[2][None],
                                          jnp.bfloat16)[0],
            (q, k, v),
        )
        return (o.astype(jnp.float32) * w).sum(), o

    assert uses_pallas(lambda *a: flash_loss(*a)[0], q, k, v), \
        "flash_attention routed to the reference, not the kernel"
    t0 = time.perf_counter()
    (_, o_k), g_k = jax.jit(
        jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    (_, o_r), g_r = jax.jit(
        jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    jax.block_until_ready((o_k, g_k, o_r, g_r))

    def rel_err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-9))

    errs = [rel_err(o_k, o_r)] + [rel_err(a, b) for a, b in zip(g_k, g_r)]
    log(f"kernel flash fwd+dq+dk+dv {shape}: max rel err (o,dq,dk,dv) = "
        f"{[round(e, 4) for e in errs]}  ({time.perf_counter() - t0:.1f}s)")
    assert all(np.isfinite(e) and e < 3e-2 for e in errs), errs

    # ---- grouped matmul fwd + bwd ---------------------------------------
    g = sz["gmm"]
    kl, kr, kg = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    lhs = jax.random.normal(kl, (g["rows"], g["d"]), jnp.bfloat16)
    rhs = (jax.random.normal(kr, (g["groups"], g["d"], g["f"]), jnp.float32)
           / np.sqrt(g["d"])).astype(jnp.bfloat16)
    gw = jax.random.normal(kg, (g["rows"], g["f"]), jnp.float32)
    # ragged groups including an EMPTY one (its d_rhs block is never written)
    cuts = np.sort(np.random.default_rng(SEED).integers(
        0, g["rows"], size=g["groups"] - 2))
    group_sizes = jnp.asarray(
        np.diff(np.concatenate([[0], cuts, [g["rows"], g["rows"]]])),
        jnp.int32)
    assert int(group_sizes.sum()) == g["rows"] and int(group_sizes[-1]) == 0

    def gmm_loss(impl):
        def loss(lhs, rhs):
            return (impl(lhs, rhs).astype(jnp.float32) * gw).sum()
        return loss

    kern = gmm_loss(lambda a, b: gmm(a, b, group_sizes, interpret=interp,
                                     force=dryrun))
    ref = gmm_loss(lambda a, b: gmm_reference(a, b, group_sizes))
    assert uses_pallas(kern, lhs, rhs), \
        "gmm routed to the reference, not the kernel"
    t0 = time.perf_counter()
    lk, gk = jax.jit(jax.value_and_grad(kern, argnums=(0, 1)))(lhs, rhs)
    lr, gr = jax.jit(jax.value_and_grad(ref, argnums=(0, 1)))(lhs, rhs)
    errs = [rel_err(lk, lr)] + [rel_err(a, b) for a, b in zip(gk, gr)]
    log(f"kernel gmm fwd+dlhs+drhs rows={g['rows']} d={g['d']} f={g['f']} "
        f"G={g['groups']}: max rel err = {[round(e, 4) for e in errs]}  "
        f"({time.perf_counter() - t0:.1f}s)")
    assert all(np.isfinite(e) and e < 3e-2 for e in errs), errs

    # ---- token table gradient: sort + gather + segment product ----------
    from bagua_tpu.ops.embed_grad import embed_grad

    e = sz["embed"]
    ki, kr = jax.random.split(jax.random.PRNGKey(SEED + 2))
    # a tenth of the table: most tokens share their row with another
    ids = jax.random.randint(ki, (e["tokens"],), 0, e["vocab"] // 10,
                             jnp.int32)
    rows = jax.random.normal(kr, (e["tokens"], e["d"]), jnp.bfloat16)
    kern = lambda ids, rows: embed_grad(ids, rows, vocab=e["vocab"],
                                        interpret=interp)
    assert uses_pallas(kern, ids, rows)
    t0 = time.perf_counter()
    got = jax.jit(kern)(ids, rows)
    want = jnp.zeros((e["vocab"], e["d"]), jnp.float32).at[ids].add(
        rows.astype(jnp.float32))
    err = rel_err(got, want)
    log(f"kernel embed_grad vocab={e['vocab']} tokens={e['tokens']} "
        f"d={e['d']}: max rel err = {err:.5f}  "
        f"({time.perf_counter() - t0:.1f}s)")
    # one bf16 rounding of the float32 sum: at most 2^-8 of the largest
    assert np.isfinite(err) and err < 2.0 ** -7, err

    # ---- RoPE's rotation: one pass over [b, s, h * d], and its VJP ------
    from bagua_tpu.models.transformer import rope_rotate
    from bagua_tpu.ops.rope import rope

    r = sz["rope"]
    kx, kg = jax.random.split(jax.random.PRNGKey(SEED + 3))
    x = jax.random.normal(kx, (r["b"], r["s"], r["h"], r["d"]), jnp.bfloat16)
    gw = jax.random.normal(kg, x.shape, jnp.float32)

    def rope_loss(rotate):
        def loss(x):
            o = rotate(x)
            return (o.astype(jnp.float32) * gw).sum(), o
        return loss

    kern = rope_loss(lambda x: rope(x, 1e6, 7, interpret=interp))
    ref = rope_loss(lambda x: rope_rotate(x, 1e6, 7))
    assert uses_pallas(lambda x: kern(x)[0], x)
    t0 = time.perf_counter()
    (_, o_k), g_k = jax.jit(jax.value_and_grad(kern, has_aux=True))(x)
    (_, o_r), g_r = jax.jit(jax.value_and_grad(ref, has_aux=True))(x)
    errs = [rel_err(o_k, o_r), rel_err(g_k, g_r)]
    log(f"kernel rope fwd+vjp {x.shape}: max rel err (o, dx) = "
        f"{[round(e, 5) for e in errs]}  ({time.perf_counter() - t0:.1f}s)")
    # one bf16 rounding either way: a bf16 ulp of the largest element
    assert all(np.isfinite(e) and e < 2.0 ** -7 for e in errs), errs

    # ---- the same pass with a per-head RMSNorm in front, and its VJP ------
    from bagua_tpu.models.transformer import RMSNorm
    from bagua_tpu.ops.rope import norm_rope

    scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(SEED + 4),
                                          (r["d"],), jnp.float32)

    def norm_loss(normed):
        def loss(x, scale):
            o = normed(x, scale)
            return (o.astype(jnp.float32) * gw).sum(), o
        return loss

    kern = norm_loss(lambda x, s: norm_rope(x, s, 1e6, 7, interpret=interp))
    ref = norm_loss(lambda x, s: rope_rotate(
        RMSNorm().apply({"params": {"scale": s}}, x), 1e6, 7))
    assert uses_pallas(lambda x, s: kern(x, s)[0], x, scale)
    t0 = time.perf_counter()
    grad = lambda f: jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))
    (_, o_k), (g_k, s_k) = grad(kern)(x, scale)
    (_, o_r), (g_r, s_r) = grad(ref)(x, scale)
    errs = [rel_err(o_k, o_r), rel_err(g_k, g_r), rel_err(s_k, s_r)]
    log(f"kernel norm_rope fwd+vjp {x.shape}: max rel err (o, dx, dscale) = "
        f"{[round(e, 5) for e in errs]}  ({time.perf_counter() - t0:.1f}s)")
    # the two modules round the normalised tensor on the way: two bf16 ulps
    assert all(np.isfinite(e) and e < 2.0 ** -6 for e in errs), errs

    # ---- a Gated DeltaNet layer's rows between its projections -----------
    from bagua_tpu.models import linear_attention as la
    from bagua_tpu.ops import gated_delta_rows as gdn_rows

    n = sz["gdn_rows"]
    dims = n["dims"]
    hk, hv, dk, dv = dims
    kw, vw = hk * dk, hv * dv
    keys = jax.random.split(jax.random.PRNGKey(SEED + 5), 8)
    rows_of = lambda key, width: jax.random.normal(
        key, (n["b"], n["s"], width), jnp.float32).astype(jnp.bfloat16)
    qkvz = rows_of(keys[0], 2 * kw + 2 * vw)
    taps = 0.5 * jax.random.normal(keys[1], (n["taps"], 2 * kw + vw))
    cots = tuple(rows_of(k, w) for k, w in zip(keys[2:5], (kw, kw, vw)))
    o, dy = rows_of(keys[5], vw), rows_of(keys[6], vw)
    w_n = 1.0 + 0.3 * jax.random.normal(keys[7], (dv,), jnp.float32)
    z_of = lambda a: a[..., 2 * kw + vw:]

    def mixed(a, t, cots):
        out, vjp = jax.vjp(lambda a, t: la.mix_rows(a, t, dims), a, t)
        return (*out, *vjp(cots))

    def gated(o, a, w, dy):
        out, vjp = jax.vjp(
            lambda o, a, w: la.gate_rows(o, z_of(a), w, hv, 1e-6), o, a, w)
        return (out, *vjp(dy))

    def by_passes(a, t, cots, o, w, dy):
        q_k_v = gdn_rows.mix(a, t, dims, l2_eps=la.L2_EPS, interpret=interp)
        y = gdn_rows.gate(o, a, w, dims, 1e-6, interp)
        do, buffer, dw = gdn_rows.gate_bwd(dy, o, a, w, dims, 1e-6, interp)
        dx, d_taps = gdn_rows.mix_bwd(*cots, a, t, buffer, dims,
                                      l2_eps=la.L2_EPS, interpret=interp)
        return q_k_v, dx, d_taps, y, do, dw

    assert uses_pallas(by_passes, qkvz, taps, cots, o, w_n, dy)
    t0 = time.perf_counter()
    q_k_v, dx, d_taps, y, do, dw = jax.jit(by_passes)(qkvz, taps, cots, o,
                                                      w_n, dy)
    *want, dx_r, d_taps_r = jax.jit(mixed)(qkvz, taps, cots)
    y_r, do_r, dz_r, dw_r = jax.jit(gated)(o, qkvz, w_n, dy)
    through = 2 * kw + vw
    errs = [*(rel_err(g, w) for g, w in zip(q_k_v, want)),
            rel_err(dx[..., :through], dx_r[..., :through]),
            rel_err(d_taps, d_taps_r)]
    log(f"kernel gdn_mix fwd+vjp {qkvz.shape}: max rel err (q, k, v, dx, "
        f"d_taps) = {[round(e, 5) for e in errs]}  "
        f"({time.perf_counter() - t0:.1f}s)")
    # the jnp form rounds the convolution's sum and the SiLU on the way, the
    # passes once: a few bfloat16 ulps of the largest element
    assert all(np.isfinite(e) and e < 2.0 ** -5 for e in errs), errs
    errs = [rel_err(y, y_r), rel_err(do, do_r),
            rel_err(z_of(dx), z_of(dz_r)), rel_err(dw, dw_r)]
    log(f"kernel gdn_gate fwd+vjp {o.shape}: max rel err (y, do, dz, d_w_n) "
        f"= {[round(e, 5) for e in errs]}")
    assert all(np.isfinite(e) and e < 2.0 ** -6 for e in errs), errs

    # ---- codec kernels, fused and tiled, f32 and bf16 --------------------
    n_chunks = 4
    for chunk in sz["codec_chunks"]:
        path = "tiled" if pc._padded_rows(chunk) > pc._MAX_FUSED_ROWS \
            else "fused"
        for dtype in (jnp.float32, jnp.bfloat16):
            x = (jax.random.normal(jax.random.PRNGKey(chunk),
                                   (n_chunks * chunk,), jnp.float32)
                 * 1e-2).astype(dtype)
            x2d = x.reshape(n_chunks, chunk).astype(jnp.float32)
            tag = f"{path} chunk={chunk} {jnp.dtype(dtype).name}"

            # minmax compress + decompress
            mn, mx, payload = pc.compress_chunked_pallas(x, n_chunks, interp)
            mn_r, mx_r, payload_r = compress_chunked(x, n_chunks)
            np.testing.assert_array_equal(np.asarray(mn), np.asarray(mn_r))
            np.testing.assert_array_equal(np.asarray(mx), np.asarray(mx_r))
            diff = np.abs(np.asarray(payload, np.int32)
                          - np.asarray(payload_r, np.int32))
            assert diff.max() <= 1 and (diff > 0).mean() < LEVEL_FLIP_BUDGET, \
                (tag, int(diff.max()), float((diff > 0).mean()))
            out = pc.decompress_chunked_pallas(mn, mx, payload, interp)
            out_r = decompress_chunked(mn, mx, payload)
            np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                                       rtol=1e-5, atol=1e-7, err_msg=tag)

            # absmax (int8 / fp8 sidecar)
            am = pc.absmax_chunked_pallas(x, n_chunks, interp)
            np.testing.assert_array_equal(
                np.asarray(am), np.asarray(jnp.abs(x2d).max(axis=1)), tag)

            # 1-bit sign pack (+ unpack inside the fused range)
            scale, packed = pc.sign_compress_chunked_pallas(
                x, n_chunks, interp)
            np.testing.assert_allclose(
                np.asarray(scale), np.asarray(jnp.abs(x2d).mean(axis=1)),
                rtol=1e-5, err_msg=tag)
            np.testing.assert_array_equal(
                np.asarray(packed), np.asarray(pc._jnp_sign_pack(x2d)), tag)
            if path == "fused":
                signs = pc.sign_decompress_chunked_pallas(
                    scale, packed, interp)[:, :chunk]
                want = jnp.where(x2d >= 0, 1.0, -1.0) * scale[:, None]
                np.testing.assert_array_equal(
                    np.asarray(signs), np.asarray(want), tag)
            log(f"kernel codec {tag}: minmax compress/decompress, absmax, "
                f"sign pack{'/unpack' if path == 'fused' else ''} match "
                f"(payload level flips {float((diff > 0).mean()):.2e})")


# ---------------------------------------------------------------------------
# trainer legs
# ---------------------------------------------------------------------------


def run_trainer(name, cfg, params, tokens, algorithm, optimizer, steps,
                cache: CacheCounter, mesh=None, **trainer_kw):
    """Init + ``steps`` train steps through the public trainer surface.
    Every step is fenced by the loss readback, so the wall seconds are
    whole-step seconds (the first includes trace + compile)."""
    import jax

    from bagua_tpu import BaguaTrainer
    from bagua_tpu.models.transformer import TransformerLM, lm_loss_fn

    model = TransformerLM(cfg)
    trainer = BaguaTrainer(lm_loss_fn(model), optimizer, algorithm,
                           mesh=mesh, **trainer_kw)
    state = trainer.init(params)
    jax.block_until_ready(state)
    mem_init = device_memory()
    data = trainer.shard_batch({"tokens": tokens})
    losses, walls = [], []
    hits0, misses0 = cache.snapshot()
    first_cache = None
    loss = None
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = trainer.train_step(state, data)
        losses.append(float(loss))
        walls.append(time.perf_counter() - t0)
        if first_cache is None:
            hits1, misses1 = cache.snapshot()
            first_cache = (hits1 - hits0, misses1 - misses0)
    jax.block_until_ready(state)
    steady = sorted(walls[1:])[len(walls[1:]) // 2] if steps > 1 else None
    log(f"{name}: losses {[round(x, 4) for x in losses]}")
    log(f"{name}: first step {walls[0]:.2f}s (trace+compile+run; persistent "
        f"cache hits/misses during it: {first_cache[0]}/{first_cache[1]}), "
        f"compile ~{walls[0] - (steady or 0):.2f}s, per-step wall "
        f"{[round(x, 3) for x in walls[1:]]}")
    log(f"{name}: (bytes_in_use, peak) per device after trainer.init: "
        f"{mem_init}")
    assert all(np.isfinite(x) for x in losses), (name, losses)
    assert losses[-1] < losses[0], (name, "loss did not decrease", losses)
    return trainer, state, data, losses, loss


def assert_placement(name, trainer, state, data, loss, dryrun: bool) -> None:
    """Loss and params on the expected platform, params over every chip of
    the mesh, the batch one shard per chip."""
    import jax

    mesh_devices = set(trainer.mesh.devices.flat)
    platform = "cpu" if dryrun else "tpu"
    assert {d.platform for d in loss.devices()} == {platform}, loss.devices()
    for leaf in jax.tree.leaves(state.params):
        assert leaf.sharding.device_set == mesh_devices, (
            name, "param leaf does not span the mesh",
            leaf.sharding.device_set)
    for leaf in jax.tree.leaves(data):
        shard_devices = [s.device for s in leaf.addressable_shards]
        assert len(shard_devices) == len(mesh_devices) \
            and set(shard_devices) == mesh_devices, (name, shard_devices)
        assert leaf.addressable_shards[0].data.shape[0] * len(mesh_devices) \
            == leaf.shape[0], (name, "batch is not split over the chips")


def legs_full_width(sz: dict, cache: CacheCounter, dryrun: bool) -> None:
    import jax
    import optax

    from bagua_tpu.algorithms.bytegrad import ByteGradAlgorithm
    from bagua_tpu.algorithms.gradient_allreduce import (
        GradientAllReduceAlgorithm,
    )
    from bagua_tpu.models.transformer import TransformerLM

    n_dev = len(jax.devices())
    cfg = sz["full"]
    tokens = seeded_tokens(sz["batch_per_chip"] * n_dev, cfg)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(SEED), tokens[:2, :-1])["params"]
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    log(f"model: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{n_params / 1e6:.1f} M params, seq {cfg.max_seq_len}, "
        f"batch {sz['batch_per_chip']}/chip x {n_dev} chips")

    # ---- leg A: the default family ---------------------------------------
    trainer, state, data, losses_a, loss = run_trainer(
        "leg A (gradient_allreduce)", cfg, params, tokens,
        GradientAllReduceAlgorithm(), optax.adamw(1e-4), STEPS_A, cache)
    assert_placement("leg A", trainer, state, data, loss, dryrun)
    # the obs plane's MFU path: on TPU the first train_step started a
    # background AOT compile of the step for XLA's cost model; joining it
    # here establishes that it finishes and returns flops
    t0 = time.perf_counter()
    analysis = trainer.step_cost_analysis(state, data)
    log(f"leg A: step_cost_analysis joined in {time.perf_counter() - t0:.2f}s"
        f", flops/step = {analysis.get('flops')}, peak table entry = "
        f"{trainer._observer.peak_flops}")
    if not dryrun:
        assert analysis.get("flops", 0) > 0, analysis
        assert trainer._observer.peak_flops, "no peak-FLOPS entry for this device"
    del trainer, state, data, loss

    # ---- leg B: the signature relaxation ---------------------------------
    trainer, state, data, losses_b, loss = run_trainer(
        "leg B (bytegrad)", cfg, params, tokens,
        ByteGradAlgorithm(), optax.adamw(1e-4), STEPS_B, cache)
    assert_placement("leg B", trainer, state, data, loss, dryrun)
    # the forward precedes any communication: same first loss as leg A
    np.testing.assert_allclose(losses_b[0], losses_a[0], rtol=1e-3)
    in_step = "pallas_call" in str(trainer.trace_step(state, data))
    chunk_bytes = [b.padded_numel * 4 // trainer.world_size
                   for b in trainer._plan.buckets]
    log(f"leg B: {len(chunk_bytes)} buckets, per-rank chunk bytes "
        f"min/max {min(chunk_bytes)}/{max(chunk_bytes)}; Pallas codec in "
        f"the traced step: {in_step}")
    if n_dev == 1:
        # world 1: ByteGrad returns the bucket untouched (no peer to
        # exchange with, algorithms/bytegrad.py reduce_bucket_grad), so the
        # step carries no codec at all — the codec kernels at this chunk
        # size are covered by the kernels leg (tiled path)
        assert not in_step
        log("leg B: codec-in-step assert SKIPPED (n_devices=1): the "
            "world-1 ByteGrad step has no collective to compress")
    elif not dryrun:
        assert in_step, "ByteGrad step took the jnp codec on a TPU mesh"
    del trainer, state, data, loss


def legs_four_chip(sz: dict, cache: CacheCounter, dryrun: bool) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    import bagua_tpu
    from bagua_tpu.algorithms.gradient_allreduce import (
        GradientAllReduceAlgorithm,
    )
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm
    from bagua_tpu.models.transformer import TransformerLM
    from bagua_tpu.parallel.mesh import build_mesh

    devices = jax.devices()[:4]
    cfg = sz["shallow"]
    tokens = seeded_tokens(8, cfg, seed=SEED + 1)  # ONE global batch
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(SEED), tokens[:2, :-1])["params"]
    dp4 = build_mesh({"dp": 4}, devices)
    tiers = build_mesh({"inter": 2, "intra": 2}, devices)

    def run(name, algorithm, mesh, optimizer=optax.adamw(1e-4), **kw):
        trainer, state, data, losses, loss = run_trainer(
            name, cfg, params, tokens, algorithm, optimizer, 3, cache,
            mesh=mesh, **kw)
        assert_placement(name, trainer, state, data, loss, dryrun)
        return losses

    # ---- dp equivalence: real psums, work not all on chip 0 --------------
    one = run("4-chip dp1 reference", GradientAllReduceAlgorithm(),
              build_mesh({"dp": 1}, devices[:1]))
    four = run("4-chip dp4", GradientAllReduceAlgorithm(), dp4)
    np.testing.assert_allclose(four, one, rtol=BF16_LOSS_RTOL)
    mem = device_memory()[:4]
    log(f"4-chip dp4: (bytes_in_use, peak) per device: {mem}")
    if not dryrun:
        assert all(used and used > 0 for used, _ in mem), mem

    # ---- two-tier: rs / allreduce / ag per tier --------------------------
    hier = run("4-chip two-tier allreduce",
               GradientAllReduceAlgorithm(hierarchical=True), tiers)
    np.testing.assert_allclose(hier, four, rtol=BF16_LOSS_RTOL)
    zero = run("4-chip two-tier staged ZeRO",
               ZeroOptimizerAlgorithm(optax.adamw(1e-4), hierarchical=True),
               tiers, optimizer=None)
    np.testing.assert_allclose(zero, four, rtol=BF16_LOSS_RTOL)

    # ---- ppermute ring, plain and with a codec ---------------------------
    ring = run("4-chip ring", GradientAllReduceAlgorithm(hierarchical=False),
               dp4, overlap="on", overlap_chunk_bytes=sz["ring_chunk_bytes"])
    np.testing.assert_allclose(ring, four, rtol=BF16_LOSS_RTOL)
    run("4-chip ring + int8 codec",
        GradientAllReduceAlgorithm(hierarchical=False), dp4, overlap="on",
        overlap_chunk_bytes=sz["ring_chunk_bytes"], compress_intra="int8")

    # ---- eager primitives against numpy ----------------------------------
    comm = bagua_tpu.BaguaCommunicator("dp", dp4)
    rng = np.random.default_rng(SEED)
    x = rng.standard_normal((4, 1024)).astype(np.float32)
    got = np.asarray(bagua_tpu.allreduce(jnp.asarray(x), comm=comm))
    np.testing.assert_allclose(got, np.broadcast_to(x.mean(0), x.shape),
                               rtol=1e-6, atol=1e-6)
    counts = rng.integers(0, 9, size=(4, 4))
    length = int(counts.sum(axis=1).max())
    send = rng.standard_normal((4, length, 8)).astype(np.float32)
    out_size = int(counts.sum(axis=0).max())
    want = np.zeros((4, out_size, 8), np.float32)
    for dst in range(4):
        at = 0
        for src in range(4):
            start = int(counts[src, :dst].sum())
            n = int(counts[src, dst])
            want[dst, at:at + n] = send[src, start:start + n]
            at += n
    got = np.asarray(bagua_tpu.alltoall_v(jnp.asarray(send), counts,
                                          comm=comm))
    np.testing.assert_array_equal(got, want)
    log(f"4-chip eager: allreduce + alltoall_v match numpy "
        f"(ragged path: {'native' if not dryrun else 'padded'})")


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dryrun", action="store_true",
                    help="rehearse on 4 virtual CPU devices: tiny widths, "
                         "interpret-mode kernels; prints DRYRUN, never the "
                         "pass line")
    args = ap.parse_args(argv)
    dryrun = args.cpu_dryrun
    if dryrun:
        # must precede the first jax import: the platform is fixed there
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()

    t_start = time.perf_counter()
    import jax
    import jaxlib

    import bagua_tpu
    from bagua_tpu.compile_cache import configure_compile_cache
    from bagua_tpu.obs.ledger import PEAK_TFLOPS_BF16

    cache_dir = configure_compile_cache()
    cache = CacheCounter()
    mesh = bagua_tpu.init_process_group()

    devices = jax.devices()
    dev = devices[0]
    log(f"smoke observation — jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {version('libtpu')}, python "
        f"{sys.version.split()[0]}")
    log(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
        f"{len(devices)} device(s), mesh {dict(mesh.shape)}, JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS')!r}, compile cache {cache_dir}")
    log("device order: " + ", ".join(
        f"id={d.id} coords={getattr(d, 'coords', None)} "
        f"core={getattr(d, 'core_on_chip', None)}" for d in devices))
    if dryrun:
        log("DRYRUN: cpu rehearsal, nothing below is a device observation")
    else:
        if dev.platform != "tpu":
            raise RuntimeError(
                f"chip_smoke needs a TPU, JAX found platform "
                f"{dev.platform!r} ({dev.device_kind!r}); use --cpu-dryrun "
                "to rehearse the command off-chip")
        if dev.device_kind not in PEAK_TFLOPS_BF16:
            raise RuntimeError(
                f"device_kind {dev.device_kind!r} is missing from the peak "
                f"table (bagua_tpu/obs/ledger.py: {sorted(PEAK_TFLOPS_BF16)})")

    sz = sizes(dryrun)
    leg_kernels(sz, dryrun)
    legs_full_width(sz, cache, dryrun)
    if len(devices) >= 4:
        legs_four_chip(sz, cache, dryrun)
    else:
        for name in ("dp equivalence", "two-tier allreduce + staged ZeRO",
                     "ppermute ring plain + codec",
                     "eager allreduce / alltoall_v"):
            log(f"4-chip leg {name}: SKIPPED (n_devices={len(devices)})")

    hits, misses = cache.snapshot()
    log(f"persistent compile cache: {hits} hits, {misses} misses over the "
        f"whole run; wall {time.perf_counter() - t_start:.1f}s")
    if dryrun:
        print("DRYRUN ok")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
