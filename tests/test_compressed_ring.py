"""Wire-speed compression: codecs fused into the chunked ring hops (ISSUE 15).

Pinned contracts:

* codec round trips are error-bounded (grid-step bounds for u8/int8,
  relative bounds for fp8), exact on zeros, survive denormal-range inputs
  via the absmax scaling, and PROPAGATE non-finite values (the gradient
  health sentinel must still see a poisoned bucket after compression);
* ``ring_*(codec=...)`` matches the fused full-precision collective within
  the codec's error bound and leaves every rank bit-identical;
* ``codec=None`` reproduces the pre-codec ring construction EXACTLY (HLO
  pin), and a trainer with the policy knobs at default lowers the same
  program as one with both tiers forced ``off``;
* compressed-DCN loss trajectories track the full-precision-DCN form
  within tolerance for bytegrad/qadam at accum 1 and 4 on the two-level
  mesh, and the compressed flat ring tracks the fused psum on the flat
  mesh;
* the acceptance ratio: a bytegrad two-level step's traced DCN wire bytes
  drop >= 3x versus the full-precision-DCN two-level form (jaxpr byte
  accounting, exact on any platform);
* the codec policy rides the env registry, the step-cache key (overlap on
  AND off — compression is a wire format, not a schedule), the
  ``BaguaHyperparameter``/autotune recommendation path, and the autopilot's
  ``compress_dcn`` hint actuates ``compress_inter`` through the service;
* the overlap scheduler's launch spans report COMPRESSED wire bytes and
  the codec that produced them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from bagua_tpu import BaguaTrainer
from bagua_tpu.algorithms import (
    ByteGradAlgorithm,
    GradientAllReduceAlgorithm,
    QAdamAlgorithm,
)
from bagua_tpu.communication import BaguaCommunicator, ReduceOp
from jax import shard_map
from bagua_tpu.compression.codecs import (
    CODECS,
    get_codec,
    validate_codec_policy,
)
from bagua_tpu.models import MLP
from bagua_tpu.parallel.mesh import build_mesh

N = 8
INTRA = 4
INTER = 2
DIM = 12
NCLASS = 10
MODEL = MLP(features=(16, NCLASS))

ALL_CODECS = sorted(CODECS)
#: the stateless grid codecs: payload element per input element, no
#: error-feedback residual required — the psum-match bounds below only
#: hold for these (onebit/topk are lossy by construction and converge
#: through the EF residual, pinned in test_ef_residual.py)
UNIFORM_CODECS = [n for n in ALL_CODECS
                  if not get_codec(n).error_feedback]
EF_CODECS = [n for n in ALL_CODECS if get_codec(n).error_feedback]


def _loss_fn(params, batch):
    logits = MODEL.apply({"params": params}, batch["x"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["y"]
    ).mean()


# ---- codec unit round trips --------------------------------------------


@pytest.mark.parametrize("name", ALL_CODECS)
def test_codec_roundtrip_error_bounded(name):
    codec = get_codec(name)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 512)).astype(np.float32))
    parts = codec.encode(x)
    if name != "topk":  # topk's value part travels exact f32
        assert parts[-1].dtype.itemsize == 1  # 1-byte payloads: the 4x win
    y = codec.decode(parts, x.shape[1])
    assert y.dtype == jnp.float32  # the accumulation-dtype contract
    assert y.shape == x.shape
    err = np.abs(np.asarray(y) - np.asarray(x)).max(axis=1)
    span = np.asarray(x).max(axis=1) - np.asarray(x).min(axis=1)
    if name == "minmax_uint8":
        bound = span / 255.0 + 1e-6
    elif name == "int8":
        bound = np.abs(np.asarray(x)).max(axis=1) / 127.0 + 1e-6
    elif name in ("onebit_ef", "topk"):
        # lossy-by-construction: per-element error bounded by the chunk's
        # largest magnitude (+ the sign scale for onebit) — the residual
        # re-injects the rest (test_ef_residual.py)
        bound = (np.abs(np.asarray(x)).max(axis=1)
                 + np.abs(np.asarray(x)).mean(axis=1))
    else:  # fp8: 2^-mantissa_bits relative + the scale quantization
        rel = 0.0625 if name == "fp8_e4m3" else 0.25
        bound = np.abs(np.asarray(x)).max(axis=1) * rel
    assert (err <= bound).all(), (name, err, bound)


def test_onebit_decode_matches_sign_times_meanabs():
    """The 1-bit wire is exactly ``mean|x| * sign(x)`` per chunk — the
    L1-optimal magnitude for a sign quantizer — through the bit-packed
    payload round trip."""
    codec = get_codec("onebit_ef")
    rng = np.random.default_rng(5)
    x = rng.normal(size=(3, 300)).astype(np.float32)  # non-multiple of 1024
    y = np.asarray(codec.decode(codec.encode(jnp.asarray(x)), 300))
    scale = np.abs(x).mean(axis=1, keepdims=True)
    signs = np.where(x >= 0, 1.0, -1.0).astype(np.float32)
    np.testing.assert_allclose(y, signs * scale, rtol=1e-6)
    assert codec.wire_bytes(1024) == 128 + 4  # the ~32x win


def test_topk_selects_largest_and_requires_m():
    codec = get_codec("topk")
    rng = np.random.default_rng(6)
    x = rng.uniform(-1, 1, size=(2, 400)).astype(np.float32)
    x[0, 11] = 9.0
    parts = codec.encode(jnp.asarray(x))
    with pytest.raises(ValueError, match="variable-payload"):
        codec.decode(parts)
    y = np.asarray(codec.decode(parts, 400))
    kk = codec.k_for(400)
    assert (np.count_nonzero(y, axis=1) == kk).all()
    assert y[0, 11] == 9.0  # largest magnitude survives exactly
    sel = np.nonzero(y[1])[0]
    np.testing.assert_array_equal(y[1, sel], x[1, sel])


def test_topk_ratio_env_knob_resolves_per_lookup(monkeypatch):
    """BAGUA_TOPK_RATIO must take effect at codec RESOLUTION time (trainer
    construction / step trace), like every other BAGUA_* knob — not be
    frozen by the import-time registry singleton."""
    assert get_codec("topk").ratio == pytest.approx(0.01)
    monkeypatch.setenv("BAGUA_TOPK_RATIO", "0.25")
    codec = get_codec("topk")
    assert codec.ratio == pytest.approx(0.25)
    assert codec.k_for(400) == 100
    assert codec.wire_bytes(400) == 8 * 100
    monkeypatch.delenv("BAGUA_TOPK_RATIO")
    assert get_codec("topk").k_for(400) == 4
    # the stateless singletons keep resolving to one shared instance
    assert get_codec("minmax_uint8") is get_codec("minmax_uint8")


@pytest.mark.parametrize("name", ALL_CODECS)
def test_codec_zeros_roundtrip_exact(name):
    codec = get_codec(name)
    y = codec.decode(codec.encode(jnp.zeros((2, 128), jnp.float32)), 128)
    assert (np.asarray(y) == 0).all()


@pytest.mark.parametrize("name", ALL_CODECS)
@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
def test_codec_nonfinite_propagates(name, poison):
    """A poisoned element must survive the codec as a non-finite output —
    the gradient-health sentinel's verdict rides the DECODED buffers, so a
    codec that silently saturated NaN/Inf to a finite grid point would
    blind it.  Clean chunks in the same batch stay finite."""
    codec = get_codec(name)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(3, 64)).astype(np.float32)
    x[1, 7] = poison
    y = np.asarray(codec.decode(codec.encode(jnp.asarray(x)), 64))
    assert not np.isfinite(y[1]).all(), (name, poison)
    assert np.isfinite(y[0]).all() and np.isfinite(y[2]).all()


@pytest.mark.parametrize("name", ["fp8_e4m3", "fp8_e5m2"])
def test_fp8_denormal_range_roundtrip(name):
    """Inputs far below fp8's own denormal range survive: the absmax
    scaling maps each chunk onto the format's full span, so a 1e-30-scale
    gradient keeps its relative structure instead of flushing to zero."""
    codec = get_codec(name)
    rng = np.random.default_rng(2)
    x = (rng.normal(size=(2, 256)) * 1e-30).astype(np.float32)
    y = np.asarray(codec.decode(codec.encode(jnp.asarray(x))))
    rel = 0.0625 if name == "fp8_e4m3" else 0.25
    bound = np.abs(x).max(axis=1, keepdims=True) * rel
    assert (np.abs(y - x) <= bound + 1e-38).all()
    # and structure is preserved, not zeroed
    assert np.corrcoef(x.reshape(-1), y.reshape(-1))[0, 1] > 0.95


def test_codec_policy_validation():
    for v in ("off", "auto", "minmax_uint8", "int8", "fp8_e4m3",
              "fp8_e5m2", "onebit_ef", "topk"):
        assert validate_codec_policy(v, "k") == v
    assert validate_codec_policy("", "k") == "auto"
    assert validate_codec_policy("AUTO", "k") == "auto"
    with pytest.raises(ValueError, match="compress_inter"):
        validate_codec_policy("uint4", "compress_inter")
    with pytest.raises(ValueError):
        BaguaTrainer(_loss_fn, optax.sgd(0.1),
                     GradientAllReduceAlgorithm(),
                     mesh=build_mesh({"dp": N}), autotune=False,
                     compress_inter="nope")
    with pytest.raises(ValueError):
        ByteGradAlgorithm(codec="nope")


# ---- compressed ring collectives ---------------------------------------


def _flat_mesh():
    return build_mesh({"dp": N})


def _run_flat(fn, xs):
    mesh = _flat_mesh()
    comm = BaguaCommunicator("dp", mesh)
    out = jax.jit(
        shard_map(lambda x: fn(comm, x[0])[None], mesh=mesh,
                  in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
    )(jnp.asarray(xs))
    return np.asarray(out)


@pytest.mark.parametrize("name", UNIFORM_CODECS)
@pytest.mark.parametrize("num_chunks", [1, 4])
def test_ring_allreduce_codec_matches_psum_bounded(name, num_chunks):
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(N, 64)).astype(np.float32)
    out = _run_flat(
        lambda c, x: c.ring_allreduce(x, ReduceOp.AVG,
                                      num_chunks=num_chunks, codec=name),
        xs,
    )
    # every rank decodes the same forwarded payloads -> bit-identical
    for r in range(1, N):
        np.testing.assert_array_equal(out[0], out[r])
    # quantization error enters once per hop (n-1 requantizations) plus
    # the final broadcast quantize; bound by hops x one grid step of the
    # running partial sum's span
    ref = xs.mean(0)
    amax = np.abs(xs).max()
    rel = {"minmax_uint8": 2 / 255.0, "int8": 2 / 127.0,
           "fp8_e4m3": 0.0625, "fp8_e5m2": 0.25}[name]
    assert np.abs(out[0] - ref).max() <= N * amax * rel


@pytest.mark.parametrize("name", EF_CODECS)
@pytest.mark.parametrize("num_chunks", [1, 4])
def test_ring_allreduce_lossy_codec_ranks_identical(name, num_chunks):
    """The sign/sparse codecs through the REAL chunked ring: no psum-match
    bound (stateless they are lossy by construction — convergence rides
    the EF residual), but the wire contract still holds: every rank
    decodes the same forwarded payloads bit-identically and the result
    stays finite."""
    rng = np.random.default_rng(8)
    xs = rng.normal(size=(N, 64)).astype(np.float32)
    out = _run_flat(
        lambda c, x: c.ring_allreduce(x, ReduceOp.AVG,
                                      num_chunks=num_chunks, codec=name),
        xs,
    )
    for r in range(1, N):
        np.testing.assert_array_equal(out[0], out[r])
    assert np.isfinite(out).all()
    assert np.abs(out).max() > 0.0


@pytest.mark.parametrize("name", ["minmax_uint8", "int8"])
def test_ring_scatter_gather_codec_pair_layout(name):
    """rs(codec) -> ag(codec) reproduces the SUM within bound, in the same
    contiguous rank layout as the full-precision pair."""
    rng = np.random.default_rng(4)
    xs = rng.normal(size=(N, 64)).astype(np.float32)
    out = _run_flat(
        lambda c, x: c.ring_allgather(
            c.ring_reduce_scatter(x, ReduceOp.SUM, codec=name), codec=name
        ),
        xs,
    )
    ref = xs.sum(0)
    amax = np.abs(xs).sum(0).max()
    rel = 2 / 255.0 if name == "minmax_uint8" else 2 / 127.0
    assert np.abs(out[0] - ref).max() <= N * amax * rel
    for r in range(1, N):
        np.testing.assert_array_equal(out[0], out[r])


def test_ring_codec_none_hlo_pin():
    """``codec=None`` is byte-for-byte the pre-codec ring construction:
    passing it explicitly lowers the identical HLO as omitting it, and
    that HLO contains no u8 payloads or codec arithmetic."""
    mesh = _flat_mesh()
    comm = BaguaCommunicator("dp", mesh)

    def lower(**kw):
        return jax.jit(
            shard_map(
                lambda x: comm.ring_allreduce(x[0], ReduceOp.AVG,
                                              num_chunks=4, **kw)[None],
                mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                check_vma=False,
            )
        ).lower(jnp.zeros((N, 64), jnp.float32)).as_text()

    plain = lower()
    assert lower(codec=None) == plain
    assert "ui8" not in plain  # stablehlo spells uint8 `ui8`
    compressed = lower(codec="minmax_uint8")
    assert compressed != plain and "ui8" in compressed


def test_trainer_default_knobs_hlo_pinned_to_off():
    """A trainer with the codec knobs at their ``auto`` default lowers the
    IDENTICAL program as one with both tiers forced ``off`` for an exact
    family — auto never compresses a family without a wire codec."""
    def hlo(**kw):
        trainer = BaguaTrainer(
            _loss_fn, optax.sgd(0.1),
            GradientAllReduceAlgorithm(hierarchical=True),
            mesh=build_mesh({"inter": INTER, "intra": INTRA}),
            bucket_bytes=256, overlap="off", autotune=False, **kw,
        )
        params = MODEL.init(
            jax.random.PRNGKey(0), jnp.zeros((1, DIM))
        )["params"]
        state = trainer.init(params)
        rng = np.random.default_rng(0)
        batch = trainer.shard_batch({
            "x": rng.normal(size=(N * 2, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2,)).astype(np.int32),
        })
        return trainer._get_step_fn().lower(state, batch).as_text()

    assert hlo() == hlo(compress_intra="off", compress_inter="off")


# ---- loss trajectories: compressed vs full-precision DCN ----------------


def _train(algo_factory, optimizer, accum, steps=5, mesh_kind="hier", **kw):
    mesh = (build_mesh({"inter": INTER, "intra": INTRA})
            if mesh_kind == "hier" else _flat_mesh())
    trainer = BaguaTrainer(
        _loss_fn, optimizer, algo_factory(), mesh=mesh,
        bucket_bytes=256, accum_steps=accum, autotune=False, **kw,
    )
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    state = trainer.init(params)
    rng = np.random.default_rng(7)
    losses = []
    for _ in range(steps):
        batch = {
            "x": rng.normal(size=(N * 2 * accum, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2 * accum,)).astype(
                np.int32
            ),
        }
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    return np.array(losses), trainer


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize(
    "algo_factory,optimizer",
    [
        (lambda: ByteGradAlgorithm(hierarchical=True), optax.sgd(0.1)),
        (lambda: QAdamAlgorithm(warmup_steps=2, lr=1e-2,
                                hierarchical=True), None),
    ],
    ids=["bytegrad", "qadam"],
)
def test_compressed_dcn_matches_full_precision_dcn(algo_factory, optimizer,
                                                   accum):
    """Two-level mesh: the native compressed DCN ring (quantized hops,
    fp32 accumulation) tracks the full-precision-DCN two-level form
    (``compress_inter="off"``) within quantization tolerance — for QAdam
    through its warmup boundary into the compressed-momentum phase."""
    l_comp, tr = _train(algo_factory, optimizer, accum)
    l_full, _ = _train(algo_factory, optimizer, accum,
                       compress_inter="off")
    assert np.isfinite(l_comp).all() and np.isfinite(l_full).all()
    np.testing.assert_allclose(l_comp, l_full, rtol=0.05, atol=0.02)


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("name", ["minmax_uint8", "int8"])
def test_compressed_flat_ring_matches_fused_psum(name, accum):
    """Flat mesh: forcing the flat/ICI codec routes the bucket allreduce
    through the compressed single-axis ring; the trajectory tracks the
    fused full-precision psum within tolerance.  (ByteGrad/QAdam's flat
    compression is the scatter-gather pipeline, pinned by
    test_compression.py and the loss goldens.)"""
    l_comp, tr = _train(lambda: GradientAllReduceAlgorithm(), optax.sgd(0.1),
                        accum, mesh_kind="flat", compress_intra=name)
    l_full, _ = _train(lambda: GradientAllReduceAlgorithm(), optax.sgd(0.1),
                       accum, mesh_kind="flat")
    assert np.isfinite(l_comp).all()
    np.testing.assert_allclose(l_comp, l_full, rtol=0.05, atol=0.02)


# ---- the acceptance ratio: DCN wire bytes ------------------------------


def _dcn_wire_bytes(trainer, state, batch):
    from bagua_tpu.analysis.jaxpr_check import iter_collectives

    jaxpr = trainer.trace_step(state, batch)
    dcn = ici = 0
    for c in iter_collectives(jaxpr):
        if "inter" in c.axes:
            dcn += c.nbytes
        else:
            ici += c.nbytes
    return dcn, ici


#: realistic-bucket fixture for the byte-ratio pins: the trajectory tests
#: above keep the tiny model for speed, but sidecar overhead is a
#: per-hop CONSTANT — on 256-byte toy buckets it eats the payload win,
#: so the wire-ratio acceptance is measured on kilo-element buckets
#: (production buckets are MBs, where the sidecar vanishes entirely)
BIG_DIM = 32
BIG_MODEL = MLP(features=(64, 32, NCLASS))
#: the error-feedback codecs' steeper gate (ISSUE 17) is set where their
#: f32 scale / index sidecars are small beside the payload: ~340 KB of
#: parameters in 64 KiB buckets
WIDE = dict(model=MLP(features=(256, 256, NCLASS)), dim=64,
            bucket_bytes=65536)


def _traced(algo, optimizer, model=BIG_MODEL, dim=BIG_DIM, bucket_bytes=8192,
            **kw):
    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]
        ).mean()

    trainer = BaguaTrainer(
        loss_fn, optimizer, algo,
        mesh=build_mesh({"inter": INTER, "intra": INTRA}),
        bucket_bytes=bucket_bytes, overlap="off", autotune=False, **kw,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, dim))
    )["params"]
    state = trainer.init(params)
    rng = np.random.default_rng(0)
    batch = trainer.shard_batch({
        "x": rng.normal(size=(N * 2, dim)).astype(np.float32),
        "y": rng.integers(0, NCLASS, size=(N * 2,)).astype(np.int32),
    })
    return trainer, state, batch


def test_bytegrad_dcn_wire_bytes_drop_3x():
    """The ISSUE 15 acceptance pin: a bytegrad two-level step's traced DCN
    wire bytes (jaxpr collective operands spanning ``inter`` — exact on
    any platform) drop >= 3x once the codec rides the hops, versus the
    same two-level decomposition moving full-precision DCN shards.  The
    scalar loss reduction is excluded from the ratio on both sides."""
    dcn_comp, ici_comp = _dcn_wire_bytes(
        *_traced(ByteGradAlgorithm(hierarchical=True), optax.sgd(0.1)))
    dcn_full, _ = _dcn_wire_bytes(
        *_traced(ByteGradAlgorithm(hierarchical=True), optax.sgd(0.1),
                 compress_inter="off"))
    loss_scalar = 4
    ratio = (dcn_full - loss_scalar) / (dcn_comp - loss_scalar)
    assert ratio >= 3.0, (dcn_full, dcn_comp, ratio)
    # the slice-local tiers still do the heavy lifting in full precision
    assert ici_comp > dcn_comp


@pytest.mark.parametrize(
    "name,gate,sizes",
    [(name, 3.0, {}) for name in ALL_CODECS]
    + [(name, 12.0, WIDE) for name in EF_CODECS],
    ids=ALL_CODECS + [f"{name}-12x" for name in EF_CODECS],
)
def test_gradient_allreduce_forced_dcn_codec_drops_bytes(name, gate, sizes):
    """Every codec cuts the exact family's forced-compressed DCN bytes >=
    3x (1-byte payloads + sidecar vs 4-byte shards); bit-packed signs and
    1% top-k cut them >= 12x once the buckets are tens of kilobytes."""
    dcn_comp, _ = _dcn_wire_bytes(
        *_traced(GradientAllReduceAlgorithm(hierarchical=True),
                 optax.sgd(0.1), compress_inter=name, **sizes))
    dcn_full, _ = _dcn_wire_bytes(
        *_traced(GradientAllReduceAlgorithm(hierarchical=True),
                 optax.sgd(0.1), **sizes))
    loss_scalar = 4
    assert (dcn_full - loss_scalar) / (dcn_comp - loss_scalar) >= gate


# ---- accounting, spans, knobs, service ---------------------------------


def test_forced_codec_on_ring_invalid_comm_drops_loudly_and_honestly():
    """A knob-forced codec on a comm world that cannot ride a ring (the
    two-axis flat path of a two-tier mesh) must NOT silently claim
    compression: the traced step stays full precision AND the byte
    accounting reports full-precision bytes (one resolution for both)."""
    from bagua_tpu.analysis.jaxpr_check import iter_collectives

    trainer, state, batch = _traced(
        GradientAllReduceAlgorithm(hierarchical=False), optax.sgd(0.1),
        compress_intra="int8")
    jaxpr = trainer.trace_step(state, batch)
    assert not any(c.dtype in ("uint8", "int8")
                   for c in iter_collectives(jaxpr))
    ctx = trainer._ctx(trainer._plan)
    tiers = ctx.bucket_tier_bytes(0, False)
    full = trainer._ctx(trainer._plan)
    full.intra_codec = "off"
    assert tiers["flat_codec"] is None
    assert tiers["ici_bytes"] == full.bucket_tier_bytes(0, False)["ici_bytes"]


@pytest.mark.parametrize("family", ["bytegrad", "qadam"])
def test_off_knob_forces_full_precision_scatter_gather(family):
    """``compress_intra="off"`` on the flat mesh strips the compression
    families' own scatter-gather pipeline down to the fused full-precision
    collective — the documented escape hatch — while the default keeps the
    u8 pipeline."""
    from bagua_tpu.analysis.jaxpr_check import iter_collectives

    def trace(**kw):
        algo = (ByteGradAlgorithm(hierarchical=False) if family == "bytegrad"
                else QAdamAlgorithm(warmup_steps=0, hierarchical=False))
        trainer = BaguaTrainer(
            _loss_fn, optax.sgd(0.1) if family == "bytegrad" else None,
            algo, mesh=_flat_mesh(), bucket_bytes=256, overlap="off",
            autotune=False, **kw,
        )
        params = MODEL.init(
            jax.random.PRNGKey(0), jnp.zeros((1, DIM))
        )["params"]
        state = trainer.init(params)
        rng = np.random.default_rng(0)
        raw = {
            "x": rng.normal(size=(N * 2, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2,)).astype(np.int32),
        }
        # one real step so QAdam's warmup boundary fires and the traced
        # program is the COMPRESSED phase
        state, _ = trainer.train_step(state, raw)
        return [c.dtype for c in iter_collectives(
            trainer.trace_step(state, trainer.shard_batch(raw)))]

    assert any(d == "uint8" for d in trace())
    assert not any(d == "uint8" for d in trace(compress_intra="off"))


def test_forced_codec_engages_for_inert_hierarchical_flag():
    """``hierarchical=True`` on a non-two-tier (single-axis) mesh is
    inert — and a knob-forced flat codec must still engage the compressed
    ring there, matching what the byte accounting reports (the review
    repro: the old guard silently lowered a full-precision psum while the
    spans claimed 4x compression)."""
    from bagua_tpu.analysis.jaxpr_check import iter_collectives

    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1),
        GradientAllReduceAlgorithm(hierarchical=True), mesh=_flat_mesh(),
        bucket_bytes=256, overlap="off", autotune=False,
        compress_intra="int8",
    )
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    state = trainer.init(params)
    rng = np.random.default_rng(0)
    batch = trainer.shard_batch({
        "x": rng.normal(size=(N * 2, DIM)).astype(np.float32),
        "y": rng.integers(0, NCLASS, size=(N * 2,)).astype(np.int32),
    })
    dtypes = [c.dtype for c in iter_collectives(
        trainer.trace_step(state, batch))]
    assert any(d == "int8" for d in dtypes), dtypes
    tiers = trainer._ctx(trainer._plan).bucket_tier_bytes(0, True)
    assert tiers["flat_codec"] == "int8"


def test_zero_flat_rings_honor_forced_codec():
    """A knob-forced flat codec reaches ZeRO's scatter/gather dance too —
    the family routes around bucket_allreduce, but its rs/ag rings must
    honor the same forced policy the byte accounting reports."""
    from bagua_tpu.algorithms import ZeroOptimizerAlgorithm
    from bagua_tpu.analysis.jaxpr_check import iter_collectives

    def build(**kw):
        trainer = BaguaTrainer(
            _loss_fn, None, ZeroOptimizerAlgorithm(optax.adam(1e-2)),
            mesh=_flat_mesh(), bucket_bytes=256, overlap="off",
            autotune=False, **kw,
        )
        params = MODEL.init(
            jax.random.PRNGKey(0), jnp.zeros((1, DIM))
        )["params"]
        state = trainer.init(params)
        rng = np.random.default_rng(0)
        batch = trainer.shard_batch({
            "x": rng.normal(size=(N * 2, DIM)).astype(np.float32),
            "y": rng.integers(0, NCLASS, size=(N * 2,)).astype(np.int32),
        })
        return trainer, state, batch

    trainer, state, batch = build(compress_intra="minmax_uint8")
    dtypes = [c.dtype for c in iter_collectives(
        trainer.trace_step(state, batch))]
    assert any(d == "uint8" for d in dtypes), dtypes
    # and the compressed construction still trains
    raw = {
        "x": np.random.default_rng(1).normal(
            size=(N * 2, DIM)).astype(np.float32),
        "y": np.random.default_rng(1).integers(
            0, NCLASS, size=(N * 2,)).astype(np.int32),
    }
    state, loss = trainer.train_step(state, raw)
    assert np.isfinite(float(loss))
    # default knobs: no u8 anywhere (ZeRO stays exact)
    trainer2, state2, batch2 = build()
    assert not any(c.dtype == "uint8" for c in iter_collectives(
        trainer2.trace_step(state2, batch2)))


def _tier_ctx_factory():
    from bagua_tpu.algorithms.base import AlgorithmContext
    from bagua_tpu.bucket import BucketPlan
    from bagua_tpu.communication import collapse_trivial_axes
    from bagua_tpu.tensor import build_params

    params = {"a": jnp.zeros((1024,), jnp.float32)}
    named = build_params(params)
    plan = BucketPlan.from_declaration_buckets(
        [[p.declaration() for p in named]], named, alignment=N
    )
    mesh = build_mesh({"inter": INTER, "intra": INTRA})
    comm = BaguaCommunicator(
        collapse_trivial_axes(mesh, ("inter", "intra")), mesh)

    def ctx(**kw):
        return AlgorithmContext(
            comm=comm, internode=BaguaCommunicator("inter", mesh),
            intranode=BaguaCommunicator("intra", mesh), plan=plan,
            world_size=N, **kw,
        )

    return ctx


def _family_default_shrinks_dcn():
    ctx = _tier_ctx_factory()
    full = ctx().bucket_tier_bytes(0, True)
    comp = ctx().bucket_tier_bytes(0, True, dcn_codec="minmax_uint8")
    assert full["dcn_codec"] is None and comp["dcn_codec"] == "minmax_uint8"
    # u8 payload + 8B sidecar vs f32 shard: close to 4x, >= 3x
    assert full["dcn_bytes"] / comp["dcn_bytes"] >= 3.0
    assert comp["ici_bytes"] == full["ici_bytes"]


def _knob_overrides_family_default():
    # ... in BOTH directions
    ctx = _tier_ctx_factory()
    full = ctx().bucket_tier_bytes(0, True)
    forced_off = ctx(inter_codec="off").bucket_tier_bytes(
        0, True, dcn_codec="minmax_uint8")
    assert forced_off["dcn_bytes"] == full["dcn_bytes"]
    forced_fp8 = ctx(inter_codec="fp8_e4m3").bucket_tier_bytes(0, True)
    assert forced_fp8["dcn_codec"] == "fp8_e4m3"


def _flat_path_scatter_gather_codec():
    # flat path on the two-tier mesh: bytegrad's scatter-gather wire codec
    ctx = _tier_ctx_factory()
    flat_comp = ctx().bucket_tier_bytes(0, False,
                                        flat_codec="minmax_uint8")
    flat_full = ctx().bucket_tier_bytes(0, False)
    assert flat_full["dcn_bytes"] / flat_comp["dcn_bytes"] >= 3.0


def _trained_tiers(algo_factory):
    """The byte accounting of every bucket of a TRAINED overlap trainer's
    own context, in its streamed launch order — what the trace-time launch
    spans used to carry."""
    _, tr = _train(algo_factory, optax.sgd(0.1), 4, steps=1, overlap="on")
    assert tr._overlap_active()
    algo = tr.algorithm
    ctx = tr._ctx(tr._plan, overlap=True)
    order = ctx.bucket_launch_order(True, dcn_codec=algo.wire_codec_dcn)
    assert sorted(order) == list(range(len(tr._plan.buckets)))
    return [ctx.bucket_tier_bytes(i, True, dcn_codec=algo.wire_codec_dcn,
                                  flat_codec=algo.wire_codec_flat)
            for i in order]


def _two_level_schedule_streams_dcn_first():
    tiers = _trained_tiers(
        lambda: GradientAllReduceAlgorithm(hierarchical=True))
    assert all(t["tier"] == "two_level" for t in tiers)
    # the DCN stage carries the 1/intra shard
    assert all(t["dcn_bytes"] <= t["bytes"] // INTRA for t in tiers)
    # DCN-dominant-first: the launch order is descending DCN bytes
    dcn = [t["dcn_bytes"] for t in tiers]
    assert dcn == sorted(dcn, reverse=True)


def _bytegrad_two_level_reports_compressed_bytes():
    tiers = _trained_tiers(lambda: ByteGradAlgorithm(hierarchical=True))
    for t in tiers:
        assert t["tier"] == "two_level"
        # compressed estimate: u8 shard + sidecar, well under the f32
        # shard the tier would otherwise report
        assert t["dcn_bytes"] < t["bytes"] // INTRA


@pytest.mark.parametrize("case", [
    _family_default_shrinks_dcn,
    _knob_overrides_family_default,
    _flat_path_scatter_gather_codec,
    _two_level_schedule_streams_dcn_first,
    _bytegrad_two_level_reports_compressed_bytes,
], ids=lambda f: f.__name__.lstrip("_"))
def test_bucket_tier_bytes_codec_aware(case):
    case()


def test_env_registry_and_step_key():
    from bagua_tpu import env as env_mod

    for var in ("BAGUA_COMPRESS_INTRA", "BAGUA_COMPRESS_INTER",
                "BAGUA_AUTOPILOT_COMPRESS_CODEC"):
        assert var in env_mod.ENV_REGISTRY
    _, tr = _train(lambda: GradientAllReduceAlgorithm(hierarchical=True),
                   optax.sgd(0.1), 1, steps=1, overlap="off")
    key = tr._step_key()
    tr.compress_inter = "int8"
    # unlike the chunk knobs, the codec policy keys the step even with the
    # overlap scheduler off — the serialized construction compresses too
    assert tr._step_key() != key


def test_recommendation_path_carries_codec_policy():
    from bagua_tpu.define import BaguaHyperparameter
    from bagua_tpu.service.autotune_task_manager import AutotuneTaskManager

    trainer = BaguaTrainer(
        _loss_fn, optax.sgd(0.1),
        GradientAllReduceAlgorithm(hierarchical=True),
        mesh=build_mesh({"inter": INTER, "intra": INTRA}),
        bucket_bytes=256, overlap="off", autotune=False,
    )
    params = MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, DIM)))["params"]
    trainer.init(params)
    trainer._apply_recommendation(BaguaHyperparameter(
        compress_inter="minmax_uint8", is_hierarchical_reduce=True,
    ))
    assert trainer.compress_inter == "minmax_uint8"
    # "" keeps current; an unknown codec is ignored with a warning
    trainer._apply_recommendation(BaguaHyperparameter())
    assert trainer.compress_inter == "minmax_uint8"
    trainer._apply_recommendation(BaguaHyperparameter(compress_inter="bad"))
    assert trainer.compress_inter == "minmax_uint8"
    hp = trainer._current_hyperparameters()
    assert hp.compress_inter == "minmax_uint8"
    assert hp.compress_intra == "auto"
    # the task manager's next materialized recommendation carries it
    mgr = AutotuneTaskManager("t", is_output_autotune_log=False)
    decls = [t.declaration() for b in trainer._plan.buckets
             for t in b.tensors]
    nxt = mgr.ask_hyperparameters(100, decls, hp, 1.0)
    assert nxt.compress_inter == "minmax_uint8"


def test_compress_dcn_hint_actuates_service_recommendation():
    from bagua_tpu.service.autotune_service import AutotuneService

    service = AutotuneService(world_size=1)
    service.report_metrics({
        "model_name": "m", "rank": -1, "train_iter": 0,
        "hyperparameters": {}, "speed": 0.0,
        "perf_hints": [{"kind": "autopilot_compress_dcn",
                        "family": "bytegrad", "codec": "int8"}],
    })
    task = service._task("m")
    assert task.recommended.compress_inter == "int8"
    assert task.sample_retried is False
    # a junk codec is refused, not actuated
    service.report_metrics({
        "model_name": "m", "rank": -1, "train_iter": 1,
        "hyperparameters": {}, "speed": 0.0,
        "perf_hints": [{"kind": "autopilot_compress_dcn",
                        "family": "bytegrad", "codec": "zstd"}],
    })
    assert task.recommended.compress_inter == "int8"
    # the default codec when the hint carries none
    task.recommended.compress_inter = ""
    service.report_metrics({
        "model_name": "m", "rank": -1, "train_iter": 2,
        "hyperparameters": {}, "speed": 0.0,
        "perf_hints": [{"kind": "autopilot_compress_dcn",
                        "family": "bytegrad"}],
    })
    assert task.recommended.compress_inter == "minmax_uint8"


def test_compress_dcn_policy_action_carries_codec():
    from bagua_tpu.autopilot.policy import PolicyConfig, config_from_env

    assert config_from_env().compress_codec == "minmax_uint8"
    cfg = PolicyConfig(compress_codec="fp8_e4m3")
    assert cfg.compress_codec == "fp8_e4m3"
