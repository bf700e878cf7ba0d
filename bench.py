"""Benchmark suite: per-algorithm synthetic throughput + loss goldens.

Mirrors the reference's CI benchmark gates
(/root/reference/.buildkite/scripts/benchmark_master.sh:83-153): every
algorithm family runs ResNet50 on synthetic ImageNet batches against a
per-family img/s floor, deterministic final losses are recorded, the MoE
path gets its own run, and a BERT-Large-config LM throughput number covers
the BASELINE.json SQuAD workload.

Default invocation prints ONE JSON line — the headline ResNet50
gradient_allreduce number vs the reference's 185 img/s/GPU CI floor.
``--suite`` additionally runs every family + MoE + BERT, printing one JSON
line each and writing ``BENCH_SUITE.json``.  ``--goldens`` prints the
deterministic-loss goldens for tests/test_loss_goldens.py.

The timed modes measure a device: they refuse to run off-TPU or on a
``device_kind`` missing from the peak table, every record names the device
it ran on, and any failure is the exit code — there is no retry and no
``null`` record.  Run ``python chip_smoke.py`` first (scripts/ci.sh does).
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import optax

# Reference CI floors (img/s per V100-class GPU, benchmark_master.sh:83-84)
FAMILY_FLOORS = {
    "gradient_allreduce": 185.0,
    "bytegrad": 180.0,
    "qadam": 170.0,
    "decentralized": 150.0,
    "low_precision_decentralized": 115.0,
    "async": 190.0,
    # no reference counterpart (ZeRO is additive); gated against the plain
    # allreduce floor since it moves the same bytes per step
    "zero": 185.0,
}
# Per-chip batch: swept 32/64/128/256/512 on v5e — throughput plateaus at
# 128-256 (the step is HBM-bandwidth-bound, see _perf_fields) and regresses
# at 512.  The reference floors were gated at batch 32 per V100; img/s is
# batch-insensitive there too, so vs_baseline stays an apples-to-apples
# throughput ratio.
BATCH_PER_DEVICE = 128
IMAGE_SIZE = 224
# enough warmup/timed steps to amortize cold first trials (r5 saw them run
# ~2x slow)
WARMUP_STEPS = 5
TIMED_STEPS = 40

# Peak per-chip specs for MFU / roofline reporting, keyed by
# ``jax.devices()[0].device_kind``.  One table shared with the trainer's
# per-step obs/mfu gauge (bagua_tpu.obs.ledger owns it).
from bagua_tpu.obs.ledger import PEAK_HBM_GBPS, PEAK_TFLOPS_BF16  # noqa: E402


class BenchSanityError(RuntimeError):
    """A physically impossible number — broken timing, not fast hardware.

    Round 1 shipped 18,820 img/s/chip from a timing bug (~188 TFLOP/s of
    conv math claimed on a 197-peak chip that measures ~30% MFU on this
    model); this bound would have tripped it."""


class BenchDeviceError(RuntimeError):
    """No TPU, or a TPU the peak table does not know: a timed mode has no
    device to measure or no denominator to judge the number by."""


def _device() -> dict:
    """The device the numbers belong to, as JAX reports it — stamped on
    every record."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices)}


def _require_chip() -> dict:
    dev = _device()
    if dev["platform"] != "tpu":
        raise BenchDeviceError(
            f"timed bench modes measure a TPU; JAX found {dev['platform']!r} "
            f"({dev['device_kind']!r}) — a number from this device would "
            "not be a device metric (CPU-safe modes: --goldens, --overlap, "
            "--flat)")
    if dev["device_kind"] not in PEAK_TFLOPS_BF16:
        raise BenchDeviceError(
            f"device_kind {dev['device_kind']!r} is missing from the peak "
            f"table (bagua_tpu/obs/ledger.py: {sorted(PEAK_TFLOPS_BF16)}); "
            "add its published peaks before benchmarking on it")
    return dev


def _algorithms():
    from bagua_tpu.algorithms.async_model_average import AsyncModelAverageAlgorithm
    from bagua_tpu.algorithms.bytegrad import ByteGradAlgorithm
    from bagua_tpu.algorithms.decentralized import (
        DecentralizedAlgorithm,
        LowPrecisionDecentralizedAlgorithm,
    )
    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.algorithms.q_adam import QAdamAlgorithm
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm

    return {
        "gradient_allreduce": lambda: GradientAllReduceAlgorithm(hierarchical=False),
        "bytegrad": lambda: ByteGradAlgorithm(hierarchical=False),
        "qadam": lambda: QAdamAlgorithm(warmup_steps=2, hierarchical=False),
        "decentralized": lambda: DecentralizedAlgorithm(
            hierarchical=False, peer_selection_mode="all"
        ),
        "low_precision_decentralized": lambda: LowPrecisionDecentralizedAlgorithm(
            hierarchical=False
        ),
        "async": lambda: AsyncModelAverageAlgorithm(sync_interval_ms=100),
        "zero": lambda: ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9)),
    }


def _emit(record: dict) -> dict:
    record = {**record, **_device()}
    print(json.dumps(record), flush=True)
    return record


def _time_steps(trainer, state, data, timed=TIMED_STEPS, warmup=WARMUP_STEPS):
    """Steps are state-chained, so a host readback of the final loss forces
    every preceding step to completion: the timer brackets that readback."""
    for _ in range(warmup):
        state, loss = trainer.train_step(state, data)
    float(loss)  # drain the queue before the timer starts
    dt = None
    for _window in range(2):
        t0 = time.perf_counter()
        for _ in range(timed):
            state, loss = trainer.train_step(state, data)
        lossf = float(loss)  # forces the chained steps to completion
        w = time.perf_counter() - t0
        # best of two windows: a single ~2 s window occasionally absorbs
        # one-off host interference (r5 saw a 5% outlier on the headline
        # family); the faster window is the honest "what the chip does"
        # figure
        dt = w if dt is None else min(dt, w)
    return dt, state, lossf


def _perf_fields(trainer, state, data, dt, timed) -> dict:
    """Achieved TFLOP/s / MFU / HBM-bandwidth utilisation from XLA's cost
    model for the compiled step, plus the physically-impossible bound.

    ``flops``/``bytes accessed`` are XLA's counts for one step of the
    PER-DEVICE (SPMD-partitioned) executable — verified empirically: an
    8-way-sharded matmul on the 8-device mesh reports 1/8 of the global
    flops — so the rates below are already per-chip; no device division.
    A rate meaningfully above the chip's peak is a measurement bug (see
    :class:`BenchSanityError`) — the margins (1.25x compute, 1.5x
    bandwidth) absorb cost-model slack while still catching the ~10x
    inflation that broken fencing produces."""
    # methodology marker: r5 switched _time_steps to min-of-2 windows; the
    # field keeps cross-round comparisons honest (r1-r4 records and the
    # reference baselines are single-window)
    fields = {"timing": f"min_of_2_windows_x{timed}_steps"}
    analysis = trainer.step_cost_analysis(state, data)
    if not analysis:
        return fields
    kind = _require_chip()["device_kind"]
    steps_per_s = timed / dt
    flops = analysis.get("flops")
    if flops:
        tflops = flops * steps_per_s / 1e12
        fields["tflops_achieved"] = round(tflops, 1)
        peak = PEAK_TFLOPS_BF16[kind]
        fields["mfu"] = round(tflops / peak, 3)
        if tflops > peak * 1.25:
            raise BenchSanityError(
                f"measured {tflops:.0f} TFLOP/s/chip on a {peak:.0f}-peak "
                f"{kind}: timing is broken"
            )
    nbytes = analysis.get("bytes accessed")
    if nbytes:
        gbps = nbytes * steps_per_s / 1e9
        fields["hbm_gbps"] = round(gbps)
        peak_bw = PEAK_HBM_GBPS[kind]
        # "bytes accessed" counts every buffer touch, including those
        # served from VMEM, so it upper-bounds true HBM traffic and
        # hbm_util can read slightly above 1.0 — it is a roofline
        # indicator (≈1 → bandwidth-bound), not a literal utilisation.
        # The PROFILER-measured fields below (hbm_gbps_measured) are the
        # ground truth: per-op memory_access_breakdown separates HBM
        # from on-chip VMEM/CMEM traffic.
        fields["hbm_util"] = round(gbps / peak_bw, 3)
        if gbps > peak_bw * 1.5:
            raise BenchSanityError(
                f"measured {gbps:.0f} GB/s/chip HBM on a {peak_bw:.0f}-peak "
                f"{kind}: timing is broken"
            )
    return fields


def _measured_memory_fields(trainer, state, data) -> dict:
    """Profiler-grounded HBM bandwidth (VERDICT r3 #3): trace a few steps
    and parse per-op memory_access_breakdown."""
    from bagua_tpu.profiling import trace_memory_traffic

    holder = {"state": state, "loss": None}

    def run_step():  # enqueue only: per-step fencing would serialize dispatch
        holder["state"], holder["loss"] = trainer.train_step(
            holder["state"], data
        )

    fields = trace_memory_traffic(
        run_step, steps=5, finalize=lambda: float(holder["loss"])
    )
    if not fields:
        raise RuntimeError(
            "the profiler trace of the timed steps held no per-op memory "
            "traffic (bagua_tpu.profiling.trace_memory_traffic returned {})")
    kind = _require_chip()["device_kind"]
    peak_bw = PEAK_HBM_GBPS[kind]
    if fields["hbm_gbps_measured"] > peak_bw:
        raise BenchSanityError(
            f"profiler-measured {fields['hbm_gbps_measured']} GB/s HBM "
            f"exceeds the {peak_bw:.0f} GB/s {kind} peak"
        )
    return {
        "hbm_gbps_measured": fields["hbm_gbps_measured"],
        "vmem_gb_per_step": fields["vmem_gb_per_step"],
        "hbm_gb_per_step": fields["hbm_gb_per_step"],
        "hbm_util_measured": round(fields["hbm_gbps_measured"] / peak_bw, 3),
    }


def bench_family(family: str, algo_factory, mesh, n_dev: int,
                 batch_per_device: int = BATCH_PER_DEVICE,
                 image_dtype=jnp.float32, suffix_config: bool = False,
                 remat: bool = False) -> dict:
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.models.resnet import ResNet50, classification_loss_fn

    model = ResNet50(num_classes=1000, remat=remat)
    batch = batch_per_device * n_dev
    # bf16 image input halves the input pipeline's HBM traffic (the first
    # conv reads the batch at full resolution); the model computes in bf16
    # internally either way
    images = jnp.zeros((batch, IMAGE_SIZE, IMAGE_SIZE, 3), image_dtype)
    labels = jnp.zeros((batch,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)

    algo = algo_factory()
    trainer = BaguaTrainer(
        classification_loss_fn(model, batch_stats=variables["batch_stats"]),
        None if algo.owns_optimizer else optax.sgd(0.1, momentum=0.9),
        algo,
        mesh=mesh,
        autotune=False,
    )
    state = trainer.init(variables["params"])
    data = trainer.shard_batch({"images": images, "labels": labels})
    try:
        dt, state, _ = _time_steps(trainer, state, data)
        perf = _perf_fields(trainer, state, data, dt, TIMED_STEPS)
        perf.update(_measured_memory_fields(trainer, state, data))
    finally:
        if hasattr(algo, "abort"):  # stop the async averaging thread even
            algo.abort()           # when timing/sanity raises mid-record

    per_device = TIMED_STEPS * batch / dt / n_dev
    floor = FAMILY_FLOORS[family]
    # sweep records disambiguate by config; the driver headline keeps its
    # canonical metric name (config is visible in image_dtype/batch fields)
    suffix = ""
    if suffix_config:
        if image_dtype != jnp.float32:
            suffix += "_bf16in"
        if batch_per_device != BATCH_PER_DEVICE:
            suffix += f"_b{batch_per_device}"
        if remat:
            suffix += "_remat"
    return {
        "metric": f"resnet50_{family}_imgs_per_sec_per_chip{suffix}",
        "value": round(per_device, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(per_device / floor, 3),
        "batch_per_chip": batch_per_device,
        "image_dtype": jnp.dtype(image_dtype).name,
        "remat": remat,
        **perf,
    }


def _bench_moe_impl(mesh, n_dev: int, dropless: bool, seq: int = 512,
                    timed: int = 10, measure: bool = False):
    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.model_parallel.moe import MoEMLP, moe_lm_loss_fn
    from bagua_tpu.model_parallel.moe.layer import globalize_expert_params
    from bagua_tpu.models.transformer import TransformerConfig, TransformerLM
    from bagua_tpu.parallel.mesh import build_mesh

    ep = n_dev if n_dev > 1 else 1
    cfg = TransformerConfig(
        vocab_size=32768, d_model=512, n_heads=8, n_layers=4, d_ff=2048,
        max_seq_len=seq, remat=(seq > 512),
    )
    model = TransformerLM(
        cfg,
        mlp_factory=lambda i: (
            lambda: MoEMLP(n_experts=max(8, 2 * ep), d_ff=cfg.d_ff,
                           ep_size=ep, dropless=dropless)
        ) if i % 2 == 1 else None,
    )
    batch = 8 * n_dev
    tokens = jnp.zeros((batch, cfg.max_seq_len + 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:2, :-1])["params"]
    moe_mesh = build_mesh({"dp": 1, "ep": ep}) if ep > 1 else mesh
    kwargs = {"expert_axis": "ep"} if ep > 1 else {}
    trainer = BaguaTrainer(
        moe_lm_loss_fn(model), optax.adam(1e-4),
        GradientAllReduceAlgorithm(hierarchical=False),
        mesh=moe_mesh, autotune=False, **kwargs,
    )
    state = trainer.init(
        globalize_expert_params(params, jax.random.PRNGKey(1), ep_size=ep)
        if ep > 1 else params
    )
    data = trainer.shard_batch({"tokens": tokens})
    dt, state, _ = _time_steps(trainer, state, data, timed=timed)
    tps = timed * batch * cfg.max_seq_len / dt
    measured = {}
    if measure:  # only the run whose fields land in a record pays the trace
        measured = _measured_memory_fields(trainer, state, data)
    return tps, measured


def bench_moe(mesh, n_dev: int) -> dict:
    """Expert-parallel MoE throughput (reference MoE CI run,
    benchmark_master.sh:126-153; here tokens/s on the transformer MoE)."""
    tokens_per_sec, measured = _bench_moe_impl(mesh, n_dev, dropless=False,
                                               measure=True)
    # metric renamed when the model grew from 2 to 8 experts — the old
    # moe_transformer_tokens_per_sec numbers are not comparable
    return {
        "metric": "moe_transformer_e8_tokens_per_sec",
        "value": round(tokens_per_sec, 0),
        **measured,
        "timing": "min_of_2_windows_x10_steps",
        "unit": "tok/s",
        "vs_baseline": None,
        "baseline_rationale": "no reference counterpart: the reference's "
                              "MoE CI is an exact-loss gate, not a "
                              "throughput benchmark (benchmark_master.sh:"
                              "126-153); record tracks round-over-round",
    }


def bench_moe_dropless(mesh, n_dev: int, capacity_tps=None) -> dict:
    """Dropless (sort + grouped-matmul) MoE vs the GShard capacity path on
    the identical model/config (``vs_baseline`` = dropless/capacity).

    At this T (4K tokens/layer) the dense dispatch einsum is still
    MXU-friendly, so capacity is expected somewhat faster — dropless buys
    exact routing (no token ever dropped) and O(T*k) memory where the
    capacity dispatch tensor is O(T^2/E).  MEASURED crossover on v5e
    (same model, seq swept, batch 8, E=8, k=2 cf=1.25):

        tokens/layer   capacity tok/s   dropless tok/s
        4,096          155,798          134,482   (capacity 1.16x)
        8,192          179,986          167,807   (capacity 1.07x)
        16,384         154,860          164,571   (DROPLESS 1.06x)
        32,768         106,282          158,159   (DROPLESS 1.49x)

    Crossover ~12-16K tokens/layer; ``bench_moe_longseq`` records the
    32K point where dropless is the right default."""
    if capacity_tps is None:
        capacity_tps, _ = _bench_moe_impl(mesh, n_dev, dropless=False)
    dropless_tps, measured = _bench_moe_impl(mesh, n_dev, dropless=True,
                                             measure=True)
    return {
        "metric": "moe_dropless_e8_tokens_per_sec",
        "value": round(dropless_tps, 0),
        **measured,
        "timing": "min_of_2_windows_x10_steps",
        "unit": "tok/s",
        "vs_baseline": round(dropless_tps / capacity_tps, 3),
    }


def bench_moe_longseq(mesh, n_dev: int) -> dict:
    """The 32K-tokens/layer point of the measured dropless/capacity
    crossover (see :func:`bench_moe_dropless`): dropless routing is the
    right default in this regime — the capacity path's O(T^2/E) dispatch
    tensor collapses its throughput (measured 1.49x on v5e)."""
    cap, _ = _bench_moe_impl(mesh, n_dev, dropless=False, seq=4096, timed=5)
    drop, measured = _bench_moe_impl(mesh, n_dev, dropless=True, seq=4096,
                                     timed=5, measure=True)
    return {
        "metric": "moe_dropless_seq4096_tokens_per_sec",
        "value": round(drop, 0),
        **measured,
        "timing": "min_of_2_windows_x5_steps",
        "unit": "tok/s",
        "vs_baseline": round(drop / cap, 3),
    }


BERT_V100_PEAK_TFLOPS = 125.0  # V100 tensor-core peak (AMP), per NVIDIA spec
#: swept 8/16/32 per chip on v5e (r5): see BENCH_BERT_SWEEP.json
BERT_BATCH_PER_CHIP = 8


def bench_bert(mesh, n_dev: int, batch_per_chip: int = BERT_BATCH_PER_CHIP,
               suffix_config: bool = False) -> dict:
    """BERT-Large-config LM throughput (BASELINE.json: ByteGrad/QAdam on
    BERT-Large SQuAD; seq 384 as in SQuAD fine-tuning)."""
    from bagua_tpu.algorithms.bytegrad import ByteGradAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.models.transformer import (
        TransformerLM, bert_large_config, lm_loss_fn,
    )

    cfg = bert_large_config(max_seq_len=384)
    model = TransformerLM(cfg)
    batch = batch_per_chip * n_dev
    tokens = jnp.zeros((batch, cfg.max_seq_len + 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:2, :-1])["params"]
    trainer = BaguaTrainer(
        lm_loss_fn(model), optax.adamw(1e-4), ByteGradAlgorithm(hierarchical=False),
        mesh=mesh, autotune=False,
    )
    state = trainer.init(params)
    data = trainer.shard_batch({"tokens": tokens})
    dt, state, _ = _time_steps(trainer, state, data, timed=10)
    perf = _perf_fields(trainer, state, data, dt, 10)
    perf.update(_measured_memory_fields(trainer, state, data))
    seq_per_sec = 10 * batch / dt
    # Baseline accounting (VERDICT r4 #4, ADVICE): the reference publishes
    # BERT-Large finetune results only as epoch-time charts (README.md:
    # 31-36) and paper scaling curves (arXiv 2107.01499) — no absolute
    # 8xV100 seq/s figure survives in its repo.  An MFU-PARITY grant
    # (give an AMP V100's 125 TFLOP/s peak the SAME utilization this chip
    # measures) algebraically CANCELS the measured MFU:
    # seq_per_sec / baseline == chip_peak / V100_peak — a constant silicon
    # ratio, not a measured comparison.  It is therefore reported as
    # ``peak_flops_ratio`` and ``vs_baseline`` stays null so JSON readers
    # don't mistake silicon for measurement.
    peak_ratio = None
    baseline = None
    if perf.get("mfu") and perf.get("tflops_achieved"):
        flops_per_seq = perf["tflops_achieved"] * 1e12 / seq_per_sec
        baseline = BERT_V100_PEAK_TFLOPS * 1e12 * perf["mfu"] / flops_per_seq
        peak_ratio = round(seq_per_sec / baseline, 3)
    suffix = (f"_b{batch_per_chip}"
              if suffix_config and batch_per_chip != BERT_BATCH_PER_CHIP
              else "")
    return {
        "metric": f"bert_large_bytegrad_seqs_per_sec{suffix}",
        "value": round(seq_per_sec, 2),
        "unit": "seq/s",
        "batch_per_chip": batch_per_chip,
        "vs_baseline": None,
        "baseline_rationale": "no measured reference baseline survives; "
                              "peak_flops_ratio is the MFU-parity identity "
                              "(chip peak / V100 peak), a silicon ratio",
        "peak_flops_ratio": peak_ratio,
        "mfu_parity_v100_seq_s": round(baseline, 2) if baseline else None,
        **perf,
    }


VGG16_HEADLINE_FLOOR = 126.5  # img/s per V100, bagua + bagua-net
# (/root/reference/rust/bagua-net/README.md:65-66 — the headline benchmark)


#: VGG is MXU-bound (61% MFU), and bigger batches feed the systolic array
#: better than ResNet's bandwidth-bound step: swept 64/128/256 per chip —
#: 970/1,323/1,401 img/s — so VGG's standard config is 256 + bf16 input
#: (ResNet's optimum stays 128, see BENCH_RESNET_SWEEP.json).
VGG_BATCH_PER_DEVICE = 256
VGG_IMAGE_DTYPE = jnp.bfloat16


def bench_vgg16(mesh, n_dev: int) -> dict:
    """The reference's flagship number: VGG16 synthetic ImageNet throughput
    (bagua-net/README.md:48-81, 4x8 V100 over 100 GbE)."""
    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.models.vgg import VGG16, vgg_loss_fn

    model = VGG16(num_classes=1000)
    batch = VGG_BATCH_PER_DEVICE * n_dev
    images = jnp.zeros((batch, IMAGE_SIZE, IMAGE_SIZE, 3), VGG_IMAGE_DTYPE)
    labels = jnp.zeros((batch,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), images[:2])["params"]
    trainer = BaguaTrainer(
        vgg_loss_fn(model), optax.sgd(0.1, momentum=0.9),
        GradientAllReduceAlgorithm(hierarchical=False), mesh=mesh,
        autotune=False,
    )
    state = trainer.init(params)
    data = trainer.shard_batch({"images": images, "labels": labels})
    dt, state, _ = _time_steps(trainer, state, data)
    perf = _perf_fields(trainer, state, data, dt, TIMED_STEPS)
    perf.update(_measured_memory_fields(trainer, state, data))
    per_device = TIMED_STEPS * batch / dt / n_dev
    return {
        "metric": "vgg16_gradient_allreduce_imgs_per_sec_per_chip",
        "value": round(per_device, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(per_device / VGG16_HEADLINE_FLOOR, 3),
        "batch_per_chip": VGG_BATCH_PER_DEVICE,
        "image_dtype": jnp.dtype(VGG_IMAGE_DTYPE).name,
        **perf,
    }


def bench_decode(mesh, n_dev: int) -> dict:
    """KV-cache autoregressive decode throughput (tokens/s) on the
    transformer LM — the inference path (additive; no reference
    counterpart)."""
    from bagua_tpu.models.generate import generate
    from bagua_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=32768, d_model=512, n_heads=8,
                            n_layers=4, d_ff=2048, max_seq_len=512)
    model = TransformerLM(cfg)
    # decode is params-bandwidth-bound (the weights stream from HBM once
    # per token regardless of batch), so throughput scales with batch until
    # the KV-cache reads catch up: swept 8 / 32 / 128 / 256 / 512 ->
    # 36.9k / 56.6k / 109.3k / 108.6k / 124.3k tok/s on v5e (saturating at
    # 128-256; 512 buys +14% at 4x the per-token latency).  128 is the
    # serving operating point this record reports.
    batch, prompt_len, new = 128, 32, 256
    prompt = jnp.zeros((batch, prompt_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]

    # same cold-trial amortization as _time_steps: each generate() is one
    # dispatch of a 287-step scan, so a handful of calls suffices.  Calls
    # are CHAINED (each prompt is the previous output's head) so the final
    # readback fences every iteration, per the _time_steps rationale.
    warmup, timed = 2, 8

    def chained(p, iters):
        for _ in range(iters):
            out = generate(model, params, p, new)
            p = out[:, :prompt_len]
        return p, out

    prompt, out = chained(prompt, warmup)
    float(out.sum())  # drain before the timer
    t0 = time.perf_counter()
    prompt, out = chained(prompt, timed)
    float(out.sum())  # readback fence
    dt = time.perf_counter() - t0
    return {
        "metric": "lm_decode_tokens_per_sec",
        "value": round(timed * batch * new / dt, 1),
        "timing": "single_window_8x_chained_generates",
        "unit": "tok/s",
        "vs_baseline": None,
        "baseline_rationale": "no reference counterpart: the reference is "
                              "a training framework with no generation/"
                              "decode path at all; record tracks "
                              "round-over-round",
        "batch": batch,
    }


def bench_longctx(mesh, n_dev: int) -> dict:
    """Long-context LM throughput — the flash-attention (Pallas) hot path.
    ``vs_baseline`` is the speedup over the same model with the plain
    materializing attention (the reference framework's only option, SURVEY.md
    §5.7: it has no long-context support at all)."""
    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn,
    )
    from bagua_tpu.ops.flash_attention import reference_attention

    cfg = TransformerConfig(
        vocab_size=32768, d_model=1024, n_heads=16, n_layers=4, d_ff=4096,
        max_seq_len=4096, remat=True, remat_policy="dots_no_batch",
    )
    batch = 2 * n_dev
    tokens = jnp.zeros((batch, cfg.max_seq_len + 1), jnp.int32)

    def run(attn_fn, want_perf=False):
        model = TransformerLM(cfg, attn_fn=attn_fn)
        params = model.init(jax.random.PRNGKey(0), tokens[:2, :128])["params"]
        trainer = BaguaTrainer(
            lm_loss_fn(model), optax.adamw(1e-4),
            GradientAllReduceAlgorithm(hierarchical=False),
            mesh=mesh, autotune=False,
        )
        state = trainer.init(params)
        data = trainer.shard_batch({"tokens": tokens})
        dt, state, _ = _time_steps(trainer, state, data, timed=10)
        perf = (
            _perf_fields(trainer, state, data, dt, 10) if want_perf else {}
        )
        if want_perf:
            perf.update(_measured_memory_fields(trainer, state, data))
        return 10 * batch * cfg.max_seq_len / dt, perf

    flash_tps, perf = run(None, want_perf=True)  # Pallas kernel on TPU
    plain_tps, _ = run(
        lambda q, k, v, dtype: reference_attention(q, k, v, dtype)
    )
    return {
        "metric": "longctx_lm_seq4096_tokens_per_sec",
        "value": round(flash_tps, 0),
        "unit": "tok/s",
        "vs_baseline": round(flash_tps / plain_tps, 3),
        **perf,
    }


def golden_task(batch_size: int = None):
    """The fixed seed/task of the exact-loss gate, shared with the elastic
    cross-topology resume gate (tests/test_elastic_resume.py) so a
    save/resize/restore run is measured against the SAME trajectory the
    goldens certify.  Returns ``(loss_fn, params, batch)``; the batch is
    the full global batch — identical under any dp split that divides it,
    which is what makes final losses comparable across world sizes."""
    from bagua_tpu.models.mlp import MLP

    if batch_size is None:
        batch_size = 8 * len(jax.devices())
    model = MLP(features=(32, 8))
    x = jax.random.normal(jax.random.PRNGKey(0), (batch_size, 4))
    y = jnp.argmax(x @ jax.random.normal(jax.random.PRNGKey(1), (4, 8)), -1)
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    return loss_fn, params, {"x": x, "y": y}


def loss_goldens(n_steps: int = 30) -> dict:
    """Deterministic final losses per family on a fixed seed/task — the
    analog of the reference's exact-loss CI gate (benchmark_master.sh:98-108).
    Platform-specific (reduction orders differ CPU vs TPU); the test asserts
    them on the 8-device CPU mesh."""
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    n_dev = len(jax.devices())
    mesh = build_mesh({"dp": n_dev})
    loss_fn, params, batch = golden_task()
    x, y = batch["x"], batch["y"]

    out = {}
    for family, factory in _algorithms().items():
        algo = factory()
        trainer = BaguaTrainer(
            loss_fn,
            None if algo.owns_optimizer else optax.sgd(0.1),
            algo, mesh=mesh, autotune=False,
        )
        state = trainer.init(params)
        batch = {"x": x, "y": y}
        for _ in range(n_steps):
            state, loss = trainer.train_step(state, batch)
        if hasattr(algo, "abort"):
            algo.abort()
        out[family] = round(float(loss), 6)

    # staged (hierarchical) ZeRO needs a tiered mesh; its reduction order
    # differs from flat ZeRO (rs(intra)+allreduce(inter)), so it gets its
    # own exact golden
    from bagua_tpu.algorithms.zero import ZeroOptimizerAlgorithm
    from bagua_tpu.parallel.mesh import hierarchical_mesh

    trainer = BaguaTrainer(
        loss_fn, None,
        ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9),
                               hierarchical=True),
        mesh=hierarchical_mesh(intra_size=max(1, n_dev // 2)),
        autotune=False,
    )
    state = trainer.init(params)
    for _ in range(n_steps):
        state, loss = trainer.train_step(state, {"x": x, "y": y})
    out["zero_hierarchical"] = round(float(loss), 6)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", action="store_true",
                    help="run every algorithm family + MoE + BERT")
    ap.add_argument("--goldens", action="store_true",
                    help="print deterministic loss goldens and exit")
    ap.add_argument("--resnet-sweep", action="store_true",
                    help="sweep ResNet input dtype (f32/bf16) x batch "
                         "(128/256), writing BENCH_RESNET_SWEEP.json")
    ap.add_argument("--overlap", action="store_true",
                    help="measure the overlap scheduler on vs off (img/s + "
                         "profiler comm-hidden ratio), writing "
                         "BENCH_OVERLAP.json")
    ap.add_argument("--flat", action="store_true",
                    help="measure the flat-resident state layout on vs off "
                         "(throughput + fused-optimizer compile audit), "
                         "writing BENCH_FLAT.json")
    ap.add_argument("--only", default=None,
                    help="re-measure ONE record through the driver and "
                         "update it in BENCH_SUITE.json (a family name, or "
                         "vgg16/bert/moe/moe_dropless/moe_longseq/longctx/"
                         "decode) — single-record refreshes stay "
                         "reproducible instead of hand-spliced")
    args = ap.parse_args()

    from bagua_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    if args.goldens:
        print(json.dumps(loss_goldens(), indent=1))
        return

    if args.overlap:
        from benchmarks.overlap_bench import run_suite

        run_suite("BENCH_OVERLAP.json")
        return

    if args.flat:
        from benchmarks.flat_resident_bench import run_suite

        run_suite("BENCH_FLAT.json")
        return

    # everything below times a device
    _require_chip()

    from bagua_tpu.parallel.mesh import build_mesh

    devices = jax.devices()
    n_dev = len(devices)
    mesh = build_mesh({"dp": n_dev}, devices)

    if args.only:
        name = args.only
        if name in _algorithms():
            rec = bench_family(name, _algorithms()[name], mesh, n_dev,
                               image_dtype=jnp.bfloat16)
        else:
            fns = {"vgg16": bench_vgg16, "bert": bench_bert,
                   "moe": bench_moe, "moe_dropless": bench_moe_dropless,
                   "moe_longseq": bench_moe_longseq,
                   "longctx": bench_longctx, "decode": bench_decode}
            if name not in fns:
                raise SystemExit(f"--only {name!r}: unknown bench (families: "
                                 f"{sorted(_algorithms())} or {sorted(fns)})")
            rec = fns[name](mesh, n_dev)
        rec = _emit(rec)
        import os

        if os.path.exists("BENCH_SUITE.json"):
            records = json.load(open("BENCH_SUITE.json"))
            records = [rec if r["metric"] == rec["metric"] else r
                       for r in records]
            if rec["metric"] not in {r["metric"] for r in records}:
                records.append(rec)
            with open("BENCH_SUITE.json", "w") as f:
                json.dump(records, f, indent=1)
        return

    if args.resnet_sweep:
        factory = _algorithms()["gradient_allreduce"]
        # dtype x batch grid, then the remat A/B on the bytes-bound trunk
        # (VERDICT r4 #6): remat trades recompute FLOPs for HBM bytes, and
        # by shrinking live activations may also admit a larger batch (512)
        configs = [
            dict(image_dtype=jnp.float32, batch_per_device=128),
            dict(image_dtype=jnp.float32, batch_per_device=256),
            dict(image_dtype=jnp.bfloat16, batch_per_device=128),
            dict(image_dtype=jnp.bfloat16, batch_per_device=256),
            dict(image_dtype=jnp.bfloat16, batch_per_device=128, remat=True),
            dict(image_dtype=jnp.bfloat16, batch_per_device=256, remat=True),
            dict(image_dtype=jnp.bfloat16, batch_per_device=512, remat=True),
        ]
        records = [
            _emit(bench_family("gradient_allreduce", factory, mesh, n_dev,
                               suffix_config=True, **cfg))
            for cfg in configs
        ]
        with open("BENCH_RESNET_SWEEP.json", "w") as f:
            json.dump(records, f, indent=1)
        return

    if args.suite:
        # same standard config as the headline (bf16 input): one metric
        # name == one configuration across invocations
        records = [
            _emit(bench_family(family, factory, mesh, n_dev,
                               image_dtype=jnp.bfloat16))
            for family, factory in _algorithms().items()
        ]
        records.append(_emit(bench_vgg16(mesh, n_dev)))
        moe_rec = _emit(bench_moe(mesh, n_dev))
        records.append(moe_rec)
        records.append(_emit(bench_moe_dropless(
            mesh, n_dev, capacity_tps=moe_rec["value"])))
        for fn in (bench_moe_longseq, bench_bert, bench_longctx,
                   bench_decode):
            records.append(_emit(fn(mesh, n_dev)))
        with open("BENCH_SUITE.json", "w") as f:
            json.dump(records, f, indent=1)
        return

    # The headline.  STANDARD CONFIG (round 4+): bf16 image input, the
    # measured optimum (BENCH_RESNET_SWEEP.json: +0.6% over round 3's f32;
    # the model computes in bf16 either way).  Used by BOTH the headline and
    # --suite so the canonical metric name denotes exactly one
    # configuration; every record carries image_dtype.
    _emit(bench_family("gradient_allreduce",
                       _algorithms()["gradient_allreduce"], mesh, n_dev,
                       image_dtype=jnp.bfloat16))


if __name__ == "__main__":
    main()
