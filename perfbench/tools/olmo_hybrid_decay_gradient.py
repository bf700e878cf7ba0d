"""Why the first gradient of the linear layers' decay vectors (``A_log``,
``dt_bias``: one entry a head) reads 0.13 to 0.82 from the float32 reference
in the Olmo-Hybrid cell, by the seed (PERF.md §6, PR 56).  On ONE chip, at the
published widths, the cell's own weights and replay batch of ``--seed``:

    python3 perfbench/tools/olmo_hybrid_decay_gradient.py --seed 3000000066

The gradient of the loss the trainer's step differentiates (``lm_loss_fn``)
on the leaves that carry the decay — ``A_log``, ``dt_bias`` and the gates'
in-projection — a sequence at a time, the sequences averaged, from

- ``bfloat16``: the model as timed (bfloat16 products, every kernel);
- ``float32``: the same program at ``dtype=float32`` — the ``gdn_*`` kernels
  and the row passes on float32 operands, their products and XLA's at the
  highest precision; the one softmax layer's attention by the reference's
  blocked plain form (the flash kernels' float32 blocks do not fit VMEM at
  8,192 rows, and the program's plain form keeps 8 GB of scores);

each against ``reference/olmo_hybrid.py``'s float32 gradient, a layer's
vector a head at a time: a head's decay rate ``A``, the reference's entry and
what each variant adds to it.  If the distance closes in float32 the gap is
rounding and the kernels' ``dg`` path is sound; if it stays, it is the
program's.  Then the rule's probe, forward and backward, at the timed rows:
the ``gdn_fwd`` / ``gdn_bwd`` kernels on bfloat16 and on float32 operands
and the scan with its state in bfloat16, each against the float32 scan's VJP
(the readings behind ``reference.RULE_TOLERANCE``).

One JSON line a reading.  ``--tiny`` rehearses the plumbing on the CPU;
``--skip-gradient`` reads the probe alone (on the CPU the ``jax.numpy``
chunks at the timed rows: what PERF.md has beside the chip's readings).
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "olmo-hybrid-7b.pretrain8192-b1-dp4"
#: the leaves that carry the decay
ENDS = ("linear_attn/A_log", "linear_attn/dt_bias",
        "linear_attn/in_proj_ba/kernel")


#: ``--tiny``: the published shapes' kind at a small size
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 2,
        "num_key_value_heads": 2, "linear_num_key_heads": 6,
        "linear_num_value_heads": 6, "vocab_size": 250}


def say(**line) -> None:
    print(json.dumps(line), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=3000000066)
    parser.add_argument("--skip-gradient", action="store_true")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()

    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.models.transformer import lm_loss_fn
    from perfbench import cells

    cell = cells.resolve(CELL)
    builder = cells.load_plugin("builders", cell.config["builder"])
    reference = cells.load_plugin("reference", cell.config["builder"])
    config, traffic = cell.config, cell.traffic
    if args.tiny:
        config = {**config, **TINY}
        traffic = {**traffic, "seq_len": 256, "model": {"remat": True}}
    hyper = reference.hyperparameters(config)
    seq = int(traffic["seq_len"])
    started = time.perf_counter()
    clock = lambda: round(time.perf_counter() - started, 1)

    if not args.skip_gradient:
        decay_gradients(args, builder, reference, config, traffic, hyper,
                        seq, clock, jax, jnp, np, lm_loss_fn)
    rule_readings(args, builder, reference, hyper, seq, clock, jax, jnp)


def decay_gradients(args, builder, reference, config, traffic, hyper, seq,
                    clock, jax, jnp, np, lm_loss_fn) -> None:
    import os

    from bagua_tpu.models.transformer import TransformerLM
    def blocked_attention(q, k, v, dtype):
        return reference.causal_attention(q, k, v).astype(dtype)

    as_timed = builder.make_model(config, traffic)
    # whole-block remat: the least memory
    in_float32 = builder.make_model(
        config, {"model": {"remat": True, "dtype": "float32"}})
    models = {"bfloat16": as_timed,
              "float32": TransformerLM(in_float32.cfg,
                                       attn_fn=blocked_attention)}
    params = builder.make_params(models["bfloat16"], args.seed)
    # the cell's replay batch (builders/olmo_hybrid.py::build)
    tokens = np.random.default_rng([args.seed, 0]).integers(
        0, int(config["vocab_size"]), size=(4, seq + 1),
        dtype=np.int32)
    pick = lambda tree: {
        name: leaf for name, leaf in reference.watched(tree).items()
        if name.endswith(ENDS)}
    mean = lambda parts: {
        name: sum(np.asarray(p[name], np.float64) for p in parts) / len(parts)
        for name in parts[0]}

    def of_model(model, precision):
        grad = jax.jit(lambda p, t: pick(jax.grad(lm_loss_fn(model))(
            p, {"tokens": t})))
        flash = os.environ.get("BAGUA_FLASH_ATTENTION")
        if precision == "highest":
            os.environ["BAGUA_FLASH_ATTENTION"] = "0"
        try:
            with jax.default_matmul_precision(precision):
                return mean([jax.device_get(grad(params, tokens[i:i + 1]))
                             for i in range(len(tokens))])
        finally:
            os.environ.pop("BAGUA_FLASH_ATTENTION", None)
            if flash is not None:
                os.environ["BAGUA_FLASH_ATTENTION"] = flash

    got = {"bfloat16": of_model(models["bfloat16"], "default")}
    say(done="bfloat16", at_s=clock())
    try:
        got["float32"] = of_model(models["float32"], "highest")
    except Exception as e:  # noqa: BLE001 - the other readings still count
        say(failed="float32", error=repr(e)[:2000])
    say(done="float32", at_s=clock())
    # the reference a sequence at a time (its own loop keeps every
    # sequence's gradient until the last: four chips' worth)
    parts = []
    for i in range(len(tokens)):
        _, grads = reference.loss_and_grads(params, tokens[i:i + 1], hyper,
                                            jax.local_devices()[:1])
        parts.append(jax.device_get(pick(grads)))
        del grads
        say(done=f"reference sequence {i}", at_s=clock())
    want = mean(parts)

    for name in sorted(want):
        halves = {"": slice(None)}
        if name.endswith("kernel"):
            heads = want[name].shape[1] // 2
            halves = {"": slice(None), "[b]": slice(0, heads),
                      "[a]": slice(heads, None)}
        for tag, at in halves.items():
            w = want[name][..., at]
            say(seed=args.seed, leaf=name + tag, distance={
                variant: float(np.linalg.norm(g[name][..., at] - w)
                               / np.linalg.norm(w))
                for variant, g in got.items()})
    for name in sorted(want):
        if name.endswith("A_log"):
            rate = np.exp(np.asarray(
                params[name.split("/")[0]]["linear_attn"]["A_log"]))
            order = np.argsort(-np.abs(want[name]))[:6]
            say(seed=args.seed, leaf=name,
                largest_heads=[{
                    "head": int(h), "A": float(rate[h]),
                    "reference": float(want[name][h]),
                    **{variant: float(g[name][h] - want[name][h])
                       for variant, g in got.items()}} for h in order])


def rule_readings(args, builder, reference, hyper, seq, clock, jax,
                  jnp) -> None:
    probe = reference.rule_probe(args.seed, seq, hyper)
    want = reference.rule_by_scan(*probe)
    rounded = reference.rule_by_scan(*probe, scan_dtype="bfloat16")
    say(seed=args.seed, rule="scan with a bfloat16 state",
        distance=reference.rule_distance(rounded, want), at_s=clock())
    for dtype, precision in ((jnp.bfloat16, "default"),
                             (jnp.float32, "highest")):
        with jax.default_matmul_precision(precision):
            got = builder.system_rule(reference, args.seed, seq, hyper, dtype)
        say(seed=args.seed, rule=f"system, {jnp.dtype(dtype).name} operands",
            distance=reference.rule_distance(got, want), at_s=clock())


if __name__ == "__main__":
    main()
