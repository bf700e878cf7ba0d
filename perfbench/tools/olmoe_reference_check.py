"""How close the system comes to ``reference/olmoe.py`` at the PUBLISHED
widths, outside any timed window; the readings behind the reference's
``LOSS_TOLERANCE`` (PERF.md §6, PR 28).  Run on the chip:

    python3 perfbench/tools/olmoe_reference_check.py logits  [--seed N]
    python3 perfbench/tools/olmoe_reference_check.py probes  [--seed N]

``logits``: one seeded sequence of the cell's length through the system's
model and through the reference, both at float32 with exact products, so
that both break the top-8's near-ties the same way; prints the largest
|difference| and the logits' own scale.

``probes``: the reference's loss triple on the cell's replay batch (the
numbers a ``run.py`` of the same seed prints as ``reference_losses``), and
the same replay with a fault the tolerance has to see: the weights rounded
to bfloat16 at the start and after every update (the nearest precision
below the float32 weights the configuration states), and one expert a
token dropped (7 of 64).  Each must differ from the clean triple by more
than the step's ``LOSS_TOLERANCE`` on some step.

One JSON line each.  Each mode is a process of its own: the chip belongs to
one at a time.
"""

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "olmoe-1b-7b.pretrain4096-dp1"


def logits(cell, builder, reference, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    float32 = {**cell.traffic, "model": {**cell.traffic.get("model", {}),
                                         "dtype": "float32"},
               "moe": {**cell.traffic.get("moe", {}), "dtype": "float32"}}
    model = builder.make_model(cell.config, float32)
    params = builder.make_params(model, seed)
    tokens = np.random.default_rng([seed, 0]).integers(
        0, int(cell.config["vocab_size"]),
        size=(1, int(cell.traffic["seq_len"])), dtype=np.int32)
    hyper = reference.hyperparameters(cell.config)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
        want = jax.jit(lambda p, t: reference.logits_fn(p, t, hyper))(
            params, tokens)
    diff = jnp.abs(got - want)
    return {"mode": "logits", "seed": seed, "tokens": int(tokens.size),
            "max_abs_diff": float(diff.max()),
            "mean_abs_diff": float(diff.mean()),
            "max_abs_logit": float(jnp.abs(want).max()),
            "rows_with_another_argmax": int(
                (got.argmax(-1) != want.argmax(-1)).sum())}


def probes(cell, builder, reference, seed: int) -> dict:
    import jax
    import numpy as np

    model = builder.make_model(cell.config, cell.traffic)
    tokens = np.random.default_rng([seed, 0]).integers(
        0, int(cell.config["vocab_size"]),
        size=(int(cell.traffic["batch_per_chip"]),
              int(cell.traffic["seq_len"]) + 1), dtype=np.int32)
    hyper = reference.hyperparameters(cell.config)
    # reduce_precision, not a pair of converts: XLA may drop those
    # (xla_allow_excess_precision) and did, on the v5e
    round_to_bf16 = jax.jit(lambda tree: jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=7), tree),
        donate_argnums=0)

    def replay(hyper, after_update=None):
        params = builder.make_params(model, seed)
        if after_update is not None:
            params = after_update(params)
        return reference.replay_losses(
            params, tokens, int(cell.traffic["replay_steps"]),
            cell.traffic["optimizer"],
            int(cell.traffic["reference_micro_batch"]), hyper, after_update)

    clean = replay(hyper)
    out = {"mode": "probes", "seed": seed, "reference": clean,
           "tolerance": reference.LOSS_TOLERANCE}
    faults = {
        "bf16_weights": replay(hyper, round_to_bf16),
        "dropped_expert": replay({**hyper, "experts_per_token":
                                  hyper["experts_per_token"] - 1}),
    }
    for name, triple in faults.items():
        out[name] = triple
        out[f"{name}_abs_diff"] = [abs(a - b) for a, b in zip(triple, clean)]
        out[f"{name}_agrees"] = reference.agree(triple, clean)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("logits", "probes"))
    ap.add_argument("--seed", type=int, default=2147483711)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    if args.mode == "logits":
        # float32 operands: the XLA attention and the kernels' own float32
        # path; the flash kernels take bfloat16 blocks
        os.environ["BAGUA_FLASH_ATTENTION"] = "0"
    from perfbench import cells

    cell = cells.resolve(CELL)
    builder = cells.load_plugin("builders", cell.config["builder"])
    reference = cells.load_plugin("reference", cell.config["builder"])
    mode = {"logits": logits, "probes": probes}[args.mode]
    print(json.dumps(mode(cell, builder, reference, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
