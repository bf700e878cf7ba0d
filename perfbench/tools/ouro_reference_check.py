"""How close the system comes to ``reference/ouro.py`` at the PUBLISHED
widths, outside any timed window; the readings behind the reference's
``LOSS_TOLERANCE`` and ``GRADIENT_TOLERANCE`` (PERF.md §6, PR 39).  Run on
the chip:

    python3 perfbench/tools/ouro_reference_check.py logits [--seed N]
    python3 perfbench/tools/ouro_reference_check.py faults [--seed N]

``logits``: one seeded sequence of ``--seq`` tokens through the system's
model and through the reference, both at float32 with exact products (the
XLA attention: ``--seq`` is bounded by 16 x seq^2 float32 scores): the
largest |difference| of the four passes' logits and of the gates' logits,
and by how much each mechanism fault below moves the reference's own.

``faults``: the comparison that decides ``correct``, through the builder's
own job, with the kernels on: the trainer replays the cell's three steps as
``drivers/train.py`` does, then ``Job.reference_losses`` /
``Job.losses_agree`` hold its losses, its first gradient
(``builders/ouro.py::timed_gradient``) and its parameters' change over the
replayed updates (``system_change``) to the clean reference — the readings
a ``run.py`` of the same seed prints — and to the reference with one fault
at a time:

    three_passes        3 passes in place of 4
    uniform_weights     1/4 a pass in place of the learned exit distribution
    last_exit_gated     p_T = lambda_T * prod (1 - lambda_j): the last pass does not take the remaining mass
    no_entropy          beta = 0
    no_post_norms       the two norms behind the sub-layers left out
    unnormed_fed_on     the final norm feeds the head and the gate, the un-normed state goes on
    bf16_parts          bfloat16 where the configuration says float32: the gate, every norm, the exit distribution and the weighting
    bf16_weights        the weights rounded to bfloat16 at the start and after every update

Each fault must come out as not agreeing, by ``LOSS_TOLERANCE``, by
``GRADIENT_TOLERANCE`` or by ``CHANGE_TOLERANCE``.  The five that change no
shape and no dtype share one compiled reference (``reference.SWITCHES``).

One JSON line each.  Each mode is a process of its own: the chip belongs to
one at a time.
"""

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "ouro-2.6b.pretrain4096-b1-dp1"


def fault_hypers(hyper: dict) -> dict:
    """The reference's hyperparameters with one mechanism left out."""
    import jax.numpy as jnp

    return {
        "three_passes": {**hyper, "passes": hyper["passes"] - 1},
        "uniform_weights": {**hyper, "uniform_weights": True},
        "last_exit_gated": {**hyper, "last_takes_rest": False},
        "no_entropy": {**hyper, "beta": 0.0},
        "no_post_norms": {**hyper, "post_norms": False},
        "unnormed_fed_on": {**hyper, "feed_normed": False},
        "bf16_parts": {**hyper, "parts_dtype": jnp.bfloat16},
    }


def logits(cell, builder, reference, args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    float32 = {**cell.traffic, "model": {**cell.traffic.get("model", {}),
                                         "dtype": "float32", "remat": False}}
    model = builder.make_model(cell.config, float32)
    params = builder.make_params(model, args.seed)
    tokens = np.random.default_rng([args.seed, 0]).integers(
        0, int(cell.config["vocab_size"]), size=(1, args.seq), dtype=np.int32)
    hyper = reference.hyperparameters(cell.config)
    with jax.default_matmul_precision("highest"):
        got, got_gates = jax.jit(
            lambda p, t: model.apply({"params": p}, t))(params, tokens)
        want, want_gates = jax.jit(
            lambda p, t: reference.logits_fn(p, t, hyper))(params, tokens)
    out = {"mode": "logits", "seed": args.seed, "tokens": int(tokens.size),
           "max_abs_diff": float(jnp.abs(got - want).max()),
           "max_abs_logit": float(jnp.abs(want).max()),
           "gates_max_abs_diff": float(jnp.abs(got_gates - want_gates).max()),
           "gates_max_abs": float(jnp.abs(want_gates).max())}
    # what each mechanism's absence does to the reference's own last-pass
    # logits and gates: far more than the system's distance, or the
    # comparison shows nothing
    with jax.default_matmul_precision("highest"):
        for name, wrong in fault_hypers(hyper).items():
            if wrong["passes"] != hyper["passes"]:
                continue  # other shapes: the gradient's to tell
            moved, moved_gates = jax.jit(
                lambda p, t: reference.logits_fn(p, t, wrong))(params, tokens)
            out[f"{name}_moves_logits_by"] = float(abs(moved - want).max())
            out[f"{name}_moves_gates_by"] = float(
                abs(moved_gates - want_gates).max())
    return out


def faults(cell, builder, reference, args) -> dict:
    import jax

    job = builder.build(cell, cell.traffic, jax.devices()[:cell.chips],
                        args.seed)
    steps = int(cell.traffic["replay_steps"])
    trainer, state = job.trainer, job.state
    replay = trainer.shard_batch(job.replay_batch)
    trainer_losses = []
    for _ in range(steps):
        state, loss = trainer.train_step(state, replay)
        trainer_losses.append(float(loss))
    del state, loss, replay, trainer
    job.trainer = job.state = None

    hyper = reference.hyperparameters(cell.config)
    # reduce_precision, not a pair of converts: XLA may drop those
    # (xla_allow_excess_precision) and did, on the v5e
    round_to_bf16 = jax.jit(lambda tree: jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=7), tree),
        donate_argnums=0)
    probes = {"clean": {},
              **{name: {"hyper": wrong}
                 for name, wrong in fault_hypers(hyper).items()},
              "bf16_weights": {"round_weights": round_to_bf16}}
    out = {"mode": "faults", "seed": args.seed,
           "trainer_losses": trainer_losses,
           "loss_tolerance": reference.LOSS_TOLERANCE,
           "gradient_tolerance": reference.GRADIENT_TOLERANCE,
           "change_tolerance": reference.CHANGE_TOLERANCE}
    for name in args.faults or probes:
        losses = job.reference_losses(steps, **probes[name])
        largest = lambda d: list(max(d.items(), key=lambda item: item[1]))
        out[name] = {
            "reference_losses": losses,
            "loss_abs_diff": [abs(a - b)
                              for a, b in zip(trainer_losses, losses)],
            "gradient_distance": job.gradient_distance,
            "largest_gradient_distance": largest(job.gradient_distance),
            "change_distance": job.change_distance,
            "largest_change_distance": largest(job.change_distance),
            "losses_agree": reference.agree(trainer_losses, losses),
            "gradients_agree": reference.gradients_agree(
                job.gradient_distance),
            "changes_agree": reference.changes_agree(job.change_distance),
            "agrees": job.losses_agree(trainer_losses, losses)}
        # a line a probe: a call that is cut keeps what it had
        print(json.dumps({"probe": name, **out[name]}), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("logits", "faults"))
    ap.add_argument("--seed", type=int, default=2147483711)
    ap.add_argument("--seq", type=int, default=2048,
                    help="`logits`: tokens of the float32 comparison")
    ap.add_argument("--faults", nargs="*", default=None,
                    help="`faults`: only these (`clean` is the system itself)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    if args.mode == "logits":
        # float32 operands: the XLA attention; the flash kernels take
        # bfloat16 blocks
        os.environ["BAGUA_FLASH_ATTENTION"] = "0"
    from perfbench import cells

    cell = cells.resolve(CELL)
    builder = cells.load_plugin("builders", cell.config["builder"])
    reference = cells.load_plugin("reference", cell.config["builder"])
    mode = {"logits": logits, "faults": faults}[args.mode]
    print(json.dumps(mode(cell, builder, reference, args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
