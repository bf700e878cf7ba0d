"""How ``correct`` of the SDAR cell tells the architecture's mechanisms from
their absence at the PUBLISHED widths, and what the share's routing does in
a window; the readings behind ``reference/sdar.py``'s limits (PERF.md §6,
PR 47).  Run on the chip:

    python3 perfbench/tools/sdar_reference_check.py faults [--seed N ...]
    python3 perfbench/tools/sdar_reference_check.py drift  [--seed N]

``faults``: the comparison that decides ``correct``, through the builder's
own job, with the kernels on, for each ``--seed`` on one trainer: the
trainer replays the cell's three steps as ``drivers/train.py`` does, then
``Job.reference_losses`` / ``Job.losses_agree`` hold its first loss, its
first gradient (``builders/sdar.py::timed_gradient``) and its parameters'
change over the replay (``system_change``) to the clean reference — the
readings a ``run.py`` of the same seed prints — and to the reference with a
fault: the weights rounded to bfloat16 at the start and after every update
(the nearest precision below the float32 weights the configuration states),
plain causal attention over the 2 L rows, noised rows that see their own
clean block, clean rows that see noised keys, positions that do not
restart, the loss read with a shift, and 1 / t dropped.  Each fault must
come out as not agreeing, by ``LOSS_TOLERANCE``, ``GRADIENT_TOLERANCE`` or
``CHANGE_TOLERANCE``.  Beside each seed's readings stands how heavy the tail
of its replay batch's ``m / t`` is, which is what the later losses follow
(``reference/sdar.py::LOSS_TOLERANCE``).

``drift``: what the routing does inside a timed window.  The trainer runs
the driver's sequence (three replayed steps, then fresh batches: five of
warm-up and ``--steps`` of window) and every ``--every`` steps the
reference's router reads, from the weights as they then are, what share of
each layer's routed pairs this rank holds on the batch about to be consumed
(an eighth where the routing is uniform) and how unevenly its 16 experts are
loaded (the busiest expert's share of the held pairs; 1/16 = 0.0625 where
even).

One JSON line each.  Each mode is a process of its own: the chip belongs to
one at a time.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "sdar-30b-a3b.blockdiff4096-b1-dp1"

#: the reference's switches with one mechanism left out or put in wrong
FAULTS = {
    "causal_over_2L": {"mask": "causal"},
    "noised_sees_own_clean_block": {"mask": "own_clean_block"},
    "clean_sees_noised": {"mask": "clean_sees_noised"},
    "positions_not_restarted": {"restart_positions": False},
    "loss_with_a_shift": {"shift": True},
    "no_one_over_t": {"weigh_by_noise": False},
}


def _weight_tail(batch: dict, block: int) -> dict:
    """How heavy the tail of the replay batch's loss weights ``m / t`` is:
    the largest, and the mean square (``ln(1 / eps)`` = 6.9 in expectation,
    most of it from a few positions)."""
    import numpy as np

    weight = batch["masked"] / np.repeat(batch["t"], block, axis=1)
    return {"largest_weight": float(weight.max()),
            "mean_square_weight": float(np.mean(weight ** 2))}


def faults(cell, builder, reference, args) -> dict:
    import jax

    devices = jax.devices()[:cell.chips]
    model, trainer = builder.make_trainer(cell, cell.traffic, devices)
    steps = int(cell.traffic["replay_steps"])
    hyper = reference.hyperparameters(cell.config)
    # reduce_precision, not a pair of converts: XLA may drop those
    # (xla_allow_excess_precision) and did, on the v5e
    round_to_bf16 = jax.jit(lambda tree: jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=7), tree),
        donate_argnums=0)
    probes = {"clean": {},
              "bf16_weights": {"round_weights": round_to_bf16},
              **{name: {"hyper": {**hyper, **wrong}}
                 for name, wrong in FAULTS.items()}}
    out = {"mode": "faults", "loss_tolerance": reference.LOSS_TOLERANCE,
           "gradient_tolerance": reference.GRADIENT_TOLERANCE,
           "change_tolerance": reference.CHANGE_TOLERANCE, "seeds": {}}
    for seed in args.seed:
        job = builder.job_of(cell, cell.traffic, model, trainer,
                             len(devices), seed)
        state = job.state
        replay = trainer.shard_batch(job.replay_batch)
        trainer_losses = []
        for _ in range(steps):
            state, loss = trainer.train_step(state, replay)
            trainer_losses.append(float(loss))
        del state, loss, replay
        job.trainer = job.state = None
        of_seed = out["seeds"][seed] = {
            "trainer_losses": trainer_losses,
            **_weight_tail(job.replay_batch, hyper["block"])}
        named = args.faults or probes
        for name in (named if seed in (args.fault_seeds or args.seed)
                     else ["clean"]):
            losses = job.reference_losses(steps, **probes[name])
            leaf, largest = max(job.gradient_distance.items(),
                                key=lambda item: item[1])
            of_seed[name] = {
                "reference_losses": losses,
                "loss_abs_diff": [abs(a - b)
                                  for a, b in zip(trainer_losses, losses)],
                "largest_gradient_distance": [leaf, largest],
                "smallest_gradient_distance": min(
                    job.gradient_distance.values()),
                "losses_agree": reference.agree(trainer_losses, losses),
                "gradients_agree": reference.gradients_agree(
                    job.gradient_distance),
                "largest_change_distance": list(max(
                    job.change_distance.items(), key=lambda item: item[1])),
                "changes_agree": reference.changes_agree(
                    job.change_distance),
                "agrees": job.losses_agree(trainer_losses, losses)}
            print(json.dumps({"seed": seed, name: of_seed[name],
                              **{k: of_seed[k] for k in
                                 ("trainer_losses", "largest_weight",
                                  "mean_square_weight")}}), flush=True)
    return out


def _held_by_layer(reference, hyper: dict):
    """``(params, batch) -> [layers, 2]``: per layer the share of the routed
    (row, expert) pairs held here and the busiest held expert's share of
    those, by the reference's equations at the default precision."""
    import jax
    import jax.numpy as jnp

    def read(params, tokens, masked):
        length = tokens.shape[1]
        noised = jnp.where(masked, hyper["mask_id"], tokens)
        x = params["embed"]["embedding"][
            jnp.concatenate([tokens, noised], axis=1)]
        mask = reference.dense_mask(length, hyper["block"])
        rows = []
        for i in range(hyper["layers"]):
            p = params[f"block_{i}"]
            held = p["mlp"]["expert_wi"].shape[0]
            x1 = reference.attend(x, p, mask, hyper)
            m = reference.rms_norm(x1, p["mlp_norm"]["scale"],
                                   hyper["rms_norm_eps"])
            m = m.reshape(-1, m.shape[-1])
            _, chosen = reference.top_k_by_argmax(
                jax.nn.softmax(m @ p["mlp"]["router"]["kernel"], axis=-1),
                hyper["experts_per_token"])
            load = jnp.sum(jax.nn.one_hot(chosen - hyper["first_expert"],
                                          held), axis=(0, 1))
            rows.append(jnp.stack([load.sum() / chosen.size,
                                   load.max() / load.sum()]))
            x = x1 + reference.moe(m, p["mlp"], hyper).reshape(x1.shape)
        return jnp.stack(rows)

    return jax.jit(read)


def drift(cell, builder, reference, args) -> dict:
    import jax

    job = builder.build(cell, cell.traffic, jax.devices()[:cell.chips],
                        args.seed[0])
    trainer, state = job.trainer, job.state
    read = _held_by_layer(reference, reference.hyperparameters(cell.config))
    replayed = int(cell.traffic["replay_steps"])
    warm = int(cell.traffic["warmup_steps"])
    batches = job.host_batches()
    readings = []
    for step in range(replayed + warm + args.steps + 1):
        batch = job.replay_batch if step < replayed else next(batches)
        in_window = step - replayed - warm
        if step in (0, replayed) or (in_window >= 0
                                     and in_window % args.every == 0):
            params = trainer.unstack_params(state)
            rows = read(params, batch["tokens"], batch["masked"])
            del params
            readings.append({"step": step,
                             "held_share": [float(r[0]) for r in rows],
                             "busiest_expert": [float(r[1]) for r in rows]})
        state, loss = trainer.train_step(state, trainer.shard_batch(batch))
    return {"mode": "drift", "seed": args.seed[0], "last_loss": float(loss),
            "window_opens_at_step": replayed + warm, "readings": readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("faults", "drift"))
    ap.add_argument("--seed", type=int, nargs="+", default=[2147483711],
                    help="`faults`: several, on one trainer; `drift`: one")
    ap.add_argument("--faults", nargs="*", default=None,
                    help="`faults`: only these (`clean` is the system itself)")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=None,
                    help="`faults`: the seeds that run --faults; the others "
                         "of --seed run `clean` alone")
    ap.add_argument("--steps", type=int, default=90,
                    help="`drift`: steps of the window")
    ap.add_argument("--every", type=int, default=15,
                    help="`drift`: steps between two readings")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import cells

    cell = cells.resolve(CELL)
    builder = cells.load_plugin("builders", cell.config["builder"])
    reference = cells.load_plugin("reference", cell.config["builder"])
    mode = {"faults": faults, "drift": drift}[args.mode]
    print(json.dumps(mode(cell, builder, reference, args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
