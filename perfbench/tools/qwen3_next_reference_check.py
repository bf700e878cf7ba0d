"""How ``correct`` of the Qwen3-Next cell tells the architecture's mechanisms
from their absence, and the float32 state of the delta rule's scan from a
bfloat16 one, at the PUBLISHED widths; the readings behind
``reference/qwen3_next.py``'s limits (PERF.md §6, PR 50).  Run on the chip:

    python3 perfbench/tools/qwen3_next_reference_check.py [--seed N ...]
        [--faults NAME ...] [--fault-seeds N ...]

The comparison that decides ``correct``, through the builder's own job, with
the kernels on, for each ``--seed`` on one trainer: the trainer replays the
cell's three steps as ``drivers/train.py`` does, then
``Job.reference_losses`` / ``Job.losses_agree`` hold its losses, its first
gradient (``builders/smallthinker.py::timed_gradient``) and its parameters'
change over the replay (``builders/sdar.py::system_change``) to the clean
reference — the readings a ``run.py`` of the same seed prints — and to the
reference with a fault: the state and the decay of the scan kept in
bfloat16 (the nearest precision below the float32 the configuration states
for them), the weights rounded to bfloat16 at the start and after every
update, alpha = 1, beta = 1, no L2 norm of q and k, the whole head rotated,
no output gate, no shared-expert gate, plain-``w`` norm scales.  Each fault
must come out as not agreeing, by ``LOSS_TOLERANCE``,
``GRADIENT_TOLERANCE`` or ``CHANGE_TOLERANCE``.

One JSON line a (seed, probe), one at the end.  One process: the chip
belongs to one at a time.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "qwen3-next-80b-a3b.pretrain4096-b2-dp1"

#: the reference's switches with one mechanism left out or put in wrong
FAULTS = {
    "bf16_scan": {"scan_dtype": "bfloat16"},
    "alpha_is_one": {"decay": False},
    "beta_is_one": {"write_strength": False},
    "no_l2_norm": {"l2_norm": False},
    "whole_head_rotation": {"rotary_dim": None},
    "no_output_gate": {"attn_gate": False},
    "no_shared_expert_gate": {"shared_gate": False},
    "plain_norm_scale": {"zero_centered": False},
}


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
    out = {"loss_tolerance": reference.LOSS_TOLERANCE,
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
        of_seed = out["seeds"][seed] = {"trainer_losses": trainer_losses}
        named = args.faults or probes
        for name in (named if seed in (args.fault_seeds or args.seed)
                     else ["clean"]):
            losses = job.reference_losses(steps, **probes[name])
            of_seed[name] = {
                "reference_losses": losses,
                "loss_abs_diff": [abs(a - b)
                                  for a, b in zip(trainer_losses, losses)],
                "largest_gradient_distance": list(max(
                    job.gradient_distance.items(), key=lambda i: i[1])),
                "largest_change_distance": list(max(
                    job.change_distance.items(), key=lambda i: i[1])),
                "losses_agree": reference.agree(
                    trainer_losses, losses, reference.LOSS_TOLERANCE),
                "gradients_agree": reference.gradients_agree(
                    job.gradient_distance),
                "changes_agree": reference.changes_agree(
                    job.change_distance, reference.CHANGE_TOLERANCE),
                "agrees": job.losses_agree(trainer_losses, losses)}
            print(json.dumps({"seed": seed, "trainer_losses": trainer_losses,
                              name: of_seed[name]}), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, nargs="+", default=[2147483711])
    ap.add_argument("--faults", nargs="*", default=None,
                    help="only these (`clean` is the system itself)")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=None,
                    help="the seeds that run --faults; the others of --seed "
                         "run `clean` alone")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import cells

    cell = cells.resolve(CELL)
    builder = cells.load_plugin("builders", cell.config["builder"])
    reference = cells.load_plugin("reference", cell.config["builder"])
    print(json.dumps(faults(cell, builder, reference, args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
