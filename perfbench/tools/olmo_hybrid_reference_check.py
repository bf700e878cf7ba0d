"""How ``correct`` of the Olmo-Hybrid cell tells the architecture's mechanisms
from their absence at the PUBLISHED widths on the four chips; the readings
behind ``reference/olmo_hybrid.py``'s limits (PERF.md §6, PR 56).  Run on the
chips:

    python3 perfbench/tools/olmo_hybrid_reference_check.py [--seed N ...]
        [--faults NAME ...]

For each ``--seed``, through the builder's own pieces with the kernels on:
the system's first gradient (``builders/olmo_hybrid.py::timed_gradient``),
its parameters' change over the replayed updates (``system_change``) and its
rule on the probe's rows (``system_rule``), held to the reference with one
thing wrong at a time — ``beta_is_sigmoid`` and ``norm_in_front`` by the
first gradient (one reference gradient each), ``bf16_weights`` (the weights
rounded to bfloat16 at the start and after every update, the nearest
precision below the float32 the configuration states for them) by the whole
replay, ``bf16_state`` (the scan's state and decay in bfloat16) by the rule's
probe.  Each must come out as not agreeing by one of the cell's limits.

One JSON line a (seed, fault).  One process: the chips belong to one at a
time.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "olmo-hybrid-7b.pretrain8192-b1-dp4"

#: the reference's switches with one mechanism put in wrong, seen by the
#: first gradient
GRADIENT_FAULTS = {
    "beta_is_sigmoid": {"neg_eigval": False},
    "norm_in_front": {"output_norm": False},
}
FAULTS = (*GRADIENT_FAULTS, "bf16_weights", "bf16_state")


def largest(distances: dict) -> list:
    return list(max(distances.items(), key=lambda item: item[1]))


def faults(cell, builder, reference, args) -> None:
    import jax

    devices = jax.devices()[:cell.chips]
    steps = int(cell.traffic["replay_steps"])
    hyper = reference.hyperparameters(cell.config)
    # reduce_precision, not a pair of converts: XLA may drop those
    round_to_bf16 = jax.jit(lambda tree: jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=7), tree),
        donate_argnums=0)
    for seed in args.seed:
        job = builder.build(cell, cell.traffic, devices, seed)
        model, trainer = job._model, job._replayer
        job.trainer = job.state = None
        seq = job.replay_batch["tokens"].shape[1] - 1
        gradient = builder.timed_gradient(trainer, model, seed,
                                          job.replay_batch, reference)
        for name in args.faults:
            out = {"seed": seed, "fault": name}
            if name in GRADIENT_FAULTS:
                _, grads = reference.loss_and_grads(
                    builder.make_params(model, seed),
                    job.replay_batch["tokens"],
                    {**hyper, **GRADIENT_FAULTS[name]})
                distance = reference.gradient_distance(
                    gradient, reference.watched(grads))
                del grads
                out.update(largest=largest(distance),
                           smallest=list(min(distance.items(),
                                             key=lambda item: item[1])),
                           agrees=reference.gradients_agree(distance))
            elif name == "bf16_state":
                got = builder.system_rule(reference, seed, seq, hyper,
                                          model.cfg.dtype)
                probe = reference.rule_probe(seed, seq, hyper)
                sound = reference.rule_by_scan(*probe)
                out.update(
                    system=reference.rule_distance(got, sound),
                    scan_in_bf16=reference.rule_distance(
                        reference.rule_by_scan(*probe, scan_dtype="bfloat16"),
                        sound))
                out["agrees"] = reference.rule_agrees(out["scan_in_bf16"])
            else:
                job._system = (
                    gradient,
                    builder.system_change(trainer, model, seed,
                                          job.replay_batch, steps, reference),
                    builder.system_rule(reference, seed, seq, hyper,
                                        model.cfg.dtype))
                losses = job.reference_losses(steps,
                                              round_weights=round_to_bf16)
                out.update(
                    reference_losses=losses,
                    largest_gradient=largest(job.gradient_distance),
                    largest_change=largest({
                        k: v for k, v in job.change_distance.items()
                        if not k.endswith(reference.CHANGE_SKIPPED)}),
                    changes=job.change_distance,
                    changes_agree=reference.changes_agree(
                        job.change_distance))
            print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, nargs="+", default=[2147483711])
    ap.add_argument("--faults", nargs="*", default=list(FAULTS),
                    choices=FAULTS)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import cells

    cell = cells.resolve(CELL)
    builder = cells.load_plugin("builders", cell.config["builder"])
    reference = cells.load_plugin("reference", cell.config["builder"])
    faults(cell, builder, reference, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
