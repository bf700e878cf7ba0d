"""How ``correct`` of the Olmo-Hybrid cell tells the architecture's mechanisms
and the four-chip step's own work from their absence at the PUBLISHED widths
on the four chips; the readings behind ``reference/olmo_hybrid.py``'s limits
(PERF.md §6, PR 56).  Run on the chips:

    python3 perfbench/tools/olmo_hybrid_reference_check.py [--seed N ...]
        [--faults NAME ...] [--seconds S]

For each ``--seed``, ONE process and one sound replay of the reference: the
cell through the ``train`` driver's own ``run`` (the call ``perfbench/run.py``
makes; its result line is printed as that file prints it, ``correct``
included), then, against what that replay left on the host
(``Job.wanted``), the SYSTEM with a fault planted —

- ``half_batch``: the trainer's own compiled step fed the first half of the
  replay batch twice, the gradient of half the batch;
- ``no_exchange``: the trainer with every chip keeping its own gradient
  (``NoExchange``: a reduce-scatter that adds nothing up; a chip's quarter
  of every bucket updated from that chip's sequence alone)

— each read by the parameters' change and the replayed losses; and the
system as it is held to a REFERENCE with one thing wrong at a time —
``beta_is_sigmoid`` and ``norm_in_front`` by the first gradient (one
reference gradient each), ``bf16_weights`` (the weights rounded to bfloat16
at the start and after every update, the nearest precision below the float32
the configuration states for them) by the whole replay, ``bf16_state`` (the
scan's state and decay in bfloat16) by the rule's probe and its cotangents.
Each must come out as not agreeing by one of the cell's limits.

One JSON line a (seed, fault).  One process: the chips belong to one at a
time.  ``--tiny`` rehearses the plumbing on four CPU devices.
"""

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
CELL = "olmo-hybrid-7b.pretrain8192-b1-dp4"

#: the reference's switches with one mechanism put in wrong, seen by the
#: first gradient
GRADIENT_FAULTS = {
    "beta_is_sigmoid": {"neg_eigval": False},
    "norm_in_front": {"output_norm": False},
}
#: faults planted in the system, read against the sound reference
SYSTEM_FAULTS = ("half_batch", "no_exchange")
FAULTS = (*SYSTEM_FAULTS, *GRADIENT_FAULTS, "bf16_weights", "bf16_state")

#: ``--tiny``: the published shapes' kind at a small size, float32
TINY = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 2,
    "num_key_value_heads": 2,
    "layer_types": ["linear_attention", "full_attention"],
    "num_hidden_layers": 2, "linear_num_key_heads": 6,
    "linear_num_value_heads": 6, "max_position_embeddings": 256,
    "vocab_size": 250}
TINY_TRAFFIC = {"seq_len": 128, "warmup_steps": 2, "trace_steps": 3,
                "model": {"dtype": "float32", "remat": True,
                          "remat_policy": "dots_no_batch"}}


def largest(distances: dict) -> list:
    return list(max(distances.items(), key=lambda item: item[1]))


def no_exchange():
    """The traffic's algorithm with the exchange emptied: under the sharded
    update a chip's chunk of a bucket is the chunk of its OWN gradient.  The
    reduce-scatter stays in the program (so the step's temporaries are the
    sound step's, which is what loads beside the state: without it the
    compile for the described v5e keeps 6.37 GiB where 5.8 load) and is fed
    so that it adds nothing up: every chip sends its own chunk, times the
    chips, and zeros for the others'."""
    import jax.numpy as jnp

    from bagua_tpu.algorithms.base import chunk_form
    from bagua_tpu.algorithms.gradient_allreduce import (
        GradientAllReduceAlgorithm, ReduceOp,
    )

    class NoExchange(GradientAllReduceAlgorithm):
        def reduce_bucket_grad(self, ctx, index, flat):
            if not ctx.update_sharded(index):
                return flat
            chips = ctx.comm.nranks()
            x = chunk_form(flat, chips)
            mine = (jnp.arange(x.shape[0]) // (x.shape[0] // chips)
                    == ctx.comm.rank())
            mine = mine.reshape((-1,) + (1,) * (x.ndim - 1))
            return ctx.bucket_reduce_scatter(jnp.where(mine, x * chips, 0),
                                             ReduceOp.AVG)

    return NoExchange(hierarchical=False)


def half_batch(batch: dict) -> dict:
    """The first half of the sequences twice: what a step sees that leaves
    half of its batch out."""
    import numpy as np

    tokens = np.asarray(batch["tokens"])
    half = tokens[:len(tokens) // 2]
    return {"tokens": np.concatenate([half, half])}


def planted(name: str, job, cell, builder, reference, steps: int) -> tuple:
    """``(change, losses)`` of the trainer with the system fault ``name``
    over ``steps`` updates on the job's replay batch."""
    model, trainer, batch = job._model, job._replayer, job.replay_batch
    if name == "half_batch":
        batch = half_batch(batch)
    else:
        _, trainer = builder.make_trainer(
            cell, job._traffic, list(trainer.mesh.devices.flat),
            algorithm=no_exchange())
    return builder.system_change(trainer, model, job._seed, batch, steps,
                                 reference)


def driven(cell, builder_name: str, args, seed: int) -> tuple:
    """The cell through the ``train`` driver's ``run`` -> (its result, the
    builder's job)."""
    from perfbench import cells

    driver = cells.load_plugin("drivers", cell.traffic["driver"])
    run_args = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                  rehearse=args.tiny, keep_trace=None)
    kept = {}
    load = cells.load_plugin

    def keeping(kind, name, *rest):
        module = load(kind, name, *rest)
        if (kind, name) == ("builders", builder_name):
            build = module.build

            def keep(*a, **kw):
                kept["job"] = build(*a, **kw)
                return kept["job"]

            module.build = keep
        return module

    cells.load_plugin = keeping
    try:
        result = driver.run(cell, run_args, T0)
    finally:
        cells.load_plugin = load
    return result, kept["job"]


def faults(cell, builder, reference, args) -> None:
    import jax

    steps = int(cell.traffic["replay_steps"])
    hyper = reference.hyperparameters(cell.config)
    # reduce_precision, not a pair of converts: XLA may drop those
    round_to_bf16 = jax.jit(lambda tree: jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=7), tree),
        donate_argnums=0)
    for seed in args.seed:
        result, job = driven(cell, cell.config["builder"], args, seed)
        print(json.dumps(result), flush=True)
        model, trainer = job._model, job._replayer
        seq = job.replay_batch["tokens"].shape[1] - 1
        sound, timed = (job.wanted["losses"],
                        job.wanted["trainer_losses"])
        for name in args.faults:
            out = {"seed": seed, "fault": name}
            if name in SYSTEM_FAULTS:
                change, losses = planted(name, job, cell, builder, reference,
                                         steps)
                distance = job.distances(change, job.wanted["change"])
                held = {k: v for k, v in distance.items()
                        if not k.endswith(reference.CHANGE_SKIPPED)}
                out.update(
                    losses=losses, reference_losses=sound,
                    loss_distance=[abs(a - b) for a, b in zip(losses, sound)],
                    losses_agree=reference.agree(losses, sound,
                                                 reference.LOSS_TOLERANCE),
                    largest_change=largest(held),
                    smallest_change=list(min(held.items(),
                                             key=lambda item: item[1])),
                    changes_agree=reference.changes_agree(distance))
            elif name in GRADIENT_FAULTS:
                _, grads = reference.loss_and_grads(
                    builder.make_params(model, seed),
                    job.replay_batch["tokens"],
                    {**hyper, **GRADIENT_FAULTS[name]})
                distance = job.distances(job._system[0],
                                         reference.watched(grads), True)
                del grads
                out.update(largest=largest(distance),
                           smallest=list(min(distance.items(),
                                             key=lambda item: item[1])),
                           agrees=reference.gradients_agree(distance))
            elif name == "bf16_state":
                probe = reference.rule_probe(seed, seq, hyper)
                sound_rule = reference.rule_by_scan(*probe)
                out.update(
                    system=reference.rule_distance(job._system[2],
                                                   sound_rule),
                    scan_in_bf16=reference.rule_distance(
                        reference.rule_by_scan(*probe, scan_dtype="bfloat16"),
                        sound_rule))
                out["agrees"] = reference.rule_agrees(out["scan_in_bf16"])
            else:
                losses = job.reference_losses(steps,
                                              round_weights=round_to_bf16)
                out.update(
                    reference_losses=losses, sound_reference_losses=sound,
                    trainer_losses=timed,
                    losses_agree=reference.agree(timed, losses,
                                                 reference.LOSS_TOLERANCE),
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
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="the driver's window: a few steps, no measurement")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    if args.tiny:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    from perfbench import cells

    cell = cells.resolve(CELL)
    if args.tiny:
        cell = dataclasses.replace(
            cell, config={**cell.config, **TINY},
            traffic={**cell.traffic, **TINY_TRAFFIC})
    builder = cells.load_plugin("builders", cell.config["builder"])
    reference = cells.load_plugin("reference", cell.config["builder"])
    faults(cell, builder, reference, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
