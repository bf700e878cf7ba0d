"""How close the system comes to ``reference/smallthinker.py`` at the
PUBLISHED widths, outside any timed window; the readings behind the
reference's ``LOSS_TOLERANCE`` (PERF.md §6, PR 34).  Run on the chip:

    python3 perfbench/tools/smallthinker_reference_check.py logits [--seed N]
    python3 perfbench/tools/smallthinker_reference_check.py timed  [--seed N]
    python3 perfbench/tools/smallthinker_reference_check.py faults [--seed N]
    python3 perfbench/tools/smallthinker_reference_check.py drift  [--seed N]

``logits``: one seeded sequence of ``--seq`` (4,608 = the window and 512
positions past it) tokens through the system's model and through the
reference, both at float32 with exact products, so that both break the
top-6's near-ties the same way; the XLA attention (28 x seq^2 float32
scores: what bounds ``--seq``) and the grouped matmuls' own float32 path.
Prints the largest |difference|, the logits' scale, what share of the
routed pairs this rank's experts hold (layer 0, the reference's router),
and by how much each of the four mechanism faults below moves the
reference's own logits.

``timed``: the logits of the model AS TIMED (bfloat16 products, the flash
and grouped-matmul kernels, the cell's 8,192 tokens) against the float32
reference on the replay batch's inputs.

``faults``: the comparison that decides ``correct``, through the builder's
own job, with the kernels on: the trainer replays the cell's three steps as
``drivers/train.py`` does, then ``Job.reference_losses`` /
``Job.losses_agree`` hold its losses and its first gradient
(``builders/smallthinker.py::timed_gradient``) to the clean reference — the
readings a ``run.py`` of the same seed prints — and to the reference with a
fault: the weights rounded to bfloat16 at the start and after every update
(the nearest precision below the float32 weights the configuration states),
five of the six winners, full attention on a window layer, RoPE on the full
layer, SiLU for ReLU.  Each fault must come out as not agreeing, by
``LOSS_TOLERANCE`` or by ``GRADIENT_TOLERANCE``.

``drift``: what the routing does inside a timed window.  The trainer runs
the driver's sequence (three replayed steps, then fresh batches: five of
warm-up and ``--steps`` of window) and every ``--every`` steps the
reference's router reads, from the weights as they then are, what share of
each layer's routed pairs this rank holds on the batch about to be consumed
(a quarter where the routing is uniform) and how unevenly its 16 experts
are loaded (the busiest expert's share of the held pairs; 1/16 = 0.0625
where even).

One JSON line each.  Each mode is a process of its own: the chip belongs to
one at a time.
"""

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "smallthinker-21b-a3b.pretrain8192-dp1"


def _compare(got, want) -> dict:
    import jax.numpy as jnp

    diff = jnp.abs(got - want)
    return {"max_abs_diff": float(diff.max()),
            "mean_abs_diff": float(diff.mean()),
            "max_abs_logit": float(jnp.abs(want).max()),
            "rows_with_another_argmax": int(
                (got.argmax(-1) != want.argmax(-1)).sum())}


def _held_share(params, tokens, reference, hyper) -> float:
    """Share of layer 0's routed (token, expert) pairs whose expert this
    rank holds, by the reference's router on the embedded tokens."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].reshape(
            -1, params["embed"]["embedding"].shape[1])
        block = params["block_0"]["mlp"]
        _, chosen = reference.top_k_by_argmax(
            x @ block["router"]["kernel"], hyper["experts_per_token"])
    local = chosen - hyper["first_expert"]
    held = (local >= 0) & (local < block["expert_wi"].shape[0])
    return float(jnp.mean(held))


def _held_by_layer(reference, hyper: dict):
    """``(params, inputs) -> [layers, 2]``: per layer the share of the
    routed pairs held here and the busiest held expert's share of those,
    by the reference's equations at the default precision."""
    import jax
    import jax.numpy as jnp

    def read(params, inputs):
        x = params["embed"]["embedding"][inputs]
        rows = []
        for i in range(hyper["layers"]):
            p = params[f"block_{i}"]
            held = p["mlp"]["expert_wi"].shape[0]
            _, chosen = reference.top_k_by_argmax(
                x.reshape(-1, x.shape[-1]) @ p["mlp"]["router"]["kernel"],
                hyper["experts_per_token"])
            load = jnp.sum(jax.nn.one_hot(chosen - hyper["first_expert"],
                                          held), axis=(0, 1))
            rows.append(jnp.stack([load.sum() / chosen.size,
                                   load.max() / load.sum()]))
            x = reference.block(x, p, hyper, i)
        return jnp.stack(rows)

    return jax.jit(read)


def _faults(reference, hyper: dict) -> dict:
    """The reference's hyperparameters with one mechanism left out."""
    rotated_everywhere = tuple(1 for _ in hyper["rope_layout"])
    return {
        "five_of_six": {**hyper, "experts_per_token":
                        hyper["experts_per_token"] - 1},
        # layer 1 (a window layer) attends to everything before it
        "full_on_a_window_layer": {
            **hyper, "window_layout": (0, 0) + hyper["window_layout"][2:]},
        "rope_on_the_full_layer": {**hyper, "rope_layout": rotated_everywhere},
        "silu_for_relu": {**hyper,
                          "activation": reference.ACTIVATIONS["silu"]},
    }


def logits(cell, builder, reference, args) -> dict:
    import jax
    import numpy as np

    float32 = {**cell.traffic, "model": {**cell.traffic.get("model", {}),
                                         "dtype": "float32", "remat": False},
               "moe": {**cell.traffic.get("moe", {}), "dtype": "float32"}}
    model = builder.make_model(cell.config, float32)
    params = builder.make_params(model, args.seed)
    tokens = np.random.default_rng([args.seed, 0]).integers(
        0, int(cell.config["vocab_size"]), size=(1, args.seq), dtype=np.int32)
    hyper = reference.hyperparameters(cell.config)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
        want = jax.jit(lambda p, t: reference.logits_fn(p, t, hyper))(
            params, tokens)
    out = {"mode": "logits", "seed": args.seed, "tokens": int(tokens.size),
           **_compare(got, want),
           "held_share_of_routed_pairs_layer0": _held_share(
               params, tokens, reference, hyper)}
    # what each mechanism's absence does to the reference's own logits:
    # far more than the system's distance, or the comparison shows nothing
    with jax.default_matmul_precision("highest"):
        for name, wrong in _faults(reference, hyper).items():
            moved = jax.jit(lambda p, t: reference.logits_fn(p, t, wrong))(
                params, tokens)
            out[f"{name}_moves_logits_by"] = float(abs(moved - want).max())
    return out


def timed(cell, builder, reference, args) -> dict:
    import jax
    import numpy as np

    model = builder.make_model(cell.config, cell.traffic)
    params = builder.make_params(model, args.seed)
    tokens = np.random.default_rng([args.seed, 0]).integers(
        0, int(cell.config["vocab_size"]),
        size=(int(cell.traffic["batch_per_chip"]),
              int(cell.traffic["seq_len"]) + 1), dtype=np.int32)[:, :-1]
    hyper = reference.hyperparameters(cell.config)
    got = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: reference.logits_fn(p, t, hyper))(
            params, tokens)
    return {"mode": "timed", "seed": args.seed, "tokens": int(tokens.size),
            **_compare(got, want),
            "held_share_of_routed_pairs_layer0": _held_share(
                params, tokens, reference, hyper)}


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
              "bf16_weights": {"round_weights": round_to_bf16},
              **{name: {"hyper": wrong}
                 for name, wrong in _faults(reference, hyper).items()}}
    out = {"mode": "faults", "seed": args.seed,
           "trainer_losses": trainer_losses,
           "loss_tolerance": reference.LOSS_TOLERANCE,
           "gradient_tolerance": reference.GRADIENT_TOLERANCE}
    for name in args.faults or probes:
        losses = job.reference_losses(steps, **probes[name])
        leaf, largest = max(job.gradient_distance.items(),
                            key=lambda item: item[1])
        out[name] = {
            "reference_losses": losses,
            "loss_abs_diff": [abs(a - b)
                              for a, b in zip(trainer_losses, losses)],
            "gradient_distance": job.gradient_distance,
            "largest_gradient_distance": [leaf, largest],
            "losses_agree": reference.agree(trainer_losses, losses),
            "gradients_agree": reference.gradients_agree(
                job.gradient_distance),
            "agrees": job.losses_agree(trainer_losses, losses)}
    return out


def drift(cell, builder, reference, args) -> dict:
    import jax

    job = builder.build(cell, cell.traffic, jax.devices()[:cell.chips],
                        args.seed)
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
            rows = read(params, batch["tokens"][:, :-1])
            del params
            readings.append({"step": step,
                             "held_share": [float(r[0]) for r in rows],
                             "busiest_expert": [float(r[1]) for r in rows]})
        state, loss = trainer.train_step(state, trainer.shard_batch(batch))
    return {"mode": "drift", "seed": args.seed, "last_loss": float(loss),
            "window_opens_at_step": replayed + warm, "readings": readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("logits", "timed", "faults", "drift"))
    ap.add_argument("--seed", type=int, default=2147483711)
    ap.add_argument("--seq", type=int, default=4608,
                    help="`logits`: tokens of the float32 comparison")
    ap.add_argument("--faults", nargs="*", default=None,
                    help="`faults`: only these (`clean` is the system itself)")
    ap.add_argument("--steps", type=int, default=100,
                    help="`drift`: steps of the window")
    ap.add_argument("--every", type=int, default=10,
                    help="`drift`: steps between two readings")
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
    mode = {"logits": logits, "timed": timed, "faults": faults,
            "drift": drift}[args.mode]
    print(json.dumps(mode(cell, builder, reference, args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
