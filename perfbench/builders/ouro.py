"""Builder ``ouro``: Ouro's looped decoder on the program's normal path —
``TransformerLM`` (one stack of sandwich-norm blocks run ``total_ut_steps``
times over the same weights, RoPE in every layer and pass, the final norm
closing every pass, a head and an exit gate on each pass's normed state),
``looped_lm_loss_fn`` (the passes' per-token cross-entropies weighed by the
learned exit distribution, less ``beta`` times its entropy) and
``BaguaTrainer``, the way a user's script builds them.  The job it hands the
``train`` driver is the ``smallthinker`` builder's (replayed losses AND the
first gradient of the loss function as timed decide ``correct``) with a
third comparison, the parameters' change over the replayed updates of the
trainer's own step; what differs besides is the model, the loss and the
reference.

A program that predates the architecture's fields (the parent commit of the
PR that brought them) is refused by ``check_program`` with a ``CellError``
before any weight is made: nothing of the program that such a parent lacks
is imported at the top of this file.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax

import bagua_tpu
from bagua_tpu.models import transformer
from bagua_tpu.models.transformer import TransformerConfig, TransformerLM
from bagua_tpu.parallel.mesh import build_mesh
from perfbench import cells

#: what the architecture needs of the program
NEEDED_FIELDS = ("n_passes", "post_norms", "exit_gate")
NEEDED_LOSS = "looped_lm_loss_fn"


def check_program() -> None:
    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [n for n in NEEDED_FIELDS if n not in have]
    if missing:
        raise cells.CellError(
            "the program under test cannot build Ouro: TransformerConfig "
            f"has no field {', '.join(missing)}")
    if not hasattr(transformer, NEEDED_LOSS):
        raise cells.CellError(
            "the program under test cannot build Ouro: models.transformer "
            f"has no {NEEDED_LOSS}")


# the job, and the helpers every builder shares: dotted-name import, JSON
# dtype names, weights on the device in one jitted call from the seed
_olmoe = cells.load_plugin("builders", "olmoe")
_import, _kwargs, make_params = (_olmoe._import, _olmoe._kwargs,
                                 _olmoe.make_params)


def loss_fn(model: TransformerLM, config: dict):
    """The loss function the trainer's step differentiates."""
    check_program()
    return getattr(transformer, NEEDED_LOSS)(
        model, beta=float(config["assumed"]["beta"]))


def timed_gradient(model: TransformerLM, config: dict, params, batch: dict,
                   reference) -> dict:
    """``reference.watched``'s leaves of the gradient of the loss function
    the trainer's step differentiates — ``looped_lm_loss_fn`` of the model AS
    TIMED (bfloat16 products, the flash kernels forward and backward, the
    traffic's remat) — at ``params`` on ``batch``: the same function of the
    same shapes, outside the trainer (``builders/smallthinker.py`` says why
    outside)."""
    loss = loss_fn(model, config)
    return jax.jit(lambda p, b: reference.watched(jax.grad(loss)(p, b)))(
        params, {name: jnp.asarray(x) for name, x in batch.items()})


def system_change(trainer, model: TransformerLM, seed: int, batch: dict,
                  steps: int, reference) -> dict:
    """The change of ``reference.watched``'s leaves (and its
    ``CHANGE_ALSO``) over ``steps`` updates of the trainer's own compiled
    step on ``batch``, from a fresh state of the same seed: what the
    trainer's state (its weights' and moments' precision, its update) makes
    of the replayed batch.  The driver's own replay goes on into the timed
    window and its state is freed before the comparison, so the steps are
    taken again here; they are the same program on the same numbers."""
    params = make_params(model, seed)
    start = reference.watched_copy(params)
    state = trainer.init(params)
    del params
    replay = trainer.shard_batch(batch)
    for _ in range(steps):
        state, _ = trainer.train_step(state, replay)
    return jax.jit(lambda before, after: reference.parameter_change(
        before, reference.watched(after, reference.CHANGE_ALSO)))(
            start, trainer.unstack_params(state))


@dataclasses.dataclass
class Job(_olmoe.Job):
    """The ``olmoe`` builder's job with ``correct`` held to two further
    comparisons beside the replayed losses: the first gradient of the
    replay batch (``reference.GRADIENT_TOLERANCE``) and the parameters'
    change over the replayed updates (``reference.CHANGE_TOLERANCE``)."""

    #: the trainer again, for ``system_change`` (the driver takes
    #: ``trainer`` and ``state`` away before the comparison)
    _replayer: object = None
    #: per watched leaf, the system's first gradient's relative distance from
    #: the reference's, and the same of the parameters' change; set by
    #: ``reference_losses``
    gradient_distance: dict = dataclasses.field(default_factory=dict)
    change_distance: dict = dataclasses.field(default_factory=dict)
    #: what the system gave (made once: ``faults`` asks again and again)
    _system: tuple | None = None

    def reference_losses(self, steps: int, **probe) -> list[float]:
        """``probe``: ``hyper=`` / ``round_weights=`` of a reference with a
        fault (``tools/ouro_reference_check.py faults``)."""
        reference = self._reference
        if self._system is None:
            change = system_change(self._replayer, self._model, self._seed,
                                   self.replay_batch, steps, reference)
            self._system = (change, timed_gradient(
                self._model, self._config,
                make_params(self._model, self._seed), self.replay_batch,
                reference))
        got_change, got_gradient = self._system

        def distances(got: dict, want: dict) -> dict:
            return {name: float(d) for name, d in
                    reference.gradient_distance(got, want).items()}

        def compare_gradient(want: dict) -> None:
            self.gradient_distance = distances(got_gradient, want)

        def compare_change(want: dict) -> None:
            self.change_distance = distances(got_change, want)

        probe.setdefault("hyper", reference.hyperparameters(self._config))
        losses = reference.replay_losses(
            make_params(self._model, self._seed),
            self.replay_batch["tokens"], steps, self._traffic["optimizer"],
            int(self._traffic["reference_micro_batch"]),
            first_gradient=compare_gradient, last_change=compare_change,
            **probe)
        # an earlier line, for the reader of a log: what the second and the
        # third comparison read
        print(json.dumps({
            "first_gradient_distance": self.gradient_distance,
            "largest": max(self.gradient_distance.values(), default=None),
            "limit": reference.GRADIENT_TOLERANCE,
            "parameter_change_distance": self.change_distance,
            "largest_change": max(self.change_distance.values(),
                                  default=None),
            "change_limit": reference.CHANGE_TOLERANCE}), flush=True)
        return losses

    def losses_agree(self, trainer_losses, reference_losses) -> bool:
        reference = self._reference
        return (reference.agree(trainer_losses, reference_losses)
                and reference.gradients_agree(self.gradient_distance)
                and reference.changes_agree(self.change_distance))


def _sizes(config: dict) -> dict:
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "f": int(config["intermediate_size"]),
        "layers": int(config["num_hidden_layers"]),
        "passes": int(config["total_ut_steps"]),
        "vocab": int(config["vocab_size"]),
    }


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward FLOP per target token that the mathematics of the
    step require (``perfbench/flops.py``'s conventions: 2 FLOP a
    multiply-accumulate, backward twice the forward, attention at the full
    ``seq x seq`` as in the dense and OLMoE cells, norms / softmax / rotary
    / the gate / the optimizer left out): EVERY pass counts, ``passes x
    layers`` layer-passes of four attention matrices, the scores and the
    weighted values, and three FFN matrices, and ``passes`` heads.  A
    weight used four times is four products; what remat replays counts for
    nothing."""
    s = _sizes(config)
    d, q_width = s["d"], s["heads"] * s["head_dim"]
    layer = (2 * d * q_width + 2 * d * s["kv_heads"] * s["head_dim"]
             + 2 * seq_len * q_width + 3 * d * s["f"])
    forward_mac = s["passes"] * (s["layers"] * layer + d * s["vocab"])
    return 3.0 * 2.0 * forward_mac


def parameters(config: dict) -> int:
    """Parameters of the model as built: token table, per layer (ONCE, however
    many passes) four attention matrices, three FFN matrices and four norms,
    a final norm, an untied head, the exit gate's vector and bias."""
    s = _sizes(config)
    d, q_width = s["d"], s["heads"] * s["head_dim"]
    layer = (2 * d * q_width + 2 * d * s["kv_heads"] * s["head_dim"]
             + 3 * d * s["f"] + 4 * d)
    return 2 * d * s["vocab"] + d + s["layers"] * layer + d + 1


def make_model(config: dict, traffic: dict) -> TransformerLM:
    check_program()
    s = _sizes(config)
    return TransformerLM(TransformerConfig(
        vocab_size=s["vocab"], d_model=s["d"], n_heads=s["heads"],
        n_kv_heads=s["kv_heads"], d_head=s["head_dim"],
        n_layers=s["layers"], d_ff=s["f"],
        max_seq_len=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        n_passes=s["passes"], post_norms=True, exit_gate=True,
        **_kwargs(traffic.get("model", {}))))


def make_trainer(cell: cells.Cell, traffic: dict, devices: list):
    """The model and its trainer over ``devices``, as the traffic mix
    configures them; nothing is placed on a device yet."""
    check_program()
    config = cell.config
    if int(traffic["seq_len"]) > int(config["max_position_embeddings"]):
        raise cells.CellError(
            f"{cell.name}: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    model = make_model(config, traffic)
    mesh = build_mesh(dict(traffic["mesh"]), devices)
    bagua_tpu.init_process_group(mesh=mesh)
    algorithm = _import(traffic["algorithm"]["class"])(
        **_kwargs(traffic["algorithm"].get("kwargs", {})))
    optimizer = getattr(optax, traffic["optimizer"]["name"])(
        **traffic["optimizer"].get("kwargs", {}))
    trainer = bagua_tpu.BaguaTrainer(
        loss_fn(model, config), optimizer, algorithm, mesh=mesh,
        **_kwargs(traffic.get("trainer", {})))
    return model, trainer


def build(cell: cells.Cell, traffic: dict, devices: list, seed: int) -> Job:
    model, trainer = make_trainer(cell, traffic, devices)
    state = trainer.init(make_params(model, seed))
    seq = int(traffic["seq_len"])
    batch = int(traffic["batch_per_chip"]) * len(devices)
    replay = np.random.default_rng([seed, 0]).integers(
        0, int(cell.config["vocab_size"]), size=(batch, seq + 1),
        dtype=np.int32)
    return Job(
        trainer=trainer, state=state, units_per_step=batch * seq,
        flops_per_unit=flops_per_token(cell.config, seq),
        replay_batch={"tokens": replay}, _model=model, _config=cell.config,
        _traffic=traffic, _seed=seed, _replayer=trainer,
        _reference=cells.load_plugin("reference", cell.config["builder"],
                                     cell.bench_dir),
    )
