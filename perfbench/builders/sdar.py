"""Builder ``sdar``: SDAR-30B-A3B-Chat's block-diffusion TRAINING step on
the program's normal path — ``TransformerLM`` (32 query heads over 4 key /
value heads of 128, RMSNorm on each head of q and k, RoPE, every layer under
the block-diffusion mask over the rows ``[x ; x~]`` with positions that
restart) with ``MoEMLP`` (SiLU-gated experts, dropless top-8 of 128
renormalised, ONE expert-parallel rank's share) as every layer's MLP,
``block_diffusion_loss_fn`` and ``BaguaTrainer``, the way a user's script
builds them.  The job it hands the ``train`` driver is the ``smallthinker``
builder's with the ``ouro`` builder's third comparison (the first replayed
loss, the first gradient AND the parameters' change over the replayed
updates decide ``correct``); what differs is the model, the loss, the batch
— tokens and noise — and the reference.

A unit of ``tokens_per_s_per_chip`` here is one CLEAN token: one position of
the sequence, trained through two rows of the trunk.

A program that predates the architecture's fields (the parent commit of the
PR that brought them) is refused by ``check_program`` with a ``CellError``
before any weight is made.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax

import bagua_tpu
from bagua_tpu.model_parallel.moe.layer import MoEMLP
from bagua_tpu.models import transformer
from bagua_tpu.models.transformer import TransformerConfig, TransformerLM
from bagua_tpu.parallel.mesh import build_mesh
from perfbench import cells

#: what the architecture needs of the program, by class, and of the module
NEEDED_FIELDS = {
    TransformerConfig: ("n_kv_heads", "d_head", "qk_norm", "attention",
                        "diffusion_block"),
    MoEMLP: ("activation", "ep_rank", "norm_topk_prob"),
}
NEEDED_FUNCTIONS = ("block_diffusion_loss_fn", "block_diffusion_noise")


def check_program() -> None:
    for cls, names in NEEDED_FIELDS.items():
        have = {f.name for f in dataclasses.fields(cls)}
        missing = [n for n in names if n not in have]
        if missing:
            raise cells.CellError(
                f"the program under test cannot build SDAR: "
                f"{cls.__name__} has no field {', '.join(missing)}")
    missing = [n for n in NEEDED_FUNCTIONS if not hasattr(transformer, n)]
    if missing:
        raise cells.CellError(
            "the program under test cannot train SDAR: models.transformer "
            f"has no {', '.join(missing)}")


# the job, and the helpers every builder shares: dotted-name import, JSON
# dtype names, and weights on the device in one jitted call from the seed —
# the ``smallthinker`` builder's, token table at unit variance: under flax's
# rows of norm 1 the stream behind layer 0 is mostly attention's running mean
# over the prefix, nearly one vector for every late row, and the seeded
# routers of the deeper layers send most rows to a few experts (my chip run,
# PR 47: 13 to 29 % of a layer's pairs held, up to 75 % of those on one
# expert); at unit variance a row's own token dominates its stream, as in a
# trained model, and the routing starts near uniform (``departures``)
_smallthinker = cells.load_plugin("builders", "smallthinker")
_import, _kwargs = _smallthinker._import, _smallthinker._kwargs
make_params = _smallthinker.make_params


def _sizes(config: dict) -> dict:
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "f": int(config["moe_intermediate_size"]),
        "held": int(config["num_experts"]),
        "experts": int(config["reduced_from"]["num_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
        "block": int(config["assumed"]["block_length"]),
        "mask_id": int(config["assumed"]["mask_token_id"]),
    }


def draw_batch(rng: np.random.Generator, config: dict, traffic: dict,
               batch: int) -> dict:
    """One host batch from ``rng``: clean tokens uniform over the held slice
    of the vocabulary but for its last row, the mask id, and the noise by
    the program's own helper for input pipelines (per diffusion block ``t ~
    U[eps, 1]``, per position masked with probability ``t``)."""
    s = _sizes(config)
    tokens = rng.integers(0, s["mask_id"],
                          size=(batch, int(traffic["seq_len"])),
                          dtype=np.int32)
    return transformer.block_diffusion_noise(
        tokens, rng, block=s["block"], mask_id=s["mask_id"],
        eps=float(traffic["noise"]["eps"]))


def loss_function(model: TransformerLM, config: dict):
    return transformer.block_diffusion_loss_fn(
        model, int(config["assumed"]["mask_token_id"]))


def timed_gradient(model: TransformerLM, config: dict, params, batch: dict,
                   reference) -> dict:
    """``reference.watched``'s leaves of the gradient of the loss function
    the trainer's step differentiates — ``block_diffusion_loss_fn`` of the
    model AS TIMED (bfloat16 products, the ``flash_bd_*`` and grouped-matmul
    kernels forward and backward, the traffic's remat) — at ``params`` on
    ``batch`` (``builders/smallthinker.py::timed_gradient`` has why it is
    taken outside the trainer)."""
    loss = loss_function(model, config)
    return jax.jit(lambda p, b: reference.watched(jax.grad(loss)(p, b)))(
        params, {name: jnp.asarray(x) for name, x in batch.items()})


def system_change(trainer, model: TransformerLM, seed: int, batch: dict,
                  steps: int, reference) -> dict:
    """The change of ``reference.watched``'s leaves (and its
    ``CHANGE_ALSO``) over ``steps`` updates of the trainer's own compiled
    step on ``batch``, from a fresh state of the same seed: what the
    trainer's state (its weights' and moments' precision, its update) makes
    of the replayed batch (``builders/ouro.py::system_change`` has why the
    steps are taken again here)."""
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
class Job(_smallthinker.Job):
    """The ``smallthinker`` builder's job on batches of tokens AND noise,
    against ``reference/sdar.py``, with ``correct`` held to three
    comparisons: the replayed losses that have a limit, the first gradient
    of the replay batch (``reference.GRADIENT_TOLERANCE``) and the
    parameters' change over the replayed updates
    (``reference.CHANGE_TOLERANCE``), as the ``ouro`` builder's."""

    #: the trainer again, for ``system_change`` (the driver takes
    #: ``trainer`` and ``state`` away before the comparison)
    _replayer: object = None
    #: per leaf, the relative distance of the system's change of the
    #: parameters from the reference's; set by ``reference_losses``
    change_distance: dict = dataclasses.field(default_factory=dict)
    #: what the system gave (made once: ``faults`` asks again and again)
    _system: tuple | None = None

    def host_batches(self) -> Iterator[dict]:
        """Fresh tokens and fresh noise every step."""
        rng = np.random.default_rng([self._seed, 1])
        batch = self.replay_batch["tokens"].shape[0]
        while True:
            yield draw_batch(rng, self._config, self._traffic, batch)

    def reference_losses(self, steps: int, **probe) -> list[float]:
        """``probe``: ``hyper=`` / ``round_weights=`` of a reference with a
        fault (``tools/sdar_reference_check.py faults``)."""
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
            make_params(self._model, self._seed), self.replay_batch, steps,
            self._traffic["optimizer"], first_gradient=compare_gradient,
            last_change=compare_change, **probe)
        # an earlier line, for the reader of a log: what the second and the
        # third comparison read
        print(json.dumps({
            "first_gradient_distance": self.gradient_distance,
            "largest": max(self.gradient_distance.values(), default=None),
            "limit": reference.GRADIENT_TOLERANCE,
            "parameter_change_distance": self.change_distance,
            "largest_change": max(self.change_distance.values(),
                                  default=None),
            "change_limit": reference.CHANGE_TOLERANCE,
            "loss_limits": reference.LOSS_TOLERANCE}), flush=True)
        return losses

    def losses_agree(self, trainer_losses, reference_losses) -> bool:
        reference = self._reference
        return (reference.agree(trainer_losses, reference_losses)
                and reference.gradients_agree(self.gradient_distance)
                and reference.changes_agree(self.change_distance))


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward FLOP per CLEAN token over what IS computed
    (``perfbench/flops.py``'s conventions otherwise: 2 FLOP a
    multiply-accumulate, backward twice the forward, norms / softmax /
    rotary / the optimizer left out).  A clean token is two rows of the
    trunk: both rows' four attention projections at their grouped widths and
    router, ``k x held / experts`` experts of three matrices a row (uniform
    routing: ``assumed``); the scores and the weighted values over the
    ``L (L + B)`` visible pairs a head — not the ``(2 L)^2`` of the dense
    rows, which would count four times what the kernels do and put ``mfu``
    past the chip —; and ONE row of the head over the held slice of the
    vocabulary, the noised one."""
    s = _sizes(config)
    d, q_width = s["d"], s["heads"] * s["head_dim"]
    row = (2 * d * q_width + 2 * d * s["kv_heads"] * s["head_dim"]
           + d * s["experts"]
           + s["k"] * s["held"] / s["experts"] * 3 * d * s["f"])
    pairs_per_token = seq_len + s["block"]          # L (L + B) / L
    attention = 2 * s["heads"] * s["head_dim"] * pairs_per_token
    forward_mac = s["layers"] * (2 * row + attention) + d * s["vocab"]
    return 3.0 * 2.0 * forward_mac


def parameters(config: dict) -> int:
    """Parameters of the model as built: token table, per layer the four
    attention matrices, the two ``[head_dim]`` scales of q and k, two norms,
    the router over all experts and three matrices for each HELD expert, a
    final norm and an untied head."""
    s = _sizes(config)
    d, q_width = s["d"], s["heads"] * s["head_dim"]
    layer = (2 * d * q_width + 2 * d * s["kv_heads"] * s["head_dim"]
             + 2 * s["head_dim"] + 2 * d + d * s["experts"]
             + s["held"] * 3 * d * s["f"])
    return 2 * d * s["vocab"] + d + s["layers"] * layer


def make_model(config: dict, traffic: dict) -> TransformerLM:
    check_program()
    s = _sizes(config)
    moe = dict(n_experts=s["experts"], d_ff=s["f"], k=s["k"],
               ep_size=s["experts"] // s["held"],
               ep_rank=int(config["deployment"]["expert_rank"]),
               norm_topk_prob=bool(config["norm_topk_prob"]), gated=True,
               activation=config["hidden_act"],
               **_kwargs(traffic.get("moe", {})))
    return TransformerLM(
        TransformerConfig(
            vocab_size=s["vocab"], d_model=s["d"], n_heads=s["heads"],
            n_kv_heads=s["kv_heads"], d_head=s["head_dim"],
            n_layers=s["layers"], d_ff=s["f"],
            max_seq_len=int(config["max_position_embeddings"]),
            rope_theta=float(config["rope_theta"]), qk_norm="head",
            norm_eps=float(config["rms_norm_eps"]),
            attention="block_diffusion", diffusion_block=s["block"],
            **_kwargs(traffic.get("model", {}))),
        mlp_factory=lambda _layer: (lambda: MoEMLP(name="mlp", **moe)))


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
        loss_function(model, config), optimizer, algorithm, mesh=mesh,
        **_kwargs(traffic.get("trainer", {})))
    return model, trainer


def job_of(cell: cells.Cell, traffic: dict, model, trainer, chips: int,
           seed: int) -> Job:
    """``seed``'s job on a trainer already made (weights, state and the
    replay batch are the seed's; ``tools/sdar_reference_check.py`` makes
    several on one trainer)."""
    state = trainer.init(make_params(model, seed))
    seq = int(traffic["seq_len"])
    batch = int(traffic["batch_per_chip"]) * chips
    return Job(
        trainer=trainer, state=state, units_per_step=batch * seq,
        flops_per_unit=flops_per_token(cell.config, seq),
        replay_batch=draw_batch(np.random.default_rng([seed, 0]),
                                cell.config, traffic, batch),
        _model=model, _config=cell.config, _traffic=traffic, _seed=seed,
        _reference=cells.load_plugin("reference", cell.config["builder"],
                                     cell.bench_dir),
        _replayer=trainer,
    )


def build(cell: cells.Cell, traffic: dict, devices: list, seed: int) -> Job:
    model, trainer = make_trainer(cell, traffic, devices)
    return job_of(cell, traffic, model, trainer, len(devices), seed)
