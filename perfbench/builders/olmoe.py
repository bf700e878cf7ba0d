"""Builder ``olmoe``: OLMoE's block on the program's normal path —
``TransformerLM`` (RoPE, QK-norm, the configuration's RMSNorm epsilon) with
``MoEMLP`` (gated experts, dropless top-k routing, raw winners'
probabilities, balance loss over all assignments) as every layer's MLP,
``moe_lm_loss_fn`` and ``BaguaTrainer``, the way a user's script builds
them.  The job it hands the ``train`` driver is the ``transformer_lm``
builder's (see there); what differs is the model, the loss and the
reference.

A program that predates the architecture's fields (the parent commit of the
PR that brought them) is refused by ``check_program`` with a ``CellError``
before any weight is made.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator

import numpy as np
import optax

import bagua_tpu
from bagua_tpu.model_parallel.moe.layer import MoEMLP, moe_lm_loss_fn
from bagua_tpu.models.transformer import TransformerConfig, TransformerLM
from bagua_tpu.parallel.mesh import build_mesh
from perfbench import cells

#: what the architecture needs of the program, by class
NEEDED_FIELDS = {
    TransformerConfig: ("rope_theta", "qk_norm", "norm_eps"),
    MoEMLP: ("gated", "norm_topk_prob", "balance_over_topk"),
}


def check_program() -> None:
    for cls, names in NEEDED_FIELDS.items():
        have = {f.name for f in dataclasses.fields(cls)}
        missing = [n for n in names if n not in have]
        if missing:
            raise cells.CellError(
                f"the program under test cannot build OLMoE: "
                f"{cls.__name__} has no field {', '.join(missing)}")


# the dense builder's helpers are this one's too: dotted-name import, JSON
# dtype names, and weights on the device in one jitted call from the seed
_lm = cells.load_plugin("builders", "transformer_lm")
_import, _kwargs, make_params = _lm._import, _lm._kwargs, _lm.make_params


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward FLOP per target token, ``perfbench/flops.py``'s
    conventions (2 FLOP a multiply-accumulate, backward twice the forward,
    attention at the full ``seq x seq``, norms / softmax / rotary / the
    optimizer left out) over the ACTIVE parameters: per layer the four
    attention matrices, the router and ``num_experts_per_tok`` experts of
    three matrices each — not all ``num_experts``."""
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    layer = (4 * d * d + 2 * seq_len * d
             + int(config["num_experts_per_tok"]) * 3 * d * f
             + d * int(config["num_experts"]))
    forward_mac = (int(config["num_hidden_layers"]) * layer
                   + d * int(config["vocab_size"]))
    return 3.0 * 2.0 * forward_mac


def parameters(config: dict) -> int:
    """Parameters of the model as built: token table, per layer four
    attention matrices, q/k norms, two norms, router and three matrices for
    each of ALL experts, a final norm and an untied head; no biases."""
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    layer = (4 * d * d + 4 * d + d * int(config["num_experts"])
             + int(config["num_experts"]) * 3 * d * f)
    return (2 * d * int(config["vocab_size"]) + d
            + int(config["num_hidden_layers"]) * layer)


@dataclasses.dataclass
class Job:
    trainer: Any
    state: Any
    units_per_step: int
    flops_per_unit: float
    replay_batch: dict
    _model: TransformerLM
    _config: dict
    _traffic: dict
    _seed: int
    _reference: Any
    unit: str = "tokens"

    def host_batches(self) -> Iterator[dict]:
        """Uniform tokens over the published vocabulary, a fresh batch each
        step: the routing is what the seeded router makes of them."""
        rng = np.random.default_rng([self._seed, 1])
        shape = self.replay_batch["tokens"].shape
        while True:
            yield {"tokens": rng.integers(0, int(self._config["vocab_size"]),
                                          size=shape, dtype=np.int32)}

    def compiled_text(self, state, batch) -> str:
        return self.trainer.compiled_step(state, batch).as_text()

    def reference_losses(self, steps: int) -> list[float]:
        """The same weights from the same seed, trained ``steps`` steps on
        the replay batch by ``perfbench/reference/olmoe.py`` on one chip.
        Call after the trainer's state is freed."""
        return self._reference.replay_losses(
            make_params(self._model, self._seed), self.replay_batch["tokens"],
            steps, self._traffic["optimizer"],
            int(self._traffic["reference_micro_batch"]),
            self._reference.hyperparameters(self._config))

    def losses_agree(self, trainer_losses, reference_losses) -> bool:
        return self._reference.agree(trainer_losses, reference_losses)


def make_model(config: dict, traffic: dict) -> TransformerLM:
    check_program()
    moe = dict(n_experts=int(config["num_experts"]),
               d_ff=int(config["intermediate_size"]),
               k=int(config["num_experts_per_tok"]),
               norm_topk_prob=bool(config["norm_topk_prob"]),
               gated=True, balance_over_topk=True,
               **_kwargs(traffic.get("moe", {})))
    return TransformerLM(
        TransformerConfig(
            vocab_size=int(config["vocab_size"]),
            d_model=int(config["hidden_size"]),
            n_heads=int(config["num_attention_heads"]),
            n_layers=int(config["num_hidden_layers"]),
            d_ff=int(config["intermediate_size"]),
            max_seq_len=int(config["max_position_embeddings"]),
            rope_theta=float(config["rope_theta"]), qk_norm=True,
            norm_eps=float(config["rms_norm_eps"]),
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
    loss = moe_lm_loss_fn(model, aux_loss_weight=float(
        config["assumed"]["router_aux_loss_coef"]))
    trainer = bagua_tpu.BaguaTrainer(
        loss, optimizer, algorithm, mesh=mesh,
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
        _traffic=traffic, _seed=seed,
        _reference=cells.load_plugin("reference", cell.config["builder"],
                                     cell.bench_dir),
    )
