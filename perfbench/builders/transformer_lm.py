"""Builder ``transformer_lm``: ``TransformerLM`` + ``lm_loss_fn`` driven through
``BaguaTrainer``, the way ``chip_smoke.py`` and a user's script do.

Everything a cell varies comes from its two data files: the sizes from the
configuration, and sequence length, batch, mesh, algorithm, optimizer,
trainer and model options from the traffic mix.  A builder gives the
``train`` driver a job with:

    trainer, state        the system under test and its initial state
    units_per_step        target tokens of one global step
    flops_per_unit        forward+backward FLOP per target token (perfbench/flops.py)
    replay_batch          one seeded host batch (the correctness replay)
    host_batches()        endless seeded host batches, a fresh one per step
    compiled_text(s, b)   optimized HLO of the compiled step
    reference_losses(n)   the plain reference's losses on the replay batch
    losses_agree(a, b)    the comparison that decides ``correct``
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax

import bagua_tpu
from bagua_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss_fn,
)
from bagua_tpu.parallel.mesh import build_mesh
from perfbench import cells, flops


def _import(dotted: str):
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def _kwargs(raw: dict) -> dict:
    """JSON has no dtypes: a ``*dtype`` argument names one."""
    return {k: jnp.dtype(v) if k.endswith("dtype") else v
            for k, v in raw.items()}


@dataclasses.dataclass
class Job:
    trainer: Any
    state: Any
    units_per_step: int
    flops_per_unit: float
    replay_batch: dict
    _model: TransformerLM
    _traffic: dict
    _vocab: int
    _seed: int
    _reference: Any
    unit: str = "tokens"

    def host_batches(self) -> Iterator[dict]:
        """Uniform tokens over the published vocabulary; never zeros, never
        the same batch twice (an all-zero batch gathers one embedding row)."""
        rng = np.random.default_rng([self._seed, 1])
        shape = self.replay_batch["tokens"].shape
        while True:
            yield {"tokens": rng.integers(0, self._vocab, size=shape,
                                          dtype=np.int32)}

    def compiled_text(self, state, batch) -> str:
        # the trainer has no public handle on its compiled step yet
        # (PERF.md, Open questions): this is the one private call
        step = self.trainer._get_step_fn()
        return step.lower(state, batch).compile().as_text()

    def reference_losses(self, steps: int) -> list[float]:
        """The same weights from the same seed, trained ``steps`` steps on
        the replay batch by ``perfbench/reference/transformer_lm.py`` on one
        chip.  Call after the trainer's state is freed."""
        return self._reference.replay_losses(
            make_params(self._model, self._seed), self.replay_batch["tokens"],
            steps, self._traffic["optimizer"],
            int(self._traffic["reference_micro_batch"]))

    def losses_agree(self, trainer_losses, reference_losses) -> bool:
        return self._reference.agree(trainer_losses, reference_losses)


def make_params(model: TransformerLM, seed: int):
    """Weights on the device in one jitted call from the seed."""
    stub = jnp.zeros((1, 8), jnp.int32)
    return jax.jit(lambda key: model.init(key, stub)["params"])(
        jax.random.PRNGKey(seed))


def make_trainer(cell: cells.Cell, traffic: dict, devices: list):
    """The model and its trainer over ``devices``, as the traffic mix
    configures them; nothing is placed on a device yet (the AOT compile test
    hands this described devices)."""
    sizes = flops.transformer_lm_sizes(cell.config)
    if int(traffic["seq_len"]) > sizes["max_positions"]:
        raise cells.CellError(
            f"{cell.name}: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {sizes['max_positions']} positions")
    model = TransformerLM(TransformerConfig(
        vocab_size=sizes["padded_vocab_size"], d_model=sizes["d_model"],
        n_heads=sizes["n_heads"], n_layers=sizes["n_layers"],
        d_ff=sizes["d_ff"], max_seq_len=sizes["max_positions"],
        **traffic.get("model", {})))
    mesh = build_mesh(dict(traffic["mesh"]), devices)
    bagua_tpu.init_process_group(mesh=mesh)
    algorithm = _import(traffic["algorithm"]["class"])(
        **_kwargs(traffic["algorithm"].get("kwargs", {})))
    optimizer = getattr(optax, traffic["optimizer"]["name"])(
        **traffic["optimizer"].get("kwargs", {}))
    trainer = bagua_tpu.BaguaTrainer(
        lm_loss_fn(model), optimizer, algorithm, mesh=mesh,
        **_kwargs(traffic.get("trainer", {})))
    return model, trainer


def build(cell: cells.Cell, traffic: dict, devices: list, seed: int) -> Job:
    model, trainer = make_trainer(cell, traffic, devices)
    state = trainer.init(make_params(model, seed))
    sizes = flops.transformer_lm_sizes(cell.config)
    seq = int(traffic["seq_len"])
    batch = int(traffic["batch_per_chip"]) * len(devices)
    replay = np.random.default_rng([seed, 0]).integers(
        0, sizes["vocab_size"], size=(batch, seq + 1), dtype=np.int32)
    return Job(
        trainer=trainer, state=state, units_per_step=batch * seq,
        flops_per_unit=flops.transformer_lm_flops_per_token(cell.config, seq),
        replay_batch={"tokens": replay}, _model=model, _traffic=traffic,
        _vocab=sizes["vocab_size"], _seed=seed,
        _reference=cells.load_plugin("reference", cell.config["builder"],
                                     cell.bench_dir),
    )
