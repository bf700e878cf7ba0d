"""Builder ``smallthinker``: SmallThinker's block on the program's normal
path — ``TransformerLM`` (28 query heads over 4 key / value heads of 128, a
4,096-token window with RoPE on three layers of four and full attention
without positions on the fourth, the router reading the block's input) with
``MoEMLP`` (ReLU-gated experts, dropless top-6 of 64, softmax over the
winners, ONE expert-parallel rank's share of the experts) as every layer's
MLP, ``lm_loss_fn`` (the config carries no auxiliary loss) and
``BaguaTrainer``, the way a user's script builds them.  The job it hands
the ``train`` driver is the ``olmoe`` builder's (see
``builders/transformer_lm.py``); what differs is the model and the
reference.

A program that predates the architecture's fields (the parent commit of the
PR that brought them) is refused by ``check_program`` with a ``CellError``
before any weight is made.
"""

from __future__ import annotations

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax

import bagua_tpu
from bagua_tpu.model_parallel.moe.layer import MoEMLP
from bagua_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss_fn,
)
from bagua_tpu.parallel.mesh import build_mesh
from perfbench import cells

#: what the architecture needs of the program, by class
NEEDED_FIELDS = {
    TransformerConfig: ("n_kv_heads", "d_head", "window", "window_layers",
                        "rope_layers", "route_before_attention"),
    MoEMLP: ("activation", "ep_rank"),
}


def check_program() -> None:
    for cls, names in NEEDED_FIELDS.items():
        have = {f.name for f in dataclasses.fields(cls)}
        missing = [n for n in names if n not in have]
        if missing:
            raise cells.CellError(
                f"the program under test cannot build SmallThinker: "
                f"{cls.__name__} has no field {', '.join(missing)}")


# the job, and the helpers every builder shares: dotted-name import and
# JSON dtype names
_olmoe = cells.load_plugin("builders", "olmoe")
_import, _kwargs = _olmoe._import, _olmoe._kwargs


def make_params(model: TransformerLM, seed: int):
    """Weights on the device in one jitted call from the seed: flax's
    defaults, but the token table at unit variance, N(0, 1) an entry
    (flax's ``nn.Embed`` draws it at 1 / d_model, rows of norm 1).  This
    model's router reads the RAW residual stream.  Under rows of norm 1 the
    stream behind layer 0 is all block output — attention's running mean
    over the prefix, nearly one vector for every late position — so the
    routers of layers 1 to 3 send most pairs to a few experts (11 to 34 %
    of a layer's pairs held here, by the seed's draw), and three replayed
    steps cannot tell bfloat16 weights from float32 ones (the loss moves by
    0.0025 where the system itself is 0.0014 away).  At unit variance the
    token's own row dominates the stream, as in a trained model: 24.2 to
    25.8 % of the pairs held in every layer of every seed, and the rounded
    weights move the loss fifty times the system's distance.  It is the
    one leaf not at the default every other cell uses (``departures``), and
    it sets the routing at the START of a run only: inside the window the
    share's router drifts towards its own experts (PERF.md §6, PR 34)."""
    stub = jnp.zeros((1, 8), jnp.int32)

    def init(key):
        params = model.init(key, stub)["params"]
        table = params["embed"]["embedding"]
        return {**params, "embed": {
            "embedding": table * math.sqrt(table.shape[1])}}

    return jax.jit(init)(jax.random.PRNGKey(seed))


def timed_gradient(model: TransformerLM, params, batch: dict,
                   reference) -> dict:
    """``reference.watched``'s leaves of the gradient of the loss
    function the trainer's step differentiates — ``lm_loss_fn`` of the model
    AS TIMED (bfloat16 products, the flash and grouped-matmul kernels
    forward and backward, the traffic's remat) — at ``params`` on ``batch``.
    The ``train`` driver hands a job neither the step's gradients nor its
    replayed state (PERF.md §7), so this is the nearest the job comes to the
    timed step: the same function of the same shapes, outside the
    trainer."""
    loss = lm_loss_fn(model)
    return jax.jit(lambda p, b: reference.watched(jax.grad(loss)(p, b)))(
        params, {name: jnp.asarray(x) for name, x in batch.items()})


@dataclasses.dataclass
class Job(_olmoe.Job):
    """The ``olmoe`` builder's job, its reference started from THIS
    builder's weights, and ``correct`` held to a second comparison: the
    first gradient of the replay batch (``reference.GRADIENT_TOLERANCE``)
    beside the replayed losses."""

    #: per watched leaf, the system's first gradient's relative distance from
    #: the reference's; set by ``reference_losses``
    gradient_distance: dict = dataclasses.field(default_factory=dict)

    def reference_losses(self, steps: int, **probe) -> list[float]:
        """``probe``: ``hyper=`` / ``round_weights=`` of a reference with a
        fault (``tools/smallthinker_reference_check.py faults``)."""
        params = make_params(self._model, self._seed)
        got = timed_gradient(self._model, params, self.replay_batch,
                             self._reference)

        def compare(want: dict) -> None:
            self.gradient_distance = {
                name: float(d) for name, d in
                self._reference.gradient_distance(got, want).items()}

        probe.setdefault("hyper",
                         self._reference.hyperparameters(self._config))
        losses = self._reference.replay_losses(
            params, self.replay_batch["tokens"], steps,
            self._traffic["optimizer"],
            int(self._traffic["reference_micro_batch"]),
            first_gradient=compare, **probe)
        # an earlier line, for the reader of a log: what the second
        # comparison read
        print(json.dumps({
            "first_gradient_distance": self.gradient_distance,
            "largest": max(self.gradient_distance.values(), default=None),
            "limit": self._reference.GRADIENT_TOLERANCE}), flush=True)
        return losses

    def losses_agree(self, trainer_losses, reference_losses) -> bool:
        return (self._reference.agree(trainer_losses, reference_losses)
                and self._reference.gradients_agree(self.gradient_distance))


def _sizes(config: dict) -> dict:
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "f": int(config["moe_ffn_hidden_size"]),
        "held": int(config["moe_num_primary_experts"]),
        "experts": int(config["reduced_from"]["moe_num_primary_experts"]),
        "k": int(config["moe_num_active_primary_experts"]),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
        "window": int(config["sliding_window_size"]),
    }


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs of one head a causal layer computes: ``j <= i``,
    and ``i - j < window`` where windowed."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward FLOP per target token over what IS computed
    (``perfbench/flops.py``'s conventions otherwise: 2 FLOP a
    multiply-accumulate, backward twice the forward, norms / softmax /
    rotary / the optimizer left out): the four attention projections at
    their grouped widths, the router, the scores and the weighted values
    over the causal half on a full layer and over the band on a window
    layer — not the full ``s x s``, which at 8,192 would count 2 to 2.7
    times what the kernels do and put ``mfu`` past the chip — ``k x held /
    experts`` experts of three matrices a token (uniform routing:
    ``assumed``) and the held slice of the vocabulary."""
    s = _sizes(config)
    d, q_width = s["d"], s["heads"] * s["head_dim"]
    matrices = (2 * d * q_width + 2 * d * s["kv_heads"] * s["head_dim"]
                + d * s["experts"]
                + s["k"] * s["held"] / s["experts"] * 3 * d * s["f"])
    windowed = config["sliding_window_layout"]
    pairs = sum(
        visible_pairs(seq_len, s["window"] if windowed[i % len(windowed)]
                      else None) for i in range(s["layers"]))
    attention = 2 * s["heads"] * s["head_dim"] * pairs / seq_len
    forward_mac = s["layers"] * matrices + attention + d * s["vocab"]
    return 3.0 * 2.0 * forward_mac


def parameters(config: dict) -> int:
    """Parameters of the model as built: token table, per layer the four
    attention matrices, two norms, the router over all experts and three
    matrices for each HELD expert, a final norm and an untied head."""
    s = _sizes(config)
    d, q_width = s["d"], s["heads"] * s["head_dim"]
    layer = (2 * d * q_width + 2 * d * s["kv_heads"] * s["head_dim"] + 2 * d
             + d * s["experts"] + s["held"] * 3 * d * s["f"])
    return 2 * d * s["vocab"] + d + s["layers"] * layer


def make_model(config: dict, traffic: dict) -> TransformerLM:
    check_program()
    s = _sizes(config)
    moe = dict(n_experts=s["experts"], d_ff=s["f"], k=s["k"],
               ep_size=s["experts"] // s["held"],
               ep_rank=int(config["deployment"]["expert_rank"]),
               norm_topk_prob=bool(config["norm_topk_prob"]), gated=True,
               activation=config["assumed"]["expert_activation"],
               **_kwargs(traffic.get("moe", {})))
    return TransformerLM(
        TransformerConfig(
            vocab_size=s["vocab"], d_model=s["d"], n_heads=s["heads"],
            n_kv_heads=s["kv_heads"], d_head=s["head_dim"],
            n_layers=s["layers"], d_ff=s["f"],
            max_seq_len=int(config["max_position_embeddings"]),
            rope_theta=float(config["rope_theta"]),
            rope_layers=tuple(config["rope_layout"]),
            window=s["window"],
            window_layers=tuple(config["sliding_window_layout"]),
            route_before_attention=True,
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
    trainer = bagua_tpu.BaguaTrainer(
        lm_loss_fn(model), optimizer, algorithm, mesh=mesh,
        **_kwargs(traffic.get("trainer", {})))
    return model, trainer


def build(cell: cells.Cell, traffic: dict, devices: list, seed: int) -> Job:
    model, trainer = make_trainer(cell, traffic, devices)
    state = trainer.init(make_params(model, seed))
    seq = int(traffic["seq_len"])
    batch = int(traffic["batch_per_chip"]) * len(devices)
    # ids from the held slice of the vocabulary: a sliced vocabulary is a
    # smaller vocabulary (the job's ``host_batches`` draws from it too)
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
