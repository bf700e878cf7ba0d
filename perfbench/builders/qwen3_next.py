"""Builder ``qwen3_next``: Qwen3-Next-80B-A3B's hybrid decoder on the
program's normal path — ``TransformerLM`` (linear attention by the gated
delta rule on three layers of four: 16 key / 32 value heads of 128, a causal
depthwise convolution of 4 taps, the gated per-head norm; output-gated
softmax attention on the fourth: 16 query over 2 key / value heads of 256,
RMSNorm on each head of q and k, RoPE over the first 64 lanes of a head;
every norm's scale ``1 + w``) with ``MoEMLP`` (SiLU-gated experts, dropless
top-10 of 512 renormalised, ONE expert-parallel rank's share, beside them a
shared expert under its own sigmoid gate) as every layer's MLP,
``lm_loss_fn`` and ``BaguaTrainer``, the way a user's script builds them.
The job it hands the ``train`` driver is the ``smallthinker`` builder's with
the ``sdar`` builder's third comparison: the replayed losses, the first
gradient AND the parameters' change over the replayed updates decide
``correct``.

A program that predates the architecture's fields (the parent commit of the
PR that brought them) is refused by ``check_program`` with a ``CellError``
before any weight is made.
"""

from __future__ import annotations

import dataclasses
import json

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
    TransformerConfig: ("n_kv_heads", "d_head", "qk_norm", "mixer_layers",
                        "linear_key_heads", "linear_value_heads",
                        "linear_key_dim", "linear_value_dim", "linear_conv",
                        "attn_gate", "rotary_dim", "norm_zero_centered"),
    MoEMLP: ("activation", "ep_rank", "norm_topk_prob", "shared_d_ff",
             "shared_gate"),
}


def check_program() -> None:
    for cls, names in NEEDED_FIELDS.items():
        have = {f.name for f in dataclasses.fields(cls)}
        missing = [n for n in names if n not in have]
        if missing:
            raise cells.CellError(
                f"the program under test cannot build Qwen3-Next: "
                f"{cls.__name__} has no field {', '.join(missing)}")


# the job, and the helpers every builder shares: dotted-name import, JSON
# dtype names, weights on the device in one jitted call from the seed (the
# token table at unit variance: ``builders/smallthinker.py::make_params``
# has why), the first gradient of the model as timed, and the parameters'
# change through the trainer's own step
_smallthinker = cells.load_plugin("builders", "smallthinker")
_sdar = cells.load_plugin("builders", "sdar")
_import, _kwargs = _smallthinker._import, _smallthinker._kwargs
make_params = _smallthinker.make_params
timed_gradient, visible_pairs = (_smallthinker.timed_gradient,
                                 _smallthinker.visible_pairs)
system_change = _sdar.system_change


def _sizes(config: dict) -> dict:
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "rotary_dim": int(int(config["head_dim"])
                          * float(config["partial_rotary_factor"])),
        "period": int(config["full_attention_interval"]),
        "lin_k_heads": int(config["linear_num_key_heads"]),
        "lin_v_heads": int(config["linear_num_value_heads"]),
        "lin_k_dim": int(config["linear_key_head_dim"]),
        "lin_v_dim": int(config["linear_value_head_dim"]),
        "taps": int(config["linear_conv_kernel_dim"]),
        "f": int(config["moe_intermediate_size"]),
        "shared_f": int(config["shared_expert_intermediate_size"]),
        "held": int(config["num_experts"]),
        "experts": int(config["reduced_from"]["num_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
    }


def mixer_pattern(config: dict) -> tuple:
    """The period of the mixers, 1 = linear attention: layer ``i`` is full
    attention where ``(i + 1) % full_attention_interval == 0``."""
    period = int(config["full_attention_interval"])
    return (1,) * (period - 1) + (0,)


def _layer_counts(s: dict) -> tuple[int, int]:
    """(linear-attention layers, full-attention layers) of the depth run."""
    full = s["layers"] // s["period"]
    return s["layers"] - full, full


def _linear_widths(s: dict) -> tuple[int, int]:
    return s["lin_k_heads"] * s["lin_k_dim"], s["lin_v_heads"] * s["lin_v_dim"]


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward FLOP per target token over what IS computed
    (``perfbench/flops.py``'s conventions: 2 FLOP a multiply-accumulate,
    backward twice the forward, norms / softmax / rotary / the optimizer
    left out).  A linear-attention layer: the two fused in-projections, the
    convolution's taps, the out-projection and the RECURRENT form of the
    delta rule, ``3 d_k d_v`` multiply-accumulates a token and value head
    (decay-and-read, rank-one update, output read) — the chunked kernels do
    about twice that, which no choice of chunk can put into this count.  A
    full-attention layer: q with its gate, k, v, o at their grouped widths
    and the scores and weighted values over the causal half.  Every layer:
    the router over all experts, ``k x held / experts`` routed experts of
    three matrices a token (uniform routing: ``assumed``), the shared expert
    and its gate.  The held slice of the vocabulary."""
    s = _sizes(config)
    d = s["d"]
    key_w, value_w = _linear_widths(s)
    linear = (d * (2 * key_w + 2 * value_w) + d * 2 * s["lin_v_heads"]
              + s["taps"] * (2 * key_w + value_w)
              + s["lin_v_heads"] * 3 * s["lin_k_dim"] * s["lin_v_dim"]
              + value_w * d)
    q_width, kv_width = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    full = (d * 2 * q_width + 2 * d * kv_width + q_width * d
            + 2 * q_width * visible_pairs(seq_len, None) / seq_len)
    experts = (d * s["experts"] + s["k"] * s["held"] / s["experts"] * 3 * d * s["f"]
               + 3 * d * s["shared_f"] + d)
    n_linear, n_full = _layer_counts(s)
    forward_mac = (n_linear * linear + n_full * full + s["layers"] * experts
                   + d * s["vocab"])
    return 3.0 * 2.0 * forward_mac


def parameters(config: dict) -> int:
    """Parameters of the model as built: token table, a linear-attention
    layer's two in-projections, taps, ``A_log``, ``dt_bias``, gated norm and
    out-projection, a full-attention layer's four matrices (q twice as wide:
    its gate) and two ``[head_dim]`` scales, per layer two norms, the router
    over all experts, three matrices for each HELD expert, the shared
    expert and its gate; a final norm and an untied head."""
    s = _sizes(config)
    d = s["d"]
    key_w, value_w = _linear_widths(s)
    linear = (d * (2 * key_w + 2 * value_w) + d * 2 * s["lin_v_heads"]
              + s["taps"] * (2 * key_w + value_w) + 2 * s["lin_v_heads"]
              + s["lin_v_dim"] + value_w * d)
    q_width, kv_width = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    full = (d * 2 * q_width + 2 * d * kv_width + q_width * d
            + 2 * s["head_dim"])
    experts = (d * s["experts"] + s["held"] * 3 * d * s["f"]
               + 3 * d * s["shared_f"] + d)
    n_linear, n_full = _layer_counts(s)
    return (2 * d * s["vocab"] + d + n_linear * linear + n_full * full
            + s["layers"] * (experts + 2 * d))


@dataclasses.dataclass
class Job(_smallthinker.Job):
    """The ``smallthinker`` builder's job (next-token batches, the first
    gradient of the model as timed) against ``reference/qwen3_next.py``,
    with ``correct`` held to three comparisons: the replayed losses
    (``reference.LOSS_TOLERANCE``), the first gradient of the replay batch
    on the leaves ``reference.watched`` picks — every gate the architecture
    adds — (``GRADIENT_TOLERANCE``) and the parameters' change over the
    replayed updates through the trainer's own step (``CHANGE_TOLERANCE``),
    as the ``sdar`` builder's."""

    #: the trainer again, for ``system_change`` (the driver takes
    #: ``trainer`` and ``state`` away before the comparison)
    _replayer: object = None
    change_distance: dict = dataclasses.field(default_factory=dict)
    #: what the system gave (made once: ``faults`` asks again and again)
    _system: tuple | None = None

    def reference_losses(self, steps: int, **probe) -> list[float]:
        """``probe``: ``hyper=`` / ``round_weights=`` of a reference with a
        fault (``tools/qwen3_next_reference_check.py faults``)."""
        reference = self._reference
        if self._system is None:
            change = system_change(self._replayer, self._model, self._seed,
                                   self.replay_batch, steps, reference)
            self._system = (change, timed_gradient(
                self._model, make_params(self._model, self._seed),
                self.replay_batch, reference))
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
            "router_limit": reference.ROUTER_GRADIENT_TOLERANCE,
            "parameter_change_distance": self.change_distance,
            "largest_change": max(self.change_distance.values(),
                                  default=None),
            "change_limit": reference.CHANGE_TOLERANCE,
            "loss_limits": reference.LOSS_TOLERANCE}), flush=True)
        return losses

    def losses_agree(self, trainer_losses, reference_losses) -> bool:
        reference = self._reference
        return (reference.agree(trainer_losses, reference_losses,
                                reference.LOSS_TOLERANCE)
                and reference.gradients_agree(self.gradient_distance)
                and reference.changes_agree(self.change_distance,
                                            reference.CHANGE_TOLERANCE))


def make_model(config: dict, traffic: dict) -> TransformerLM:
    check_program()
    s = _sizes(config)
    moe = dict(n_experts=s["experts"], d_ff=s["f"], k=s["k"],
               ep_size=s["experts"] // s["held"],
               ep_rank=int(config["deployment"]["expert_rank"]),
               norm_topk_prob=bool(config["norm_topk_prob"]), gated=True,
               activation=config["hidden_act"], shared_d_ff=s["shared_f"],
               shared_gate=True, **_kwargs(traffic.get("moe", {})))
    return TransformerLM(
        TransformerConfig(
            vocab_size=s["vocab"], d_model=s["d"], n_heads=s["heads"],
            n_kv_heads=s["kv_heads"], d_head=s["head_dim"],
            n_layers=s["layers"], d_ff=s["f"],
            max_seq_len=int(config["max_position_embeddings"]),
            rope_theta=float(config["rope_theta"]),
            rotary_dim=s["rotary_dim"], qk_norm="head", attn_gate=True,
            norm_zero_centered=True,
            norm_eps=float(config["rms_norm_eps"]),
            mixer_layers=mixer_pattern(config),
            linear_key_heads=s["lin_k_heads"],
            linear_value_heads=s["lin_v_heads"],
            linear_key_dim=s["lin_k_dim"], linear_value_dim=s["lin_v_dim"],
            linear_conv=s["taps"],
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


def job_of(cell: cells.Cell, traffic: dict, model, trainer, chips: int,
           seed: int) -> Job:
    """``seed``'s job on a trainer already made (weights, state and the
    replay batch are the seed's; ``tools/qwen3_next_reference_check.py``
    makes several on one trainer)."""
    state = trainer.init(make_params(model, seed))
    seq = int(traffic["seq_len"])
    batch = int(traffic["batch_per_chip"]) * chips
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
        _replayer=trainer,
    )


def build(cell: cells.Cell, traffic: dict, devices: list, seed: int) -> Job:
    model, trainer = make_trainer(cell, traffic, devices)
    return job_of(cell, traffic, model, trainer, len(devices), seed)
