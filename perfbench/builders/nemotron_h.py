"""Builder ``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B's hybrid decoder on
the program's normal path — ``TransformerLM`` with ONE sub-layer a block
under the published pattern (``layer_kinds``: ``M`` a Mamba-2 state-space
mixer of 64 heads of 64 over 8 groups and a state of 128, a causal depthwise
convolution of 4 taps with bias, the gate before the grouped norm; ``*``
softmax attention of 32 query over 2 key / value heads of 128 with no
positional encoding; ``E`` ``MoEMLP``: a sigmoid router whose bias enters the
choice alone, dropless top-6 of 128 renormalised and scaled by 2.5, ungated
ReLU^2 experts, ONE expert-parallel rank's share, beside them an ungated
shared expert of twice the width), ``lm_loss_fn`` and ``BaguaTrainer``, the
way a user's script builds them.  The job it hands the ``train`` driver is
the ``qwen3_next`` builder's: the replayed losses, the first gradient AND the
parameters' change over the replayed updates decide ``correct``.

A program that predates the architecture's fields (the parent commit of the
PR that brought them) is refused by ``check_program`` with a ``CellError``
before any weight is made.
"""

from __future__ import annotations

import dataclasses

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
    TransformerConfig: ("n_kv_heads", "d_head", "rope_layers", "layer_kinds",
                        "ssm_heads", "ssm_head_dim", "ssm_groups",
                        "ssm_state", "ssm_conv", "ssm_chunk"),
    MoEMLP: ("activation", "ep_rank", "norm_topk_prob", "shared_d_ff",
             "router_score", "score_bias", "score_bias_std", "routed_scale"),
}

#: the pattern's letters -> ``TransformerConfig.layer_kinds``
KINDS = {"M": "ssm", "E": "moe", "*": "attn"}


def check_program() -> None:
    for cls, names in NEEDED_FIELDS.items():
        have = {f.name for f in dataclasses.fields(cls)}
        missing = [n for n in names if n not in have]
        if missing:
            raise cells.CellError(
                f"the program under test cannot build Nemotron-H: "
                f"{cls.__name__} has no field {', '.join(missing)}")


# the job and the helpers every builder shares (``builders/qwen3_next.py``
# has the list): weights from the seed with the token table at unit
# variance, the three comparisons of ``correct``
_next = cells.load_plugin("builders", "qwen3_next")
_import, _kwargs = _next._import, _next._kwargs
make_params, visible_pairs, Job = (_next.make_params, _next.visible_pairs,
                                   _next.Job)


def _sizes(config: dict) -> dict:
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "ssm_heads": int(config["mamba_num_heads"]),
        "ssm_head_dim": int(config["mamba_head_dim"]),
        "ssm_groups": int(config["n_groups"]),
        "ssm_state": int(config["ssm_state_size"]),
        "taps": int(config["conv_kernel"]),
        "chunk": int(config["chunk_size"]),
        "f": int(config["moe_intermediate_size"]),
        "shared_f": (int(config["moe_shared_expert_intermediate_size"])
                     * int(config["n_shared_experts"])),
        "held": int(config["n_routed_experts"]),
        "experts": int(config["reduced_from"]["n_routed_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
    }


def layer_kinds(config: dict) -> tuple:
    """``TransformerConfig.layer_kinds`` of the configuration's pattern, one
    kind a layer."""
    pattern = str(config["hybrid_override_pattern"])
    if len(pattern) != int(config["num_hidden_layers"]):
        raise cells.CellError(
            f"hybrid_override_pattern {pattern!r} does not name each of the "
            f"{config['num_hidden_layers']} layers")
    return tuple(KINDS[letter] for letter in pattern)


def _ssm_widths(s: dict) -> tuple[int, int]:
    """(d_inner, the convolved width) of a state-space layer."""
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    return inner, inner + 2 * s["ssm_groups"] * s["ssm_state"]


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward FLOP per target token over what IS computed
    (``perfbench/flops.py``'s conventions: 2 FLOP a multiply-accumulate,
    backward twice the forward, norms / softmax / the optimizer left out).
    A state-space layer: the fused in-projection, the convolution's taps,
    the out-projection and the RECURRENT form of the scan, ``5 P N`` FLOP a
    token and head forward (the decay ``P N``, the rank-one update and the
    read ``2 P N`` each) — a count no choice of chunk can inflate: the
    chunked kernels do more.  An attention layer: q, k, v, o at their
    grouped widths and the scores and weighted values over the causal half.
    An expert layer: the router over all experts, ``k x held / experts``
    routed experts of TWO matrices a token (uniform routing: ``assumed``)
    and the shared expert once."""
    s = _sizes(config)
    d = s["d"]
    inner, conv_width = _ssm_widths(s)
    ssm = (d * (inner + conv_width + s["ssm_heads"]) + s["taps"] * conv_width
           + s["ssm_heads"] * 2.5 * s["ssm_head_dim"] * s["ssm_state"]
           + inner * d)
    q_width, kv_width = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    attn = (2 * d * q_width + 2 * d * kv_width
            + 2 * q_width * visible_pairs(seq_len, None) / seq_len)
    experts = (d * s["experts"]
               + s["k"] * s["held"] / s["experts"] * 2 * d * s["f"]
               + 2 * d * s["shared_f"])
    kinds = layer_kinds(config)
    forward_mac = (kinds.count("ssm") * ssm + kinds.count("attn") * attn
                   + kinds.count("moe") * experts + d * s["vocab"])
    return 3.0 * 2.0 * forward_mac


def parameters(config: dict) -> int:
    """Parameters of the model as built: token table; a state-space layer's
    in-projection, taps and their bias, ``dt_bias`` / ``A_log`` / ``D``, the
    gated norm and the out-projection; an attention layer's four matrices;
    an expert layer's router over all experts, its score bias, two matrices
    for each HELD expert and the shared expert's two; one norm a layer; a
    final norm and an untied head."""
    s = _sizes(config)
    d = s["d"]
    inner, conv_width = _ssm_widths(s)
    ssm = (d * (inner + conv_width + s["ssm_heads"])
           + (s["taps"] + 1) * conv_width + 3 * s["ssm_heads"] + inner
           + inner * d)
    q_width, kv_width = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    attn = 2 * d * q_width + 2 * d * kv_width
    experts = (d * s["experts"] + s["experts"] + s["held"] * 2 * d * s["f"]
               + 2 * d * s["shared_f"])
    kinds = layer_kinds(config)
    return (2 * d * s["vocab"] + d + kinds.count("ssm") * ssm
            + kinds.count("attn") * attn + kinds.count("moe") * experts
            + s["layers"] * d)


def make_model(config: dict, traffic: dict) -> TransformerLM:
    check_program()
    s = _sizes(config)
    moe = dict(n_experts=s["experts"], d_ff=s["f"], k=s["k"],
               ep_size=s["experts"] // s["held"],
               ep_rank=int(config["deployment"]["expert_rank"]),
               norm_topk_prob=bool(config["norm_topk_prob"]), gated=False,
               activation=config["mlp_hidden_act"], shared_d_ff=s["shared_f"],
               router_score="sigmoid", score_bias=True,
               score_bias_std=float(config["assumed"]["score_bias_std"]),
               routed_scale=float(config["routed_scaling_factor"]),
               **_kwargs(traffic.get("moe", {})))
    return TransformerLM(
        TransformerConfig(
            vocab_size=s["vocab"], d_model=s["d"], n_heads=s["heads"],
            n_kv_heads=s["kv_heads"], d_head=s["head_dim"],
            n_layers=s["layers"], d_ff=s["f"],
            max_seq_len=int(config["max_position_embeddings"]),
            # no layer rotates and no position table is learned: the
            # family's attention has no positional encoding (``assumed``)
            rope_theta=float(config["rope_theta"]), rope_layers=(0,),
            norm_eps=float(config["layer_norm_epsilon"]),
            layer_kinds=layer_kinds(config), ssm_heads=s["ssm_heads"],
            ssm_head_dim=s["ssm_head_dim"], ssm_groups=s["ssm_groups"],
            ssm_state=s["ssm_state"], ssm_conv=s["taps"],
            ssm_chunk=s["chunk"], **_kwargs(traffic.get("model", {}))),
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
    replay batch are the seed's; ``tools/nemotron_h_reference_check.py``
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
