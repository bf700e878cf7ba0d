"""Builder ``olmo_hybrid``: Olmo-Hybrid-7B's decoder on the program's normal
path — ``TransformerLM`` (linear attention by the gated delta rule on three
layers of four: 30 key = 30 value heads, keys of 96 lanes and values of 192,
a write strength of ``2 sigmoid(b)``, a causal depthwise convolution of 4
taps, the gated per-head norm; softmax attention on the fourth: 30 heads of
128, RMSNorm over the whole width of q and k, no rotation; the dense
SiLU-gated MLP of width 11,008 in every layer; a block norms each
sub-layer's OUTPUT and nothing in front of it), ``lm_loss_fn`` and
``BaguaTrainer``, the way a user's script builds them.  The job it hands the
``train`` driver is the ``qwen3_next`` builder's — the replayed losses, the
first gradient and the parameters' change over the replayed updates decide
``correct`` — with two differences that the size forces (one pipeline stage
is 0.93 B parameters: no single chip holds its weights, a gradient and the
moments): the first gradient of the model as timed is taken as the trainer's
step takes it, one sequence a chip over the trainer's own mesh, and what is
kept for a comparison waits on the host.  A fourth comparison holds the
rule's own precision, forward and backward (``reference.rule_probe``: the
``gdn_fwd`` / ``gdn_bwd`` kernels at the timed rows against the scan and its
VJP).

A program that predates the architecture's fields (the parent commit of the
PR that brought them) is refused by ``check_program`` with a ``CellError``
before any weight is made.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import bagua_tpu
from bagua_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss_fn,
)
from bagua_tpu.parallel.mesh import build_mesh
from perfbench import cells

#: what the architecture needs of the program
NEEDED_FIELDS = {
    TransformerConfig: ("qk_norm", "rope_layers", "mixer_layers",
                        "linear_key_heads", "linear_value_heads",
                        "linear_key_dim", "linear_value_dim", "linear_conv",
                        "linear_neg_eigval", "post_norms", "pre_norms"),
}


#: the family's base (Olmo 3), what ``rope_theta`` would be were it not null
OLMO3_ROPE_THETA = 500000.0


def check_program() -> None:
    for cls, names in NEEDED_FIELDS.items():
        have = {f.name for f in dataclasses.fields(cls)}
        missing = [n for n in names if n not in have]
        if missing:
            raise cells.CellError(
                f"the program under test cannot build Olmo-Hybrid: "
                f"{cls.__name__} has no field {', '.join(missing)}")


# the job, and the helpers every builder shares: dotted-name import, JSON
# dtype names, weights on the device in one jitted call from the seed (flax's
# defaults, the token table too: no router reads the stream here)
_olmoe = cells.load_plugin("builders", "olmoe")
_import, _kwargs, make_params = (_olmoe._import, _olmoe._kwargs,
                                 _olmoe.make_params)
visible_pairs = cells.load_plugin("builders", "smallthinker").visible_pairs


def _sizes(config: dict) -> dict:
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "lin_k_heads": int(config["linear_num_key_heads"]),
        "lin_v_heads": int(config["linear_num_value_heads"]),
        "lin_k_dim": int(config["linear_key_head_dim"]),
        "lin_v_dim": int(config["linear_value_head_dim"]),
        "taps": int(config["linear_conv_kernel_dim"]),
        "f": int(config["intermediate_size"]),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
    }


def mixer_pattern(config: dict) -> tuple:
    """The mixers of the layers run, 1 = linear attention."""
    kinds = {"linear_attention": 1, "full_attention": 0}
    pattern = tuple(kinds[kind] for kind in config["layer_types"])
    if len(pattern) != int(config["num_hidden_layers"]):
        raise cells.CellError("layer_types names another depth than "
                              "num_hidden_layers")
    return pattern


def _linear_widths(s: dict) -> tuple[int, int]:
    return s["lin_k_heads"] * s["lin_k_dim"], s["lin_v_heads"] * s["lin_v_dim"]


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward FLOP per target token over what IS computed
    (``perfbench/flops.py``'s conventions: 2 FLOP a multiply-accumulate,
    backward twice the forward, norms / softmax / the optimizer left out).
    A linear-attention layer: the two fused in-projections, the
    convolution's taps, the out-projection and the RECURRENT form of the
    delta rule, ``3 d_k d_v`` multiply-accumulates a token and value head —
    the chunked kernels do about twice that, which no choice of chunk can
    put into this count.  A full-attention layer: q, k, v, o and the scores
    and weighted values over the causal half.  Every layer: the dense gated
    MLP's three matrices.  The held slice of the vocabulary."""
    s = _sizes(config)
    d = s["d"]
    key_w, value_w = _linear_widths(s)
    linear = (d * (2 * key_w + 2 * value_w) + d * 2 * s["lin_v_heads"]
              + s["taps"] * (2 * key_w + value_w)
              + s["lin_v_heads"] * 3 * s["lin_k_dim"] * s["lin_v_dim"]
              + value_w * d)
    full = 4 * d * d + 2 * d * visible_pairs(seq_len, None) / seq_len
    n_linear = sum(mixer_pattern(config))
    forward_mac = (n_linear * linear + (s["layers"] - n_linear) * full
                   + s["layers"] * 3 * d * s["f"] + d * s["vocab"])
    return 3.0 * 2.0 * forward_mac


def parameters(config: dict) -> int:
    """Parameters of the model as built: token table, a linear-attention
    layer's two in-projections, taps, ``A_log``, ``dt_bias``, gated norm and
    out-projection, a full-attention layer's four matrices and two
    whole-width norm scales, per layer the MLP's three matrices and the two
    output norms; a final norm and an untied head."""
    s = _sizes(config)
    d = s["d"]
    key_w, value_w = _linear_widths(s)
    linear = (d * (2 * key_w + 2 * value_w) + d * 2 * s["lin_v_heads"]
              + s["taps"] * (2 * key_w + value_w) + 2 * s["lin_v_heads"]
              + s["lin_v_dim"] + value_w * d)
    full = 4 * d * d + 2 * d
    n_linear = sum(mixer_pattern(config))
    return (2 * d * s["vocab"] + d + n_linear * linear
            + (s["layers"] - n_linear) * full
            + s["layers"] * (3 * d * s["f"] + 2 * d))


def timed_gradient(trainer, model: TransformerLM, seed: int, batch: dict,
                   reference) -> dict:
    """``reference.watched``'s leaves of the gradient of the loss function
    the trainer's step differentiates — ``lm_loss_fn`` of the model AS TIMED
    (bfloat16 products, the ``gdn_*`` and flash kernels forward and
    backward, the traffic's remat) — at the seed's weights on ``batch``,
    taken as the step takes it: each chip of the trainer's mesh its own
    sequences, the chips' gradients averaged.  On the host."""
    loss = lm_loss_fn(model)
    mesh = trainer.mesh
    axes = tuple(mesh.axis_names)

    def mean_gradient(params, local):
        return jax.lax.pmean(reference.watched(jax.grad(loss)(params, local)),
                             axes)

    sharded = jax.jit(jax.shard_map(
        mean_gradient, mesh=mesh, in_specs=(P(), P(axes)), out_specs=P(),
        check_vma=False))
    params = jax.device_put(make_params(model, seed),
                            NamedSharding(mesh, P()))
    return jax.device_get(sharded(params, trainer.shard_batch(batch)))


def system_change(trainer, model: TransformerLM, seed: int, batch: dict,
                  steps: int, reference) -> tuple[dict, list]:
    """The change of ``reference.watched``'s leaves (and its
    ``CHANGE_ALSO``) over ``steps`` updates of the trainer's own compiled
    step on ``batch``, from a fresh state of the same seed
    (``builders/sdar.py::system_change``, with what is kept on the host),
    and the losses those steps saw."""
    params = make_params(model, seed)
    start = reference.watched_copy(params)
    state = trainer.init(params)
    del params
    replay = trainer.shard_batch(batch)
    losses = []
    for _ in range(steps):
        state, loss = trainer.train_step(state, replay)
        losses.append(float(loss))
    after = reference.watched_copy(trainer.unstack_params(state))
    return {name: after[name] - start[name] for name in start}, losses


def system_rule(reference, seed: int, seq: int, hyper: dict, dtype) -> dict:
    """The probe's rows through the rule as a layer of the model runs it,
    forward and backward: ``ops.gated_delta.gated_delta_rule`` on operands
    of the model's dtype (the ``gdn_fwd`` and ``gdn_bwd`` kernels on the
    chip) under the probe's cotangent -> ``reference.RULE_QUANTITIES``."""
    from bagua_tpu.ops.gated_delta import gated_delta_rule

    q, k, v, g, beta, do = reference.rule_probe(seed, seq, hyper)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    return jax.device_get(jax.jit(functools.partial(
        reference.rule_with_cotangents, gated_delta_rule))(
            q, k, v, g, beta, do))


@dataclasses.dataclass
class Job(_olmoe.Job):
    """The ``olmoe`` builder's job (next-token batches over the held slice
    of the vocabulary) against ``reference/olmo_hybrid.py``, with
    ``correct`` held to four comparisons: the replayed losses
    (``reference.LOSS_TOLERANCE``), the first gradient of the replay batch on
    the leaves ``reference.watched`` picks (``GRADIENT_TOLERANCE``), the
    parameters' change over the replayed updates through the trainer's own
    step (``CHANGE_TOLERANCE``) and the rule by itself on the probe's rows
    (``RULE_TOLERANCE``)."""

    #: the trainer again, for the system's side of the comparisons (the
    #: driver takes ``trainer`` and ``state`` away before them)
    _replayer: object = None
    gradient_distance: dict = dataclasses.field(default_factory=dict)
    change_distance: dict = dataclasses.field(default_factory=dict)
    rule_distance: dict = dataclasses.field(default_factory=dict)
    #: what the system gave (made once: a fault check asks again and again)
    _system: tuple | None = None
    #: what the reference gave last, on the host: its first gradient and its
    #: change on the compared leaves, and the losses of both sides (a
    #: planted fault of the SYSTEM is read against them without another
    #: replay)
    wanted: dict = dataclasses.field(default_factory=dict)

    def reference_losses(self, steps: int, **probe) -> list[float]:
        """``probe``: ``hyper=`` / ``round_weights=`` of a reference with a
        fault (``tests/test_olmo_hybrid.py``,
        ``perfbench/tests/test_olmo_hybrid_cell.py``)."""
        reference = self._reference
        hyper = probe.setdefault("hyper",
                                 reference.hyperparameters(self._config))
        seq = self.replay_batch["tokens"].shape[1] - 1
        if self._system is None:
            self._system = (
                timed_gradient(self._replayer, self._model, self._seed,
                               self.replay_batch, reference),
                system_change(self._replayer, self._model, self._seed,
                              self.replay_batch, steps, reference)[0],
                system_rule(reference, self._seed, seq, hyper,
                            self._model.cfg.dtype))
        got_gradient, got_change, got_rule = self._system

        def compare_gradient(want: dict) -> None:
            self.wanted["gradient"] = want = jax.device_get(want)
            self.gradient_distance = self.distances(got_gradient, want, True)

        def compare_change(want: dict) -> None:
            self.wanted["change"] = want
            self.change_distance = self.distances(got_change, want)

        self.rule_distance = reference.rule_distance(
            got_rule, reference.rule_by_scan(
                *reference.rule_probe(self._seed, seq, hyper),
                scan_dtype=hyper["scan_dtype"]))
        losses = reference.replay_losses(
            make_params(self._model, self._seed), self.replay_batch, steps,
            self._traffic["optimizer"], first_gradient=compare_gradient,
            last_change=compare_change, **probe)
        # an earlier line, for the reader of a log: what the second, the
        # third and the fourth comparison read
        print(json.dumps({
            "first_gradient_distance": self.gradient_distance,
            "largest": max(self.gradient_distance.values(), default=None),
            "limit": reference.GRADIENT_TOLERANCE,
            "parameter_change_distance": self.change_distance,
            "largest_change": max(self.change_distance.values(),
                                  default=None),
            "change_limit": reference.CHANGE_TOLERANCE,
            "rule_distance": self.rule_distance,
            "rule_limit": reference.RULE_TOLERANCE,
            "loss_limits": reference.LOSS_TOLERANCE}), flush=True)
        return losses

    def distances(self, got: dict, want: dict, gate_halves=False) -> dict:
        """Per compared leaf ``|got - want| / |want|``; ``gate_halves``: the
        two halves of the gates' in-projection by themselves besides (the
        first gradient's comparison)."""
        reference = self._reference
        if gate_halves:
            got, want = (reference.with_gate_halves(t) for t in (got, want))
        return {name: float(d) for name, d in
                reference.gradient_distance(got, want).items()}

    def losses_agree(self, trainer_losses, reference_losses) -> bool:
        reference = self._reference
        self.wanted["losses"] = list(reference_losses)
        self.wanted["trainer_losses"] = list(trainer_losses)
        return (reference.agree(trainer_losses, reference_losses,
                                reference.LOSS_TOLERANCE)
                and reference.gradients_agree(self.gradient_distance)
                and reference.changes_agree(self.change_distance)
                and reference.rule_agrees(self.rule_distance))


def make_model(config: dict, traffic: dict) -> TransformerLM:
    check_program()
    s = _sizes(config)
    theta = config.get("rope_theta")
    return TransformerLM(TransformerConfig(
        vocab_size=s["vocab"], d_model=s["d"], n_heads=s["heads"],
        n_kv_heads=s["kv_heads"], n_layers=s["layers"], d_ff=s["f"],
        max_seq_len=int(config["max_position_embeddings"]),
        # null in the published config: no layer rotates, and the model
        # has no table of positions either (``rope_theta=None`` would give it
        # the learned one); a number rotates q and k of the full layers
        rope_theta=float(theta or OLMO3_ROPE_THETA),
        rope_layers=(int(theta is not None),),
        qk_norm=True, pre_norms=False, post_norms=True,
        norm_eps=float(config["rms_norm_eps"]),
        mixer_layers=mixer_pattern(config),
        linear_key_heads=s["lin_k_heads"],
        linear_value_heads=s["lin_v_heads"],
        linear_key_dim=s["lin_k_dim"], linear_value_dim=s["lin_v_dim"],
        linear_conv=s["taps"],
        linear_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        **_kwargs(traffic.get("model", {}))))


def make_trainer(cell: cells.Cell, traffic: dict, devices: list,
                 algorithm=None):
    """The model and its trainer over ``devices``, as the traffic mix
    configures them (``algorithm``: another than the traffic's, a planted
    fault's); nothing is placed on a device yet."""
    check_program()
    config = cell.config
    if int(traffic["seq_len"]) > int(config["max_position_embeddings"]):
        raise cells.CellError(
            f"{cell.name}: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    model = make_model(config, traffic)
    mesh = build_mesh(dict(traffic["mesh"]), devices)
    bagua_tpu.init_process_group(mesh=mesh)
    if algorithm is None:
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
        _replayer=trainer,
    )
