"""A block of ONE sub-layer — ``x + F(N(x))`` with one norm and one of a
state-space mixer, softmax attention or an expert layer — for the decoders
whose published layer pattern is a string of kinds over the whole depth
(Nemotron-H: ``MEMEM*EME...``, aperiodic and three-valued) and not a period
of mixers inside the two-sub-layer :class:`bagua_tpu.models.transformer.Block`.
``TransformerLM`` builds it where ``TransformerConfig.layer_kinds`` is set.

Module names are the areas' (``obs.spans.AREA_COMPONENTS``): ``ssm_norm`` /
``ssm`` (:class:`bagua_tpu.models.state_space.Mamba2`, area ``ssm``),
``attn_norm`` / ``attn`` (:class:`~bagua_tpu.models.transformer.Attention`
with this layer's window and rotation), ``mlp_norm`` / ``mlp`` (what
``mlp_factory`` makes: an expert layer).
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn

#: the kinds a layer can be
KINDS = ("ssm", "attn", "moe")


def check_layer_kinds(cfg, slots=None) -> None:
    """What ``layer_kinds`` asks of the configuration; the paths that cannot
    take a one-sub-layer block, or a state that runs over the whole sequence
    on one device, refuse it here."""
    kinds = cfg.layer_kinds
    if len(kinds) != cfg.n_layers or any(k not in KINDS for k in kinds):
        raise ValueError(
            f"layer_kinds names the kind of each of the {cfg.n_layers} "
            f"layers, one of {KINDS}; got {kinds}")
    if cfg.decode or slots is not None:
        raise NotImplementedError(
            "layer_kinds (one sub-layer a block, state-space layers) is not "
            "implemented on the decode paths: a recurrent state and the "
            "convolution's taps beside the key / value cache")
    if cfg.sp_axis is not None:
        raise NotImplementedError(
            "layer_kinds is not implemented under sp_axis: a sequence shard "
            "of a state-space layer would need the state of the shard "
            "before it")
    if cfg.tp_axis is not None or cfg.tp_size > 1:
        raise NotImplementedError(
            "layer_kinds is not implemented under the tensor-parallel axis "
            "(tp_axis / tp_size): the state-space heads and groups are not "
            "sharded")
    if cfg.n_passes > 1 or cfg.exit_gate or cfg.block_diffusion:
        raise NotImplementedError(
            "layer_kinds is not implemented for a looped stack (n_passes > "
            "1, exit_gate) or under attention='block_diffusion'")
    if (cfg.mixer_layers is not None or cfg.post_norms
            or cfg.route_before_attention):
        raise ValueError(
            "layer_kinds replaces the two-sub-layer block: mixer_layers, "
            "post_norms and route_before_attention are that block's options")


class SingleBlock(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models.transformer's
    attn_fn: Optional[Callable] = None
    mlp: Optional[Callable[[], nn.Module]] = None
    #: index of the layer: its kind, and an attention layer's window and
    #: rotation under the configuration's layer patterns
    layer: int = 0

    @nn.compact
    def __call__(self, x, slots=None):
        from .transformer import Attention, RMSNorm

        cfg = self.cfg
        check_layer_kinds(cfg, slots)
        kind = cfg.layer_kinds[self.layer]
        norm = lambda name: RMSNorm(cfg.dtype, cfg.param_dtype, cfg.norm_eps,
                                    cfg.norm_zero_centered, name=name)
        if kind == "ssm":
            from .state_space import Mamba2

            return x + Mamba2(cfg, name="ssm")(norm("ssm_norm")(x))
        if kind == "attn":
            attn = Attention(cfg, self.attn_fn, cfg.layer_window(self.layer),
                             cfg.layer_rotary(self.layer), name="attn")
            return x + attn(norm("attn_norm")(x))
        if self.mlp is None:
            raise ValueError(
                "layer_kinds names an expert layer ('moe'): it needs an "
                "mlp_factory that makes one")
        return x + self.mlp()(norm("mlp_norm")(x))
