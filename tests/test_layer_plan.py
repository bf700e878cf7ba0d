"""The seam between a configuration and its blocks: ``TransformerConfig.
layer_plan()`` (what each layer is), ``NOT_BUILT`` (which feature no consumer
carries yet, every pair of it) and the parameter tree each of the seven forms
of decoder initialises — written out here, so that a renamed module breaks
this file and not a checkpoint."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from bagua_tpu.model_parallel.moe import MoEMLP
from bagua_tpu.models.transformer import (
    NOT_BUILT, Attention, SubLayer, TransformerConfig, TransformerLM,
)
from bagua_tpu.parallel.pipeline import PipelinedTransformerLM

D, HEADS, FF, VOCAB = 32, 2, 48, 64
BASE = dict(vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=2, d_ff=FF,
            max_seq_len=16, dtype=jnp.float32)
LINEAR = dict(linear_key_heads=1, linear_value_heads=2, linear_key_dim=8,
              linear_value_dim=8)
SSM = dict(ssm_heads=2, ssm_head_dim=8, ssm_groups=1, ssm_state=8,
           ssm_chunk=8)

FULL, ROTARY = SubLayer("attn"), SubLayer("attn", None, True)
MLP, LIN, SSM_LAYER = SubLayer("mlp"), SubLayer("linear_attn"), SubLayer("ssm")


def experts(**kw):
    kw = {"gated": True, **kw}
    return lambda _layer: lambda: MoEMLP(
        n_experts=4, d_ff=8, k=2, dropless=True, dtype=jnp.float32,
        name="mlp", **kw)


#: one small configuration of each form the decoder takes, with the expert
#: layer it is published with: (config, mlp_factory, the plan its published
#: pattern says)
FORMS = {
    "dense": (TransformerConfig(**BASE), None, ((FULL, MLP),) * 2),
    # SmallThinker: full attention without positions on every fourth layer,
    # the window and the rotation on the three behind it
    "smallthinker": (
        TransformerConfig(**{**BASE, "n_layers": 4}, n_kv_heads=1, d_head=16,
                          rope_theta=1.5e6, rope_layers=(0, 1, 1, 1),
                          window=5, window_layers=(0, 1, 1, 1),
                          route_before_attention=True),
        experts(activation="relu"),
        ((FULL, MLP),) + ((SubLayer("attn", 5, True), MLP),) * 3),
    # Ouro: the plan holds each shared layer once, however many passes run
    "ouro": (
        TransformerConfig(**BASE, n_passes=4, post_norms=True, exit_gate=True,
                          rope_theta=1e6),
        None, ((ROTARY, MLP),) * 2),
    "sdar": (
        TransformerConfig(**BASE, attention="block_diffusion",
                          diffusion_block=4, qk_norm="head", rope_theta=1e6,
                          n_kv_heads=1, d_head=16),
        experts(norm_topk_prob=True), ((ROTARY, MLP),) * 2),
    # Qwen3-Next: softmax attention on every fourth layer
    "qwen3_next": (
        TransformerConfig(**{**BASE, "n_layers": 4}, **LINEAR,
                          mixer_layers=(1, 1, 1, 0), attn_gate=True,
                          rotary_dim=8, qk_norm="head", rope_theta=1e7,
                          norm_zero_centered=True, n_kv_heads=1, d_head=16),
        experts(shared_d_ff=8, shared_gate=True),
        ((LIN, MLP),) * 3 + ((ROTARY, MLP),)),
    # Nemotron-H: one sub-layer a block, a kind a layer, no positions
    "nemotron_h": (
        TransformerConfig(**{**BASE, "n_layers": 5}, **SSM,
                          layer_kinds=("ssm", "moe", "ssm", "attn", "moe"),
                          rope_theta=1e4, rope_layers=(0,), n_kv_heads=1,
                          d_head=16),
        experts(gated=False, activation="relu2", shared_d_ff=8),
        ((SSM_LAYER,), (MLP,), (SSM_LAYER,), (FULL,), (MLP,))),
    # Olmo-Hybrid: Qwen3-Next's period, norms behind the sub-layers, dense MLP
    "olmo_hybrid": (
        TransformerConfig(**{**BASE, "n_layers": 4}, **LINEAR,
                          mixer_layers=(1, 1, 1, 0), linear_neg_eigval=True,
                          pre_norms=False, post_norms=True, qk_norm=True,
                          rope_theta=1e4, rope_layers=(0,)),
        None, ((LIN, MLP),) * 3 + ((FULL, MLP),)),
}


@pytest.mark.parametrize("form", FORMS)
def test_the_plan_is_what_the_published_pattern_says(form):
    cfg, _, plan = FORMS[form]
    assert cfg.layer_plan() == plan
    assert cfg.layer_plan() is cfg.layer_plan()              # built once
    for i, layer in enumerate(plan):
        attn = [sub for sub in layer if sub.kind == "attn"]
        if attn:
            assert cfg.layer_window(i) == attn[0].window
            assert cfg.layer_rotary(i) is attn[0].rotary


def test_a_replaced_field_is_a_plan_of_its_own():
    cfg = FORMS["smallthinker"][0]
    assert dataclasses.replace(cfg, window=None).layer_plan() == (
        (FULL, MLP),) + ((ROTARY, MLP),) * 3
    assert cfg.layer_plan()[1][0].window == 5


# ---------------------------------------------------------------------------
# what is not built: every row of the table, through a model
# ---------------------------------------------------------------------------

#: a feature of ``NOT_BUILT`` -> (the fields that give a configuration it and
#: nothing the table ranks above it, a word its refusal says)
FEATURES = {
    "looped": (dict(n_passes=2), "n_passes"),
    "exit_gate": (dict(exit_gate=True), "exit_gate"),
    "block_diffusion": (dict(attention="block_diffusion", diffusion_block=4),
                        "block_diffusion"),
    "linear_attn": (dict(mixer_layers=(1,), **LINEAR), "mixer_layers"),
    "norm_zero_centered": (dict(norm_zero_centered=True),
                           "norm_zero_centered"),
    "single_sublayer": (dict(layer_kinds=("attn", "attn")), "layer_kinds"),
    # layers of two kinds that differ in nothing a row of their own names
    "mixed_layers": (dict(rope_theta=1e4, rope_layers=(0, 1)),
                     r"2 kinds|rope_layers pattern"),
    "window": (dict(window=4), "window"),
    "grouped_kv": (dict(n_kv_heads=1), "grouped key / value"),
    "attn_gate": (dict(attn_gate=True), "attn_gate"),
    "rotary_dim": (dict(rope_theta=1e4, rotary_dim=8), "rotary_dim"),
    "flat_qk_norm": (dict(qk_norm=True), "qk_norm"),
    "rope": (dict(rope_theta=1e4), "rope_theta"),
}
#: a consumer -> (the fields that turn it on, a word a refusal names it by)
CONSUMERS = {
    "pipeline": ({}, "pipelin"),
    "decode": (dict(decode=True), "decode"),
    "sp_axis": (dict(sp_axis="sp"), "sp_axis"),
    "tp_axis": (dict(tp_axis="tp", tp_size=2), "tensor"),
    "looped": (dict(n_passes=2), "looped"),
    "block_diffusion": (dict(attention="block_diffusion", diffusion_block=4),
                        "block_diffusion"),
}


def _refusal(cfg, pipelined=False):
    tokens = jnp.zeros((2, 9), jnp.int32)
    model = (PipelinedTransformerLM(cfg, pp_size=1) if pipelined
             else TransformerLM(cfg))
    with pytest.raises(NotImplementedError) as refused:
        model.init(jax.random.PRNGKey(0), tokens)
    return str(refused.value)


#: every (feature, consumer) pair of the table with its row's reason
PAIRS = [(feature, consumer, why) for features, consumers, why in NOT_BUILT
         for feature in features.split() for consumer in consumers.split()]


def test_the_table_names_a_pair_once():
    assert len({pair[:2] for pair in PAIRS}) == len(PAIRS) == 29


@pytest.mark.parametrize("feature,consumer,why", PAIRS,
                         ids=[f"{f}-{c}" for f, c, _ in PAIRS])
def test_every_pair_of_the_table_refuses_by_both_names(feature, consumer,
                                                       why):
    fields, feature_word = FEATURES[feature]
    turned_on, consumer_word = CONSUMERS[consumer]
    cfg = TransformerConfig(**{**BASE, **fields, **turned_on})
    said = _refusal(cfg, pipelined=consumer == "pipeline")
    # this row and no other: its reason, filled in from the configuration
    assert said == why.format(cfg=cfg, n=len(set(cfg.layer_plan())))
    assert re.search(feature_word, said), said
    assert re.search(consumer_word, said), said


def test_the_tables_order_is_the_precedence():
    """The pipelined stack's rows come first, a structural feature before
    what one attention layer cannot do."""
    cfg = TransformerConfig(**BASE, n_passes=2, decode=True, rope_theta=1e4,
                            n_kv_heads=1)
    assert "n_passes > 1" in _refusal(cfg)
    assert "n_passes=2" in _refusal(cfg, pipelined=True)
    nemotron = dataclasses.replace(FORMS["nemotron_h"][0], decode=True)
    assert "layer_kinds" in _refusal(nemotron)


def test_a_feature_under_no_consumer_and_a_consumer_of_no_feature_build():
    for fields, _ in FEATURES.values():
        if "layer_kinds" not in fields:       # needs an expert layer's factory
            TransformerLM(TransformerConfig(**{**BASE, **fields})).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    PipelinedTransformerLM(TransformerConfig(**BASE), pp_size=1).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 9), jnp.int32))


@pytest.mark.parametrize("own,fields,word", [
    (dict(window=4), {}, "windows"),
    (dict(rotary=True), dict(rope_theta=1e4), "rope_theta"),
    ({}, dict(n_kv_heads=1), "grouped key / value"),
    ({}, dict(attn_gate=True), "attn_gate"),
])
def test_an_attention_layer_by_itself_refuses_what_it_cannot_decode(
        own, fields, word):
    """Its window and its rotation are the layer's own, whatever the
    configuration's patterns say."""
    cfg = TransformerConfig(**BASE, decode=True, **fields)
    layer = Attention(cfg, **own)
    with pytest.raises(NotImplementedError, match=word):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, D)))
    # the layer the patterns leave alone decodes
    if own:
        Attention(cfg, window=None, rotary=False).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1, D)))


def test_flat_qk_norm_under_the_tensor_parallel_axis_is_refused_by_itself():
    cfg = TransformerConfig(**BASE, qk_norm=True, tp_axis="tp", tp_size=2)
    with pytest.raises(NotImplementedError, match="all heads"):
        Attention(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, D)))


# ---------------------------------------------------------------------------
# the parameter tree of each form: paths and shapes, written out
# ---------------------------------------------------------------------------

SCALE = {"scale": (32,)}
ATTENTION = {"q/kernel": (32, 2, 16), "k/kernel": (32, 2, 16),
             "v/kernel": (32, 2, 16), "o/kernel": (2, 16, 32)}
GROUPED = {**ATTENTION, "k/kernel": (32, 1, 16), "v/kernel": (32, 1, 16)}
HEAD_NORMS = {"q_norm/scale": (16,), "k_norm/scale": (16,)}
DENSE_MLP = {"wi_gate/kernel": (32, 48), "wi_up/kernel": (32, 48),
             "wo/kernel": (48, 32)}
EXPERTS = {"router/kernel": (32, 4), "expert_wi": (4, 32, 8),
           "expert_wo": (4, 8, 32)}
GATED = {**EXPERTS, "expert_wg": (4, 32, 8)}
SHARED = {"shared_wi/kernel": (32, 8), "shared_wo/kernel": (8, 32)}
DELTA = {"in_proj_qkvz/kernel": (32, 48), "in_proj_ba/kernel": (32, 4),
         "conv": (4, 32), "A_log": (2,), "dt_bias": (2,), "norm": (8,),
         "out_proj/kernel": (16, 32)}
MAMBA = {"in_proj": (32, 50), "conv": (4, 32), "conv_bias": (32,),
         "A_log": (2,), "dt_bias": (2,), "D": (2,), "norm": (16,),
         "out_proj/kernel": (16, 32)}
TRUNK = {"embed": {"embedding": (64, 32)}, "final_norm": SCALE,
         "lm_head": {"kernel": (32, 64)}}

_dense = {"attn_norm": SCALE, "attn": ATTENTION, "mlp_norm": SCALE,
          "mlp": DENSE_MLP}
_smallthinker = {"attn_norm": SCALE, "attn": GROUPED, "mlp_norm": SCALE,
                 "mlp": GATED}
_ouro = {**_dense, "attn_post_norm": SCALE, "mlp_post_norm": SCALE}
_sdar = {**_smallthinker, "attn": {**GROUPED, **HEAD_NORMS}}
_qwen_mlp = {**GATED, **SHARED, "shared_wg/kernel": (32, 8),
             "shared_gate/kernel": (32, 1)}
_qwen_linear = {"linear_attn_norm": SCALE, "linear_attn": DELTA,
                "mlp_norm": SCALE, "mlp": _qwen_mlp}
# the output gate: a head's q projection is [q | gate]
_qwen_full = {"attn_norm": SCALE,
              "attn": {**GROUPED, **HEAD_NORMS, "q/kernel": (32, 2, 32)},
              "mlp_norm": SCALE, "mlp": _qwen_mlp}
_nemotron = {"ssm": {"ssm_norm": SCALE, "ssm": MAMBA},
             "moe": {"mlp_norm": SCALE, "mlp": {**EXPERTS, **SHARED}},
             "attn": {"attn_norm": SCALE, "attn": GROUPED}}
_olmo_linear = {"linear_attn": DELTA, "linear_attn_post_norm": SCALE,
                "mlp": DENSE_MLP, "mlp_post_norm": SCALE}
_olmo_full = {"attn": {**ATTENTION, "q_norm/scale": (32,),
                       "k_norm/scale": (32,)},
              "attn_post_norm": SCALE, "mlp": DENSE_MLP,
              "mlp_post_norm": SCALE}

#: form -> (the leaves beside the blocks, block_0, block_1, ...): module ->
#: leaf -> shape
TREES = {
    "dense": ({**TRUNK, "pos_embed": {"": (16, 32)}}, [_dense] * 2),
    "smallthinker": (TRUNK, [_smallthinker] * 4),
    "ouro": ({**TRUNK, "exit_gate": {"kernel": (32, 1), "bias": (1,)}},
             [_ouro] * 2),
    "sdar": (TRUNK, [_sdar] * 2),
    "qwen3_next": (TRUNK, [_qwen_linear] * 3 + [_qwen_full]),
    "nemotron_h": (TRUNK, [_nemotron[kind] for kind in
                           ("ssm", "moe", "ssm", "attn", "moe")]),
    "olmo_hybrid": (TRUNK, [_olmo_linear] * 3 + [_olmo_full]),
}


def _written_out(form):
    rest, blocks = TREES[form]
    modules = {**rest, **{f"block_{i}/{name}": leaves
                          for i, block in enumerate(blocks)
                          for name, leaves in block.items()}}
    return {f"{module}/{leaf}".rstrip("/"): shape
            for module, leaves in modules.items()
            for leaf, shape in leaves.items()}


def _tree(form):
    cfg, factory, _ = FORMS[form]
    rows = 16 if cfg.block_diffusion else 8
    params = TransformerLM(cfg, mlp_factory=factory).init(
        jax.random.PRNGKey(0), jnp.zeros((1, rows), jnp.int32))["params"]
    return {
        "/".join(key.key for key in path): tuple(leaf.shape)
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("form", FORMS)
def test_the_parameter_tree_is_the_one_written_out(form):
    assert _tree(form) == _written_out(form)
