"""Operations the algorithm needs, computed from shapes — never from XLA.

XLA's ``cost_analysis()`` counts rematerialised work, counts a Pallas custom
call as zero and mixes VMEM into "bytes accessed"; the numbers here are what
the mathematics of one training step requires, so a change that recomputes
more, or moves work into a kernel, cannot move them.

Conventions (stated because MFU figures are only comparable under the same
ones):

* one multiply-accumulate = 2 FLOP; backward = 2 x forward, so a training
  step is 3 x the forward pass; recomputation (remat) counts for nothing;
* attention is counted at the FULL ``seq x seq`` score matrix, not the causal
  half: the usual convention (PaLM appendix B, nanoGPT, llm.c), and what
  the materialising XLA path really computes;
* the embedding lookup is a gather: 0 FLOP; norms, softmax, activation and
  the optimizer update are left out (they are O(d) per token against the
  O(d^2) of the matrices — under 0.5 % here).
"""

from __future__ import annotations


def transformer_lm_sizes(config: dict) -> dict:
    """The sizes ``TransformerLM`` is built with, read from a configuration
    file that keeps its source's own key names (BERT's ``hidden_size`` ...,
    GPT-2's ``n_embd`` ...).  ``assumed`` may pad the vocabulary."""

    def pick(*keys):
        for key in keys:
            if config.get(key) is not None:
                return int(config[key])
        raise KeyError(f"configuration has none of {keys}")

    d_model = pick("hidden_size", "n_embd")
    try:
        d_ff = pick("intermediate_size", "n_inner")
    except KeyError:
        d_ff = 4 * d_model  # GPT-2: n_inner unset means 4 x n_embd
    vocab = pick("vocab_size")
    return {
        "d_model": d_model,
        "n_layers": pick("num_hidden_layers", "n_layer"),
        "n_heads": pick("num_attention_heads", "n_head"),
        "d_ff": d_ff,
        "vocab_size": vocab,
        "padded_vocab_size": int(
            config.get("assumed", {}).get("padded_vocab_size", vocab)),
        "max_positions": pick("max_position_embeddings", "n_positions"),
    }


def transformer_lm_params(config: dict) -> int:
    """Parameters of ``TransformerLM`` as built: token and position tables,
    per block four attention matrices, three gated-FFN matrices and two
    RMSNorm scales, a final norm and an untied head; no biases."""
    s = transformer_lm_sizes(config)
    d, ff, v = s["d_model"], s["d_ff"], s["padded_vocab_size"]
    block = 4 * d * d + 3 * d * ff + 2 * d
    return (v * d + s["max_positions"] * d + s["n_layers"] * block
            + d + d * v)


def transformer_lm_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward FLOP per target token at sequence ``seq_len``."""
    s = transformer_lm_sizes(config)
    d, ff, v = s["d_model"], s["d_ff"], s["padded_vocab_size"]
    matrices = 4 * d * d + 3 * d * ff          # MAC per token per block
    attention = 2 * seq_len * d                # QK^T and PV, full s x s
    forward_mac = s["n_layers"] * (matrices + attention) + d * v
    return 3.0 * 2.0 * forward_mac
