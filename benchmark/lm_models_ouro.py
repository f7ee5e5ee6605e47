"""Operations the training step of a looped LM needs (ONE stack of two-block
layers, grouped-query attention then a dense gated feed-forward, run
``total_ut_steps`` times over the same weights; an untied head read once a
pass), computed from its published shapes and from what the step counted:
tokens, causal query-key pairs (summed over the attention blocks' RUNS: a block
that ran four times counted four times) and passes. The interface
``readers/model_mfu.py`` asks of a FLOP model: ``COUNTS``, the window's counts
it needs, and ``train_flops(config, per_step)`` over a step's share of each.
Matrix products count 2 operations a multiply-add; the backward pass costs
twice the forward; the embedding's gather, rotary turns, norms, softmaxes, the
exit gate's one unit and what rematerialisation recomputes are NOT counted (a
share of the work the model needs, not of the work the program chose to do)."""
from __future__ import annotations

COUNTS = ("lm_tokens", "lm_attn_pairs", "lm_loop_passes")


def forward_parts(config: dict, tokens: float, pairs: float,
                  passes: float) -> dict:
    """Forward operations by part, for the layers the configuration runs,
    every pass counted. ``pairs`` already holds every run of every block."""
    c = config
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // heads
    runs = tokens * passes * layers     # (token, layer run)
    return {
        "attn_projections": runs * (2 * d * (heads + 2 * kv) * hd
                                    + 2 * heads * hd * d),
        # a pair and head: one product of the key's width, one of the value's
        "attn_scores": pairs * heads * 2 * 2 * hd,
        "dense_ffn": runs * 3 * 2 * d * c["intermediate_size"],
        # every pass's logits over the whole vocabulary
        "head": tokens * passes * 2 * d * c["vocab_size"],
    }


def train_flops(config: dict, per_step: dict) -> float:
    """Forward and backward of one step: three times the forward's products.
    ``per_step``: a step's count of each of ``COUNTS``."""
    return 3.0 * sum(forward_parts(
        config, per_step["lm_tokens"], per_step["lm_attn_pairs"],
        per_step["lm_loop_passes"]).values())
