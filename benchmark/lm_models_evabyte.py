"""Operations the training step of an EVA byte LM needs (two blocks a layer: EVA
attention, then a dense gated feed-forward; a head several vocabularies wide),
computed from its published shapes and from what the step counted: tokens,
causal query-key pairs inside the windows and (query, chunk summary) pairs, both
summed over the EVA blocks. The interface ``readers/model_mfu.py`` asks of a FLOP
model: ``COUNTS``, the window's counts it needs, and ``train_flops(config,
per_step)`` over a step's share of each. Matrix products count 2 operations a
multiply-add; the backward pass costs twice the forward; the summaries' pooling
(a 16-position softmax and two weighted sums a chunk), rotary turns, norms,
softmaxes and what rematerialisation recomputes are NOT counted (a share of the
work the model needs, not of the work the program chose to do)."""
from __future__ import annotations

COUNTS = ("lm_tokens", "lm_attn_pairs", "lm_eva_summary_pairs")


def forward_parts(config: dict, tokens: float, pairs: float,
                  summary_pairs: float) -> dict:
    """Forward operations by part, for the layers the configuration runs."""
    c = config
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    # a pair and head: one product of the key's width, one of the value's
    pair = 2 * 2 * d
    return {
        # queries, keys, values and the output projection, hidden x hidden each
        "eva_projections": tokens * layers * 4 * 2 * d * d,
        "eva_local_scores": pairs * pair,
        "eva_summary_scores": summary_pairs * pair,
        "dense_ffn": tokens * layers * 3 * 2 * d * c["intermediate_size"],
        "head": tokens * 2 * d * c["num_pred_heads"] * c["vocab_size"],
    }


def train_flops(config: dict, per_step: dict) -> float:
    """Forward and backward of one step: three times the forward's products.
    ``per_step``: a step's count of each of ``COUNTS``."""
    return 3.0 * sum(forward_parts(
        config, per_step["lm_tokens"], per_step["lm_attn_pairs"],
        per_step["lm_eva_summary_pairs"]).values())
