"""Operations the training step of a short-convolution hybrid LM needs (two
blocks a layer: a gated short convolution or a grouped-query attention, then a
dense gated feed-forward or a gated expert block without a shared expert; the
head tied to the embedding table), computed from its published shapes and from
what the step counted: tokens, causal query-key pairs (summed over the attention
blocks), assignments that landed on held experts (summed over the expert
blocks). The interface ``readers/model_mfu.py`` asks of a FLOP model:
``COUNTS``, the window's counts it needs, and ``train_flops(config, per_step)``
over a step's share of each. Matrix products count 2 operations a multiply-add,
the convolution's taps likewise; the backward pass costs twice the forward; the
embedding's gather, the gates' products, rotary turns, norms, softmaxes, the
top-k and what rematerialisation recomputes are NOT counted (a share of the
work the model needs, not of the work the program chose to do)."""
from __future__ import annotations

COUNTS = ("lm_tokens", "lm_attn_pairs", "lm_assignments_held")


def forward_parts(config: dict, tokens: float, pairs: float,
                  assignments_held: float) -> dict:
    """Forward operations by part, for the layers the configuration runs."""
    c = config
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    kinds = c["layer_types"][:layers]
    conv, attn = kinds.count("conv"), kinds.count("full_attention")
    dense = min(c["num_dense_layers"], layers)
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // heads
    router_width = c.get("published", {}).get("num_experts", c["num_experts"])
    gated = 3 * 2 * d                   # gate, up, down: per unit of width
    return {
        # W_in to [B | C | x], the taps, W_out
        "conv": tokens * conv * (2 * d * 3 * d + 2 * d * c["conv_L_cache"]
                                 + 2 * d * d),
        "attn_projections": tokens * attn * (2 * d * (heads + 2 * kv) * hd
                                             + 2 * heads * hd * d),
        # a pair and head: one product of the key's width, one of the value's
        "attn_scores": pairs * heads * 2 * 2 * hd,
        "dense_ffn": tokens * dense * gated * c["intermediate_size"],
        "router": tokens * (layers - dense) * 2 * d * router_width,
        "routed_experts": assignments_held * gated
        * c["moe_intermediate_size"],
        # the tied head: logits against the slice's rows
        "head": tokens * 2 * d * c["vocab_size"],
    }


def train_flops(config: dict, per_step: dict) -> float:
    """Forward and backward of one step: three times the forward's products.
    ``per_step``: a step's count of each of ``COUNTS``."""
    return 3.0 * sum(forward_parts(
        config, per_step["lm_tokens"], per_step["lm_attn_pairs"],
        per_step["lm_assignments_held"]).values())
