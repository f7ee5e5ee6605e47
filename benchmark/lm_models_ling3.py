"""Operations the training step of a KDA / latent-attention hybrid expert LM
needs (two blocks a layer: Kimi Delta Attention or, closing each group of
``layer_group_size`` layers, latent attention; then a dense gated feed-forward
or a gated expert block with one shared expert), computed from its published
shapes and from what the step counted: tokens, causal query-key pairs (summed
over the latent-attention blocks), assignments that landed on held experts
(summed over the expert blocks). The interface ``readers/model_mfu.py`` asks of
a FLOP model: ``COUNTS``, the window's counts it needs, and
``train_flops(config, per_step)`` over a step's share of each. Matrix products
count 2 operations a multiply-add, the convolutions' taps likewise; the backward
pass costs twice the forward. The delta rule is counted by the MODEL's
recurrence a token and head, four ``d_k x d_v`` passes over the state (the decay
a channel, ``k^T S``, the rank-one update, the read ``S^T q``), 2 operations
each: what the chunked form adds on top (its triangular systems, the products
inside a chunk) is the program's choice, as what rematerialisation recomputes
is. The embedding's gather, gates, norms, rotary turns, softmaxes and the top-k
are NOT counted."""
from __future__ import annotations

COUNTS = ("lm_tokens", "lm_attn_pairs", "lm_assignments_held")


def forward_parts(config: dict, tokens: float, pairs: float,
                  assignments_held: float) -> dict:
    """Forward operations by part, for the layers the configuration runs."""
    c = config
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    heads, hd = c["num_attention_heads"], c["head_dim"]
    mla = sum((i + 1) % c["layer_group_size"] == 0 for i in range(layers))
    kda = layers - mla
    dense = min(c["first_k_dense_replace"], layers)
    width = heads * hd
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    router_width = c.get("published", {}).get("num_experts", c["num_experts"])
    gated = 3 * 2 * d                   # gate, up, down: per unit of width
    return {
        # W_q, W_k, W_v, W_a and W_o; W_beta and W_g; three sets of taps
        "kda_projections": tokens * kda * (
            2 * d * width * 5 + 2 * d * heads * 2
            + 3 * 2 * width * c["short_conv_kernel_size"]),
        # decay, k^T S, the rank-one update, S^T q: a token and head
        "kda_recurrence": tokens * kda * heads * 4 * 2 * hd * hd,
        # queries; the latent and the shared rotary key; keys and values
        # expanded from the latent; the output projection
        "mla_projections": tokens * mla * (
            2 * d * heads * qk
            + 2 * d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + 2 * c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                               + c["v_head_dim"])
            + 2 * heads * c["v_head_dim"] * d),
        # a pair and head: one product of the key's width, one of the value's
        "mla_scores": pairs * 2 * heads * (qk + c["v_head_dim"]),
        "dense_ffn": tokens * dense * gated * c["intermediate_size"],
        "router_and_shared": tokens * (layers - dense) * (
            2 * d * router_width
            + gated * c["moe_shared_expert_intermediate_size"]),
        "routed_experts": assignments_held * gated
        * c["moe_intermediate_size"],
        "head": tokens * 2 * d * c["vocab_size"],
    }


def train_flops(config: dict, per_step: dict) -> float:
    """Forward and backward of one step: three times the forward's products.
    ``per_step``: a step's count of each of ``COUNTS``."""
    return 3.0 * sum(forward_parts(
        config, per_step["lm_tokens"], per_step["lm_attn_pairs"],
        per_step["lm_assignments_held"]).values())
