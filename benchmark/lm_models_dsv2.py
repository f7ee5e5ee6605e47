"""Operations the training step of a two-block-a-layer LM needs (latent
attention, then a dense gated feed-forward or a gated expert block), computed
from its published shapes and from what the step counted: tokens, causal
query-key pairs (summed over the attention blocks), assignments that landed on
held experts (summed over the expert blocks). The interface ``readers/
model_mfu.py`` asks of a FLOP model: ``COUNTS``, the window's counts it needs,
and ``train_flops(config, per_step)`` over a step's share of each. Matrix
products count 2 operations a multiply-add; the backward pass costs twice the
forward; rotary turns, norms, softmaxes and what rematerialisation recomputes
are NOT counted (a share of the work the model needs, not of the work the
program chose to do)."""
from __future__ import annotations

COUNTS = ("lm_tokens", "lm_attn_pairs", "lm_assignments_held")


def forward_parts(config: dict, tokens: float, pairs: float,
                  assignments_held: float) -> dict:
    """Forward operations by part, for the layers the configuration runs."""
    c = config
    d, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    layers = c["num_hidden_layers"]
    expert_layers = sum(
        i >= c["first_k_dense_replace"] and i % c["moe_layer_freq"] == 0
        for i in range(layers))
    # queries; the latent and the shared rotary key; keys and values expanded
    # from the latent; the output projection
    mla_token = 2 * d * heads * qk \
        + 2 * d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + 2 * c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"]) \
        + 2 * heads * c["v_head_dim"] * d
    # a pair and head: one product of the key's width, one of the value's
    pair = 2 * heads * (qk + c["v_head_dim"])
    router_width = c.get("published", {}).get("n_routed_experts",
                                              c["n_routed_experts"])
    gated = 3 * 2 * d                   # gate, up, down: per unit of width
    return {
        "mla_projections": tokens * layers * mla_token,
        "mla_scores": pairs * pair,
        "dense_ffn": tokens * (layers - expert_layers) * gated
        * c["intermediate_size"],
        "router_and_shared": tokens * expert_layers * (
            2 * d * router_width
            + gated * c["n_shared_experts"] * c["moe_intermediate_size"]),
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
