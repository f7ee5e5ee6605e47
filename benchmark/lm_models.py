"""Operations a layer-typed LM's training step needs, computed from its shapes
and from what the step counted (tokens, the sequence length, the assignments
that landed on held experts). The yardstick's arithmetic lives here, not in
the program. Matrix products count 2 operations a multiply-add; the backward
pass costs twice the forward; what rematerialisation recomputes is NOT counted
(``lm_mfu_share`` is a share of the work the model needs, not of the work the
program chose to do)."""
from __future__ import annotations


def forward_flops(config: dict, tokens: int, seq_len: int,
                  assignments_held: int) -> float:
    """Forward operations of ``tokens`` tokens in sequences of ``seq_len``,
    ``assignments_held`` (token, expert) pairs computed by the held experts
    summed over the expert layers, for the layers the configuration runs."""
    c = config
    d = c["hidden_size"]
    pattern = c["hybrid_override_pattern"][:c["num_hidden_layers"]]
    h, p, n, g = (c["mamba_num_heads"], c["mamba_head_dim"],
                  c["ssm_state_size"], c["n_groups"])
    d_inner = h * p
    conv_dim = d_inner + 2 * g * n
    # in/out projections, the depthwise conv, and the recurrence as written:
    # per head a decay (P N), an outer-product update (2 P N) and a read-out
    # (2 P N) a token
    mamba = 2 * d * (d_inner + conv_dim + h) + 2 * d_inner * d \
        + 2 * conv_dim * c["conv_kernel"] + 5 * h * p * n
    heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
    attn_proj = 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
    # scores and values over the keys at or before each query: S (S + 1) / 2
    # pairs a sequence, 2 products of head_dim a pair and head
    attn_pairs = tokens / seq_len * seq_len * (seq_len + 1) / 2
    attn_scores = attn_pairs * heads * hd * 2 * 2
    routed_width = c.get("published", {}).get("n_routed_experts",
                                              c["n_routed_experts"])
    expert_token = 2 * d * routed_width \
        + 2 * 2 * d * c["moe_shared_expert_intermediate_size"] \
        * c["n_shared_experts"]
    per_assignment = 2 * 2 * d * c["moe_intermediate_size"]
    n_m, n_a, n_e = pattern.count("M"), pattern.count("*"), pattern.count("E")
    head = 2 * d * c["vocab_size"]
    return (tokens * (n_m * mamba + n_a * attn_proj + n_e * expert_token
                      + head)
            + n_a * attn_scores + assignments_held * per_assignment)


def train_flops(config: dict, tokens: int, seq_len: int,
                assignments_held: int) -> float:
    """Forward and backward: three times the forward's products."""
    return 3.0 * forward_flops(config, tokens, seq_len, assignments_held)
