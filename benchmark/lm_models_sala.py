"""Operations the training step of a block-sparse + Lightning hybrid LM needs
(two blocks a layer: a ``minicpm4`` or ``lightning-attn`` mixer, then a dense gated
feed-forward), computed from its published shapes and from what the step counted:
tokens, the (query, key) pairs a head of the sparse blocks attended, the (query,
pooled key) pairs scored to choose them, and the Lightning scans' chunks. The
interface ``readers/model_mfu.py`` asks of a FLOP model: ``COUNTS``, the window's
counts it needs, and ``train_flops(config, per_step)`` over a step's share of each.
Matrix products count 2 operations a multiply-add; the backward pass costs twice
the forward, except the selection, which has none; norms, gates' sigmoids, rotary
turns, softmaxes, the top-k and what rematerialisation recomputes are NOT counted
(a share of the work the model needs, not of the work the program chose to do)."""
from __future__ import annotations

COUNTS = ("lm_tokens", "lm_sparse_pairs", "lm_sparse_select_pairs",
          "lm_lightning_chunks")
_KINDS = ("minicpm4", "lightning-attn")


def forward_parts(config: dict, tokens: float, pairs: float,
                  select_pairs: float, chunks: float) -> dict:
    """Forward operations by part, for the layers the configuration runs."""
    c = config
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    sparse, linear = (c["mixer_types"][:layers].count(k) for k in _KINDS)
    heads, hd = c["num_attention_heads"], c["head_dim"]
    lh, ld = c["lightning_nh"], c["lightning_head_dim"]
    chunk = c["lightning_chunk"]
    return {
        # Wq, the gate and Wo hidden x hidden each; Wk and Wv to the 2 heads
        "sparse_projections": tokens * sparse * 2 * d * (
            3 * heads * hd + 2 * c["num_key_value_heads"] * hd),
        # a pair and head: one product of the key's width, one of the value's
        "sparse_scores": pairs * heads * 2 * 2 * hd,
        "sparse_selection": select_pairs * heads * 2 * hd,
        # q, k, v, the gate and the output projection
        "lightning_projections": tokens * linear * 5 * 2 * d * lh * ld,
        # a chunk and head: its causal pairs (scores, then values), the state
        # it leaves (k^T v) and the state it reads (q H), 128 x 128 each
        "lightning_scan": chunks * lh * (
            chunk * (chunk + 1) // 2 * 2 * 2 * ld + 2 * 2 * chunk * ld * ld),
        "dense_ffn": tokens * layers * 3 * 2 * d * c["intermediate_size"],
        "head": tokens * 2 * d * c["vocab_size"],
    }


def train_flops(config: dict, per_step: dict) -> float:
    """Forward and backward of one step: three times the forward's products,
    the selection once. ``per_step``: a step's count of each of ``COUNTS``."""
    parts = forward_parts(
        config, per_step["lm_tokens"], per_step["lm_sparse_pairs"],
        per_step["lm_sparse_select_pairs"], per_step["lm_lightning_chunks"])
    selection = parts.pop("sparse_selection")
    return 3.0 * sum(parts.values()) + selection
