"""Configuration of the layer-typed LM, read from a file with the published
``config.json``'s own keys (docs/HYBRID_LM.md)."""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Dict, Optional, Tuple

__all__ = ["HybridLMConfig", "MAMBA", "EXPERTS", "ATTENTION", "LATENT",
           "DENSE", "EVA", "SPARSE", "LIGHTNING", "SHORTCONV", "KDA",
           "MIXER_TYPES", "LAYER_TYPES"]

MAMBA, EXPERTS, ATTENTION, LATENT, DENSE, EVA = "M", "E", "*", "L", "D", "V"
SPARSE, LIGHTNING, SHORTCONV, KDA = "S", "N", "C", "K"
#: A published ``mixer_types`` entry -> the letter of its block.
MIXER_TYPES = {"minicpm4": SPARSE, "lightning-attn": LIGHTNING}
#: A published ``layer_types`` entry -> the letter of its mixer.
LAYER_TYPES = {"conv": SHORTCONV, "full_attention": ATTENTION}
#: What a family that publishes ``layer_types`` hard-sets, by its
#: ``model_type``. ``lfm2_moe``: ``full_attention`` is grouped-query attention
#: with an RMSNorm over each query and key head and a rotary turn over the
#: whole head, the experts' router a sigmoid. ``ouro``: the rotary turn and no
#: norm a head, a norm on every mixer's OUTPUT (sandwich norm), and the stack
#: run ``total_ut_steps`` times.
_LAYER_TYPES_FAMILIES = {
    "lfm2_moe": {"attn_qk_norm": True, "attn_rope": True,
                 "scoring_func": "sigmoid"},
    "ouro": {"attn_rope": True, "post_norm": True}}
#: The family's switches as published (``minicpm_sala``): each kind of mixer
#: is implemented so and in no other form.
_MIXER_SWITCHES = {
    "minicpm4": {"attn_use_rope": False, "qk_norm": True,
                 "attn_use_output_gate": True},
    "lightning-attn": {"lightning_use_rope": True, "qk_norm": True,
                       "use_output_norm": True, "use_output_gate": True,
                       "lightning_scale": "1/sqrt(d)"}}

#: What a family that publishes ``layer_group_size`` (groups of linear-
#: attention layers closed by a latent-attention one) is implemented with, and
#: in no other form: a file that says otherwise is refused by the key's name.
_GROUP_SWITCHES = {
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "linear_silu": True, "use_qk_norm": True, "use_mla_nope": False,
    "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
    "gated_attention_proj_granularity_type": "head_wise",
    "q_lora_rank": None, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False}
#: Per-layer clamps of that family's expert activations: zero is no clamp.
_SWIGLU_LIMITS = ("expert_swiglu_limit_list",
                  "share_expert_swiglu_limit_list")

#: Keys a file gives under the published ``config.json``'s own names: those
#: every file has, those a kind of block needs (a file has the keys of the
#: blocks its pattern runs), and those with a default. ``file key:field``
#: where the dataclass calls it otherwise: two published families use
#: ``chunk_size``, Mamba-2 for its scan's chunk and EVA for the positions a
#: summary pools, and a file's key is read by the kind of block it runs.
_ALWAYS_KEYS = ("hidden_size", "vocab_size")
_KEYS_OF_KIND = {
    MAMBA: ("mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
            "conv_kernel", "chunk_size", "time_step_min", "time_step_max",
            "time_step_floor"),
    ATTENTION: ("num_attention_heads", "num_key_value_heads", "head_dim"),
    LATENT: ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "rope_theta"),
    DENSE: ("intermediate_size",),
    EXPERTS: ("num_experts_per_tok", "moe_intermediate_size",
              "routed_scaling_factor", "norm_topk_prob"),
    EVA: ("num_attention_heads", "window_size", "chunk_size:eva_chunk_size",
          "rope_theta"),
    SPARSE: ("num_attention_heads", "num_key_value_heads", "head_dim"),
    LIGHTNING: ("lightning_nh", "lightning_nkv", "lightning_head_dim",
                "rope_theta"),
    SHORTCONV: ("conv_L_cache",),
    KDA: ("num_attention_heads:kda_num_heads", "head_dim:kda_head_dim",
          "short_conv_kernel_size", "kda_lower_bound"),
}
_OPTIONAL_KEYS = ("moe_shared_expert_intermediate_size", "scoring_func",
                  "hidden_act", "aux_loss_alpha", "num_pred_heads",
                  "norm_add_unit_offset", "scale_emb", "scale_depth",
                  "dim_model_base", "use_expert_bias", "tie_word_embeddings",
                  "total_ut_steps", "early_exit_threshold", "n_group",
                  "topk_group")
#: A sparse block's sizes, as the published ``sparse_config`` group names them.
_SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size", "window_size",
                "init_blocks", "topk", "dense_len")
#: Keys of this repo, optional in a file.
_OWN_KEYS = ("learning_rate", "adagrad_step", "init_std", "attn_block",
             "moe_block", "loss_block", "ffn_slab", "row_bucket",
             "comm_policy", "lightning_chunk", "expert_bias_update_rate",
             "exit_entropy_weight", "kda_chunk")


@dataclasses.dataclass
class HybridLMConfig:
    """Shape + optimizer of one chip's share of a layer-typed LM. Widths are
    the published ones; ``pattern``, ``held`` and ``vocab_size`` say what of
    the model lives here."""
    hidden_size: int = 64
    vocab_size: int = 64
    #: One pre-norm residual block per letter: ``M`` Mamba-2, ``*`` grouped-
    #: query attention, ``L`` latent attention, ``V`` EVA (exact keys inside
    #: a window, pooled chunk summaries of every earlier window), ``D`` a
    #: dense gated feed-forward, ``E`` an expert block, ``S`` block-sparse
    #: attention (per query and key-value head, the keys of a window, of the
    #: first block and of ``sparse_topk`` blocks it chooses by itself), ``N``
    #: Lightning linear attention (a fixed decay a head). A layer of two
    #: blocks (attention, then a feed-forward) is two letters. ``C`` a gated
    #: short convolution (two gates around a depthwise causal convolution of
    #: ``conv_L_cache`` taps). ``K`` Kimi Delta Attention (a delta-rule state
    #: with a decay a channel: :mod:`.kda`).
    pattern: str = "MEM*E"
    norm_eps: float = 1e-5
    #: Every RMSNorm scales by ``1 + w`` (``w`` drawn at zero), not by ``w``.
    norm_add_unit_offset: bool = False
    #: Every block norms its mixer's OUTPUT too before the residual add
    #: (sandwich norm): ``u + RMSNorm_w2(mixer(RMSNorm_w1(u)))``, a second
    #: norm leaf a block (``post_norm``).
    post_norm: bool = False
    #: The stack and the final norm run this many times over the SAME leaves
    #: (a looped LM): a pass's normed output is the next pass's input and
    #: what the head and the exit gate read. Above 1 the loss is every pass's
    #: cross-entropy weighed by the exit distribution the gate emits, less
    #: ``exit_entropy_weight`` times its entropy (docs/HYBRID_LM.md "A looped
    #: stack").
    total_ut_steps: int = 1
    exit_entropy_weight: float = 0.0
    #: Published beside ``total_ut_steps``: the cumulative exit mass at which
    #: decoding stops looping. Carried; training takes no exit.
    early_exit_threshold: float = 1.0
    #: Targets a position: head ``h`` of the ``num_pred_heads * vocab_size``
    #: wide output predicts the token ``1 + h`` ahead.
    num_pred_heads: int = 1
    # -- Mamba-2 ------------------------------------------------------------
    mamba_num_heads: int = 2
    mamba_head_dim: int = 16
    ssm_state_size: int = 16
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 8
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # -- attention ------------------------------------------------------------
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    #: The ``*`` block norms each query and key head (one weight vector of
    #: ``head_dim`` each) and turns both over the whole head, half layout,
    #: plain ``rope_theta``: as the ``layer_types`` family publishes its
    #: ``full_attention``. Off, the block has no positions (``nemotron_h``).
    attn_qk_norm: bool = False
    attn_rope: bool = False
    # -- gated short convolution: taps of the depthwise causal convolution ----
    conv_L_cache: int = 3
    # -- Kimi Delta Attention: ``kda_num_heads`` heads of ``kda_head_dim`` keys
    # and values, a convolution of ``short_conv_kernel_size`` taps before
    # each of q, k, v, the log decay a channel in ``(kda_lower_bound, 0)`` ---
    kda_num_heads: int = 2
    kda_head_dim: int = 16
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    #: Positions a chunk of the delta rule: a power of two (whole sub-chunks
    #: of 16, or one shorter).
    kda_chunk: int = 64
    # -- latent attention: keys and values expanded from one latent -----------
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    #: The published ``rope_scaling`` group (``type: yarn``), or None.
    rope_scaling: Optional[Dict[str, Any]] = None
    # -- EVA: rotary over the whole head (``num_attention_heads`` heads of
    # ``head_dim``, ``rope_theta``, no scaling) ---------------------------------
    #: Positions a query sees key by key: its own aligned window's.
    window_size: int = 8
    #: Positions one summary pools (the published ``chunk_size``).
    eva_chunk_size: int = 2
    # -- block-sparse attention (InfLLM-v2): ``num_attention_heads`` query and
    # ``num_key_value_heads`` key-value heads of ``head_dim``, no positions, a
    # norm over each query and key head, a gate on the output. Sizes under
    # the published ``sparse_config``'s names --------------------------------
    #: Positions one pooled key is the mean of, and the step between two.
    sparse_kernel_size: int = 4
    sparse_kernel_stride: int = 2
    #: Keys a block: what a query chooses, ``sparse_topk`` of them beside the
    #: forced ones (the first ``sparse_init_blocks`` and those that hold one
    #: of its last ``sparse_window_size`` positions).
    sparse_block_size: int = 4
    sparse_window_size: int = 8
    sparse_init_blocks: int = 1
    sparse_topk: int = 2
    #: A sequence no longer than this is attended densely (plain causal).
    sparse_dense_len: int = 16
    # -- Lightning linear attention: rotary over the whole head
    # (``rope_theta``, half layout), a norm over each query and key head and
    # over the output, a gate on it ------------------------------------------
    lightning_nh: int = 2
    lightning_nkv: int = 2
    lightning_head_dim: int = 16
    #: The heads held here, by their number ``h`` (1..) among the model's
    #: ``lightning_published_nh`` (empty / 0: all ``lightning_nh``, the whole
    #: model's): a head's decay reads both, so a share of the heads keeps
    #: the rates they were published with.
    lightning_heads: Tuple[int, ...] = ()
    lightning_published_nh: int = 0
    #: Positions a chunk of the scan.
    lightning_chunk: int = 8
    # -- muP (``minicpm``): the embedding times ``scale_emb``, every residual
    # branch times ``scale_depth / sqrt(published layers)``, the head's input
    # over ``hidden_size / dim_model_base``; 0 leaves a scaling out -----------
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    dim_model_base: int = 0
    #: Layers of the whole model (0: those of ``pattern``, which are its
    #: first): a Lightning head's decay and the residual scale read it.
    published_layers: int = 0
    # -- dense feed-forward ---------------------------------------------------
    intermediate_size: int = 128
    # -- expert block ---------------------------------------------------------
    #: Width of the router: every expert of the model, held here or not.
    router_experts: int = 8
    #: The experts this chip holds (ids among ``router_experts``).
    held: Tuple[int, ...] = (0, 1)
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    #: Width of the shared experts together (in a file that gives none:
    #: ``n_shared_experts * moe_intermediate_size``).
    moe_shared_expert_intermediate_size: int = 64
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: ``sigmoid`` (with a selection bias) or ``softmax`` (without).
    scoring_func: str = "sigmoid"
    #: Group-limited choice (sigmoid router): the experts in ``n_group``
    #: contiguous groups, a token's experts taken from its ``topk_group``
    #: best groups only. 1: no groups.
    n_group: int = 1
    topk_group: int = 1
    #: A sigmoid router's selection bias: seeded small, or (False) zero.
    use_expert_bias: bool = True
    #: Rate ``u`` of the selection bias's own update after every step, outside
    #: the gradient: ``b_e += u * sign(mean(c) - c_e)`` from the step's
    #: assignments ``c`` to EVERY expert (balancing without an auxiliary
    #: loss). 0: the bias stays as seeded.
    expert_bias_update_rate: float = 0.0
    #: The head is the embedding table itself: logits against the rows a
    #: step pulled, no ``head`` leaf (docs/HYBRID_LM.md "A tied head").
    tie_word_embeddings: bool = False
    #: ``relu2``: two matrices an expert; ``silu``: three, ``silu(gate) * up``.
    hidden_act: str = "relu2"
    #: Weight of the sequence-wise balance loss of every expert block.
    aux_loss_alpha: float = 0.0
    # -- optimizer: the server plane's AdaGrad on every parameter -------------
    #: Client-side prescale of every pushed delta (the PSModel contract:
    #: the server reconstructs ``grad = delta / learning_rate``).
    learning_rate: float = 0.05
    #: ``AddOption.rho``: the step is ``rho / sqrt(G + eps) * grad``.
    adagrad_step: float = 0.002
    init_std: float = 0.02
    seed: int = 0
    # -- how the step program blocks its work ---------------------------------
    attn_block: int = 512
    moe_block: int = 512
    loss_block: int = 2048
    #: A dense feed-forward works on at most this many positions at a time
    #: (token by token, any cut is exact); a sequence no longer is one slab.
    ffn_slab: int = 8192
    #: Pulled-row counts are rounded up to a multiple of this, so that a
    #: step's distinct-id count picks one of few compiled shapes. Never more
    #: than the vocabulary: a table is not asked for more rows than it has.
    row_bucket: int = 1024
    table_name: str = "hybrid_lm_emb"
    comm_policy: str = "ps"
    # -- provenance (carried, not interpreted) --------------------------------
    source: str = ""
    reduced: Tuple[str, ...] = ()
    assumed: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        self.row_bucket = min(self.row_bucket, self.vocab_size)

    # -- derived widths -------------------------------------------------------
    @property
    def d_inner(self) -> int:
        """``mamba_num_heads * mamba_head_dim`` (NOT ``expand * hidden``)."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def in_proj_dim(self) -> int:
        """``[z | xBC | dt]``."""
        return self.d_inner + self.conv_dim + self.mamba_num_heads

    @property
    def q_dim(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def qk_head_dim(self) -> int:
        """A latent-attention head's query / key width: ``[nope | rope]``."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def gated_experts(self) -> bool:
        return self.hidden_act == "silu"

    @property
    def balanced(self) -> bool:
        """Whether the loss carries the expert blocks' balance term."""
        return self.aux_loss_alpha > 0 and EXPERTS in self.pattern

    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.pattern) if k == EXPERTS)

    def attention_blocks(self) -> int:
        return sum(k in (ATTENTION, LATENT) for k in self.pattern)

    def eva_blocks(self) -> int:
        return self.pattern.count(EVA)

    def layer_of(self, block: int) -> int:
        """Published index of the layer block ``block`` belongs to: a layer
        is a mixer and the feed-forward blocks after it."""
        return sum(k not in (DENSE, EXPERTS) for k in self.pattern[:block])

    @property
    def layers(self) -> int:
        return self.published_layers or self.layer_of(len(self.pattern))

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.layers) \
            if self.scale_depth else 1.0

    @property
    def logit_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base \
            if self.dim_model_base else 1.0

    @property
    def sparse_pool(self) -> Tuple[int, int]:
        """(pooled keys a block holds the start of, those before them that
        reach into it): a block's score is the largest over both."""
        return (self.sparse_block_size // self.sparse_kernel_stride,
                self.sparse_kernel_size // self.sparse_kernel_stride - 1)

    def validate(self) -> None:
        from multiverso_tpu.utils.log import check
        check(set(self.pattern) <= {MAMBA, EXPERTS, ATTENTION, LATENT, DENSE,
                                    EVA, SPARSE, LIGHTNING, SHORTCONV, KDA}
              and self.pattern, f"bad layer pattern {self.pattern!r}")
        check(not self.attn_rope or self.head_dim % 2 == 0,
              "rotary width must be even")
        check(self.conv_L_cache >= 1, "a convolution has at least one tap")
        check(self.total_ut_steps >= 1 and self.exit_entropy_weight >= 0.0,
              "total_ut_steps is at least one, exit_entropy_weight not "
              "negative")
        check(self.total_ut_steps == 1 or (
            not {EXPERTS, SPARSE} & set(self.pattern)
            and self.num_pred_heads == 1 and not self.dim_model_base),
            "a looped stack (total_ut_steps > 1) carries no expert or sparse "
            "block's counts from pass to pass, and has one plain head")
        check(not self.tie_word_embeddings or self.num_pred_heads == 1,
              "a tied head is one vocabulary wide")
        check(self.expert_bias_update_rate >= 0.0
              and (not self.expert_bias_update_rate
                   or (self.scoring_func == "sigmoid"
                       and self.use_expert_bias)),
              "only a sigmoid router's selection bias is updated")
        check(self.mamba_num_heads % self.n_groups == 0,
              "mamba heads must divide into n_groups")
        check(self.d_inner % self.n_groups == 0, "d_inner % n_groups")
        check(self.num_attention_heads % self.num_key_value_heads == 0,
              "query heads must be a multiple of key-value heads")
        check(len(set(self.held)) == len(self.held) and all(
            0 <= e < self.router_experts for e in self.held),
            f"held experts {self.held} not among {self.router_experts}")
        check(self.num_experts_per_tok <= self.router_experts,
              "more experts a token than experts")
        check(1 <= self.topk_group <= self.n_group
              and self.router_experts % self.n_group == 0
              and (self.n_group == 1 or (
                  self.scoring_func == "sigmoid"
                  and self.num_experts_per_tok
                  <= self.topk_group * (self.router_experts // self.n_group)
                  and self.router_experts // self.n_group >= 2)),
              "group-limited routing: a sigmoid router's experts in n_group "
              "equal groups of two or more, topk_group of them holding the "
              "experts a token takes")
        check(self.kda_lower_bound < 0 and self.short_conv_kernel_size >= 1
              and self.kda_chunk >= 1
              and self.kda_chunk & (self.kda_chunk - 1) == 0,
              "a KDA gate's bound is negative, its chunk a power of two")
        check(self.scoring_func in ("sigmoid", "softmax"),
              f"unknown scoring_func {self.scoring_func!r}")
        check(self.hidden_act in ("relu2", "silu"),
              f"unknown hidden_act {self.hidden_act!r}")
        check(self.qk_rope_head_dim % 2 == 0, "rotary width must be even")
        if EVA in self.pattern:
            check(self.head_dim % 2 == 0, "rotary width must be even")
            check(self.window_size % self.eva_chunk_size == 0,
                  "an EVA window is a whole number of chunks")
            check(self.window_size % min(self.attn_block, self.window_size)
                  == 0, "an EVA window is a whole number of attention blocks")
        if SPARSE in self.pattern:
            stride, block = self.sparse_kernel_stride, self.sparse_block_size
            check(self.sparse_kernel_size % stride == 0 and block % stride
                  == 0, "pooled keys step evenly through a key block")
            check(self.attn_block % block == 0,
                  "an attention block is a whole number of key blocks")
            check(self.sparse_init_blocks >= 1 and self.sparse_dense_len
                  >= self.sparse_kernel_size,
                  "every query sees the first key block, and a sequence "
                  "that chooses has a pooled key")
        if LIGHTNING in self.pattern:
            check(self.lightning_nkv == self.lightning_nh
                  and self.lightning_head_dim % 2 == 0,
                  "Lightning attention has a key head a query head, of "
                  "even width")
            held, whole = self.lightning_heads, self.lightning_published_nh
            check((whole or self.lightning_nh) == self.lightning_nh
                  or held, "a share of the Lightning heads names them")
            check(not held or (len(set(held)) == len(held)
                               == self.lightning_nh and all(
                1 <= h <= (whole or self.lightning_nh) for h in held)),
                f"held Lightning heads {held} are not {self.lightning_nh} "
                f"of 1..{whole or self.lightning_nh}")
        check(self.num_pred_heads >= 1 and self.ffn_slab >= 1,
              "num_pred_heads and ffn_slab are at least one")
        check(self.rope_scaling is None
              or self.rope_scaling.get("type") == "yarn",
              f"unknown rope_scaling {self.rope_scaling!r}")

    # -- files ------------------------------------------------------------
    @classmethod
    def from_dict(cls, d: Dict[str, Any], **overrides) -> "HybridLMConfig":
        """From the published keys. ``n_routed_experts`` (``num_experts`` in
        a file that publishes its experts under that name) counts the experts
        HELD (``held_experts`` names them, default the first ones; a file
        without the key holds none) and ``published.<that key>`` the
        router's width; likewise ``lightning_nh`` counts the Lightning heads
        held, ``held_lightning_heads`` names them by their published number
        (1..) and ``published.lightning_nh`` says of how many (a file that
        holds fewer than published has to name them). The pattern is the
        first ``num_hidden_layers`` of ``hybrid_override_pattern`` where the
        file has one, or two blocks a layer from the first
        ``num_hidden_layers`` of ``mixer_types`` or of ``layer_types`` (an
        entry this program does not know raises, as does a ``model_type``
        whose switches it does not know: :meth:`_pattern_of_layers`), or
        two blocks a layer from ``layer_group_size`` (KDA layers in groups
        closed by a latent-attention one, :meth:`_pattern_of_groups`; such a
        file names its router's scores ``score_function`` and its selection
        bias ``moe_router_enable_expert_bias``);
        else every layer is two
        blocks, attention (latent where the file has a
        ``kv_lora_rank``, EVA where its ``attention_class`` is ``eva``) and a
        feed-forward: experts, where the file has any, from layer
        ``first_k_dense_replace`` on at every ``moe_layer_freq``-th layer,
        dense before and between. A file that gives no ``head_dim`` has heads
        of ``hidden_size / num_attention_heads``."""
        published = d.get("published", {})
        experts_key = "num_experts" if "num_experts" in d \
            else "n_routed_experts"
        n_held = int(d.get(experts_key, 0))
        router = int(published.get(experts_key, n_held))
        held = tuple(d.get("held_experts", range(n_held)))
        if len(held) != n_held:
            raise ValueError(f"held_experts names {len(held)} experts, "
                             f"{experts_key} says {n_held}")
        if "hybrid_override_pattern" in d:
            pattern = d["hybrid_override_pattern"][:d["num_hidden_layers"]]
        elif "mixer_types" in d:
            pattern = cls._pattern_of_mixers(d)
        elif "layer_types" in d:
            pattern = cls._pattern_of_layers(d, n_held)
        elif "layer_group_size" in d:
            pattern = cls._pattern_of_groups(d, n_held)
        else:
            if d.get("q_lora_rank") is not None:
                raise ValueError("a query latent (q_lora_rank) is not "
                                 "implemented")
            if d.get("aux_loss_alpha", 0) and not d.get("seq_aux", True):
                raise ValueError("only the sequence-wise balance loss "
                                 "(seq_aux) is implemented")
            eva = d.get("attention_class")
            if eva not in (None, "eva"):
                raise ValueError(f"unknown attention_class {eva!r}")
            if eva and d.get("num_key_value_heads",
                             d["num_attention_heads"]) \
                    != d["num_attention_heads"]:
                raise ValueError("EVA with grouped key-value heads is not "
                                 "implemented")
            first = d.get("first_k_dense_replace", 0) if n_held \
                else d["num_hidden_layers"]
            freq = d.get("moe_layer_freq", 1)
            mixer = LATENT if "kv_lora_rank" in d else \
                EVA if eva else ATTENTION
            pattern = "".join(
                mixer + (EXPERTS if i >= first and i % freq == 0 else DENSE)
                for i in range(d["num_hidden_layers"]))
        kinds = sorted(set(pattern))
        if MAMBA in kinds and EVA in kinds:
            raise ValueError("a file's chunk_size is Mamba-2's or EVA's, "
                             "not both")
        kw = {key: d[key] for key in _ALWAYS_KEYS}
        for kind in kinds:
            for key in _KEYS_OF_KIND.get(kind, ()):
                key, _, field = key.partition(":")
                if key != "head_dim" or "layer_types" not in d:
                    kw[field or key] = d[key]
        if EVA in kinds or "layer_types" in d:
            kw["head_dim"] = d.get("head_dim") or \
                d["hidden_size"] // d["num_attention_heads"]
        if "layer_types" in d:
            kw.update(_LAYER_TYPES_FAMILIES[d["model_type"]],
                      rope_theta=d["rope_theta"])
        kw.update({key: d[key] for key in _OPTIONAL_KEYS if key in d})
        if SPARSE in kinds:
            kw.update({"sparse_" + key: d["sparse_config"][key]
                       for key in _SPARSE_KEYS})
        if "scale_depth" in d or LIGHTNING in kinds:
            kw["published_layers"] = published.get("num_hidden_layers",
                                                   d["num_hidden_layers"])
        if LIGHTNING in kinds:
            kw.update(
                lightning_published_nh=published.get("lightning_nh",
                                                     d["lightning_nh"]),
                lightning_heads=tuple(d.get("held_lightning_heads", ())))
        if "layer_group_size" in d:
            kw.update(scoring_func=d["score_function"], use_expert_bias=d[
                "moe_router_enable_expert_bias"])
        kw.setdefault("moe_shared_expert_intermediate_size",
                      d.get("n_shared_experts", 0)
                      * d.get("moe_intermediate_size", 0))
        kw.setdefault("hidden_act", d.get("mlp_hidden_act", "relu2"))
        kw.update(
            pattern=pattern,
            norm_eps=d.get("layer_norm_epsilon", d.get(
                "rms_norm_eps", d.get("norm_eps", 1e-5))),
            rope_scaling=d.get("rope_scaling"), source=d.get("source", ""),
            reduced=tuple(d.get("reduced", ())),
            assumed=dict(d.get("assumed", {})))
        if experts_key in d:
            kw.update(router_experts=router, held=held)
        kw.update({key: d[key] for key in _OWN_KEYS if key in d})
        kw.update(overrides)
        cfg = cls(**kw)
        cfg.validate()
        return cfg

    @staticmethod
    def _pattern_of_mixers(d: Dict[str, Any]) -> str:
        """Two blocks a layer, the mixer ``mixer_types`` names and a dense
        feed-forward, for the first ``num_hidden_layers`` of the list."""
        names = d["mixer_types"][:d["num_hidden_layers"]]
        unknown = sorted(set(names) - set(MIXER_TYPES))
        if unknown or len(names) < d["num_hidden_layers"]:
            raise ValueError(
                f"mixer_types names {len(names)} layers of "
                f"{d['num_hidden_layers']}, unknown kinds {unknown}")
        for name in set(names):
            for key, value in _MIXER_SWITCHES[name].items():
                if d.get(key) != value:
                    raise ValueError(f"{name} with {key} = {d.get(key)!r} "
                                     f"is not implemented")
        return "".join(MIXER_TYPES[name] + DENSE for name in names)

    @staticmethod
    def _pattern_of_layers(d: Dict[str, Any], n_held: int) -> str:
        """Two blocks a layer for the first ``num_hidden_layers`` of
        ``layer_types``: the mixer the entry names (:data:`LAYER_TYPES`), then
        a dense feed-forward in the first ``num_dense_layers`` layers (in
        every layer of a file that holds no expert) and an expert block in
        each later one."""
        if d.get("model_type") not in _LAYER_TYPES_FAMILIES:
            raise ValueError(
                f"layer_types under model_type {d.get('model_type')!r}: the "
                f"family's switches are known for "
                f"{sorted(_LAYER_TYPES_FAMILIES)}")
        names = d["layer_types"][:d["num_hidden_layers"]]
        unknown = sorted(set(names) - set(LAYER_TYPES))
        if unknown or len(names) < d["num_hidden_layers"]:
            raise ValueError(
                f"layer_types names {len(names)} layers of "
                f"{d['num_hidden_layers']}, unknown kinds {unknown}")
        if d.get("conv_bias", False):
            raise ValueError("a short convolution with conv_bias is not "
                             "implemented")
        dense = d["num_dense_layers"] if n_held else len(names)
        return "".join(LAYER_TYPES[name] + (DENSE if i < dense else EXPERTS)
                       for i, name in enumerate(names))

    @staticmethod
    def _pattern_of_groups(d: Dict[str, Any], n_held: int) -> str:
        """Two blocks a layer for a file with ``layer_group_size``: layer
        ``i`` (0-based) mixes by latent attention where ``(i + 1) %
        layer_group_size == 0`` and by Kimi Delta Attention everywhere else;
        then a dense feed-forward in the first ``first_k_dense_replace``
        layers (in every layer of a file that holds no expert) and an expert
        block in each later one. The family's switches have to read as
        :data:`_GROUP_SWITCHES` has them, and the activation clamps of the
        layers kept (:data:`_SWIGLU_LIMITS`) be zero: the form of a non-zero
        one is not published with the file."""
        for key, value in _GROUP_SWITCHES.items():
            if d.get(key, value) != value:
                raise ValueError(f"layer_group_size with {key} = "
                                 f"{d[key]!r} is not implemented")
        layers = d["num_hidden_layers"]
        for key in _SWIGLU_LIMITS:
            if any(d.get(key, ())[:layers]):
                raise ValueError(
                    f"{key} is not zero in the first {layers} layers: the "
                    f"clamp's form is not implemented")
        dense = d.get("first_k_dense_replace", 0) if n_held else layers
        return "".join(
            (LATENT if (i + 1) % d["layer_group_size"] == 0 else KDA)
            + (DENSE if i < dense else EXPERTS) for i in range(layers))

    @classmethod
    def from_file(cls, path: str, **overrides) -> "HybridLMConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f), **overrides)
