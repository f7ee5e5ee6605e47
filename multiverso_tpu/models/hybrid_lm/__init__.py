"""A layer-typed LM (state-space, attention and expert layers chosen per
layer from a pattern) trained through the parameter-server plane: see
docs/HYBRID_LM.md."""

from multiverso_tpu.models.hybrid_lm import rope
from multiverso_tpu.models.hybrid_lm.config import (ATTENTION, DENSE, EVA,
                                                    EXPERTS, KDA, LATENT,
                                                    LIGHTNING, MAMBA,
                                                    SHORTCONV, SPARSE,
                                                    HybridLMConfig)
from multiverso_tpu.models.hybrid_lm.model import (APPLY_PROGRAM,
                                                   DELTA_PROGRAM, HybridLM,
                                                   dense_param_count,
                                                   exit_distribution,
                                                   forward_hidden,
                                                   init_buffers, init_params,
                                                   layer_forward,
                                                   looped_hidden, make_loss,
                                                   pack_batch, param_shapes,
                                                   rmsnorm,
                                                   updated_expert_bias)

__all__ = ["HybridLMConfig", "HybridLM", "MAMBA", "EXPERTS", "ATTENTION",
           "LATENT", "DENSE", "EVA", "SPARSE", "LIGHTNING", "SHORTCONV", "KDA",
           "DELTA_PROGRAM", "APPLY_PROGRAM", "dense_param_count",
           "exit_distribution", "forward_hidden", "looped_hidden",
           "init_buffers", "init_params", "layer_forward", "make_loss",
           "pack_batch", "param_shapes", "rmsnorm", "rope",
           "updated_expert_bias"]
