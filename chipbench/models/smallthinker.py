"""How the program builds the ``smallthinker`` family, and how the
benchmark's seeded weights get into it.  The only file of the family that
imports the program: at module level, so that a checkout without the family
fails on this file's first import, before a weight is drawn.  The reference
module is handed in, never imported from here.
"""

from __future__ import annotations

from paddle_tpu.text.models.smallthinker import (SmallThinkerConfig,
                                                 SmallThinkerForCausalLM)

#: keys of the configuration's file the program's config takes as they are
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "moe_ffn_hidden_size", "moe_num_primary_experts",
         "moe_num_active_primary_experts",
         "moe_primary_router_apply_softmax", "norm_topk_prob",
         "rms_norm_eps", "rope_theta", "rope_layout",
         "sliding_window_layout", "sliding_window_size",
         "max_position_embeddings", "tie_word_embeddings",
         "initializer_range")


def program_config(cfg, dtype=None):
    return SmallThinkerConfig(**{k: cfg[k] for k in _KEYS if k in cfg},
                              dtype=str(dtype or "float32"))


def build(cfg, params, ref, dtype=None):
    """``SmallThinkerForCausalLM`` at the configuration's sizes, holding
    ``params`` (the reference's weights under the program's own leaf
    names): every leaf is drawn and handed over as it is, one at a time,
    so nothing is copied or cast."""
    taken = set()

    def hand_over(name, shape):
        leaf = params[name]
        if tuple(leaf.shape) != tuple(shape):
            raise RuntimeError(f"{name}: the reference has {leaf.shape}, "
                               f"the program wants {tuple(shape)}")
        taken.add(name)
        return leaf

    model = SmallThinkerForCausalLM(program_config(cfg, dtype),
                                    param_init=hand_over)
    left = sorted(set(params) - taken)
    if left:
        raise RuntimeError(f"weights the model did not take: {left}")
    return model
