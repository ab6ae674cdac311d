"""How the program builds the ``ouro`` family, and how the benchmark's seeded
weights get into it.  The only file of the family that imports the program:
at module level, so that a checkout without the family fails on this file's
first import, before a weight is drawn.  The reference module is handed in,
never imported from here.
"""

from __future__ import annotations

from paddle_tpu.text.models.ouro import OuroConfig, OuroForCausalLM

#: keys of the configuration's file the program's config takes as they are
_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "head_dim", "hidden_act", "max_position_embeddings", "rms_norm_eps",
         "rope_theta", "rope_scaling", "tie_word_embeddings",
         "total_ut_steps", "early_exit_threshold", "sliding_window",
         "use_sliding_window", "initializer_range")


def program_config(cfg, dtype=None):
    return OuroConfig(**{k: cfg[k] for k in _KEYS if k in cfg},
                      dtype=str(dtype or "float32"))


def build(cfg, params, ref, dtype=None):
    """``OuroForCausalLM`` at the configuration's sizes, holding ``params``
    (the reference's flat dict under the program's own leaf names): every
    leaf is handed over as it is, one at a time, so the model's arrays ARE
    the dict's and nothing is copied or cast."""
    taken = set()

    def hand_over(name, shape):
        leaf = params[name]
        if tuple(leaf.shape) != tuple(shape):
            raise RuntimeError(f"{name}: the reference has {leaf.shape}, "
                               f"the program wants {tuple(shape)}")
        taken.add(name)
        return leaf

    model = OuroForCausalLM(program_config(cfg, dtype), param_init=hand_over)
    left = sorted(set(params) - taken)
    if left:
        raise RuntimeError(f"weights the model did not take: {left}")
    return model
