"""Operations the LFM2-MoE family needs, from its shapes.

Counted are the operations the algorithm requires, multiply-add as 2: two a
weight a token over the weights a token USES (the convolution layers'
projections and taps, the attention layers' four projections, the dense
feed-forwards, the router and ``num_experts_per_tok`` experts of the
``num_experts`` in an expert layer, the head), and attention's two products
over each token's real context in the ATTENTION layers only.  Element-wise
work (RMSNorm, SiLU, gates, rotary, softmax, top-k, the sorts and gathers of
routing) is left out, so a share of peak worked out from these counts is a
floor.
"""

from __future__ import annotations


def _sizes(cfg):
    H, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    types = list(cfg["layer_types"])
    n_attn = sum(t == "full_attention" for t in types)
    dense = int(cfg["num_dense_layers"])
    return {"H": H, "nh": nh, "nkv": int(cfg["num_key_value_heads"]),
            "hd": H // nh, "I": int(cfg["intermediate_size"]),
            "F": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "K": int(cfg["conv_L_cache"]), "V": int(cfg["vocab_size"]),
            "attn": n_attn, "conv": len(types) - n_attn, "dense": dense,
            "sparse": len(types) - dense}


def attention_shape(cfg):
    """``(attention layers, query heads, KV heads, head size)``."""
    z = _sizes(cfg)
    return z["attn"], z["nh"], z["nkv"], z["hd"]


def grouped_kernels(cfg):
    """Kernels the TPU's compiler gives the grouped products of ONE pass of
    the blocks: three products (gate, up, down) an expert layer and one
    that lays out their group offsets (``decode_scope.UNSCOPED``)."""
    return _sizes(cfg)["sparse"] * (3 + 1)


def conv_params(cfg):
    """One convolution layer: ``W_in`` [H, 3H], ``W_out`` [H, H], K taps a
    channel."""
    z = _sizes(cfg)
    return 4 * z["H"] * z["H"] + z["K"] * z["H"]


def attention_params(cfg):
    """One attention layer's four projections."""
    z = _sizes(cfg)
    return 2 * z["H"] * z["nh"] * z["hd"] + 2 * z["H"] * z["nkv"] * z["hd"]


def expert_params(cfg):
    z = _sizes(cfg)
    return 3 * z["H"] * z["F"]


def block_params_per_token(cfg):
    """Weights a token meets in the blocks' products."""
    z = _sizes(cfg)
    return (z["conv"] * conv_params(cfg) + z["attn"] * attention_params(cfg)
            + z["dense"] * 3 * z["H"] * z["I"]
            + z["sparse"] * (z["H"] * z["E"] + z["k"] * expert_params(cfg)))


def head_params(cfg):
    z = _sizes(cfg)
    return z["V"] * z["H"]


def attention_flops(cfg, context):
    """Attention of ONE query token over ``context`` keys, the attention
    layers only: QK^T and PV, 2 * context * heads * head size each."""
    z = _sizes(cfg)
    return z["attn"] * 4 * context * z["nh"] * z["hd"]


def causal_attention_flops(cfg, seq):
    z = _sizes(cfg)
    return z["attn"] * 4 * z["nh"] * z["hd"] * seq * (seq + 1) // 2


def prefill_flops(cfg, prompt_len):
    """A prompt ingested: every token through the blocks, causal attention,
    the head at the last position only."""
    return 2 * block_params_per_token(cfg) * prompt_len \
        + causal_attention_flops(cfg, prompt_len) + 2 * head_params(cfg)


def decode_flops(cfg, context):
    """One output token decoded against ``context`` cached tokens."""
    return 2 * (block_params_per_token(cfg) + head_params(cfg)) \
        + attention_flops(cfg, context)
