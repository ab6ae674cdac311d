"""Operations and bytes the Ouro family (a looped decoder) needs, from its
shapes.

Counted are the operations the equations require, multiply-add as 2: two a
weight a token over the weights a token meets, and it meets every layer
``total_ut_steps`` times; attention's two products over each token's real
context in every (step, layer), ``total_ut_steps * num_hidden_layers`` cache
rows; the head once.  Element-wise work (RMSNorm, SiLU, rotary, softmax, the
exit gate's one column) is left out, so a share of a peak worked out from
these counts is a floor.  The count is of the work the equations need,
whatever implements it: a program that skipped a step would read over 100%,
and this configuration forbids that.
"""

from __future__ import annotations


def _sizes(cfg):
    H, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]),
            "R": int(cfg["total_ut_steps"]), "H": H, "nh": nh,
            "nkv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or H // nh),
            "I": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def attention_shape(cfg):
    """``(cache rows, query heads, KV heads, head size)``: a row a (step,
    layer)."""
    z = _sizes(cfg)
    return z["R"] * z["L"], z["nh"], z["nkv"], z["hd"]


def layer_matmul_params(cfg):
    """One layer's weights that take part in a matrix product: q, k, v, o
    and the three of the SwiGLU."""
    z = _sizes(cfg)
    return 2 * z["H"] * z["nh"] * z["hd"] + 2 * z["H"] * z["nkv"] * z["hd"] \
        + 3 * z["H"] * z["I"]


def layer_params(cfg):
    """All of one layer's leaves: its products' weights and four gains."""
    return layer_matmul_params(cfg) + 4 * _sizes(cfg)["H"]


def block_params_per_token(cfg):
    """Weights a token meets in the layers' products: every layer once a
    step."""
    z = _sizes(cfg)
    return z["R"] * z["L"] * layer_matmul_params(cfg)


def head_params(cfg):
    z = _sizes(cfg)
    return z["V"] * z["H"]


def attention_flops(cfg, context):
    """Attention of ONE query token over ``context`` keys in every (step,
    layer): QK^T and PV, 2 * context * heads * head size each."""
    rows, nh, _, hd = attention_shape(cfg)
    return rows * 4 * context * nh * hd


def causal_attention_flops(cfg, seq):
    rows, nh, _, hd = attention_shape(cfg)
    return rows * 4 * nh * hd * seq * (seq + 1) // 2


def prefill_flops(cfg, prompt_len):
    """A prompt ingested: every token through the layers ``R`` times,
    causal attention in every (step, layer), the head at the last position
    only."""
    return 2 * block_params_per_token(cfg) * prompt_len \
        + causal_attention_flops(cfg, prompt_len) + 2 * head_params(cfg)


def decode_flops(cfg, context):
    """One output token decoded against ``context`` cached tokens."""
    return 2 * (block_params_per_token(cfg) + head_params(cfg)) \
        + attention_flops(cfg, context)


def kv_bytes_per_token(cfg, itemsize=2):
    """Cached bytes one token position holds over all rows, K and V."""
    rows, _, nkv, hd = attention_shape(cfg)
    return 2 * rows * nkv * hd * itemsize


def decode_step(cfg, contexts, steps, itemsize=2):
    """``(flops, bytes)`` of ``steps`` decode steps that decoded one token
    against each of ``contexts`` cached lengths (all steps' lanes
    together).  A step streams the layers' weights ``R`` times (the loop
    reads them again at every step), the final norm and the gate with them,
    and the head once; it reads the live K and V of its lanes' contexts
    over all cache rows.  The embedding's rows, the queries, the outputs
    and the page table are KBs and are left out: a floor."""
    z = _sizes(cfg)
    weights = z["R"] * (z["L"] * layer_params(cfg) + 2 * z["H"] + 1) \
        + head_params(cfg)
    moved = steps * weights * itemsize \
        + sum(contexts) * kv_bytes_per_token(cfg, itemsize)
    return sum(decode_flops(cfg, c) for c in contexts), moved
