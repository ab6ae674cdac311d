"""Operations and bytes the SmallThinker family needs, from its shapes.

Counted are the operations the algorithm requires, multiply-add as 2: two a
weight a token over the weights a token USES (every layer's four attention
projections, its router and ``moe_num_active_primary_experts`` of its
experts, the head), and attention's two products over the keys each query
SEES: every earlier key in a full layer, the last ``sliding_window_size``
in a window layer.  Element-wise work (RMSNorm, ReLU, gates, rotary,
softmax, top-k, the sorts and gathers of routing) is left out, so a share
of peak worked out from these counts is a floor.
"""

from __future__ import annotations


def _sizes(cfg):
    swa = [int(s) for s in cfg["sliding_window_layout"]]
    return {"H": int(cfg["hidden_size"]),
            "nh": int(cfg["num_attention_heads"]),
            "nkv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg["head_dim"]), "F": int(cfg["moe_ffn_hidden_size"]),
            "E": int(cfg["moe_num_primary_experts"]),
            "k": int(cfg["moe_num_active_primary_experts"]),
            "V": int(cfg["vocab_size"]), "L": len(swa),
            "window_layers": sum(swa), "full_layers": len(swa) - sum(swa),
            "W": int(cfg["sliding_window_size"])}


def grouped_kernels(cfg):
    """Kernels the TPU's compiler gives the grouped products of ONE pass of
    the blocks WITHOUT their name stack (``decode_scope.UNSCOPED``: what it
    makes of ``jax.lax.ragged_dot``): none.  At 32 lanes a decode step
    routes 192 rows, and a chunk 1,536, which ``ops.grouped_matmul`` gives
    its own kernel, under ``moe_experts`` (compiled for a described v5e:
    24 such kernels a program, no ``ragged-dot``)."""
    return 0


def attention_params(cfg):
    """One layer's four projections."""
    z = _sizes(cfg)
    return 2 * z["H"] * z["nh"] * z["hd"] + 2 * z["H"] * z["nkv"] * z["hd"]


def expert_params(cfg):
    z = _sizes(cfg)
    return 3 * z["H"] * z["F"]


def block_params_per_token(cfg):
    """Weights a token meets in the blocks' products."""
    z = _sizes(cfg)
    return z["L"] * (attention_params(cfg) + z["H"] * z["E"]
                     + z["k"] * expert_params(cfg))


def head_params(cfg):
    z = _sizes(cfg)
    return z["V"] * z["H"]


def _per_key(cfg):
    """QK^T and PV of one query head over one key: 4 * head size."""
    z = _sizes(cfg)
    return 4 * z["nh"] * z["hd"]


def seen(cfg, context):
    """Keys a query that sees ``context`` keys of a full layer sees in a
    window layer."""
    return min(int(context), _sizes(cfg)["W"])


def attention_flops(cfg, context):
    """Attention of ONE query over ``context`` keys, every layer: all of
    them in a full layer, the window's in a window layer."""
    z = _sizes(cfg)
    return _per_key(cfg) * (z["full_layers"] * int(context)
                            + z["window_layers"] * seen(cfg, context))


def _seen_sum(cfg, first, n):
    """Keys the queries at positions ``first .. first + n - 1`` see in a
    window layer, summed: query ``p`` sees ``min(p + 1, W)``."""
    w = _sizes(cfg)["W"]
    lo, hi = first + 1, first + n          # the contexts p + 1
    ramp_hi = min(hi, w)
    ramp = (lo + ramp_hi) * (ramp_hi - lo + 1) // 2 if ramp_hi >= lo else 0
    return ramp + w * max(hi - max(lo - 1, w), 0)


def prefill_flops(cfg, prompt_len):
    """A prompt ingested: every token through the blocks, causal attention
    (the window's in a window layer), the head at the last position only."""
    z = _sizes(cfg)
    s = int(prompt_len)
    attn = _per_key(cfg) * (z["full_layers"] * s * (s + 1) // 2
                            + z["window_layers"] * _seen_sum(cfg, 0, s))
    return 2 * block_params_per_token(cfg) * s + attn + 2 * head_params(cfg)


def decode_flops(cfg, context):
    """One output token decoded against ``context`` cached tokens."""
    return 2 * (block_params_per_token(cfg) + head_params(cfg)) \
        + attention_flops(cfg, context)


def swa_decode(cfg, contexts, kv_itemsize=2, q_itemsize=2, out_itemsize=4):
    """``(flops, bytes)`` of the window layers' decode kernels of one step
    over lanes that see ``contexts`` keys: each reads the K and V of the
    keys in its window once, its query row and writes its output row (the
    kernel's output is float32), in every window layer."""
    z = _sizes(cfg)
    keys = sum(seen(cfg, c) for c in contexts)
    rows = len(contexts) * z["nh"] * z["hd"]
    flops = z["window_layers"] * _per_key(cfg) * keys
    moved = z["window_layers"] * (
        2 * keys * z["nkv"] * z["hd"] * kv_itemsize
        + rows * (q_itemsize + out_itemsize))
    return flops, moved


def swa_chunk_flops(cfg, first, n):
    """Operations of the window layers' chunk kernels over ONE chunk of
    ``n`` real prompt tokens from position ``first``: keys each query sees,
    and no others."""
    z = _sizes(cfg)
    return z["window_layers"] * _per_key(cfg) * _seen_sum(cfg, first, n)
