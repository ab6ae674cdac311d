"""Operations the GPT-2 family needs, from its shapes.

Counted are the operations the algorithm requires, multiply-add as 2:
matrix products of the blocks and of the output head, and attention's two
products over each token's real context.  Nothing recomputed is counted,
and element-wise work (LayerNorm, GELU, softmax, the optimizer) is left
out, so a share of peak worked out from these counts is a floor.
"""

from __future__ import annotations


def _sizes(cfg):
    H = int(cfg["n_embd"])
    return (int(cfg["n_layer"]), H, int(cfg.get("n_inner") or 4 * H),
            int(cfg["vocab_size"]))


def block_matmul_params(cfg):
    """Weights that take part in a matrix product, blocks only."""
    L, H, I, _ = _sizes(cfg)
    return L * (H * 3 * H + H * H + 2 * H * I)


def head_params(cfg):
    _, H, _, V = _sizes(cfg)
    return V * H


def attention_flops(cfg, context):
    """Forward attention of ONE query token over ``context`` keys, all
    layers: QK^T and PV, 2*context*H each."""
    L, H, _, _ = _sizes(cfg)
    return L * 4 * context * H


def causal_attention_flops(cfg, seq):
    """Forward causal attention of a whole sequence: token t sees t keys."""
    L, H, _, _ = _sizes(cfg)
    return L * 4 * H * seq * (seq + 1) // 2


def train_flops_per_sample(cfg, seq):
    """Forward + backward of one sequence of ``seq`` tokens: 3 x forward
    (the backward pass costs twice the forward), loss over every position's
    full vocabulary."""
    fwd = 2 * (block_matmul_params(cfg) + head_params(cfg)) * seq \
        + causal_attention_flops(cfg, seq)
    return 3 * fwd


def prefill_flops(cfg, prompt_len):
    """A prompt ingested: every token through the blocks, causal attention,
    the head at the last position only."""
    return 2 * block_matmul_params(cfg) * prompt_len \
        + causal_attention_flops(cfg, prompt_len) + 2 * head_params(cfg)


def decode_flops(cfg, context):
    """One output token decoded against ``context`` cached tokens."""
    return 2 * (block_matmul_params(cfg) + head_params(cfg)) \
        + attention_flops(cfg, context)
