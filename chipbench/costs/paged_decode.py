"""Operations and bytes of the paged decode-attention kernel, from the
batch's real context lengths.

One decode step of one layer reads every cached K and V of every live slot
once (``context * kv_heads * head_dim`` elements each) and does QK^T and PV
over them.  The query, the output and the page table are a few KB and are
left out, so the share of the roofline is a floor.
"""

from __future__ import annotations


def step(contexts, heads, kv_heads, head_dim, kv_itemsize=2):
    """``(flops, bytes)`` of one layer's kernel call over slots whose cached
    lengths are ``contexts``."""
    total = sum(contexts)
    flops = 4 * total * heads * head_dim
    bytes_moved = 2 * total * kv_heads * head_dim * kv_itemsize
    return flops, bytes_moved
