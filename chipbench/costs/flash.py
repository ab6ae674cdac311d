"""Operations and bytes of the flash attention kernels, from shapes.

Forward: QK^T and PV over the causal half, read Q, K, V, write O and the
row log-sum-exp.  Backward (the dk/dv and the dq kernel together): dV, dP,
dK and dQ, four products where the forward has two; the scores they
recompute are recomputation and are not counted.  Bytes are one pass over
each operand in its stored type.
"""

from __future__ import annotations


def forward(batch, seq, heads, head_dim, causal=True, itemsize=2):
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    flops = batch * heads * 4 * head_dim * pairs
    bytes_moved = batch * heads * seq * (4 * head_dim * itemsize + 4)
    return flops, bytes_moved


def backward(batch, seq, heads, head_dim, causal=True, itemsize=2):
    flops = 2 * forward(batch, seq, heads, head_dim, causal)[0]
    # read Q, K, V, O, dO and the log-sum-exp; write dQ, dK, dV
    bytes_moved = batch * heads * seq * (8 * head_dim * itemsize + 4)
    return flops, bytes_moved
