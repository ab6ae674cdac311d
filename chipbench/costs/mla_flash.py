"""Operations and bytes of the flash attention kernels where q and k have
one head size and v another (latent attention: 192 and 128), from shapes;
``costs/flash.py`` with the two sizes apart.

Forward: QK^T at ``d_qk`` and PV at ``d_v`` over the causal half; read Q, K,
V, write O and the row log-sum-exp.  Backward (the dk/dv and the dq kernel
together): dV and dP at ``d_v``, dK and dQ at ``d_qk``, four products where
the forward has two; the scores they recompute are not counted.  Bytes are
one pass over each operand in its stored type.  The program pads 192 to 256
lanes inside the kernels' operands; the operations counted here are the
algorithm's, at 192.
"""

from __future__ import annotations


def forward(batch, seq, heads, d_qk, d_v, causal=True, itemsize=2):
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    flops = batch * heads * 2 * (d_qk + d_v) * pairs
    moved = batch * heads * seq * ((2 * d_qk + 2 * d_v) * itemsize + 4)
    return flops, moved


def backward(batch, seq, heads, d_qk, d_v, causal=True, itemsize=2):
    flops = 2 * forward(batch, seq, heads, d_qk, d_v, causal)[0]
    # read Q, K, V, O, dO and the log-sum-exp; write dQ, dK, dV
    moved = batch * heads * seq * ((4 * d_qk + 4 * d_v) * itemsize + 4)
    return flops, moved
