"""Ring attention — context parallelism over the ICI ring (SURVEY.md §5.7
item 3, the flagship TPU-idiomatic component; reference analog: PaddleNLP's
ring_flash_attention built on p2p send/recv groups).

Design: q/k/v are sharded along the SEQUENCE dim across the mesh axis.
Inside a shard_map, each device holds one sequence block; K/V blocks rotate
one hop per step with ``lax.ppermute`` (the ICI ring IS the communication
pattern).  Each step computes its local block attention with the PALLAS
flash kernel (``flash_attention_with_lse`` — the S_loc x S_loc score matrix
never materializes, fixing the round-2 weakness where the per-chip block
was a naive quadratic einsum) and merges blocks with the exact logsumexp
rule: ``out = out*exp(lse - lse') + o_s*exp(lse_s - lse')``.  Causal
masking is exact across ring steps — each step's K/V block is globally
before (full), at (diagonal flash-causal), or after (skipped via
``lax.switch``) the local q block.  Backward is AD-derived: ppermute
transposes to the reverse rotation and the flash primitive carries a custom
VJP that is differentiable in BOTH (o, lse).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .flash_attention import MIN_BLOCK, flash_attention_with_lse

NEG_INF = -1e30


def _block_attn(qf, kf, vf, scale, causal):
    """[BH, S, D] f32 block attention -> (o [BH,S,D] f32, lse [BH,S,1] f32).

    Routes to the Pallas flash kernel when the block shape allows; otherwise
    an einsum with explicit logsumexp (exact same contract)."""
    s_q, s_k = qf.shape[1], kf.shape[1]
    if (jax.default_backend() == "tpu" and s_q >= 2 * MIN_BLOCK
            and s_q % MIN_BLOCK == 0 and s_k % MIN_BLOCK == 0
            and qf.shape[-1] <= 256):
        o, lse = flash_attention_with_lse(qf, kf, vf, scale, causal)
        return o.astype(jnp.float32), lse
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * jnp.float32(scale)
    if causal:
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        s = jnp.where(mask[None], s, jnp.float32(NEG_INF))
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bqk,bkd->bqd", p, vf) / jnp.maximum(l, 1e-30)
    return o, m + jnp.log(jnp.maximum(l, 1e-30))


def _ring_body(q, k, v, axis, scale, causal):
    """Per-device body: q,k,v local [B, S_loc, H, D]."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    b, s_loc, h, d = q.shape

    def bhsd(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], d)

    qf = bhsd(q).astype(jnp.float32)
    out = jnp.zeros_like(qf)
    lse = jnp.full((b * h, s_loc, 1), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    kv = (k, v)
    for step in range(n):
        src = (idx - step) % n  # whose K/V block we hold this step
        kc, vc = kv
        kf = bhsd(kc).astype(jnp.float32)
        vf = bhsd(vc).astype(jnp.float32)

        if causal:
            def past(q_, k_, v_):
                return _block_attn(q_, k_, v_, scale, causal=False)

            def diag(q_, k_, v_):
                return _block_attn(q_, k_, v_, scale, causal=True)

            def future(q_, k_, v_):
                return (jnp.zeros_like(q_),
                        jnp.full((q_.shape[0], q_.shape[1], 1), NEG_INF,
                                 jnp.float32))

            case = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
            o_s, lse_s = lax.switch(case, (past, diag, future), qf, kf, vf)
        else:
            o_s, lse_s = _block_attn(qf, kf, vf, scale, causal=False)

        new_lse = jnp.logaddexp(lse, lse_s)
        out = out * jnp.exp(lse - new_lse) + o_s * jnp.exp(lse_s - new_lse)
        lse = new_lse
        if step + 1 < n:
            kv = lax.ppermute(kv, axis, perm)

    out = out.reshape(b, h, s_loc, d)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # [B, S, H, D]


def ring_attention_fn(q, k, v, mesh, axis="sep", scale=None, causal=False):
    """Raw-array ring attention.

    q, k, v: [B, S, H, D] global; S is laid out over ``axis`` (S % axis_size
    == 0).  Returns [B, S, H, D] with the same layout.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    spec = P(None, axis)

    def body(q_l, k_l, v_l):
        return _ring_body(q_l, k_l, v_l, axis, scale, causal)

    mapped = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    return mapped(q, k, v)
