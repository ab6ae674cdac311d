"""Attention functionals.

``scaled_dot_product_attention`` is the hot op: on TPU it routes to the
Pallas flash-attention kernel in ``paddle_tpu.ops.flash_attention`` when
shapes allow (seq multiple of block, head_dim <= 256); otherwise falls back
to the jnp composition, which XLA still fuses well.
(reference: paddle/nn/functional/fused attention front-ends in incubate/.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...tensor.dispatch import apply, unwrap


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale, training):
    # q,k,v: (B, S, H, D) — paddle layout
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    qT = jnp.swapaxes(q, 1, 2)  # (B,H,S,D)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p and training:
        from ...framework import random as _rng

        keep = jax.random.bernoulli(_rng.next_key(), 1 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1 - dropout_p), 0.0).astype(probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vT)
    return jnp.swapaxes(out, 1, 2)  # back to (B,S,H,D)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None, name=None):
    """paddle layout: (batch, seq, num_heads, head_dim)."""
    use_flash = False
    qv = unwrap(query)
    kv_ = unwrap(key)
    # Context parallelism: when the job's hybrid mesh carries a live sep
    # axis, long self-attention routes through ring attention (sequence
    # sharded over the ICI ring, flash kernel per block) automatically.
    if attn_mask is None and dropout_p == 0.0 and qv.ndim == 4:
        try:
            from ...distributed.topology import get_hybrid_communicate_group

            hcg = get_hybrid_communicate_group()
            sep = hcg.get_sep_parallel_world_size() if hcg is not None else 1
        except Exception:
            sep = 1
        if sep > 1:
            # already inside a manual 'sep' region (SEP utils / shard_map)?
            # then that code owns the distribution — don't nest.
            try:
                jax.lax.axis_index("sep")  # raises when 'sep' is unbound
                sep = 1
            except Exception:
                pass
        if (sep > 1 and kv_.shape == qv.shape and qv.shape[1] % sep == 0):
            from ...ops.ring_attention import ring_attention_fn

            def ring_fn(q, k, v):
                return ring_attention_fn(q, k, v, hcg.mesh, axis="sep",
                                         scale=scale, causal=is_causal)

            return apply(ring_fn, query, key, value, op_name="ring_attention")
    if (attn_mask is None and dropout_p == 0.0 and qv.ndim == 4):
        from ...ops.flash_attention import supported

        use_flash = supported(qv.shape, kv_.shape, is_causal)
    if use_flash:
        from ...ops.flash_attention import flash_attention_bshd

        def fn(q, k, v):
            return flash_attention_bshd(q, k, v, causal=is_causal, scale=scale)

        return apply(fn, query, key, value, op_name="flash_attention")

    def fn(q, k, v, *m):
        return _sdpa_ref(q, k, v, m[0] if m else None, dropout_p, is_causal, scale, training)

    args = (query, key, value) if attn_mask is None else (query, key, value, attn_mask)
    return apply(fn, *args, op_name="scaled_dot_product_attention")
