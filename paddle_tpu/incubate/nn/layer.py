"""incubate fused layers (reference: python/paddle/incubate/nn/layer/
fused_transformer.py)."""

from __future__ import annotations

import math

from ...nn.layer import Layer, LayerList
from ...nn import initializer as I
from ...tensor.tensor import Parameter
from . import functional as FF


class FusedMultiHeadAttention(Layer):
    def __init__(self, embed_dim, num_heads, dropout_rate=0.5,
                 attn_dropout_rate=0.5, kdim=None, vdim=None, normalize_before=False,
                 need_weights=False, qkv_weight_attr=None, qkv_bias_attr=None,
                 linear_weight_attr=None, linear_bias_attr=None,
                 pre_ln_scale_attr=None, pre_ln_bias_attr=None, ln_scale_attr=None,
                 ln_bias_attr=None, epsilon=1e-5, nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        self.dropout_rate = dropout_rate
        self.attn_dropout_rate = attn_dropout_rate
        self._epsilon = epsilon
        self.qkv_weight = self.create_parameter(
            [3, num_heads, self.head_dim, embed_dim], attr=qkv_weight_attr,
            default_initializer=I.XavierUniform())
        self.qkv_bias = self.create_parameter(
            [3, num_heads, self.head_dim], attr=qkv_bias_attr, is_bias=True)
        self.linear_weight = self.create_parameter(
            [embed_dim, embed_dim], attr=linear_weight_attr,
            default_initializer=I.XavierUniform())
        self.linear_bias = self.create_parameter([embed_dim], attr=linear_bias_attr,
                                                 is_bias=True)
        self.pre_ln_scale = self.create_parameter(
            [embed_dim], attr=pre_ln_scale_attr, default_initializer=I.Constant(1.0))
        self.pre_ln_bias = self.create_parameter([embed_dim], attr=pre_ln_bias_attr,
                                                 is_bias=True)
        self.ln_scale = self.create_parameter(
            [embed_dim], attr=ln_scale_attr, default_initializer=I.Constant(1.0))
        self.ln_bias = self.create_parameter([embed_dim], attr=ln_bias_attr,
                                             is_bias=True)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        return FF.fused_multi_head_attention(
            query, self.qkv_weight, self.linear_weight,
            pre_layer_norm=self.normalize_before,
            pre_ln_scale=self.pre_ln_scale, pre_ln_bias=self.pre_ln_bias,
            ln_scale=self.ln_scale, ln_bias=self.ln_bias,
            pre_ln_epsilon=self._epsilon, qkv_bias=self.qkv_bias,
            linear_bias=self.linear_bias, attn_mask=attn_mask,
            dropout_rate=self.dropout_rate,
            attn_dropout_rate=self.attn_dropout_rate,
            ln_epsilon=self._epsilon, training=self.training,
            num_heads=self.num_heads)


class FusedFeedForward(Layer):
    def __init__(self, d_model, dim_feedforward, dropout_rate=0.1, epsilon=1e-5,
                 activation="relu", act_dropout_rate=None, normalize_before=False,
                 linear1_weight_attr=None, linear1_bias_attr=None,
                 linear2_weight_attr=None, linear2_bias_attr=None,
                 ln1_scale_attr=None, ln1_bias_attr=None, ln2_scale_attr=None,
                 ln2_bias_attr=None, nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.activation = activation
        self.dropout_rate = dropout_rate
        self.act_dropout_rate = act_dropout_rate if act_dropout_rate is not None \
            else dropout_rate
        self._epsilon = epsilon
        bound = 1.0 / math.sqrt(d_model)
        self.linear1_weight = self.create_parameter(
            [d_model, dim_feedforward], attr=linear1_weight_attr,
            default_initializer=I.Uniform(-bound, bound))
        self.linear1_bias = self.create_parameter([dim_feedforward],
                                                  attr=linear1_bias_attr, is_bias=True)
        self.linear2_weight = self.create_parameter(
            [dim_feedforward, d_model], attr=linear2_weight_attr,
            default_initializer=I.Uniform(-bound, bound))
        self.linear2_bias = self.create_parameter([d_model], attr=linear2_bias_attr,
                                                  is_bias=True)
        self.ln1_scale = self.create_parameter([d_model], attr=ln1_scale_attr,
                                               default_initializer=I.Constant(1.0))
        self.ln1_bias = self.create_parameter([d_model], attr=ln1_bias_attr,
                                              is_bias=True)
        self.ln2_scale = self.create_parameter([d_model], attr=ln2_scale_attr,
                                               default_initializer=I.Constant(1.0))
        self.ln2_bias = self.create_parameter([d_model], attr=ln2_bias_attr,
                                              is_bias=True)

    def forward(self, src, cache=None):
        return FF.fused_feedforward(
            src, self.linear1_weight, self.linear2_weight,
            linear1_bias=self.linear1_bias, linear2_bias=self.linear2_bias,
            ln1_scale=self.ln1_scale, ln1_bias=self.ln1_bias,
            ln2_scale=self.ln2_scale, ln2_bias=self.ln2_bias,
            dropout1_rate=self.act_dropout_rate, dropout2_rate=self.dropout_rate,
            activation=self.activation, ln1_epsilon=self._epsilon,
            ln2_epsilon=self._epsilon, pre_layer_norm=self.normalize_before,
            training=self.training)


class FusedLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None, bias_attr=None,
                 transpose_weight=False, name=None):
        super().__init__()
        self.transpose_weight = transpose_weight
        shape = [out_features, in_features] if transpose_weight else \
            [in_features, out_features]
        self.weight = self.create_parameter(shape, attr=weight_attr)
        self.bias = self.create_parameter([out_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return FF.fused_linear(x, self.weight, self.bias, self.transpose_weight)


class FusedTransformerEncoderLayer(Layer):
    """reference: paddle.incubate.nn.FusedTransformerEncoderLayer — one
    encoder block over the fused attention/ffn front-ends (the fusion
    itself is XLA's; this class keeps the reference's constructor and
    state_dict shape)."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout_rate=0.1,
                 activation="relu", attn_dropout_rate=None,
                 act_dropout_rate=None, normalize_before=False,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.fused_attn = FusedMultiHeadAttention(
            d_model, nhead,
            dropout_rate=dropout_rate,
            attn_dropout_rate=(attn_dropout_rate if attn_dropout_rate
                               is not None else dropout_rate),
            normalize_before=normalize_before,
            qkv_weight_attr=weight_attr, qkv_bias_attr=bias_attr,
            linear_weight_attr=weight_attr, linear_bias_attr=bias_attr)
        self.ffn = FusedFeedForward(
            d_model, dim_feedforward, dropout_rate=dropout_rate,
            activation=activation,
            act_dropout_rate=(act_dropout_rate if act_dropout_rate
                              is not None else dropout_rate),
            normalize_before=normalize_before,
            linear1_weight_attr=weight_attr, linear1_bias_attr=bias_attr,
            linear2_weight_attr=weight_attr, linear2_bias_attr=bias_attr)

    def forward(self, src, src_mask=None, cache=None):
        if cache is not None:
            raise NotImplementedError(
                "FusedTransformerEncoderLayer cache (incremental decoding) "
                "is not supported; use nn.TransformerEncoderLayer's cache "
                "path")
        out = self.fused_attn(src, attn_mask=src_mask)
        return self.ffn(out)


class FusedMultiTransformer(Layer):
    """reference: paddle.incubate.nn.FusedMultiTransformer — the serving
    decoder stack (pre-LN self-attention + FFN per layer) with static
    KV caches written at ``time_step`` for incremental decoding.

    TPU-native: caches are fixed-shape [B, max_len, H, D] buffers updated
    with dynamic_update_slice (one compiled decode step serves every
    position), and the whole stack is one traced program — the reference's
    single-CUDA-kernel fusion is XLA's fusion here.
    """

    def __init__(self, embed_dim, num_heads, dim_feedforward, dropout_rate=0.0,
                 activation="gelu", normalize_before=True, num_layers=1,
                 nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.dropout_rate = dropout_rate
        self.activation = activation
        self.num_layers = num_layers
        self.normalize_before = normalize_before
        self.layers = LayerList([
            _FusedMTBlock(embed_dim, num_heads, dim_feedforward,
                          dropout_rate, activation, normalize_before)
            for _ in range(num_layers)])

    def gen_cache(self, batch_size, max_length, dtype=None, impl="dense",
                  page_size=16):
        """Per-layer KV cache buffers.

        dtype defaults to the MODEL's compute dtype (r4 weak #8: f32-only
        caches doubled serving HBM for bf16 models — bf16 caches halve the
        KV footprint and the attention math still runs its softmax in f32).

        impl="paged": block-paged pools [B, PP, page, H, D] instead of the
        dense [B, max_length] rectangle, each entry ``(tag, k_pages,
        v_pages)`` — decode attention runs the Pallas scalar-prefetch paged
        kernel and serving HBM is bounded by pages
        (ceil(max_length/page_size) per sequence), the property the
        reference's paged engine exists for.
        """
        import jax.numpy as jnp

        from ...tensor.tensor import Tensor

        if dtype is None:
            dtype = self.layers[0].qkv.weight._value.dtype
        if impl == "paged":
            pp = -(-max_length // page_size)
            shape = (batch_size, pp, page_size, self.num_heads, self.head_dim)
            return [("served", Tensor(jnp.zeros(shape, dtype)),
                     Tensor(jnp.zeros(shape, dtype)))
                    for _ in range(self.num_layers)]
        if impl != "dense":
            raise ValueError(f"impl must be 'dense' or 'paged', got {impl!r}")
        shape = (batch_size, max_length, self.num_heads, self.head_dim)
        return [(Tensor(jnp.zeros(shape, dtype)),
                 Tensor(jnp.zeros(shape, dtype)))
                for _ in range(self.num_layers)]

    def forward(self, src, attn_mask=None, caches=None, time_step=None):
        new_caches = []
        out = src
        for i, blk in enumerate(self.layers):
            cache = caches[i] if caches is not None else None
            out, new_cache = blk(out, attn_mask, cache, time_step)
            new_caches.append(new_cache)
        if caches is not None:
            return out, new_caches
        return out


class _FusedMTBlock(Layer):
    def __init__(self, embed_dim, num_heads, dim_feedforward, dropout_rate,
                 activation, normalize_before=True):
        super().__init__()
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        from ...nn import LayerNorm

        self.ln1 = LayerNorm(embed_dim)
        self.qkv = FusedLinear(embed_dim, 3 * embed_dim)
        self.out_proj = FusedLinear(embed_dim, embed_dim)
        self.ln2 = LayerNorm(embed_dim)
        self.fc1 = FusedLinear(embed_dim, dim_feedforward)
        self.fc2 = FusedLinear(dim_feedforward, embed_dim)
        self.dropout_rate = dropout_rate
        self.activation = activation

    def forward(self, src, attn_mask, cache, time_step):
        from ...nn import functional as F
        from ...tensor.dispatch import apply
        import jax
        import jax.numpy as jnp

        # pre-LN: h = attn(ln1(src)); src += h  (reference serving default)
        # post-LN: src = ln1(src + attn(src))   (r4 weak #8: was refused)
        h = self.ln1(src) if self.normalize_before else src
        B, T = h.shape[0], h.shape[1]
        qkv = self.qkv(h).reshape([B, T, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        new_cache = None
        if cache is not None and len(cache) == 3:
            # PAGED serving cache (gen_cache(impl="paged")): this layer's
            # pages [B, PP, ps, H, D] on the serving engine's cache contract
            # (ops/paged_attention) as a pool of one layer, an identity page
            # table, every length ``time_step``.  Prefill must start at
            # time_step 0; continuation chunks need the dense cache.
            from ...ops.paged_attention import paged_cache_attend
            from ...tensor.tensor import Tensor

            if time_step is None:
                raise ValueError("caches need time_step (decode position)")
            if attn_mask is not None:
                raise NotImplementedError(
                    "paged FusedMultiTransformer caches do not take an "
                    "attn_mask; per-sequence lengths belong in per-slot "
                    "lengths (`ServingEngine`)")
            if T > 1:
                ts_val = getattr(time_step, "_value", time_step)
                try:
                    if int(ts_val) != 0:
                        raise ValueError(
                            "paged prefill must start at time_step 0; use "
                            "the dense cache for continuation chunks")
                except TypeError:
                    pass  # traced: the caller's contract
            tag, kp, vp = cache
            paged = list(kp.shape)                      # [B, PP, ps, H, D]
            one_layer = [1, B * paged[1]] + paged[2:]
            table = Tensor(jnp.arange(B * paged[1], dtype=jnp.int32)
                           .reshape(B, paged[1]))
            lens = apply(
                lambda t_: jnp.full((B,), t_.astype(jnp.int32).reshape(())),
                time_step, op_name="paged_lens")
            o, (kp, vp) = paged_cache_attend(
                q, k, v,
                (tag, 0, (kp.reshape(one_layer), vp.reshape(one_layer)),
                 table, lens),
                lambda q, k, v: F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, training=False))
            new_cache = (tag, kp.reshape(paged), vp.reshape(paged))
        elif cache is not None:
            ck, cv = cache
            if time_step is None:
                raise ValueError("caches need time_step (decode position)")
            ts_val = getattr(time_step, "_value", time_step)
            if not hasattr(ts_val, "aval") or not hasattr(
                    ts_val.aval, "weak_type") or hasattr(ts_val, "item"):
                try:  # eager: catch silent overwrite past the cache end
                    if int(ts_val) + T > ck.shape[1]:
                        raise ValueError(
                            f"decode position {int(ts_val)}+{T} exceeds "
                            f"cache max_length {ck.shape[1]}")
                except TypeError:
                    pass  # traced value: bounds are the caller's contract

            def upd(buf, new):
                def fn(b_, n_, t_):
                    t_ = t_.astype(jnp.int32).reshape(())
                    zero = jnp.zeros((), jnp.int32)
                    return jax.lax.dynamic_update_slice(
                        b_, n_.astype(b_.dtype), (zero, t_, zero, zero))

                return apply(fn, buf, new, time_step, op_name="cache_update")

            ck = upd(ck, k)
            cv = upd(cv, v)
            new_cache = (ck, cv)
            # attend over the cache prefix [0, time_step + T)
            k_all, v_all = ck, cv
            L = k_all.shape[1]

            def masked_attn(qq, kk, vv, ts, *mask):
                # [B, T, H, D] x [B, L, H, D]; causal WITHIN the new-token
                # window too (prefill with T>1 must not see its own future)
                s = jnp.einsum("bthd,blhd->bhtl", qq, kk) \
                    / jnp.sqrt(jnp.float32(qq.shape[-1]))
                pos = jnp.arange(L)[None, None, None, :]
                tq = jnp.arange(T)[None, None, :, None]
                limit = ts.astype(jnp.int32) + 1 + tq
                s = jnp.where(pos < limit, s, -1e30)
                if mask:
                    s = s + mask[0].astype(jnp.float32)
                p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(qq.dtype)
                return jnp.einsum("bhtl,blhd->bthd", p, vv)

            attn_args = (q, k_all, v_all, time_step) \
                if attn_mask is None else (q, k_all, v_all, time_step,
                                           attn_mask)
            o = apply(masked_attn, *attn_args,
                      op_name="fused_mt_cached_attn")
        else:
            o = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                               is_causal=attn_mask is None,
                                               training=self.training)
        o = self.out_proj(o.reshape([B, T, -1]))
        if self.dropout_rate and self.training:
            o = F.dropout(o, p=self.dropout_rate, training=True)
        src = src + o
        if not self.normalize_before:
            src = self.ln1(src)
        h2 = self.fc1(self.ln2(src) if self.normalize_before else src)
        h2 = self.fc2(getattr(F, self.activation)(h2))
        if self.dropout_rate and self.training:
            h2 = F.dropout(h2, p=self.dropout_rate, training=True)
        out = src + h2
        if not self.normalize_before:
            out = self.ln2(out)
        return out, new_cache

