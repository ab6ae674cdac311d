"""Llama family (reference analog: PaddleNLP paddlenlp/transformers/llama —
the modern decoder architecture: RMSNorm pre-norm, rotary position
embeddings, grouped-query attention, SwiGLU MLP, no biases).

TPU-first notes:
- RoPE uses the HF half-split rotate convention so weights interchange
  with the torch/transformers reference bit-for-bit (cross-validated in
  tests/test_text.py).
- GQA K/V heads are repeated to the query head count BEFORE sdpa, so the
  Pallas flash kernel serves the attention (the repeat is a broadcast XLA
  folds into the kernel's K/V loads).
- Projections route through the same column/row-parallel helpers as GPT:
  under a live 'mp' mesh axis the weights shard and the partitioner
  inserts the Megatron collectives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...nn import functional as F
from ...nn.layer import Layer
from ...nn.layers.norm import RMSNorm
from ...ops.paged_attention import paged_cache_attend
from ...tensor.dispatch import apply as _apply
from ...tensor.tensor import Tensor
from .gpt import _col_linear, _row_linear, _vocab_embedding

__all__ = ["LlamaModel", "LlamaForCausalLM", "LlamaConfig"]


class LlamaConfig(dict):
    """Config bag (attribute + dict access, PaddleNLP-style)."""

    def __init__(self, **kw):
        defaults = dict(vocab_size=32000, hidden_size=4096,
                        intermediate_size=11008, num_hidden_layers=32,
                        num_attention_heads=32, num_key_value_heads=None,
                        max_position_embeddings=4096, rms_norm_eps=1e-6,
                        rope_theta=10000.0, tie_word_embeddings=False)
        defaults.update(kw)
        if defaults["num_key_value_heads"] is None:
            defaults["num_key_value_heads"] = defaults["num_attention_heads"]
        super().__init__(**defaults)
        self.__dict__ = self


def _rope_cos_sin(positions, head_dim, theta):
    """[S] or [B, S] int positions -> cos/sin [..., S, head_dim] in the HF
    half-split layout (freqs duplicated across the two halves)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv        # [..., S, d/2]
    ang = jnp.concatenate([ang, ang], axis=-1)                  # [..., S, d]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _apply_rope(q, k, cos, sin):
    """q/k [B, S, h, d]; cos/sin [S, d] or [B, S, d] broadcast over heads."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return q * c + _rotate_half(q) * s, k * c + _rotate_half(k) * s


def _reference_init(layer):
    """HF _init_weights: every >=2D weight N(0, 0.02), preserving any TP
    sharding already laid on the parameter."""
    import jax.random as _jr

    from ...framework import random as _rng

    key = _rng.next_key()
    for _, p in layer.named_parameters():
        if p._value.ndim >= 2:
            key, sub = _jr.split(key)
            new = (0.02 * _jr.normal(sub, p._value.shape, jnp.float32)
                   ).astype(p._value.dtype)
            sh = p._value.sharding
            if hasattr(sh, "spec"):
                new = jax.device_put(new, sh)
            p._value = new


def _projections(linear):
    """``(column, row)``: how a block makes its bias-free projections, each
    called ``(leaf, d_in, d_out)``.  By default the Megatron column / row
    classes with their own initializer; a decoder that hands its leaves
    over one at a time in its served type (``ouro.py``) gives ``linear``,
    one factory for both."""
    if linear is not None:
        return linear, linear
    # llama uses no biases (bias=False reaches the TP classes too)
    return (lambda leaf, i, o: _col_linear(i, o, bias=False),
            lambda leaf, i, o: _row_linear(i, o, bias=False))


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)) — two column-parallel inputs,
    one row-parallel output (Megatron layout)."""

    def __init__(self, hidden_size, intermediate_size, linear=None):
        super().__init__()
        col, row = _projections(linear)
        self.gate_proj = col("gate_proj", hidden_size, intermediate_size)
        self.up_proj = col("up_proj", hidden_size, intermediate_size)
        self.down_proj = row("down_proj", intermediate_size, hidden_size)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaAttention(Layer):
    def __init__(self, config, linear=None):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // self.num_heads
        self.rope_theta = config.rope_theta
        col, row = _projections(linear)
        self.q_proj = col("q_proj", h, self.num_heads * self.head_dim)
        self.k_proj = col("k_proj", h, self.num_kv_heads * self.head_dim)
        self.v_proj = col("v_proj", h, self.num_kv_heads * self.head_dim)
        self.o_proj = row("o_proj", self.num_heads * self.head_dim, h)

    def forward(self, x, rope, attn_bias=None, cache=None):
        B, S = x.shape[0], x.shape[1]
        hd = self.head_dim
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)
        # local head counts from actual widths (TP shards carry h/mp heads)
        hq = q.shape[-1] // hd
        hkv = k.shape[-1] // hd
        rep = hq // hkv

        def attend(qv, kv, vv, cos, sin):
            qh = qv.reshape(B, S, hq, hd)
            kh = kv.reshape(B, S, hkv, hd)
            vh = vv.reshape(B, S, hkv, hd)
            # the rotary tables are float32: rotate there, hand q and k on
            # in the projections' own type (a bfloat16 decoder's hidden
            # state stays bfloat16 behind the attention)
            qr, kr = _apply_rope(qh, kh, cos, sin)
            return qr.astype(qh.dtype), kr.astype(kh.dtype), vh

        qh, kh, vh = _apply(attend, q, k, v, rope[0], rope[1],
                            op_name="llama_rope", n_outs=3)
        if cache is not None and len(cache) == 5:
            # PAGED cache ``(tag, layer, pools, table, lens)``, the serving
            # engine's contract (see GPTDecoderLayer): keys stored
            # pre-rotated like the dense path, pools at hkv heads — grouped
            # attention against them is ops.paged_attention's business, and
            # only a whole prompt's own dense attention repeats K/V.
            if attn_bias is not None:
                raise NotImplementedError(
                    "paged cache + attention_mask: per-sequence padding "
                    "masks belong in per-slot lengths (`ServingEngine`) — "
                    "the uniform generate() paged path does not take a mask")

            def prefill_attend(qh, kh, vh):
                if rep > 1:
                    kh = _apply(lambda t: jnp.repeat(t, rep, axis=2), kh,
                                op_name="gqa_repeat")
                    vh = _apply(lambda t: jnp.repeat(t, rep, axis=2), vh,
                                op_name="gqa_repeat")
                return F.scaled_dot_product_attention(
                    qh, kh, vh, is_causal=True, training=False)

            att, pools = paged_cache_attend(qh, kh, vh, cache, prefill_attend)
            return self.o_proj(att.reshape([B, S, hq * hd])), pools
        if cache is not None:
            # STATIC cache decode (GPT pattern): fixed [B, T, hkv, hd]
            # buffers updated in place at ``pos``; keys stored PRE-ROTATED
            k_buf, v_buf, pos = cache

            def write(buf, new, p):
                # rope math runs in f32; store in the buffer's dtype
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, new.astype(buf.dtype), p, 1)

            k_buf = _apply(write, k_buf, kh, pos, op_name="cache_write")
            v_buf = _apply(write, v_buf, vh, pos, op_name="cache_write")
            T = k_buf.shape[1]

            def expand_and_mask(kb, vb, p, *bias):
                kk, vv2 = kb, vb
                if rep > 1:
                    kk = jnp.repeat(kk, rep, axis=2)
                    vv2 = jnp.repeat(vv2, rep, axis=2)
                i = jnp.arange(S, dtype=jnp.int32)[:, None]
                j = jnp.arange(T, dtype=jnp.int32)[None, :]
                m = jnp.where(j <= p + i, jnp.float32(0.0),
                              jnp.float32(-1e30))[None, None]
                if bias:  # key-side padding bias [B,1,1,T] joins the mask
                    b = bias[0]
                    if b.shape[-1] != T:
                        raise ValueError(
                            f"cache-mode attention_mask must cover all "
                            f"{T} cache slots, got {b.shape[-1]}")
                    m = m + b
                return kk, vv2, m

            mask_args = (k_buf, v_buf, pos) + (
                (attn_bias,) if attn_bias is not None else ())
            kf, vf, mask = _apply(expand_and_mask, *mask_args,
                                  op_name="cache_expand", n_outs=3)
            att = F.scaled_dot_product_attention(qh, kf, vf, attn_mask=mask,
                                                 dropout_p=0.0,
                                                 training=False)
            att = att.reshape([B, S, hq * hd])
            return self.o_proj(att), (k_buf, v_buf, pos)
        if rep > 1:  # GQA: broadcast kv heads up to the q head count
            kh = _apply(lambda t: jnp.repeat(t, rep, axis=2), kh,
                        op_name="gqa_repeat")
            vh = _apply(lambda t: jnp.repeat(t, rep, axis=2), vh,
                        op_name="gqa_repeat")
        if attn_bias is not None:
            att = F.scaled_dot_product_attention(qh, kh, vh,
                                                 attn_mask=attn_bias,
                                                 training=self.training)
        else:
            att = F.scaled_dot_product_attention(qh, kh, vh, is_causal=True,
                                                 training=self.training)
        att = att.reshape([B, S, hq * hd])
        return self.o_proj(att)


class LlamaDecoderLayer(Layer):
    def __init__(self, config):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config.hidden_size, config.intermediate_size)

    def forward(self, x, rope, attn_bias=None, cache=None):
        if cache is not None:
            att, new_cache = self.self_attn(self.input_layernorm(x), rope,
                                            attn_bias, cache)
            x = x + att
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        x = x + self.self_attn(self.input_layernorm(x), rope, attn_bias)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config=None, **kw):
        super().__init__()
        self.config = config if isinstance(config, LlamaConfig) \
            else LlamaConfig(**(config or {}), **kw)
        cfg = self.config
        self.embed_tokens = _vocab_embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = [LlamaDecoderLayer(cfg)
                       for _ in range(cfg.num_hidden_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", l)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        # reference init — the Embedding default N(0,1) would start CE ~8x
        # above ln(V)
        _reference_init(self)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                cache=None):
        x = self.embed_tokens(input_ids)
        S = x.shape[1]
        if position_ids is None:
            position_ids = Tensor(jnp.arange(S, dtype=jnp.int32))
        hd = self.config.hidden_size // self.config.num_attention_heads
        theta = self.config.rope_theta
        # rope tables + padding bias built ONCE and shared by all layers
        cos, sin = _apply(
            lambda pos: _rope_cos_sin(pos, hd, theta), position_ids,
            op_name="rope_tables", n_outs=2)
        bias = None
        if attention_mask is not None:
            if cache is not None:
                # cache mode: the mask covers KEY SLOTS [B, T_cache]; the
                # causal part comes from the cache position mask
                def build_kbias(am):
                    return jnp.where(am.astype(jnp.bool_), 0.0,
                                     -1e30).astype(jnp.float32)[:, None,
                                                                None, :]

                bias = _apply(build_kbias, attention_mask,
                              op_name="llama_key_pad")
            else:
                def build_bias(am):
                    # [B, S] padding mask -> additive causal+pad [B,1,S,S]
                    pad = jnp.where(am.astype(jnp.bool_), 0.0,
                                    -1e30)[:, None, None, :]
                    i = jnp.arange(S)[:, None]
                    j = jnp.arange(S)[None, :]
                    causal = jnp.where(j <= i, 0.0, -1e30)[None, None]
                    return (pad + causal).astype(jnp.float32)

                bias = _apply(build_bias, attention_mask,
                              op_name="llama_mask")
        if isinstance(cache, tuple):
            # ONE paged cache ``(tag, pools, table, lens)`` for all layers
            # (GPTModel.forward): each reads and writes the stacked pools
            # at its own index and hands the tuple on
            pools = cache[1]
            for i, layer in enumerate(self.layers):
                x, pools = layer(x, (cos, sin), bias,
                                 (cache[0], i, pools) + cache[2:])
            return self.norm(x), pools
        if cache is not None:
            new_caches = []
            for layer, c in zip(self.layers, cache):
                x, nc = layer(x, (cos, sin), bias, c)
                new_caches.append(nc)
            return self.norm(x), new_caches
        for layer in self.layers:
            x = layer(x, (cos, sin), bias)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config=None, **kw):
        super().__init__()
        self.llama = LlamaModel(config, **kw)
        cfg = self.llama.config
        self.tie = cfg.tie_word_embeddings
        if not self.tie:
            self.lm_head = _col_linear(cfg.hidden_size, cfg.vocab_size,
                                       bias=False)
            _reference_init(self.lm_head)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                labels=None):
        hidden = self.llama(input_ids, position_ids, attention_mask)
        if self.tie:
            w = self.llama.embed_tokens.weight  # [vocab, hidden]
            logits = _apply(lambda h, wv: h @ wv.T, hidden, w,
                            op_name="matmul")
        else:
            logits = self.lm_head(hidden)
        if labels is not None:
            return F.cross_entropy(
                logits[:, :-1].reshape([-1, logits.shape[-1]]),
                labels[:, 1:].reshape([-1]), reduction="mean")
        return logits

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, top_p=1.0, seed=None, use_cache=True,
                 decode_strategy="sampling", num_beams=4, length_penalty=0.0,
                 eos_token_id=None, cache_impl="dense", page_size=16,
                 max_len=None):
        """Autoregressive decode.

        ``use_cache=True`` (default): jitted two-phase decode — compiled
        prefill writes the prompt K/V into fixed [B, T, hkv, hd] buffers
        (keys stored pre-rotated), then ONE compiled single-token step
        (donated cache, static shapes) runs per new token.  Greedy output
        is identical to the eager loop.  ``use_cache=False``: eager
        full-prefix loop (debug/reference path).

        ``cache_impl="paged"``: block-paged KV pools + the Pallas
        paged-attention kernel; GQA attends grouped against the pools, so
        the kv cache stays at hkv heads in HBM (see GPT.generate)."""
        if decode_strategy == "beam_search":
            from ._decode import beam_search

            return beam_search(self, input_ids, max_new_tokens,
                               num_beams=num_beams,
                               length_penalty=length_penalty,
                               eos_token_id=eos_token_id)
        if not use_cache:
            return self._generate_eager(input_ids, max_new_tokens,
                                        temperature, top_k, top_p, seed)
        if max_new_tokens <= 0:
            return input_ids
        import numpy as np

        ids0 = np.asarray(input_ids.numpy()).astype("int64")
        B, S0 = ids0.shape
        # max_len pre-sizes the cache independently of max_new_tokens (see
        # GPT.generate)
        T = max(S0 + max_new_tokens, max_len or 0)
        cfg = self.llama.config
        if T > cfg.max_position_embeddings:
            raise ValueError(
                f"generate: prompt {S0} + max_new_tokens {max_new_tokens} "
                f"(cache {T}) exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}")
        L = cfg.num_hidden_layers
        hkv = cfg.num_key_value_heads
        hd = cfg.hidden_size // cfg.num_attention_heads

        from ...framework import random as _rng
        from ...framework.state import no_grad_ctx
        from ._decode import jitted_decode

        dt0 = self.llama.embed_tokens.weight._value.dtype
        if cache_impl == "paged":
            from ._decode import decode_loop, paged_cache, paged_pool_shape

            pool = (L,) + paged_pool_shape(B, T, hkv, hd, page_size)

            def fwd_paged(params, bufs, ids, pools, pos):
                with no_grad_ctx(), _rng.rng_scope(jax.random.key(0)), \
                        self.bind(params, bufs):
                    S = ids.shape[1]
                    pos_ids = Tensor(pos + jnp.arange(S, dtype=jnp.int32))
                    hidden, pools = self.llama(
                        Tensor(ids), position_ids=pos_ids,
                        cache=paged_cache(pools, B, pos))
                    h = hidden._value[:, -1].astype(jnp.float32)
                    if self.tie:
                        w = self.llama.embed_tokens.weight._value
                        logits = h @ w.T.astype(jnp.float32)
                    else:
                        logits = h @ self.lm_head.weight._value.astype(jnp.float32)
                return logits, tuple(p._value for p in pools)

            def init_cache():
                kp = jnp.zeros(pool, dt0)
                return kp, jnp.zeros_like(kp)

            return decode_loop(self, fwd_paged, ids0, max_new_tokens,
                               init_cache, temperature=temperature,
                               top_k=top_k, top_p=top_p, seed=seed,
                               program_key=("paged", B, S0, T, page_size,
                                            temperature, top_k, top_p,
                                            bool(self.training)))
        if cache_impl != "dense":
            raise ValueError(f"cache_impl must be 'dense' or 'paged', "
                             f"got {cache_impl!r}")

        def fwd(params, bufs, ids, ks, vs, pos):
            with no_grad_ctx(), _rng.rng_scope(jax.random.key(0)), \
                    self.bind(params, bufs):
                S = ids.shape[1]
                pos_ids = Tensor(pos + jnp.arange(S, dtype=jnp.int32))
                cache = [(Tensor(ks[i]), Tensor(vs[i]), Tensor(pos))
                         for i in range(L)]
                hidden, new_cache = self.llama(Tensor(ids),
                                               position_ids=pos_ids,
                                               cache=cache)
                h = hidden._value[:, -1].astype(jnp.float32)
                if self.tie:
                    w = self.llama.embed_tokens.weight._value
                    logits = h @ w.T.astype(jnp.float32)
                else:
                    logits = h @ self.lm_head.weight._value.astype(jnp.float32)
                ks = jnp.stack([c[0]._value for c in new_cache])
                vs = jnp.stack([c[1]._value for c in new_cache])
            return logits, ks, vs

        dt = self.llama.embed_tokens.weight._value.dtype
        return jitted_decode(self, fwd, ids0, max_new_tokens,
                             (L, B, T, hkv, hd), dt,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, seed=seed)

    def _generate_eager(self, input_ids, max_new_tokens=32, temperature=1.0,
                        top_k=0, top_p=1.0, seed=None):
        """Greedy/sampled decode, eager full-prefix loop (reference path)."""
        import numpy as np

        ids = np.asarray(input_ids.numpy()).astype("int64")
        rs = np.random.RandomState(seed if seed is not None else 0)
        was = [(m, m.training) for m in self.sublayers(include_self=True)]
        self.eval()
        try:
            for _ in range(max_new_tokens):
                logits = self(Tensor(jnp.asarray(ids))).numpy()[:, -1]
                if temperature == 0.0:
                    nxt = logits.argmax(-1)
                else:
                    logits = logits / max(temperature, 1e-6)
                    if top_k:
                        top_k = min(int(top_k), logits.shape[-1])
                        kth = np.sort(logits, -1)[:, -top_k][:, None]
                        logits = np.where(logits < kth, -np.inf, logits)
                    p = np.exp(logits - logits.max(-1, keepdims=True))
                    p = p / p.sum(-1, keepdims=True)
                    if top_p < 1.0:  # nucleus: keep the smallest top set
                        srt = np.argsort(-p, axis=-1)
                        ps = np.take_along_axis(p, srt, -1)
                        keep = np.cumsum(ps, -1) - ps < top_p
                        ps = np.where(keep, ps, 0.0)
                        ps = ps / ps.sum(-1, keepdims=True)
                        pick = np.stack([rs.choice(ps.shape[-1], p=ps[b])
                                         for b in range(ps.shape[0])])
                        nxt = np.take_along_axis(srt, pick[:, None], -1)[:, 0]
                    else:
                        nxt = np.stack([rs.choice(p.shape[-1], p=p[b])
                                        for b in range(p.shape[0])])
                ids = np.concatenate([ids, nxt[:, None]], axis=1)
        finally:
            for m, t in was:
                m.training = t
        return Tensor(jnp.asarray(ids))
