"""SmallThinker family (public ``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct``): a decoder whose attention
layers are, by ``sliding_window_layout``, SLIDING-WINDOW layers with rotary
positions or FULL layers with no positions at all (NoPE), each followed by
64 softmax-routed ReGLU experts (top 6, no shared expert) whose router reads
the attention's input, before attention runs.  RMSNorm pre-norm, no bias,
an untied head.

With ``n(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``, layer ``l`` of
kind ``s = sliding_window_layout[l]`` (``rope_layout[l]``, the same list
where published)::

    a      = n_1(x)
    (E, w) = top_k(softmax(a W_r)) in float32, w renormalised over the k
    h      = x + attn_s(a)
    b      = n_2(h)
    out    = h + sum_{e in E} w_e W_down_e(relu(b W_gate_e) * (b W_up_e))

and one more RMSNorm (``norm``) after the last layer, then the head.

- ``attn_1``: grouped-query attention, rotary positions (``llama.py``'s
  halves, ``rope_theta``) on q and k, key j visible to query i iff ``i -
  sliding_window_size < j <= i``;
- ``attn_0``: the same with no rotary positions and every earlier key
  visible.

The router runs under the scope ``moe_route`` before the attention, which
runs under ``swa_attention`` (window layers) or ``global_attention`` (full
layers), then the experts under ``moe_experts``.

Serving: ``forward(..., cache=)`` is what ``serving.adapter
.StatedCacheAdapter`` drives.  ``serving_caches`` states the full layers,
the window layers and the window; the server then keeps the window layers'
pages in a pool of their own, which a sequence reuses behind its window,
and hands the decoder both pools and both tables: ``cache=(tag, (kp, vp,
kw, vw), table [B, 2 * NP], lens)``, the full layers' table first.
Nothing of ``serving`` is imported here.

Memory: every leaf is made in ``config.dtype`` by ``param_init(name,
shape)`` (default: drawn on the device from ``(config.seed, name)``), one at
a time, as ``lfm2.py`` does.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from ...distributed.fleet.meta_parallel.moe import DroplessMoELayer
from ...framework import dtypes as _dt
from ...nn.layer import Layer
from ...nn.layers.common import Embedding, Linear
from ...nn.layers.norm import RMSNorm
from ...nn.param_attr import ParamAttr
from ...ops.paged_attention import NEG_INF, paged_cache_attend
from ...tensor.dispatch import apply as _apply
from ...tensor.tensor import Tensor
from .llama import _apply_rope, _rope_cos_sin

__all__ = ["SmallThinkerConfig", "SmallThinkerModel",
           "SmallThinkerForCausalLM"]

_PERIOD = (0, 1, 1, 1)


class SmallThinkerConfig(dict):
    """Config bag (attribute + dict access); the keys are the published
    ``config.json``'s, the defaults SmallThinker-21BA3B's.  Not published
    and set here: ``initializer_range``, ``dtype`` and ``seed`` (what the
    default ``param_init`` draws in and from)."""

    def __init__(self, **kw):
        defaults = dict(
            vocab_size=151936, hidden_size=2560, num_hidden_layers=52,
            num_attention_heads=28, num_key_value_heads=4, head_dim=128,
            moe_ffn_hidden_size=768, moe_num_primary_experts=64,
            moe_num_active_primary_experts=6,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True,
            rms_norm_eps=1e-6, rope_theta=1.5e6, rope_layout=None,
            sliding_window_layout=None, sliding_window_size=4096,
            max_position_embeddings=16384, tie_word_embeddings=False,
            initializer_range=0.02, dtype="float32", seed=0)
        defaults.update(kw)
        n = defaults["num_hidden_layers"]
        for key in ("rope_layout", "sliding_window_layout"):
            if defaults[key] is None:
                defaults[key] = [_PERIOD[i % 4] for i in range(n)]
            if len(defaults[key]) != n:
                raise ValueError(f"{key} names {len(defaults[key])} layers, "
                                 f"num_hidden_layers is {n}")
        if not defaults["moe_primary_router_apply_softmax"]:
            raise NotImplementedError("only the softmax router is built")
        if defaults["tie_word_embeddings"]:
            raise NotImplementedError("the published head is untied")
        super().__init__(**defaults)
        self.__dict__ = self


def seeded_init(config):
    """The default ``param_init``: matrices ``normal(0,
    initializer_range)``, norm gains ones, drawn on the device from
    ``(config.seed, leaf name)`` in ``config.dtype``."""
    dtype = _dt.to_jax(config.dtype)
    std = float(config.initializer_range)
    root = jax.random.key(int(config.seed) & 0x7FFFFFFF)

    def make(name, shape):
        if name.endswith("norm.weight") or name.endswith("layernorm.weight"):
            return jnp.ones(shape, dtype)
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    return make


def _attr(make, name):
    return ParamAttr(initializer=lambda shape, dtype=None: make(name, shape))


def _linear(make, name, d_in, d_out):
    return Linear(d_in, d_out, bias_attr=False,
                  weight_attr=_attr(make, name + ".weight"))


def _norm(make, name, width, eps):
    return RMSNorm(width, epsilon=eps,
                   weight_attr=_attr(make, name + ".weight"))


def dense_attend(q, k, v, window=None):
    """Causal attention of q ``[B, S, H, D]`` over k / v ``[B, S, HKV, D]``
    (query head h reads KV head h // (H // HKV)), keys ``i - window < j <=
    i`` with a window, ``j <= i`` without; float32 scores."""
    B, S, H, D = q.shape
    HKV = k.shape[2]
    qg = q.reshape(B, S, HKV, H // HKV, D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k.astype(jnp.float32)) \
        * jnp.float32(D ** -0.5)
    i = jnp.arange(S, dtype=jnp.int32)[:, None]
    j = jnp.arange(S, dtype=jnp.int32)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    p = jax.nn.softmax(jnp.where(seen, s, jnp.float32(NEG_INF)), axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


class SmallThinkerAttention(Layer):
    """Grouped-query attention; rotary positions and a window by layer."""

    def __init__(self, config, make, name, rotary, window):
        super().__init__()
        c = config
        h, hd = c.hidden_size, c.head_dim
        self.num_heads, self.num_kv_heads = (c.num_attention_heads,
                                             c.num_key_value_heads)
        self.head_dim, self.rotary, self.window = hd, rotary, window
        self.q_proj = _linear(make, name + ".q_proj", h, self.num_heads * hd)
        self.k_proj = _linear(make, name + ".k_proj", h,
                              self.num_kv_heads * hd)
        self.v_proj = _linear(make, name + ".v_proj", h,
                              self.num_kv_heads * hd)
        self.o_proj = _linear(make, name + ".o_proj", self.num_heads * hd, h)

    def forward(self, x, rope, cache=None):
        """``cache``: ``None`` (dense causal attention over ``x``) or the
        paged cache ``(tag, rank, (kp, vp), table, lens)`` of this layer's
        kind; then ``(y, pools)`` comes back."""
        scope = "swa_attention" if self.window is not None \
            else "global_attention"
        with jax.named_scope(scope):
            B, S = x.shape[0], x.shape[1]
            hq, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
            q = self.q_proj(x).reshape([B, S, hq, hd])
            k = self.k_proj(x).reshape([B, S, hkv, hd])
            v = self.v_proj(x).reshape([B, S, hkv, hd])
            if self.rotary:
                def rotate(qh, kh, cos, sin):
                    qr, kr = _apply_rope(qh, kh, cos, sin)
                    return qr.astype(qh.dtype), kr.astype(kh.dtype)

                # no op_name: autocast would round the float32 tables
                q, k = _apply(rotate, q, k, rope[0], rope[1], n_outs=2)
            window = self.window

            def dense(qh, kh, vh):
                return _apply(lambda a, b, c: dense_attend(a, b, c, window),
                              qh, kh, vh)

            if cache is None:
                return self.o_proj(dense(q, k, v).reshape([B, S, hq * hd]))
            att, pools = paged_cache_attend(q, k, v, cache, dense,
                                            window=window)
            return self.o_proj(att.reshape([B, S, hq * hd])), pools


class SmallThinkerDecoderLayer(Layer):
    def __init__(self, config, layer_idx, make, name):
        super().__init__()
        c = config
        self.windowed = bool(c.sliding_window_layout[layer_idx])
        #: this layer's index among the layers of its kind: where its
        #: pages lie in its pool
        self.rank = sum(bool(t) == self.windowed
                        for t in c.sliding_window_layout[:layer_idx])
        self.input_layernorm = _norm(make, name + ".input_layernorm",
                                     c.hidden_size, c.rms_norm_eps)
        self.self_attn = SmallThinkerAttention(
            c, make, name + ".self_attn",
            rotary=bool(c.rope_layout[layer_idx]),
            window=int(c.sliding_window_size) if self.windowed else None)
        self.post_attention_layernorm = _norm(
            make, name + ".post_attention_layernorm", c.hidden_size,
            c.rms_norm_eps)
        ff = name + ".block_sparse_moe."
        self.block_sparse_moe = DroplessMoELayer(
            c.hidden_size, c.moe_ffn_hidden_size, c.moe_num_primary_experts,
            c.moe_num_active_primary_experts, norm_topk_prob=c.norm_topk_prob,
            initializer_range=c.initializer_range,
            param_init=lambda leaf, shape: make(ff + leaf, shape),
            router="softmax", activation="relu")

    def forward(self, x, rope, cache=None):
        """``(x, the pools a paged cache hands on, or None)``."""
        a = self.input_layernorm(x)
        # the router reads the attention's input, before the attention
        routing = self.block_sparse_moe.route(a)
        if cache is None:
            att, pools = self.self_attn(a, rope), None
        else:
            att, pools = self.self_attn(a, rope, cache)
        h = x + att
        b = self.post_attention_layernorm(h)
        if self.training:
            return h + self.block_sparse_moe(b, routing=routing), pools
        # evaluation (and every served program): no buffer is touched
        y, _ = self.block_sparse_moe(b, return_load=True, routing=routing)
        return h + y, pools


class SmallThinkerModel(Layer):
    def __init__(self, config=None, param_init=None, **kw):
        super().__init__()
        self.config = config if isinstance(config, SmallThinkerConfig) \
            else SmallThinkerConfig(**(config or {}), **kw)
        c = self.config
        make = param_init or seeded_init(c)
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size,
            weight_attr=_attr(make, "model.embed_tokens.weight"))
        self.layers = [SmallThinkerDecoderLayer(c, i, make,
                                                f"model.layers.{i}")
                       for i in range(c.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.norm = _norm(make, "model.norm", c.hidden_size, c.rms_norm_eps)

    @property
    def num_window_layers(self):
        return sum(layer.windowed for layer in self.layers)

    def forward(self, input_ids, position_ids=None, cache=None):
        """Hidden states ``[B, S, H]`` after ``norm``; served (``cache``
        given: ``(tag, pools, table, lens)``), ``(hidden, pools)``.  With
        window layers the pools are ``(kp, vp, kw, vw)`` and the table's
        row is the full layers' ``NP`` entries, then the window layers'."""
        x = self.embed_tokens(input_ids)
        if position_ids is None:
            position_ids = Tensor(jnp.arange(x.shape[1], dtype=jnp.int32))
        c = self.config
        rope = _apply(lambda pos: _rope_cos_sin(pos, c.head_dim,
                                                c.rope_theta),
                      position_ids, op_name="rope_tables", n_outs=2)
        kinds = {}
        if cache is not None:
            tag, pools, table, lens = cache
            if self.num_window_layers:
                half = table.shape[1] // 2
                kinds[False] = [tuple(pools[:2]), _apply(
                    lambda t: t[:, :half], table)]
                kinds[True] = [tuple(pools[2:]), _apply(
                    lambda t: t[:, half:], table)]
            else:
                kinds[False] = [tuple(pools), table]
        for layer in self.layers:
            if cache is None:
                x, _ = layer(x, rope)
                continue
            kind = kinds[layer.windowed]
            x, kind[0] = layer(x, rope, cache=(tag, layer.rank, kind[0],
                                               kind[1], lens))
        x = self.norm(x)
        if cache is None:
            return x
        held = kinds[False][0] + (kinds[True][0] if True in kinds else ())
        return x, held


class SmallThinkerForCausalLM(Layer):
    """The decoder with its own head; the mean next-token loss when given
    ``labels``, logits otherwise."""

    def __init__(self, config=None, param_init=None, **kw):
        super().__init__()
        self.model = SmallThinkerModel(config, param_init=param_init, **kw)
        c = self.model.config
        make = param_init or seeded_init(c)
        self.lm_head = _linear(make, "lm_head", c.hidden_size, c.vocab_size)

    @property
    def config(self):
        return self.model.config

    def head_weight(self):
        """The head's matrix as ``[vocab, hidden]``."""
        return _apply(lambda w: w.T, self.lm_head.weight)

    def serving_caches(self):
        """What a server has to hold for ONE sequence, all
        ``serving.adapter.StatedCacheAdapter`` reads of its sizes: pages
        for the full layers' grouped heads, and for the ``window_layers``,
        which see their last ``window`` keys only, pages of a pool of
        their own.  ``ServingEngine(model)`` picks its adapter by this
        method's presence; no flag is asked."""
        c, dec = self.config, self.model
        windowed = dec.num_window_layers
        return {"attention_layers": len(dec.layers) - windowed,
                "window_layers": windowed,
                "window": int(c.sliding_window_size),
                "kv_heads": int(c.num_key_value_heads),
                "head_dim": int(c.head_dim),
                "max_positions": int(c.max_position_embeddings),
                "dtype": dec.embed_tokens.weight._value.dtype}

    def forward(self, input_ids, position_ids=None, labels=None):
        from ...nn import functional as F

        hidden = self.model(input_ids, position_ids)
        with jax.named_scope("lm_head_loss"):
            logits = self.lm_head(hidden)
            if labels is None:
                return logits
            return F.cross_entropy(
                logits[:, :-1].reshape([-1, logits.shape[-1]]),
                labels[:, 1:].reshape([-1]), reduction="mean")
