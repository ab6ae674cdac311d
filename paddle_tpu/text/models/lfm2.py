"""LFM2-MoE family (reference analog: HF ``modeling_lfm2_moe.py``; public
``config.json`` of ``LiquidAI/LFM2-24B-A2B``, ``model_type: lfm2_moe``): a
hybrid decoder whose layers are, by ``layer_types``, either a GATED SHORT
CONVOLUTION or grouped-query attention, each followed by a feed-forward
that is dense SwiGLU in the first ``num_dense_layers`` layers and 64
sigmoid-routed experts (top 4, no shared expert) after them.  RMSNorm
pre-norm, no bias anywhere, the head tied to the embedding.

With ``n(x) = x * rsqrt(mean(x^2) + norm_eps) * g``::

    h   = x + op_i(operator_norm(x))
    out = h + ff_i(ffn_norm(h))

and one more RMSNorm (``embedding_norm``) after the last layer.

- ``op_i`` of a ``"conv"`` layer: ``[B, C, z] = split3(W_in u)``, ``s = B *
  z``, ``c_t = sum_k w_k * s_{t-(K-1)+k}`` per channel (depthwise, causal,
  ``K = conv_L_cache`` taps, ``s`` zero before the sequence), ``y = W_out (C
  * c)``.  What a sequence carries from one call to the next is the last
  ``K - 1`` rows of ``s``: ``[K - 1, H]`` a layer, whatever the context.
- ``op_i`` of a ``"full_attention"`` layer: bias-free q/k/v projections,
  RMSNorm over each head's ``head_dim`` on q and on k, rotary positions
  (``llama.py``'s halves), causal softmax at ``head_dim ** -0.5``, grouped
  KV heads, bias-free ``out_proj``.  Keys go into a paged cache normed and
  rotated, through the one cache seam ``paged_cache_attend``.
- ``ff_i``: ``w2(silu(w1 x) * w3 x)``, or ``DroplessMoELayer`` holding all
  the experts: ``p = sigmoid(W_r x)`` in float32, the experts the top k of
  ``p + expert_bias`` (``e_score_correction_bias``: it selects and does not
  weigh), the weights ``p`` there over ``(their sum + 1e-6)``, times
  ``routed_scaling_factor``.

Memory: every leaf is made in ``config.dtype`` by ``param_init(name,
shape)`` (default: drawn on the device from ``(config.seed, name)``), one at
a time: a 5B-parameter cut is never held in float32, and a caller with its
own weights hands them over leaf by leaf without a second copy.

Serving: ``forward(..., cache=, conv_state=, valid=)`` is what
``serving.adapter.StatedCacheAdapter`` drives; the model states the caches
a server has to hold for it (``serving_caches``), which is how
``ServingEngine(model)`` picks that adapter: nothing of ``serving`` is
imported here.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from ...distributed.fleet.meta_parallel.moe import DroplessMoELayer
from ...framework import dtypes as _dt
from ...nn import functional as F
from ...nn.layer import Layer
from ...nn.layers.common import Embedding, Linear
from ...nn.layers.norm import RMSNorm
from ...nn.param_attr import ParamAttr
from ...ops.paged_attention import paged_cache_attend
from ...tensor.dispatch import apply as _apply
from ...tensor.tensor import Tensor
from .llama import _apply_rope, _rope_cos_sin

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM"]

_PERIOD = ("full_attention", "conv", "conv", "conv")


def published_layer_types(n, num_dense_layers=2):
    """The published pattern's first ``n`` entries: ``conv`` in the leading
    dense layers, then periods of ``attn conv conv conv``."""
    return [("conv" if i < num_dense_layers
             else _PERIOD[(i - num_dense_layers) % len(_PERIOD)])
            for i in range(n)]


class Lfm2MoeConfig(dict):
    """Config bag (attribute + dict access); the keys are the published
    ``config.json``'s, the defaults LFM2-24B-A2B's.  Not published and set
    here: ``tie_word_embeddings`` (the family's checkpoints tie),
    ``initializer_range``, ``norm_topk_eps`` (the public implementation's
    1e-6), ``dtype`` and ``seed`` (what the default ``param_init`` draws
    in and from)."""

    def __init__(self, **kw):
        defaults = dict(
            vocab_size=65536, hidden_size=2048, intermediate_size=11776,
            moe_intermediate_size=1536, num_hidden_layers=40,
            num_attention_heads=32, num_key_value_heads=8, layer_types=None,
            num_dense_layers=2, num_experts=64, num_experts_per_tok=4,
            norm_topk_prob=True, routed_scaling_factor=1.0,
            use_expert_bias=True, conv_L_cache=3, conv_bias=False,
            norm_eps=1e-5, rope_theta=1000000.0,
            max_position_embeddings=128000, tie_word_embeddings=True,
            initializer_range=0.02, norm_topk_eps=1e-6, dtype="float32",
            seed=0)
        rope = kw.pop("rope_parameters", None)
        if rope:
            if rope.get("rope_type", "default") != "default":
                raise NotImplementedError(
                    f"rope_type {rope['rope_type']!r}: only the default "
                    "rotary positions are built")
            defaults["rope_theta"] = float(rope["rope_theta"])
        defaults.update(kw)
        if defaults["conv_bias"]:
            raise NotImplementedError("conv_bias: the published "
                                      "configuration has none")
        if defaults["layer_types"] is None:
            defaults["layer_types"] = published_layer_types(
                defaults["num_hidden_layers"], defaults["num_dense_layers"])
        if len(defaults["layer_types"]) != defaults["num_hidden_layers"]:
            raise ValueError(
                f"layer_types names {len(defaults['layer_types'])} layers, "
                f"num_hidden_layers is {defaults['num_hidden_layers']}")
        for kind in defaults["layer_types"]:
            if kind not in ("conv", "full_attention"):
                raise ValueError(f"unknown layer type {kind!r}")
        super().__init__(**defaults)
        self.__dict__ = self

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def seeded_init(config):
    """The default ``param_init``: every leaf drawn on the device from
    ``(config.seed, its name)`` in ``config.dtype``: matrices
    ``normal(0, initializer_range)``, the convolution's taps ``normal(0,
    K ** -0.5)`` (the operator keeps its input's scale), norm gains ones, the experts'
    selection bias zeros (a checkpoint holds a trained one)."""
    dtype = _dt.to_jax(config.dtype)
    std = float(config.initializer_range)
    root = jax.random.key(int(config.seed) & 0x7FFFFFFF)

    def make(name, shape):
        if name.endswith(("norm.weight", "layernorm.weight",
                          "layernorm_2.weight")):
            return jnp.ones(shape, dtype)
        if name.endswith("e_score_correction_bias"):
            return jnp.zeros(shape, jnp.float32)
        if name.endswith(".bias"):
            return jnp.zeros(shape, dtype)
        scale = int(config.conv_L_cache) ** -0.5 \
            if name.endswith("conv_weight") else std
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    return make


def _attr(make, name):
    """A leaf's ``ParamAttr``: ``make(name, shape)`` in the initializer's
    place, whatever type the layer would have asked for."""
    return ParamAttr(initializer=lambda shape, dtype=None: make(name, shape))


def _linear(make, name, d_in, d_out):
    return Linear(d_in, d_out, bias_attr=False,
                  weight_attr=_attr(make, name + ".weight"))


def _norm(make, name, width, eps):
    return RMSNorm(width, epsilon=eps,
                   weight_attr=_attr(make, name + ".weight"))


class Lfm2ShortConv(Layer):
    """The gated short convolution (HF ``Lfm2MoeShortConv``)."""

    def __init__(self, config, make, name):
        super().__init__()
        h, self.taps = config.hidden_size, int(config.conv_L_cache)
        self.in_proj = _linear(make, name + ".in_proj", h, 3 * h)
        self.conv_weight = self.create_parameter(
            [h, self.taps], attr=_attr(make, name + ".conv_weight"))
        self.out_proj = _linear(make, name + ".out_proj", h, h)

    def forward(self, x, state=None, valid=None):
        """``x`` [B, S, H]; ``state`` [B, K-1, H], the rows of ``s`` the
        sequences enter with (``None``: zeros, a sequence's start);
        ``valid`` [B], how many of the S lanes are real (``None``: all).
        Returns ``(y, the state after each row's last real lane)``."""
        taps = self.taps
        with jax.named_scope("short_conv"):
            bcz = self.in_proj(x)

            def conv(bcz, w, *rest):
                gate_b, gate_c, z = jnp.split(bcz, 3, axis=-1)
                s = gate_b * z
                n, seq = s.shape[0], s.shape[1]
                rest = list(rest)
                before = rest.pop(0).astype(s.dtype) if state is not None \
                    else jnp.zeros((n, taps - 1, s.shape[2]), s.dtype)
                ext = jnp.concatenate([before, s], axis=1)
                c = sum(w[:, k].astype(s.dtype) * ext[:, k:k + seq]
                        for k in range(taps))
                last = rest.pop(0).astype(jnp.int32) if valid is not None \
                    else jnp.full((n,), seq, jnp.int32)
                rows = last[:, None] + jnp.arange(taps - 1,
                                                  dtype=jnp.int32)[None]
                after = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
                return gate_c * c, after

            extra = [t for t in (state, valid) if t is not None]
            # no op_name: autocast must not touch the lanes' count
            y, after = _apply(conv, bcz, self.conv_weight, *extra, n_outs=2)
            return self.out_proj(y), after


class Lfm2Attention(Layer):
    """Grouped-query attention with RMSNorm on each head of q and k."""

    def __init__(self, config, make, name):
        super().__init__()
        c = config
        h, hd = c.hidden_size, c.head_dim
        self.num_heads, self.num_kv_heads = (c.num_attention_heads,
                                             c.num_key_value_heads)
        self.head_dim = hd
        self.q_proj = _linear(make, name + ".q_proj", h, self.num_heads * hd)
        self.k_proj = _linear(make, name + ".k_proj", h,
                              self.num_kv_heads * hd)
        self.v_proj = _linear(make, name + ".v_proj", h,
                              self.num_kv_heads * hd)
        self.out_proj = _linear(make, name + ".out_proj",
                                self.num_heads * hd, h)
        self.q_layernorm = _norm(make, name + ".q_layernorm", hd, c.norm_eps)
        self.k_layernorm = _norm(make, name + ".k_layernorm", hd, c.norm_eps)

    def forward(self, x, rope, cache=None):
        """``cache``: ``None`` (dense causal attention over ``x``) or the
        paged cache ``(tag, layer, (kp, vp), table, lens)`` with ``layer``
        this layer's rank among the attention layers; then ``(y, pools)``
        comes back."""
        with jax.named_scope("gqa_attention"):
            B, S = x.shape[0], x.shape[1]
            hq, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
            rep = hq // hkv
            q = self.q_layernorm(self.q_proj(x).reshape([B, S, hq, hd]))
            k = self.k_layernorm(self.k_proj(x).reshape([B, S, hkv, hd]))
            v = self.v_proj(x).reshape([B, S, hkv, hd])

            def rotate(qh, kh, cos, sin):
                qr, kr = _apply_rope(qh, kh, cos, sin)
                return qr.astype(qh.dtype), kr.astype(kh.dtype)

            # no op_name: autocast would round the float32 rotary tables
            q, k = _apply(rotate, q, k, rope[0], rope[1], n_outs=2)

            def dense(qh, kh, vh):
                if rep > 1:
                    kh = _apply(lambda t: jnp.repeat(t, rep, axis=2), kh,
                                op_name="gqa_repeat")
                    vh = _apply(lambda t: jnp.repeat(t, rep, axis=2), vh,
                                op_name="gqa_repeat")
                return F.scaled_dot_product_attention(
                    qh, kh, vh, is_causal=True, training=False)

            if cache is None:
                return self.out_proj(dense(q, k, v).reshape([B, S, hq * hd]))
            att, pools = paged_cache_attend(q, k, v, cache, dense)
            return self.out_proj(att.reshape([B, S, hq * hd])), pools


class Lfm2MLP(Layer):
    """``w2(silu(w1 x) * w3 x)``, no bias."""

    def __init__(self, config, make, name):
        super().__init__()
        h, inner = config.hidden_size, config.intermediate_size
        self.w1 = _linear(make, name + ".w1", h, inner)
        self.w3 = _linear(make, name + ".w3", h, inner)
        self.w2 = _linear(make, name + ".w2", inner, h)

    def forward(self, x):
        return self.w2(F.silu(self.w1(x)) * self.w3(x))


class Lfm2DecoderLayer(Layer):
    def __init__(self, config, layer_idx, make, name):
        super().__init__()
        c = config
        self.is_attention = c.layer_types[layer_idx] == "full_attention"
        #: this layer's index among the layers of its own kind: where its
        #: pages, or its state, lie in the stacked caches
        self.rank = sum(
            (t == "full_attention") == self.is_attention
            for t in c.layer_types[:layer_idx])
        self.operator_norm = _norm(make, name + ".operator_norm",
                                   c.hidden_size, c.norm_eps)
        if self.is_attention:
            self.self_attn = Lfm2Attention(c, make, name + ".self_attn")
        else:
            self.conv = Lfm2ShortConv(c, make, name + ".conv")
        self.ffn_norm = _norm(make, name + ".ffn_norm", c.hidden_size,
                              c.norm_eps)
        self.sparse = layer_idx >= c.num_dense_layers
        if self.sparse:
            ff = name + ".feed_forward."
            self.feed_forward = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok,
                routed_scaling_factor=c.routed_scaling_factor,
                norm_topk_prob=c.norm_topk_prob,
                initializer_range=c.initializer_range,
                norm_topk_eps=c.norm_topk_eps,
                param_init=lambda leaf, shape: make(ff + leaf, shape))
            if c.use_expert_bias:
                bias = self.feed_forward.e_score_correction_bias
                bias._value = jnp.asarray(
                    make(ff + "e_score_correction_bias", tuple(bias.shape)),
                    jnp.float32)
        else:
            self.feed_forward = Lfm2MLP(c, make, name + ".feed_forward")

    def forward(self, x, rope, cache=None, state=None, valid=None):
        """``(x, what the operator hands on)``: the pools of a paged cache
        from an attention layer, the state after the call from a
        convolution layer."""
        h = self.operator_norm(x)
        if not self.is_attention:
            op, carried = self.conv(h, state, valid)
        elif cache is None:
            op, carried = self.self_attn(h, rope), None
        else:
            op, carried = self.self_attn(h, rope, cache)
        x = x + op
        h = self.ffn_norm(x)
        if not self.sparse or self.training:
            return x + self.feed_forward(h), carried
        # evaluation (and every served program): no buffer is touched, so
        # nothing is counted or published from inside a program
        y, _ = self.feed_forward(h, return_load=True)
        return x + y, carried


class Lfm2MoeModel(Layer):
    def __init__(self, config=None, param_init=None, **kw):
        super().__init__()
        self.config = config if isinstance(config, Lfm2MoeConfig) \
            else Lfm2MoeConfig(**(config or {}), **kw)
        c = self.config
        make = param_init or seeded_init(c)
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size,
            weight_attr=_attr(make, "model.embed_tokens.weight"))
        self.layers = [Lfm2DecoderLayer(c, i, make, f"model.layers.{i}")
                       for i in range(c.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.embedding_norm = _norm(make, "model.embedding_norm",
                                    c.hidden_size, c.norm_eps)

    @property
    def num_attention_layers(self):
        return sum(layer.is_attention for layer in self.layers)

    @property
    def num_conv_layers(self):
        return len(self.layers) - self.num_attention_layers

    def forward(self, input_ids, position_ids=None, cache=None,
                conv_state=None, valid=None):
        """Hidden states ``[B, S, H]`` after ``embedding_norm``.

        Served (``cache`` given): ``cache`` is ``(tag, (kp, vp), table,
        lens)``, ONE paged cache for the attention layers, each reading and
        writing the stacked pools at its rank; ``conv_state`` ``[L_conv, B,
        K-1, H]`` is what the sequences enter the convolution layers with
        and ``valid`` [B] the real lanes of each row.  Then ``(hidden,
        pools, the state after the call [L_conv, B, K-1, H])`` comes
        back."""
        x = self.embed_tokens(input_ids)
        if position_ids is None:
            position_ids = Tensor(jnp.arange(x.shape[1], dtype=jnp.int32))
        hd, theta = self.config.head_dim, self.config.rope_theta
        rope = _apply(lambda pos: _rope_cos_sin(pos, hd, theta),
                      position_ids, op_name="rope_tables", n_outs=2)
        pools = None if cache is None else cache[1]
        after = []
        for layer in self.layers:
            if layer.is_attention:
                paged = None if cache is None \
                    else (cache[0], layer.rank, pools) + tuple(cache[2:])
                x, carried = layer(x, rope, cache=paged)
                pools = carried if paged is not None else pools
            else:
                entering = None if conv_state is None else _apply(
                    lambda s, r=layer.rank: s[r], conv_state)
                x, carried = layer(x, rope, state=entering, valid=valid)
                after.append(carried)
        x = self.embedding_norm(x)
        if cache is None and conv_state is None:
            return x
        return x, pools, _apply(lambda *s: jnp.stack(s), *after)


class Lfm2MoeForCausalLM(Layer):
    """The decoder with its head (tied to the embedding as published
    checkpoints of the family tie it; ``tie_word_embeddings=False`` gives
    it a matrix of its own); the mean next-token loss when given
    ``labels``, logits otherwise."""

    def __init__(self, config=None, param_init=None, **kw):
        super().__init__()
        self.model = Lfm2MoeModel(config, param_init=param_init, **kw)
        c = self.model.config
        self.tie = bool(c.tie_word_embeddings)
        if not self.tie:
            make = param_init or seeded_init(c)
            self.lm_head = _linear(make, "lm_head", c.hidden_size,
                                   c.vocab_size)

    @property
    def config(self):
        return self.model.config

    def head_weight(self):
        """The head's matrix as ``[vocab, hidden]``."""
        if self.tie:
            return self.model.embed_tokens.weight
        return _apply(lambda w: w.T, self.lm_head.weight)

    def serving_caches(self):
        """What a server has to hold for ONE sequence of this decoder, all
        ``serving.adapter.StatedCacheAdapter`` reads of its sizes: pages for
        the attention layers' grouped heads, and ``state_shape`` ``(layers,
        rows, width)``, the rows of gate products each convolution layer
        carries.  ``ServingEngine(model)`` picks its adapter by this
        method's presence; no flag is asked."""
        c, dec = self.config, self.model
        return {"attention_layers": dec.num_attention_layers,
                "kv_heads": int(c.num_key_value_heads),
                "head_dim": int(c.head_dim),
                "state_shape": (dec.num_conv_layers, int(c.conv_L_cache) - 1,
                                int(c.hidden_size)),
                "max_positions": int(c.max_position_embeddings),
                "dtype": dec.embed_tokens.weight._value.dtype}

    def forward(self, input_ids, position_ids=None, labels=None):
        hidden = self.model(input_ids, position_ids)
        with jax.named_scope("lm_head_loss"):
            logits = _apply(lambda h, w: h @ w.T, hidden, self.head_weight(),
                            op_name="matmul")
            if labels is None:
                return logits
            return F.cross_entropy(
                logits[:, :-1].reshape([-1, logits.shape[-1]]),
                labels[:, 1:].reshape([-1]), reduction="mean")
