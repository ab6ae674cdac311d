"""Ouro family (reference analog: the public ``modeling_ouro.py``; public
``config.json`` of ``ByteDance/Ouro-2.6B``, ``model_type: ouro``; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): a LOOPED
decoder.  The whole stack of ``num_hidden_layers`` layers runs
``total_ut_steps`` times over the hidden state with ONE set of weights;
every (step, layer) keeps keys and values of its own.

With ``n(x; g) = x * rsqrt(mean(x^2) + rms_norm_eps) * g`` (in float32),
R steps and L layers::

    h = E[ids]
    for t in 0..R-1:
        for l in 0..L-1:                      # weights do not depend on t
            h = h + n(attn_l(n(h; g1_l)); g2_l)     # sandwich norms
            h = h + n(mlp_l(n(h; g3_l)); g4_l)
        h = n(h; g_f);  h_t = h;  lambda_t = sigmoid(h_t . w_e + b_e)

``attn_l`` is ``llama.py``'s attention (bias-free projections, rotate-half
rotary positions over the whole head, causal softmax at ``head_dim **
-0.5``) and ``mlp_l`` its SwiGLU; layer ``l`` at step ``t`` attends the K
and V that layer ``l`` AT STEP ``t`` wrote for the earlier positions: cache
row ``t * L + l``, ``R * L`` rows.  A token leaves at the first step whose
cumulative exit probability (:func:`exit_distribution`) reaches
``early_exit_threshold``; the published 1 is reached by the last step
only, so every token runs all R steps and ``logits = h_{R-1} W_head``.

The R steps are ONE traced loop (``lax.scan``) over the L layer bodies, on
the dense path and on the served path alike: a program holds L layer
bodies, not R * L, and the cache row is a traced scalar through the one
cache seam (``ops.paged_attention.paged_cache_attend``).

Refused by name, not half-built: a threshold under 1 (lanes of one batch at
different depths, cache rows of skipped steps), the paper's decode-time
cache sharing (the last step's K/V for all steps: a different result),
``rope_scaling``, a sliding window.

Precision: the hidden state between layers and steps (the residual stream)
is carried in float32 whatever ``config.dtype``; every product, every
branch and the cache are in ``config.dtype``.  A sandwich norm hands each
branch to the stream at unit RMS while the stream grows to an RMS of ten
and more, so an addition in bfloat16 would round away some 4% of every
branch, 192 times a token (on the chip that alone put 6% of noise on the
logits of a bfloat16 model; the stream is ``[tokens, hidden]``: its type
costs nothing).

Memory: every leaf is made in ``config.dtype`` by ``param_init(name,
shape)`` one at a time, as in ``lfm2.py``: 2.67B parameters are never held
in float32.  Serving: the model states its caches (``serving_caches``:
pages only, ``R * L`` rows of them, no per-slot state), which is how
``ServingEngine(model)`` picks its adapter; nothing of ``serving`` is
imported here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.state import no_grad_ctx
from ...nn import functional as F
from ...nn.layer import Layer
from ...nn.layers.common import Embedding, Linear
from ...tensor.dispatch import apply as _apply
from ...tensor.tensor import Tensor
from .lfm2 import _attr, _linear, _norm, seeded_init
from .llama import LlamaAttention, LlamaMLP, _rope_cos_sin

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "exit_distribution"]


class OuroConfig(dict):
    """Config bag (attribute + dict access); the keys are the published
    ``config.json``'s, the defaults Ouro-2.6B's.  Not published and set
    here: ``initializer_range``, ``dtype`` and ``seed`` (what the default
    ``param_init`` draws in and from)."""

    def __init__(self, **kw):
        defaults = dict(
            vocab_size=49152, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=48, num_attention_heads=16,
            num_key_value_heads=16, head_dim=128, hidden_act="silu",
            max_position_embeddings=65536, rms_norm_eps=1e-6,
            rope_theta=1000000.0, rope_scaling=None,
            tie_word_embeddings=False, total_ut_steps=4,
            early_exit_threshold=1.0, sliding_window=None,
            use_sliding_window=False, initializer_range=0.02,
            dtype="float32", seed=0)
        defaults.update(kw)
        c = defaults
        if float(c["early_exit_threshold"]) < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {c['early_exit_threshold']} < 1: "
                "tokens of one batch would leave at different steps (lanes "
                "at different depths, cache rows of skipped steps); only "
                "the published 1 (every token runs every step) is built")
        if c["rope_scaling"] is not None:
            raise NotImplementedError("rope_scaling: the published "
                                      "configuration has none")
        if c["use_sliding_window"]:
            raise NotImplementedError("use_sliding_window: every published "
                                      "layer attends in full")
        if c["tie_word_embeddings"]:
            raise NotImplementedError("tie_word_embeddings: the published "
                                      "head is a matrix of its own")
        if c["hidden_act"] != "silu":
            raise NotImplementedError(f"hidden_act {c['hidden_act']!r}: "
                                      "the feed-forward is SwiGLU")
        if c["head_dim"] * c["num_attention_heads"] != c["hidden_size"]:
            raise NotImplementedError(
                f"head_dim {c['head_dim']} x {c['num_attention_heads']} "
                f"heads is not hidden_size {c['hidden_size']}")
        if int(c["total_ut_steps"]) < 1:
            raise ValueError("total_ut_steps must be at least 1")
        super().__init__(**defaults)
        self.__dict__ = self


def exit_distribution(gates):
    """``p [R, ...]`` from the gates ``lambda [R, ...]``: ``p_t = lambda_t *
    prod_{j<t}(1 - lambda_j)``, and the last step takes what is left,
    ``prod_{j<R-1}(1 - lambda_j)``, so ``p`` sums to one over the steps."""
    lam = gates._value if isinstance(gates, Tensor) else jnp.asarray(gates)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


class OuroDecoderLayer(Layer):
    """One layer body: ``llama.py``'s attention and SwiGLU between sandwich
    norms (a norm before AND after each, the second inside the residual)."""

    def __init__(self, config, make, name):
        super().__init__()
        c = config

        def linear(prefix):
            return lambda leaf, i, o: _linear(make, f"{prefix}.{leaf}", i, o)

        def norm(leaf):
            return _norm(make, f"{name}.{leaf}", c.hidden_size,
                         c.rms_norm_eps)

        #: the type of the products and the branches (the stream is f32)
        self.compute_dtype = c.dtype
        self.input_layernorm = norm("input_layernorm")
        self.self_attn = LlamaAttention(c, linear=linear(name + ".self_attn"))
        self.input_layernorm_2 = norm("input_layernorm_2")
        self.post_attention_layernorm = norm("post_attention_layernorm")
        self.mlp = LlamaMLP(c.hidden_size, c.intermediate_size,
                            linear=linear(name + ".mlp"))
        self.post_attention_layernorm_2 = norm("post_attention_layernorm_2")

    def forward(self, x, rope, cache=None):
        """``(x, pools)``: ``x`` is the residual stream in float32;
        ``cache`` is ``None`` (dense causal attention, ``pools`` None) or
        the paged cache ``(tag, row, pools, table, lens)`` with ``row`` this
        (step, layer)'s cache row."""
        with jax.named_scope("gqa_attention"):
            att = self.self_attn(
                self.input_layernorm(x).astype(self.compute_dtype), rope,
                None, cache)
        att, pools = att if cache is not None else (att, None)
        x = x + self.input_layernorm_2(att)
        x = x + self.post_attention_layernorm_2(self.mlp(
            self.post_attention_layernorm(x).astype(self.compute_dtype)))
        return x, pools


class OuroModel(Layer):
    def __init__(self, config=None, param_init=None, **kw):
        super().__init__()
        self.config = config if isinstance(config, OuroConfig) \
            else OuroConfig(**(config or {}), **kw)
        c = self.config
        make = param_init or seeded_init(c)
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size,
            weight_attr=_attr(make, "model.embed_tokens.weight"))
        self.layers = [OuroDecoderLayer(c, make, f"model.layers.{i}")
                       for i in range(c.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.norm = _norm(make, "model.norm", c.hidden_size, c.rms_norm_eps)
        self.early_exit_gate = Linear(
            c.hidden_size, 1,
            weight_attr=_attr(make, "model.early_exit_gate.weight"),
            bias_attr=_attr(make, "model.early_exit_gate.bias"))

    def _looped(self, x, rope, cache):
        """The R steps as one traced loop over the L layer bodies: ``(the
        last step's h [B, S, H], h [R, B, S, H], gates [R, B, S], pools)``.

        ONE recorded op whose inputs are the hidden state and every leaf
        the loop reads, so the eager tape differentiates through the scan;
        inside it the layers run on the loop's own tracers and record
        nothing.  The pools ride in the loop's carry and each layer body
        reads and writes row ``t * L + l`` of them, ``t`` the loop's traced
        counter."""
        steps, depth = int(self.config.total_ut_steps), len(self.layers)
        names, leaves = zip(*(
            (n, p) for n, p in self.named_parameters()
            if not n.startswith("embed_tokens.")))
        tag, pools, table, lens = cache if cache is not None \
            else (None, (), None, None)
        paged = [*pools, table, lens] if cache is not None else []

        def run(h, cos, sin, *values):
            weights, rest = values[:len(leaves)], values[len(leaves):]
            rope_t = (Tensor(cos), Tensor(sin))

            def one_step(carry, t):
                h, kv = Tensor(carry[0]), tuple(Tensor(p) for p in carry[1])
                with jax.named_scope("loop_step"):
                    for i, layer in enumerate(self.layers):
                        row = None if cache is None else (
                            tag, t * depth + i, kv, Tensor(rest[-2]),
                            Tensor(rest[-1]))
                        h, carried = layer(h, rope_t, row)
                        kv = carried if cache is not None else kv
                    h = self.norm(h)
                with jax.named_scope("exit_gate"):
                    gate = F.sigmoid(self.early_exit_gate(h))
                carry = (h._value, tuple(p._value for p in kv))
                return carry, (h._value, gate._value[..., 0])

            with no_grad_ctx(), self.bind(dict(zip(names, weights))):
                (h, kv), (hs, gates) = jax.lax.scan(
                    one_step, (h.astype(jnp.float32),
                               tuple(rest[:len(pools)])),
                    jnp.arange(steps, dtype=jnp.int32))
            return (h, hs, gates, *kv)

        last, hs, gates, *kv = _apply(run, x, rope[0], rope[1], *leaves,
                                      *paged, n_outs=None)
        return last, hs, gates, tuple(kv)

    def forward(self, input_ids, position_ids=None, cache=None):
        """Dense (``cache`` None): ``(hidden [R, B, S, H], gates [R, B,
        S])``, every step's hidden state after the final norm and its exit
        gate.

        Served: ``cache`` is ``(tag, (kp, vp), table, lens)``, ONE paged
        cache of ``R * L`` rows; then ``(the last step's hidden [B, S, H],
        pools)`` comes back: the served programs read no gate and no
        earlier step's state, and what nothing reads is dropped when the
        program is lowered (the scope ``exit_gate`` names operations of
        the dense program only)."""
        x = self.embed_tokens(input_ids)
        if position_ids is None:
            position_ids = Tensor(jnp.arange(x.shape[1], dtype=jnp.int32))
        hd, theta = self.config.head_dim, self.config.rope_theta
        rope = _apply(lambda pos: _rope_cos_sin(pos, hd, theta),
                      position_ids, op_name="rope_tables", n_outs=2)
        last, hs, gates, pools = self._looped(x, rope, cache)
        if cache is None:
            return hs, gates
        return last, pools


class OuroForCausalLM(Layer):
    """The looped decoder with its head, a matrix of its own as published:
    logits of the LAST step, or the mean next-token loss when given
    ``labels``."""

    def __init__(self, config=None, param_init=None, **kw):
        super().__init__()
        self.model = OuroModel(config, param_init=param_init, **kw)
        c = self.model.config
        self.lm_head = _linear(param_init or seeded_init(c), "lm_head",
                               c.hidden_size, c.vocab_size)

    @property
    def config(self):
        return self.model.config

    def head_weight(self):
        """The head's matrix as ``[vocab, hidden]``."""
        return _apply(lambda w: w.T, self.lm_head.weight)

    def serving_caches(self):
        """What a server has to hold for ONE sequence of this decoder:
        pages only, for ``total_ut_steps * num_hidden_layers`` cache rows
        (the layers that hold pages are not the layers that hold weights),
        and no per-slot state.  ``ServingEngine(model)`` picks its adapter
        by this method's presence; no flag is asked."""
        c = self.config
        return {"attention_layers": int(c.total_ut_steps)
                * int(c.num_hidden_layers),
                "loop_steps": int(c.total_ut_steps),
                "kv_heads": int(c.num_key_value_heads),
                "head_dim": int(c.head_dim),
                "max_positions": int(c.max_position_embeddings),
                "dtype": self.model.embed_tokens.weight._value.dtype}

    def forward(self, input_ids, position_ids=None, labels=None):
        hidden, _ = self.model(input_ids, position_ids)
        with jax.named_scope("lm_head_loss"):
            logits = _apply(lambda h, w: h @ w.T, hidden[-1],
                            self.head_weight(), op_name="matmul")
            if labels is None:
                return logits
            return F.cross_entropy(
                logits[:, :-1].reshape([-1, logits.shape[-1]]),
                labels[:, 1:].reshape([-1]), reduction="mean")
