"""DeepSeek-V3 family (reference analog: HF ``modeling_deepseek_v3.py``;
DeepSeek-V3, arXiv:2412.19437 section 2.1): multi-head latent attention
(MLA), a leading dense SwiGLU layer, then sigmoid-routed experts without
dropped tokens beside always-on shared experts, RMSNorm pre-norm, no bias,
an untied head.  Kanana-2-30B-A3B publishes under this ``model_type``.

Training only: the blocks have no cache path, so the model is not served
(ROADMAP R1-R3: a latent paged cache and the expert feed-forward under the
engine).

- MLA: keys and values come from one low-rank latent ``c`` (RMSNorm'd) per
  token; each head's query and key carry ``qk_nope_head_dim`` dimensions of
  their own and ``qk_rope_head_dim`` rotary ones, the key's rotary part ONE
  head shared by all.  q and k are 192 wide and v 128 at the published
  sizes: ``ops/flash_attention.py`` takes the two widths as they are.
- ``rope_interleave``: the rotary dimensions are pairs ``(2i, 2i+1)``;
  they are de-interleaved to halves and then rotated as halves.
- The expert layer is ``fleet.meta_parallel.DroplessMoELayer``; with
  ``experts_held`` / ``expert_offset`` the model holds one chip's share of
  every expert layer (expert parallelism without its exchange).
  ``bias_update_speed`` (not a published key; DeepSeek-V3 trained at 0.001,
  0 leaves the bias alone) balances the experts' load while training.
- ``recompute=True`` checkpoints each decoder layer
  (``fleet.utils.recompute``, which keeps the flash kernel's output and
  log-sum-exp and recomputes the rest; the model names no policy).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...distributed.fleet.meta_parallel.moe import DroplessMoELayer
from ...distributed.fleet.utils.recompute import recompute as _recompute
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from ...nn.layers.common import Embedding, Linear
from ...nn.layers.norm import RMSNorm
from ...nn.param_attr import ParamAttr
from ...tensor.dispatch import apply as _apply
from ...tensor.tensor import Tensor
from .llama import _rope_cos_sin, _rotate_half

__all__ = ["DeepseekV3Config", "DeepseekV3Model", "DeepseekV3ForCausalLM"]


class DeepseekV3Config(dict):
    """Config bag (attribute + dict access); the keys are the published
    ``config.json``'s, the defaults Kanana-2-30B-A3B's.  ``experts_held``
    (default: all ``n_routed_experts``) and ``expert_offset`` say which
    experts this process holds."""

    def __init__(self, **kw):
        defaults = dict(
            vocab_size=128256, hidden_size=2048, intermediate_size=6144,
            moe_intermediate_size=768, num_hidden_layers=48,
            num_attention_heads=32, kv_lora_rank=512, q_lora_rank=None,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            n_routed_experts=128, n_shared_experts=2, num_experts_per_tok=6,
            first_k_dense_replace=1, norm_topk_prob=True,
            routed_scaling_factor=2.448, rms_norm_eps=1e-6,
            rope_theta=1000000.0, rope_interleave=True,
            initializer_range=0.02, experts_held=None, expert_offset=0,
            bias_update_speed=0.0)
        defaults.update(kw)
        if defaults["q_lora_rank"] is not None:
            raise NotImplementedError(
                "q_lora_rank: the low-rank query path is not built; the "
                "supported configuration publishes q_lora_rank null")
        if defaults["experts_held"] is None:
            defaults["experts_held"] = defaults["n_routed_experts"]
        super().__init__(**defaults)
        self.__dict__ = self


def _linear(d_in, d_out, std):
    return Linear(d_in, d_out, bias_attr=False,
                  weight_attr=ParamAttr(initializer=I.Normal(0.0, std)))


def _deinterleave(x):
    """Pairs ``(2i, 2i+1)`` of the last axis to halves ``[evens | odds]``."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


class DeepseekV3Attention(Layer):
    """Multi-head latent attention, training form (no cache)."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.nope, self.rope, self.v_dim = (c.qk_nope_head_dim,
                                            c.qk_rope_head_dim, c.v_head_dim)
        self.kv_rank = c.kv_lora_rank
        self.interleave = c.rope_interleave
        std = c.initializer_range
        self.q_proj = _linear(c.hidden_size,
                              self.num_heads * (self.nope + self.rope), std)
        self.kv_a_proj_with_mqa = _linear(c.hidden_size,
                                          self.kv_rank + self.rope, std)
        self.kv_a_layernorm = RMSNorm(self.kv_rank, epsilon=c.rms_norm_eps)
        self.kv_b_proj = _linear(self.kv_rank,
                                 self.num_heads * (self.nope + self.v_dim),
                                 std)
        self.o_proj = _linear(self.num_heads * self.v_dim, c.hidden_size, std)

    def forward(self, x, rope):
        with jax.named_scope("mla_attention"):
            B, S = x.shape[0], x.shape[1]
            nh, nope, rd, vd = self.num_heads, self.nope, self.rope, self.v_dim
            rank, interleave = self.kv_rank, self.interleave
            q = self.q_proj(x)
            kva = self.kv_a_proj_with_mqa(x)
            latent = self.kv_a_layernorm(kva[:, :, :rank])
            kvb = self.kv_b_proj(latent)

            def heads(qv, kr, kvbv, cos, sin):
                qh = qv.reshape(B, S, nh, nope + rd)
                q_rot, k_rot = qh[..., nope:], kr.reshape(B, S, 1, rd)
                if interleave:
                    q_rot, k_rot = _deinterleave(q_rot), _deinterleave(k_rot)
                c, s = cos[None, :, None, :], sin[None, :, None, :]
                q_rot = q_rot * c + _rotate_half(q_rot) * s
                k_rot = k_rot * c + _rotate_half(k_rot) * s
                kv = kvbv.reshape(B, S, nh, nope + vd)
                qh = jnp.concatenate(
                    [qh[..., :nope], q_rot.astype(qh.dtype)], -1)
                kh = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(k_rot.astype(kv.dtype),
                                      (B, S, nh, rd))], -1)
                return qh, kh, kv[..., nope:]

            # no op_name: autocast would round the float32 rotary tables
            qh, kh, vh = _apply(heads, q, kva[:, :, rank:], kvb, rope[0],
                                rope[1], n_outs=3)
            att = F.scaled_dot_product_attention(qh, kh, vh, is_causal=True,
                                                 training=self.training)
            return self.o_proj(att.reshape([B, S, nh * vd]))


class DeepseekV3MLP(Layer):
    """SwiGLU without bias: ``down(silu(gate(x)) * up(x))``."""

    def __init__(self, hidden_size, intermediate_size, std):
        super().__init__()
        self.gate_proj = _linear(hidden_size, intermediate_size, std)
        self.up_proj = _linear(hidden_size, intermediate_size, std)
        self.down_proj = _linear(intermediate_size, hidden_size, std)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class DeepseekV3DecoderLayer(Layer):
    def __init__(self, config, layer_idx):
        super().__init__()
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                epsilon=c.rms_norm_eps)
        self.sparse = layer_idx >= c.first_k_dense_replace
        if self.sparse:
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, experts_held=c.experts_held,
                expert_offset=c.expert_offset,
                num_shared_experts=c.n_shared_experts,
                routed_scaling_factor=c.routed_scaling_factor,
                norm_topk_prob=c.norm_topk_prob,
                initializer_range=c.initializer_range,
                bias_update_speed=c.bias_update_speed)
        else:
            self.mlp = DeepseekV3MLP(c.hidden_size, c.intermediate_size,
                                     c.initializer_range)

    def forward(self, x, cos, sin):
        """``(x, this call's load over the router's experts)`` from an
        expert layer, ``x`` alone from a dense one.  The load is handed back
        and not kept, so that the layer may run under ``recompute``."""
        x = x + self.self_attn(self.input_layernorm(x), (cos, sin))
        h = self.post_attention_layernorm(x)
        if not self.sparse:
            return x + self.mlp(h)
        y, load = self.mlp(h, return_load=True)
        return x + y, load


class DeepseekV3Model(Layer):
    def __init__(self, config=None, recompute=False, **kw):
        super().__init__()
        self.config = config if isinstance(config, DeepseekV3Config) \
            else DeepseekV3Config(**(config or {}), **kw)
        c = self.config
        self.recompute = bool(recompute)
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                initializer=I.Normal(0.0, c.initializer_range)))
        self.layers = [DeepseekV3DecoderLayer(c, i)
                       for i in range(c.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)

    def forward(self, input_ids, position_ids=None):
        x = self.embed_tokens(input_ids)
        if position_ids is None:
            position_ids = Tensor(jnp.arange(x.shape[1], dtype=jnp.int32))
        rd, theta = self.config.qk_rope_head_dim, self.config.rope_theta
        cos, sin = _apply(lambda pos: _rope_cos_sin(pos, rd, theta),
                          position_ids, op_name="rope_tables", n_outs=2)
        for layer in self.layers:
            out = _recompute(layer, x, cos, sin) if self.recompute \
                else layer(x, cos, sin)
            if layer.sparse:
                x, load = out
                layer.mlp.count(load)
            else:
                x = out[0] if isinstance(out, (tuple, list)) else out
        return self.norm(x)


@jax.named_scope("lm_head_loss")
def _head_loss(hidden, w, labels):
    """Next-token loss through the untied head ``w`` [hidden, vocab]; the
    scope is the one ``GPTForCausalLM`` gives its head and loss."""
    logits = _apply(lambda h, wv: h @ wv, hidden, w, op_name="matmul")
    return F.cross_entropy(
        logits[:, :-1].reshape([-1, logits.shape[-1]]),
        labels[:, 1:].reshape([-1]), reduction="mean")


class DeepseekV3ForCausalLM(Layer):
    """The decoder with its untied head; returns the mean next-token loss
    when given ``labels`` (as ``GPTForCausalLM`` does), logits otherwise."""

    def __init__(self, config=None, recompute=False, **kw):
        super().__init__()
        self.model = DeepseekV3Model(config, recompute=recompute, **kw)
        c = self.model.config
        self.lm_head = _linear(c.hidden_size, c.vocab_size,
                               c.initializer_range)

    def forward(self, input_ids, position_ids=None, labels=None):
        hidden = self.model(input_ids, position_ids)
        if labels is None:
            return self.lm_head(hidden)
        return _head_loss(hidden, self.lm_head.weight, labels)
