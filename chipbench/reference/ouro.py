"""Ouro (looped decoder) in plain ``jax.numpy`` and float32: the reference
of the ``ouro`` family.

Follows the public ``config.json`` of ``ByteDance/Ouro-2.6B`` (``model_type:
ouro``), the paper "Scaling Latent Reasoning via Looped Language Models"
(arXiv:2510.25741) and ISSUE 32's equations, which are the public
``modeling_ouro.py`` as its writer and this file's remember it (that file is
not in this repository and was not fetched).  With ``n(x; g) = x *
rsqrt(mean(x^2) + rms_norm_eps) * g``, R = ``total_ut_steps`` and L =
``num_hidden_layers``::

    h = E[ids]
    for t in 0..R-1:
        for l in 0..L-1:          # the SAME weights at every step
            u = n(h; g1_l);  q, k, v = u Wq_l, u Wk_l, u Wv_l   (no bias)
            rotary positions on q and k (halves rotated over the whole head)
            a = softmax(q k^T / sqrt(head_dim) + causal) v
            h = h + n(a Wo_l; g2_l)
            u = n(h; g3_l);  m = (silu(u Wgate_l) * (u Wup_l)) Wdown_l
            h = h + n(m; g4_l)
        h = n(h; g_f);  h_t = h;  lambda_t = sigmoid(h_t . w_e + b_e)
    logits = h_{R-1} W_head       # early_exit_threshold 1: the last step

Every step attends its OWN keys and values: with no cache that is simply
the step's own ``k`` and ``v``.  ``share_last_kv=True`` is the variant this
configuration forbids (every step attends the LAST step's K and V, the
paper's decode-time cache sharing): a control for the tests, which must
fail where the program passes.

A Python loop over steps and layers, no cache, no kernels; imports nothing
of the program.  Every product with a weight goes through ``mm`` (a control
swaps it for a product in lower precision).

Parameters are one FLAT dict ``{the program's leaf name: array}``, each
drawn on the device by a call of its own from ``(seed, name)``, so 2.67B
parameters are never held in float32: matrices ``normal(0,
initializer_range)``, norm gains ``1 + normal(0, 0.02)`` (a dropped gain
shows), the gate's weight and bias drawn (zeros would hide a dropped bias).

``jax_enable_x64`` may be on in this process, so every dtype is explicit.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def sizes(cfg):
    H, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]),
            "R": int(cfg["total_ut_steps"]), "H": H, "nh": nh,
            "nkv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or H // nh),
            "I": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            # what ``compare.serve_numbers`` pads a request to: the served
            # context, not the 65,536 positions the config declares
            "P": int(cfg.get("serve_positions")
                     or cfg["max_position_embeddings"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


_NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
          "post_attention_layernorm_2")


def param_shapes(cfg):
    """``{leaf name: shape}`` under the program's names; a matrix is
    ``[in, out]``."""
    z = sizes(cfg)
    H, hd = z["H"], z["hd"]
    out = {"model.embed_tokens.weight": (z["V"], H)}
    for i in range(z["L"]):
        p = f"model.layers.{i}."
        for n in _NORMS:
            out[p + n + ".weight"] = (H,)
        a = p + "self_attn."
        out[a + "q_proj.weight"] = (H, z["nh"] * hd)
        out[a + "k_proj.weight"] = (H, z["nkv"] * hd)
        out[a + "v_proj.weight"] = (H, z["nkv"] * hd)
        out[a + "o_proj.weight"] = (z["nh"] * hd, H)
        out[p + "mlp.gate_proj.weight"] = (H, z["I"])
        out[p + "mlp.up_proj.weight"] = (H, z["I"])
        out[p + "mlp.down_proj.weight"] = (z["I"], H)
    out["model.norm.weight"] = (H,)
    out["model.early_exit_gate.weight"] = (H, 1)
    out["model.early_exit_gate.bias"] = (1,)
    out["lm_head.weight"] = (H, z["V"])
    return out


def n_params(cfg):
    return sum(int(np.prod(s)) for s in param_shapes(cfg).values())


def seed_key(seed):
    """A PRNG key from any whole-number seed, also one past 32 signed bits."""
    seed = int(seed) % (1 << 62)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _is_gain(name):
    return name.endswith(("norm.weight", "layernorm.weight",
                          "layernorm_2.weight"))


def init_params(seed, cfg, dtype=F32, shape=None):
    """Every leaf in ``dtype``, drawn on the device by a call of its own
    from ``(seed, leaf name)``.

    ``shape`` (a cell's ``weights``) bends the published initializer
    towards what a trained checkpoint looks like, as in
    ``reference/lfm2.py``: ``outlier_channels`` hidden channels, drawn from
    the seed and the same in every layer, get the gain of every RMSNorm
    that FEEDS a product (``input_layernorm``, ``post_attention_layernorm``,
    the final norm) times ``outlier_gain``: the few loud channels trained
    transformers carry, and what makes a product in int8 lose more than
    one in bfloat16.  The sandwich's second norms scale what is ADDED to
    the residual and stay as drawn.
    """
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    shape = shape or {}
    n_loud = int(shape.get("outlier_channels", 0))
    loud_gain = float(shape.get("outlier_gain", 1.0))
    root = seed_key(seed)
    loud = jax.random.permutation(
        jax.random.fold_in(root, 0x10AD), z["H"])[:n_loud]

    def draw(name, dims):
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        gain = _is_gain(name)
        feeds = gain and not name.endswith("_2.weight")
        return _drawer(dims, std, gain, loud_gain if feeds else 1.0,
                       jnp.dtype(dtype))(key, loud)

    return {name: draw(name, dims)
            for name, dims in param_shapes(cfg).items()}


@functools.lru_cache(maxsize=None)
def _drawer(dims, scale, gain, loud_gain, kind):
    """One compiled draw for every leaf of a shape and a kind."""
    def make(key, loud):
        w = jax.random.normal(key, dims, F32) * scale
        if gain:
            w = (w + 1.0).at[loud].multiply(loud_gain) if loud_gain != 1.0 \
                else w + 1.0
        return w.astype(kind)

    return jax.jit(make)


# ------------------------------------------------------------------ forward
def dense(x, w):
    """The reference's matrix product.  A control swaps this for a product in
    lower precision (``lower_precision.py``)."""
    return jnp.matmul(x, w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def _qkv(u, p, pre, z, mm):
    """Rotated q ``[B, S, nh, hd]``, rotated k and v ``[B, S, nkv, hd]``."""
    B, S, _ = u.shape
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    q = mm(u, p[pre + "q_proj.weight"]).reshape(B, S, nh, hd)
    k = mm(u, p[pre + "k_proj.weight"]).reshape(B, S, nkv, hd)
    v = mm(u, p[pre + "v_proj.weight"]).reshape(B, S, nkv, hd)
    inv = 1.0 / (z["theta"] ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return _rope(q, cos, sin), _rope(k, cos, sin), v


def _attend(q, k, v, z):
    """Causal softmax attention, one KV head's group of query heads at a
    time, so that the scores of a long request fit beside the weights."""
    B, S, nh, hd = q.shape
    nkv = k.shape[2]
    q = jnp.moveaxis(q.reshape(B, S, nkv, nh // nkv, hd), 2, 0)
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))

    def group(qkv):
        qg, kg, vg = qkv                     # [B,S,g,hd], [B,S,hd], [B,S,hd]
        s = jnp.einsum("bqgd,bkd->bgqk", qg, kg) * F32(hd ** -0.5)
        s = jnp.where(causal, s, F32(-1e30))
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(s, -1), vg)

    a = jax.lax.map(group, (q, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(a, 0, 2).reshape(B, S, nh * hd)


def _layer(h, p, pre, z, mm, kv=None):
    """One layer body; ``kv`` (a control) replaces the keys and values the
    layer attends by another step's.  Returns ``(h, (k, v))``."""
    u = _rms(h, p[pre + "input_layernorm.weight"], z["eps"])
    q, k, v = _qkv(u, p, pre + "self_attn.", z, mm)
    a = _attend(q, *(kv or (k, v)), z)
    h = h + _rms(mm(a, p[pre + "self_attn.o_proj.weight"]),
                 p[pre + "input_layernorm_2.weight"], z["eps"])
    u = _rms(h, p[pre + "post_attention_layernorm.weight"], z["eps"])
    m = mm(jax.nn.silu(mm(u, p[pre + "mlp.gate_proj.weight"]))
           * mm(u, p[pre + "mlp.up_proj.weight"]),
           p[pre + "mlp.down_proj.weight"])
    return h + _rms(m, p[pre + "post_attention_layernorm_2.weight"],
                    z["eps"]), (k, v)


class _F32(dict):
    """The flat parameters, a leaf upcast to float32 where it is used."""

    def __init__(self, params):
        super().__init__()
        self._params = params

    def __missing__(self, name):
        return self._params[name].astype(F32)


def hidden_and_gates(params, ids, cfg, mm=dense, share_last_kv=False):
    """``(h [R, B, S, H], gates [R, B, S])`` in float32: every step's
    hidden state after the final norm, and its exit gate.

    ``share_last_kv``: the FORBIDDEN variant.  The steps run once as
    published to find the last step's keys and values of every layer; then
    they run again with every step attending those."""
    z = sizes(cfg)
    p = _F32(params)

    def run(shared):
        h = p["model.embed_tokens.weight"][ids]
        hs, gates, last = [], [], []
        for t in range(z["R"]):
            last = []
            for i in range(z["L"]):
                h, kv = _layer(h, p, f"model.layers.{i}.", z, mm,
                               None if shared is None else shared[i])
                last.append(kv)
            h = _rms(h, p["model.norm.weight"], z["eps"])
            hs.append(h)
            gates.append(jax.nn.sigmoid(
                mm(h, p["model.early_exit_gate.weight"])[..., 0]
                + p["model.early_exit_gate.bias"][0]))
        return jnp.stack(hs), jnp.stack(gates), last

    hs, gates, last = run(None)
    if share_last_kv:
        hs, gates, _ = run(last)
    return hs, gates


def exit_distribution(gates):
    """``p [R, ...]``: ``p_t = lambda_t * prod_{j<t}(1 - lambda_j)``, the
    last step taking the rest, ``prod_{j<R-1}(1 - lambda_j)``."""
    out, stay = [], jnp.ones_like(gates[0])
    for t in range(gates.shape[0] - 1):
        out.append(gates[t] * stay)
        stay = stay * (1.0 - gates[t])
    return jnp.stack(out + [stay])


def logits(params, ids, cfg, mm=dense, share_last_kv=False):
    """Logits ``[B, S, V]`` in float32, of the last step."""
    hs, _ = hidden_and_gates(params, ids, cfg, mm, share_last_kv)
    return mm(hs[-1], params["lm_head.weight"].astype(F32))
