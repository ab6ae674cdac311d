"""LFM2-MoE in plain ``jax.numpy`` and float32: the reference of the
``lfm2`` family.

Follows the public ``config.json`` of ``LiquidAI/LFM2-24B-A2B``
(``model_type: lfm2_moe``) and ISSUE 30's equations, which are HF
``modeling_lfm2_moe.py`` as its writer read it (that file is not in this
repository: where it differs from an equation here, the equation here was
followed).  With ``n(x) = x * rsqrt(mean(x^2) + norm_eps) * g``:

- layer ``i``: ``h = x + op_i(n_op(x))``, ``out = h + ff_i(n_ff(h))``; after
  the last layer one more RMSNorm (``embedding_norm``), then the head, tied
  to the embedding;
- ``op_i`` of a ``"conv"`` layer: ``[B, C, z] = split3(W_in u)``, ``s = B *
  z``, ``c_t = w_0 s_{t-2} + w_1 s_{t-1} + w_2 s_t`` per channel (two shifts
  and three multiplies; ``s`` zero before the sequence), ``y = W_out (C *
  c)``;
- ``op_i`` of a ``"full_attention"`` layer: bias-free q, k, v to 32, 8, 8
  heads of 64, RMSNorm over the 64 of each head on q and on k, rotary
  positions (halves rotated, theta 1e6), scale 1/8, causal softmax, 4 query
  heads a KV head, bias-free ``out_proj``;
- ``ff_i``, ``i < num_dense_layers``: ``W_2 (silu(W_1 x) * W_3 x)``; after
  them 64 experts of that form: ``p = sigmoid(W_r x)`` in float32, the four
  experts the top 4 of ``p + expert_bias`` (it selects and does not weigh),
  weights ``p`` there over ``(their sum + 1e-6)``, times
  ``routed_scaling_factor``.  The experts run one at a time over ALL
  positions under a mask (``lax.scan``).

No cache, no kernels, no batching tricks; imports nothing of the program.
Every product with a weight goes through ``mm`` (a control swaps it for a
product in lower precision), the router's alone is always float32.

Parameters are one FLAT dict ``{the program's leaf name: array}``, a leaf a
layer (the layers differ in kind, so nothing is stacked): each is drawn on
the device by a call of its own from ``(seed, name)``, so 5.27B parameters
never exist in float32, and the program's model takes the same arrays in
(``models/lfm2.py``) without a second copy.  Drawn where the published
initializer has a constant, so that a dropped leaf shows: RMSNorm gains ``1
+ normal(0, 0.02)``, ``expert_bias`` ``normal(0, 0.02)`` (a checkpoint holds
a trained one).  The convolution's taps are ``normal(0, K ** -0.5)``: the
operator keeps its input's scale and the state weighs in the logits.

``jax_enable_x64`` may be on in this process, so every dtype is explicit.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def sizes(cfg):
    H, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "H": H, "nh": nh,
            "nkv": int(cfg["num_key_value_heads"]), "hd": H // nh,
            "I": int(cfg["intermediate_size"]),
            "F": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
            "V": int(cfg["vocab_size"]), "K": int(cfg["conv_L_cache"]),
            "dense": int(cfg["num_dense_layers"]),
            "types": list(cfg["layer_types"]),
            # what ``compare.serve_numbers`` pads a request to: the served
            # context, not the 128,000 positions the config declares
            "P": int(cfg.get("serve_positions")
                     or cfg["max_position_embeddings"]),
            "eps": float(cfg["norm_eps"]),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "topk_eps": float(cfg.get("norm_topk_eps", 1e-6)),
            "bias": bool(cfg["use_expert_bias"])}


def param_shapes(cfg):
    """``{leaf name: shape}`` under the program's names."""
    z = sizes(cfg)
    H, hd = z["H"], z["hd"]
    out = {"model.embed_tokens.weight": (z["V"], H),
           "model.embedding_norm.weight": (H,)}
    for i, kind in enumerate(z["types"]):
        p = f"model.layers.{i}."
        out[p + "operator_norm.weight"] = (H,)
        out[p + "ffn_norm.weight"] = (H,)
        if kind == "conv":
            out[p + "conv.in_proj.weight"] = (H, 3 * H)
            out[p + "conv.conv_weight"] = (H, z["K"])
            out[p + "conv.out_proj.weight"] = (H, H)
        else:
            a = p + "self_attn."
            out[a + "q_proj.weight"] = (H, z["nh"] * hd)
            out[a + "k_proj.weight"] = (H, z["nkv"] * hd)
            out[a + "v_proj.weight"] = (H, z["nkv"] * hd)
            out[a + "out_proj.weight"] = (z["nh"] * hd, H)
            out[a + "q_layernorm.weight"] = (hd,)
            out[a + "k_layernorm.weight"] = (hd,)
        f = p + "feed_forward."
        if i < z["dense"]:
            out[f + "w1.weight"] = (H, z["I"])
            out[f + "w3.weight"] = (H, z["I"])
            out[f + "w2.weight"] = (z["I"], H)
        else:
            out[f + "gate_weight"] = (H, z["E"])
            out[f + "w_gate"] = (z["E"], H, z["F"])
            out[f + "w_up"] = (z["E"], H, z["F"])
            out[f + "w_down"] = (z["E"], z["F"], H)
            out[f + "e_score_correction_bias"] = (z["E"],)
    return out


def n_params(cfg):
    """Parameters held: every leaf but the selection bias, a buffer."""
    return sum(int(np.prod(s)) for n, s in param_shapes(cfg).items()
               if not n.endswith("e_score_correction_bias"))


def seed_key(seed):
    """A PRNG key from any whole-number seed, also one past 32 signed bits."""
    seed = int(seed) % (1 << 62)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _is_gain(name):
    return name.endswith(("norm.weight", "layernorm.weight"))


def init_params(seed, cfg, dtype=F32, shape=None):
    """Every leaf in ``dtype`` (the selection bias in float32), drawn on the
    device by a call of its own from ``(seed, leaf name)``.

    ``shape`` (a cell's ``weights``) bends the published initializer
    towards what a trained checkpoint looks like: ``outlier_channels``
    hidden channels, drawn from the seed and the same in every layer, get
    the gain of every RMSNorm over the hidden size times ``outlier_gain``:
    the few loud channels trained transformers carry (Bondarenko et al.
    2021, arXiv:2109.12948; Dettmers et al. 2022, arXiv:2208.07339), and
    what makes a product in int8 lose more than one in bfloat16.  The norm
    in front of a ROUTER is left as drawn: six channels that carry 40% of
    its input would choose the experts alone, where a trained router (and
    its trained bias) spreads the load.
    """
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    shape = shape or {}
    n_loud = int(shape.get("outlier_channels", 0))
    loud_gain = float(shape.get("outlier_gain", 1.0))
    root = seed_key(seed)
    loud = jax.random.permutation(
        jax.random.fold_in(root, 0x10AD), z["H"])[:n_loud]

    quiet = {f"model.layers.{i}.ffn_norm.weight"
             for i in range(z["dense"], z["L"])}

    def draw(name, dims):
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        scale, kind = std, jnp.dtype(dtype)
        if name.endswith("conv_weight"):
            scale = z["K"] ** -0.5
        elif name.endswith("e_score_correction_bias"):
            kind = jnp.dtype(F32)
        gain = _is_gain(name)
        return _drawer(dims, scale, gain, loud_gain
                       if gain and dims == (z["H"],) and name not in quiet
                       else 1.0, kind)(key, loud)

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


def _shift(s, n):
    """``s`` [B, S, H] moved ``n`` positions later, zeros in front."""
    return jnp.pad(s, ((0, 0), (n, 0), (0, 0)))[:, :s.shape[1]]


def _conv(u, p, pre, z, mm):
    gate_b, gate_c, zz = jnp.split(mm(u, p[pre + "in_proj.weight"]), 3, -1)
    s = gate_b * zz
    w = p[pre + "conv_weight"]
    taps = z["K"]
    c = sum(w[:, k] * _shift(s, taps - 1 - k) for k in range(taps))
    return mm(gate_c * c, p[pre + "out_proj.weight"])


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def _attention(u, p, pre, z, mm):
    B, S, _ = u.shape
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    q = mm(u, p[pre + "q_proj.weight"]).reshape(B, S, nh, hd)
    k = mm(u, p[pre + "k_proj.weight"]).reshape(B, S, nkv, hd)
    v = mm(u, p[pre + "v_proj.weight"]).reshape(B, S, nkv, hd)
    q = _rms(q, p[pre + "q_layernorm.weight"], z["eps"])
    k = _rms(k, p[pre + "k_layernorm.weight"], z["eps"])
    inv = 1.0 / (z["theta"] ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)
    q, k = _rope(q, jnp.cos(ang), jnp.sin(ang)), \
        _rope(k, jnp.cos(ang), jnp.sin(ang))
    # query head h reads KV head h // (nh // nkv); one KV head's group at a
    # time, so that the scores of 4,096 positions fit beside the weights
    q = jnp.moveaxis(q.reshape(B, S, nkv, nh // nkv, hd), 2, 0)
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))

    def group(qkv):
        qg, kg, vg = qkv                     # [B,S,g,hd], [B,S,hd], [B,S,hd]
        s = jnp.einsum("bqgd,bkd->bgqk", qg, kg) * F32(hd ** -0.5)
        s = jnp.where(causal, s, F32(-1e30))
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(s, -1), vg)

    a = jax.lax.map(group, (q, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    a = jnp.moveaxis(a, 0, 2)                # [B, S, nkv, g, hd]
    return mm(a.reshape(B, S, nh * hd), p[pre + "out_proj.weight"])


def route(h, router_w, bias, z):
    """``(expert ids [T, k], weights [T, k])`` in float32."""
    p = jax.nn.sigmoid(jnp.dot(h.astype(F32), router_w.astype(F32),
                               precision=HIGHEST))
    chosen = p + bias.astype(F32) if z["bias"] else p
    _, idx = jax.lax.top_k(chosen, z["k"])
    w = jnp.take_along_axis(p, idx, -1)
    if z["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + F32(z["topk_eps"]))
    return idx, w * F32(z["scale"])


def _experts(h, p, pre, z, mm):
    """``(y, load [E])``: every expert over every position, its result
    weighted by the router's weight there (nought where it was not
    chosen)."""
    shape = h.shape
    h = h.reshape(-1, shape[-1])
    idx, w = route(h, p[pre + "gate_weight"],
                   p[pre + "e_score_correction_bias"], z)
    chose = idx[:, :, None] == jnp.arange(z["E"], dtype=idx.dtype)[None, None]
    gate = jnp.sum(jnp.where(chose, w[:, :, None], F32(0.0)), 1)   # [T, E]

    def one(acc, xs):
        wg, wu, wd, g = xs
        y = mm(jax.nn.silu(mm(h, wg.astype(F32))) * mm(h, wu.astype(F32)),
               wd.astype(F32))
        return acc + g[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p[pre + "w_gate"], p[pre + "w_up"],
                         p[pre + "w_down"], gate.T))
    return y.reshape(shape), chose.any(1)


def _dense_ff(h, p, pre, mm):
    return mm(jax.nn.silu(mm(h, p[pre + "w1.weight"]))
              * mm(h, p[pre + "w3.weight"]), p[pre + "w2.weight"])


def hidden_and_load(params, ids, cfg, mm=dense):
    """``(final hidden states [B, S, H] float32, chosen [L_moe, B*S, E]
    bool)``: which experts each position chose in each expert layer."""
    z = sizes(cfg)

    class _F32(dict):
        def __missing__(self, name):       # a leaf is upcast where it is used
            return params[name].astype(F32)

    p = _F32()
    big = ("w_gate", "w_up", "w_down")     # upcast an expert at a time
    for name in params:
        if name.endswith(big):
            p[name] = params[name]
    x = p["model.embed_tokens.weight"][ids]
    chosen = []
    for i, kind in enumerate(z["types"]):
        pre = f"model.layers.{i}."
        u = _rms(x, p[pre + "operator_norm.weight"], z["eps"])
        if kind == "conv":
            x = x + _conv(u, p, pre + "conv.", z, mm)
        else:
            x = x + _attention(u, p, pre + "self_attn.", z, mm)
        h = _rms(x, p[pre + "ffn_norm.weight"], z["eps"])
        if i < z["dense"]:
            x = x + _dense_ff(h, p, pre + "feed_forward.", mm)
        else:
            y, chose = _experts(h, p, pre + "feed_forward.", z, mm)
            x = x + y
            chosen.append(chose)
    x = _rms(x, p["model.embedding_norm.weight"], z["eps"])
    return x, (jnp.stack(chosen) if chosen else None)


# ---- the load per expert over what was compared ---------------------------
#: summed over every call of ``logits`` with the reference's own product:
#: ``[L_moe, E]`` assignments, and the calls counted
LOAD = {"counts": None, "calls": 0}


def load_summary():
    """Busiest expert over the mean and the experts no position chose, a
    layer and over all layers, of what ``logits`` has seen."""
    c = LOAD["counts"]
    if c is None:
        return None
    per_layer = c.max(1) * c.shape[1] / np.maximum(c.sum(1), 1)
    return {"requests": LOAD["calls"], "assignments": int(c.sum()),
            "busiest_over_mean_by_layer": [round(float(v), 3)
                                           for v in per_layer],
            "busiest_over_mean": round(float(per_layer.max()), 3),
            "experts_untouched_by_layer": [int(v) for v in (c == 0).sum(1)]}


def _note_load(counts):
    counts = np.asarray(counts, np.int64)
    LOAD["counts"] = counts if LOAD["counts"] is None \
        else LOAD["counts"] + counts
    LOAD["calls"] += 1
    if LOAD["calls"] % 4 == 0:       # a run compares 12 requests
        print(f"[chipbench] lfm2 reference expert load {load_summary()}",
              flush=True)


def logits(params, ids, cfg, mm=dense):
    """Logits ``[B, S, V]`` in float32.  With the reference's own product
    the experts' load over the REAL positions (up to the last id that is not
    the pad, 0) joins ``LOAD`` and its summary is printed."""
    h, chosen = hidden_and_load(params, ids, cfg, mm)
    if chosen is not None and mm is dense:
        at = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
        last = jnp.max(jnp.where(ids != 0, at, -1), 1, keepdims=True)
        real = (at <= last).reshape(-1)
        counts = jnp.sum(chosen & real[None, :, None], 1, dtype=jnp.int32)
        jax.debug.callback(_note_load, counts)
    return mm(h, params["model.embed_tokens.weight"].astype(F32).T)
