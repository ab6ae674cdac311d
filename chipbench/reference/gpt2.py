"""GPT-2 in plain ``jax.numpy`` and float32: the reference of the
``gpt2`` family.

Follows Radford et al. 2019 / the public ``config.json`` of
``openai-community/gpt2-medium``: learned positions, pre-LayerNorm blocks
(eps 1e-5), one fused QKV projection, causal softmax attention, a 4x GELU
feed-forward, a final LayerNorm and an output head tied to the token
embedding.  Imports nothing of the program.  Departures, each noted:

- GELU is the exact (erf) form, as the program's ``hidden_act="gelu"``; the
  published config says ``gelu_new`` (tanh form).
- The fused QKV projection's columns are head-major, ``[head, (q,k,v),
  head_dim]``; the published checkpoint's are ``[(q,k,v), head, head_dim]``.
  With weights drawn from a seed that is a permutation of columns.
- Weights are drawn from the seed, normal(0, 0.02) as the published
  initializer, the two projections into the residual scaled by
  ``1/sqrt(2*n_layer)``; biases and LayerNorm offsets are drawn too
  (normal(0, 0.02), gains 1 + normal(0, 0.02)) where the published
  initializer has zeros and ones, so that a dropped bias shows.

Parameters are one dict of stacked arrays (layer axis first), so the layers
run under ``lax.scan`` and the whole model compiles as one block.
``jax_enable_x64`` may be on in this process (the program turns it on), so
every dtype here is explicit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
LAYER_KEYS = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
              "ln2_g", "ln2_b", "fc_w", "fc_b", "fc2_w", "fc2_b")


def sizes(cfg):
    H = int(cfg["n_embd"])
    inner = cfg.get("n_inner") or 4 * H
    return {"L": int(cfg["n_layer"]), "H": H, "nh": int(cfg["n_head"]),
            "I": int(inner), "V": int(cfg["vocab_size"]),
            "P": int(cfg["n_positions"]),
            "eps": float(cfg.get("layer_norm_epsilon", 1e-5))}


def param_shapes(cfg):
    z = sizes(cfg)
    L, H, I, V, P = z["L"], z["H"], z["I"], z["V"], z["P"]
    return {"wte": (V, H), "wpe": (P, H), "lnf_g": (H,), "lnf_b": (H,),
            "ln1_g": (L, H), "ln1_b": (L, H), "qkv_w": (L, H, 3 * H),
            "qkv_b": (L, 3 * H), "proj_w": (L, H, H), "proj_b": (L, H),
            "ln2_g": (L, H), "ln2_b": (L, H), "fc_w": (L, H, I),
            "fc_b": (L, I), "fc2_w": (L, I, H), "fc2_b": (L, H)}


def n_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def seed_key(seed):
    """A PRNG key from any whole-number seed, also one past 32 signed bits."""
    seed = int(seed) % (1 << 62)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def init_params(seed, cfg, dtype=F32, shape=None):
    """All weights in one jitted call on the device, in ``dtype``.

    ``shape`` (a cell's ``weights``, where it has one) bends the published
    initializer towards what a trained checkpoint looks like, so that greedy
    decoding does not fall into repeating one token at wide margins:

    - ``position_scale``: the position embedding's deviation times this, so
      that what comes next depends on where it stands;
    - ``outlier_channels``, ``outlier_gain``: that many hidden channels,
      drawn from the seed and the same in every layer, get every
      LayerNorm's gain times ``outlier_gain``: the few channels of large
      magnitude that trained transformers carry (Bondarenko et al. 2021,
      arXiv:2109.12948; Dettmers et al. 2022, arXiv:2208.07339), and what
      makes a product in int8 lose more than one in bfloat16.
    """
    shapes = param_shapes(cfg)
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    shape = shape or {}
    position = float(shape.get("position_scale", 1.0))
    n_out = int(shape.get("outlier_channels", 0))
    gain = float(shape.get("outlier_gain", 1.0))

    def make(key):
        out = {}
        loud = jax.random.permutation(
            jax.random.fold_in(key, len(shapes)), z["H"])[:n_out]
        for i, (name, dims) in enumerate(sorted(shapes.items())):
            w = jax.random.normal(jax.random.fold_in(key, i), dims, F32) * std
            if name in ("proj_w", "fc2_w"):
                w = w / math.sqrt(2.0 * z["L"])
            if name == "wpe":
                w = w * position
            if name.endswith("_g"):
                w = (w + 1.0).at[..., loud].multiply(gain)
            out[name] = w.astype(dtype)
        return out

    return jax.jit(make)(seed_key(seed))


# ------------------------------------------------------------------ forward
def dense(x, w):
    """The reference's matrix product.  A control swaps this for a product in
    lower precision (``lower_precision.py``)."""
    return jnp.matmul(x, w)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _block(x, lp, z, mm):
    B, S, H = x.shape
    nh, hd = z["nh"], H // z["nh"]
    h = _ln(x, lp["ln1_g"], lp["ln1_b"], z["eps"])
    qkv = (mm(h, lp["qkv_w"]) + lp["qkv_b"]).reshape(B, S, nh, 3, hd)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * F32(hd ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    s = jnp.where(causal, s, F32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H)
    x = x + mm(a, lp["proj_w"]) + lp["proj_b"]
    h = _ln(x, lp["ln2_g"], lp["ln2_b"], z["eps"])
    h = jax.nn.gelu(mm(h, lp["fc_w"]) + lp["fc_b"], approximate=False)
    return x + mm(h, lp["fc2_w"]) + lp["fc2_b"]


def hidden(params, ids, cfg, mm=dense, remat=False):
    """Final hidden states ``[B, S, H]`` in float32 for token ids
    ``[B, S]``."""
    z = sizes(cfg)
    p = {k: v.astype(F32) for k, v in params.items()}
    S = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][jnp.arange(S)][None]
    layers = {k: p[k] for k in LAYER_KEYS}

    def body(x, lp):
        return _block(x, lp, z, mm), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, layers)
    return _ln(x, p["lnf_g"], p["lnf_b"], z["eps"])


def logits(params, ids, cfg, mm=dense, remat=False):
    h = hidden(params, ids, cfg, mm, remat)
    return mm(h, params["wte"].astype(F32).T)


def lm_loss(params, ids, labels, cfg, mm=dense, remat=True):
    """Mean next-token cross-entropy over all ``B*(S-1)`` positions, as the
    program's ``GPTForCausalLM(labels=...)`` defines its loss."""
    lg = logits(params, ids, cfg, mm, remat)[:, :-1]
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


# ----------------------------------------------------------------- training
def train_steps(params, batches, cfg, hyper, mm=dense):
    """Follow the program's first steps in float32 at ``highest``.

    ``batches`` is a list of ``(ids, labels)``.  Returns each step's loss,
    the first step's gradient and the parameters after the last step (both
    dicts of stacked float32 arrays).
    """
    import importlib

    optim = importlib.import_module(f"{__package__}.{hyper['name']}")

    @jax.jit
    def one(p, state, t, ids, labels):
        loss, g = jax.value_and_grad(lm_loss)(p, ids, labels, cfg, mm)
        p, state = optim.update(p, g, state, t, hyper)
        return loss, g, p, state

    with jax.default_matmul_precision("highest"):
        p = {k: x.astype(F32) for k, x in params.items()}
        state = optim.init_state(p, hyper)
        losses, g1 = [], None
        for i, (ids, labels) in enumerate(batches):
            loss, g, p, state = one(p, state, F32(i + 1), ids, labels)
            losses.append(float(loss))
            if i == 0:
                g1 = g
            del g
    return losses, g1, p


def split_qkv_bias(x, cfg):
    """The fused QKV bias ``[..., 3H]`` (head-major columns) as its three
    parts ``[..., 3, H]``.  They are leaves of their own in every norm that
    ``correct`` compares: a key's bias has no gradient under softmax (it
    shifts every score of a row alike), so a third of the fused leaf moves
    under Adam by round-off alone."""
    nh = int(cfg["n_head"])
    y = x.reshape(x.shape[:-1] + (nh, 3, x.shape[-1] // (3 * nh)))
    return jnp.moveaxis(y, -2, -3).reshape(x.shape[:-1] + (3, -1))


def leaf_norms(tree, cfg):
    """Norm of every leaf, per layer for a stacked one: ``{name: [floats]}``
    (a list of one for an unstacked leaf).  ``qkv_b`` gives ``qkv_b.q``,
    ``qkv_b.k`` and ``qkv_b.v`` (``split_qkv_bias``)."""
    out = {}
    for k, x in tree.items():
        x = x.astype(F32)
        if k == "qkv_b":
            n = jnp.sqrt(jnp.sum(jnp.square(split_qkv_bias(x, cfg)), -1))
            for j, part in enumerate("qkv"):
                out[f"{k}.{part}"] = [float(a) for a in n[:, j]]
            continue
        if k in LAYER_KEYS:
            n = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), -1))
        else:
            n = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
        out[k] = [float(a) for a in n]
    return out
