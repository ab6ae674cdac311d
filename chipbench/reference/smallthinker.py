"""SmallThinker in plain ``jax.numpy`` and float32: the reference of the
``smallthinker`` family.

Follows the public ``config.json`` of ``PowerInfer/SmallThinker-21BA3B-
Instruct`` and the family's public description (the public modeling file
is not in this repository; what the config does not say is listed under
``assumed`` in the configuration's file).  With ``n(x) =
x * rsqrt(mean(x^2) + rms_norm_eps) * g``, layer ``l`` of kind ``s =
sliding_window_layout[l]``:

- ``a = n_1(x)``; the router reads ``a``: ``softmax(a W_r)`` over the 64
  experts in float32, the top 6, their weights renormalised over the 6;
- ``h = x + attn_s(a)``: 28 query heads of 128 over 4 KV heads (7 a KV
  head), scale ``128 ** -0.5``; ``s = 1``: rotary positions (halves
  rotated, theta ``rope_theta``) on q and k and key j visible to query i
  iff ``i - window < j <= i``; ``s = 0``: no positions, ``j <= i``;
- ``out = h + sum_e w_e W_down_e(relu(b W_gate_e) * (b W_up_e))``, ``b =
  n_2(h)``; after the last layer ``n_f``, then the untied head.

Attention runs a block of queries at a time (a window layer over the keys
its window reaches); each expert runs over the tokens routed to it, a
chunk of rows at a time, so 16,384 positions fit.  No cache, no kernels,
no batching tricks; imports nothing of the program.  Every product with a
weight goes through ``mm`` (a control swaps it for a product in lower
precision); the router's is always float32.

The weights (:class:`Weights`) are a mapping ``{the program's leaf name:
array}`` that DRAWS a leaf where it is read, on the device, from ``(seed,
name)``: the program takes them leaf by leaf (``models/smallthinker.py``),
and inside the reference's jitted forward a layer's leaves are drawn when
that layer runs.  So the 7.4 GB of weights never sit beside the logits of
16,384 positions over 151,936 ids (10 GB in float32), which the comparison
(``compare.serve_numbers``) takes whole.  RMSNorm gains are ``1 + normal(0,
0.02)`` where the published initializer has ones, so that a dropped gain
shows.

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
NEG = F32(-1e30)
#: query positions a block of the attention, rows a chunk of an expert
Q_BLOCK, ROWS = 512, 512


def sizes(cfg):
    return {"L": int(cfg["num_hidden_layers"]),
            "H": int(cfg["hidden_size"]),
            "nh": int(cfg["num_attention_heads"]),
            "nkv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg["head_dim"]), "F": int(cfg["moe_ffn_hidden_size"]),
            "E": int(cfg["moe_num_primary_experts"]),
            "k": int(cfg["moe_num_active_primary_experts"]),
            "V": int(cfg["vocab_size"]),
            # what ``compare.serve_numbers`` pads a request to: the served
            # context, which is the published one
            "P": int(cfg.get("serve_positions")
                     or cfg["max_position_embeddings"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "window": int(cfg["sliding_window_size"]),
            "rope": [int(r) for r in cfg["rope_layout"]],
            "swa": [int(s) for s in cfg["sliding_window_layout"]],
            "norm_topk": bool(cfg["norm_topk_prob"])}


def param_shapes(cfg):
    """``{leaf name: shape}`` under the program's names."""
    z = sizes(cfg)
    H, hd, E, F = z["H"], z["hd"], z["E"], z["F"]
    out = {"model.embed_tokens.weight": (z["V"], H),
           "model.norm.weight": (H,), "lm_head.weight": (H, z["V"])}
    for i in range(z["L"]):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = (H,)
        out[p + "post_attention_layernorm.weight"] = (H,)
        a = p + "self_attn."
        out[a + "q_proj.weight"] = (H, z["nh"] * hd)
        out[a + "k_proj.weight"] = (H, z["nkv"] * hd)
        out[a + "v_proj.weight"] = (H, z["nkv"] * hd)
        out[a + "o_proj.weight"] = (z["nh"] * hd, H)
        f = p + "block_sparse_moe."
        out[f + "gate_weight"] = (H, E)
        out[f + "w_gate"] = (E, H, F)
        out[f + "w_up"] = (E, H, F)
        out[f + "w_down"] = (E, F, H)
    return out


def n_params(cfg):
    return sum(int(np.prod(s)) for s in param_shapes(cfg).values())


def seed_key(seed):
    """A PRNG key from any whole-number seed, also one past 32 signed bits."""
    seed = int(seed) % (1 << 62)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


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


@jax.tree_util.register_pytree_node_class
class Weights:
    """The weights of a seed, as a read-only mapping that draws a leaf where
    it is read.  A pytree whose one array is the seed's key, so a jitted
    function that takes it draws inside itself.  ``spec`` holds what a
    draw needs: the shapes, the type, the initializer's scale and the loud
    channels (:func:`init_params`)."""

    def __init__(self, key_data, spec):
        self.key_data, self.spec = key_data, spec

    def tree_flatten(self):
        return (self.key_data,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, leaves):
        return cls(leaves[0], spec)

    def with_key(self, key_data):
        return Weights(key_data, self.spec)

    def keys(self):
        return [name for name, _ in self.spec[0]]

    def __iter__(self):
        return iter(self.keys())

    def __len__(self):
        return len(self.spec[0])

    def __contains__(self, name):
        return name in dict(self.spec[0])

    def __getitem__(self, name):
        shapes, kind, std, hidden, n_loud, loud_gain, quiet = self.spec
        dims = dict(shapes)[name]
        root = jax.random.wrap_key_data(self.key_data)
        loud = jax.random.permutation(jax.random.fold_in(root, 0x10AD),
                                      hidden)[:n_loud]
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        gain = name.endswith("norm.weight")
        return _drawer(dims, std, gain, loud_gain
                       if gain and name not in quiet else 1.0,
                       jnp.dtype(kind))(key, loud)


def init_params(seed, cfg, dtype=F32, shape=None):
    """The :class:`Weights` of ``seed`` in ``dtype``.

    ``shape`` (a cell's ``weights``) bends the initializer towards what a
    trained checkpoint looks like, as ``reference/lfm2.py`` does:
    ``outlier_channels`` hidden channels, drawn from the seed and the same
    in every layer, get the gain of every RMSNorm over the hidden size
    times ``outlier_gain`` (Bondarenko et al. 2021, arXiv:2109.12948;
    Dettmers et al. 2022, arXiv:2208.07339).  The norm that feeds the
    ROUTER (``input_layernorm``, which attention reads too) is left as
    drawn: a few channels that carry most of its input would choose the
    experts alone."""
    z = sizes(cfg)
    shape = shape or {}
    quiet = tuple(f"model.layers.{i}.input_layernorm.weight"
                  for i in range(z["L"]))
    spec = (tuple((n, tuple(s)) for n, s in param_shapes(cfg).items()),
            jnp.dtype(dtype).name, float(cfg.get("initializer_range", 0.02)),
            z["H"], int(shape.get("outlier_channels", 0)),
            float(shape.get("outlier_gain", 1.0)), quiet)
    return Weights(jax.random.key_data(seed_key(seed)), spec)


# ------------------------------------------------------------------ forward
def dense(x, w):
    """The reference's matrix product.  A control swaps this for a product in
    lower precision (``lower_precision.py``)."""
    return jnp.matmul(x, w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope(x, pos, theta):
    """``x`` [S, h, d] rotated at positions ``pos`` [S] (halves rotated)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = d // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def _attention(a, p, pre, z, rotary, window, mm):
    """One sequence ``a`` [S, H]: a block of ``Q_BLOCK`` queries at a time,
    against every key (full layer) or the ``window + Q_BLOCK`` keys the
    block's windows reach."""
    S = a.shape[0]
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    g = nh // nkv
    q = mm(a, p[pre + "q_proj.weight"].astype(F32)).reshape(S, nh, hd)
    k = mm(a, p[pre + "k_proj.weight"].astype(F32)).reshape(S, nkv, hd)
    v = mm(a, p[pre + "v_proj.weight"].astype(F32)).reshape(S, nkv, hd)
    pos = jnp.arange(S, dtype=jnp.int32)
    if rotary:
        q, k = _rope(q, pos, z["theta"]), _rope(k, pos, z["theta"])
    block_q = min(Q_BLOCK, S)
    blocks = -(-S // block_q)
    q = jnp.pad(q, ((0, blocks * block_q - S), (0, 0), (0, 0)))
    q = q.reshape(blocks, block_q, nkv, g, hd)
    span = S if window is None else min(S, window + block_q)

    def block(args):
        qb, first = args                  # [Q, nkv, g, hd], position
        start = 0 if window is None else jnp.clip(first - window, 0,
                                                  S - span)
        kb = jax.lax.dynamic_slice_in_dim(k, start, span)
        vb = jax.lax.dynamic_slice_in_dim(v, start, span)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, kb) * F32(hd ** -0.5)
        i = first + jnp.arange(block_q, dtype=jnp.int32)[:, None]
        j = start + jnp.arange(span, dtype=jnp.int32)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        s = jnp.where(seen, s, NEG)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, -1), vb)

    firsts = jnp.arange(0, blocks * block_q, block_q, dtype=jnp.int32)
    out = jax.lax.map(block, (q, firsts)).reshape(-1, nh * hd)[:S]
    return mm(out, p[pre + "o_proj.weight"].astype(F32))


def route(a, router_w, z):
    """``(expert ids [T, k], weights [T, k])`` in float32."""
    scores = jax.nn.softmax(jnp.dot(a.astype(F32), router_w.astype(F32),
                                    precision=HIGHEST), -1)
    w, idx = jax.lax.top_k(scores, z["k"])
    if z["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return idx, w


def _experts(b, idx, w, p, pre, z, mm):
    """``sum_e w_e expert_e(b)`` over the (token, expert) pairs the router
    chose: each expert over the tokens routed to it, ``ROWS`` at a time."""
    T, k, E = b.shape[0], z["k"], z["E"]
    flat = idx.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True)
    token = (order // k).astype(jnp.int32)
    weight = w.reshape(-1)[order]
    counts = jnp.bincount(flat, length=E).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    wg_all, wu_all, wd_all = (p[pre + n] for n in ("w_gate", "w_up",
                                                   "w_down"))

    def expert(e, y):
        wg, wu, wd = (x[e].astype(F32) for x in (wg_all, wu_all, wd_all))

        def chunk(c, y):
            rows = starts[e] + c * ROWS + jnp.arange(ROWS, dtype=jnp.int32)
            live = rows < starts[e] + counts[e]
            rows = jnp.where(live, rows, 0)
            t = token[rows]
            xs = b[t]
            out = mm(jax.nn.relu(mm(xs, wg)) * mm(xs, wu), wd)
            return y.at[t].add(jnp.where(live, weight[rows], F32(0.0))[:, None]
                               * out)

        return jax.lax.fori_loop(0, (counts[e] + ROWS - 1) // ROWS, chunk, y)

    return jax.lax.fori_loop(0, E, expert, jnp.zeros_like(b))


def _layer(x, p, i, z, mm):
    pre = f"model.layers.{i}."
    a = _rms(x, p[pre + "input_layernorm.weight"], z["eps"])
    window = z["window"] if z["swa"][i] else None
    h = x + _attention(a, p, pre + "self_attn.", z, bool(z["rope"][i]),
                       window, mm)
    b = _rms(h, p[pre + "post_attention_layernorm.weight"], z["eps"])
    f = pre + "block_sparse_moe."
    idx, w = route(a, p[f + "gate_weight"], z)
    return h + _experts(b, idx, w, p, f, z, mm)


def hidden(params, ids, cfg, mm=dense):
    """``(final hidden states [S, H] float32, key)`` of ONE sequence
    ``ids`` [S].  Each layer's weights are drawn after the layer before it
    ran (an ``optimization_barrier`` ties the key to the activations), so
    a compiled forward holds one layer's weights at a time; the key comes
    back tied to the last of them, for the head."""
    z = sizes(cfg)
    x = params["model.embed_tokens.weight"][ids].astype(F32)
    for i in range(z["L"]):
        x, key = jax.lax.optimization_barrier((x, params.key_data))
        x = _layer(x, params.with_key(key), i, z, mm)
    x, key = jax.lax.optimization_barrier((x, params.key_data))
    return _rms(x, params.with_key(key)["model.norm.weight"], z["eps"]), key


#: the real lengths (up to the last id that is not the pad, 0) of the
#: sequences ``logits`` has run with the reference's own product
LENGTHS = []


def _note_lengths(lengths, window):
    LENGTHS.extend(int(n) for n in lengths)
    if len(LENGTHS) % 12 == 0:       # a run compares 12 or 24 requests
        print(f"[chipbench] smallthinker reference lengths "
              f"{{'requests': {len(LENGTHS)}, 'past_window': "
              f"{sum(n > window for n in LENGTHS)}, 'past_8192': "
              f"{sum(n > 8192 for n in LENGTHS)}, 'longest': "
              f"{max(LENGTHS)}}}", flush=True)


def logits(params, ids, cfg, mm=dense):
    """Logits ``[B, S, V]`` in float32.  With the reference's own product
    the real length of each row joins ``LENGTHS`` and a summary is
    printed: how many compared requests outgrew the window."""
    out = []
    for row in range(ids.shape[0]):
        h, key = hidden(params, ids[row], cfg, mm)
        out.append(mm(h, params.with_key(key)["lm_head.weight"].astype(F32)))
    if mm is dense:
        at = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
        jax.debug.callback(functools.partial(
            _note_lengths, window=sizes(cfg)["window"]),
            jnp.max(jnp.where(ids != 0, at, -1), 1) + 1)
    return jnp.stack(out)
