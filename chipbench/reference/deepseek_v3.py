"""DeepSeek-V3's decoder in plain ``jax.numpy`` and float32: the reference
of the ``deepseek_v3`` family (Kanana-2-30B-A3B publishes under it).

Follows DeepSeek-V3 (arXiv:2412.19437 section 2.1) and the public
``modeling_deepseek_v3.py`` / ``config.json``:

- layer: ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; FFN is a
  SwiGLU of ``intermediate_size`` in the first ``first_k_dense_replace``
  layers and the expert layer after them; final RMSNorm, untied head;
- MLA: ``q = x W_q`` as ``[q_nope | q_rope]`` per head; ``x W_kva`` as
  ``[c | k_rope]`` with ``k_rope`` one head shared by all; ``RMSNorm(c)
  W_kvb`` as ``[k_nope | v]`` per head; rotary at ``rope_theta`` on the rope
  parts with ``rope_interleave`` (pairs ``(2i, 2i+1)`` de-interleaved to
  halves, then rotated as halves); causal softmax of ``q k^T /
  sqrt(qk_nope + qk_rope)``; ``rope_scaling`` null;
- experts: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` experts are
  the top of ``s + b`` (``n_group = topk_group = 1``: no group limit);
  weights ``routed_scaling_factor * s_i / (sum of the chosen s + 1e-20)``;
  ``y = sum_i w_i SwiGLU_i(x) + SwiGLU_shared(x)``; no token is dropped.

The share of a deployment (``experts_held`` experts from ``expert_offset``
on, a sliced vocabulary) is the configuration's: the router keeps its
width, and what experts held elsewhere would add is left out.  A plain
dense mask over the held experts: every expert sees every token, weight 0
where it was not chosen.  Imports nothing of the program.  Departures:

- ``b`` (``e_score_correction_bias``) starts at zeros (published
  checkpoints hold a trained one) and is trained as DeepSeek-V3 section
  2.1.2 says: after every step ``b_i += bias_update_speed * sign(mean load -
  load_i)`` over the step's tokens and all the router's experts, the held
  and the absent alike.  It takes part in the selection only.  There is no
  sequence-wise auxiliary loss: it is not in ``config.json``.
- Weights are drawn from the seed, normal(0, ``initializer_range``) as the
  published initializer; RMSNorm gains are 1 + normal(0, 0.02) where the
  published initializer has ones, so that a dropped gain shows.

Parameters are one dict of arrays stacked by layer: attention and norms
over all layers, the dense FFN over the leading ones, the expert leaves
over the rest.  ``init_params`` hands them back on the HOST: at 576M
parameters the comparison's own copy (``compare.reference_training`` keeps
one beside the training state) does not fit on the chip with the float32
state, gradients and activations of a step.  ``jax_enable_x64`` may be on
in this process, so every dtype here is explicit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
ATTN_KEYS = ("ln1", "q_w", "kva_w", "kv_norm", "kvb_w", "o_w", "ln2")
DENSE_KEYS = ("ffn_gate", "ffn_up", "ffn_down")
EXPERT_KEYS = ("router_w", "exp_gate", "exp_up", "exp_down",
               "sh_gate", "sh_up", "sh_down")
GAINS = ("ln1", "ln2", "kv_norm", "norm_f")
#: (batch, head) rows of attention computed at once: ``[ATTN_BLOCK, S, S]``
#: float32 scores; tokens an expert layer's dense mask takes at once:
#: ``[EXPERT_ROWS, held * F]`` float32 hidden values.  Blocks, so that a step
#: at the published widths fits beside its float32 state (16 GB a chip).
ATTN_BLOCK = 2
EXPERT_ROWS = 1024
LOSS_ROWS = 1024


def sizes(cfg):
    """``n_routed_experts`` counts the experts held here; a configuration
    that holds a share gives the router's width as ``router_experts`` and
    its first expert as ``expert_offset``."""
    held = int(cfg["n_routed_experts"])
    return {
        "L": int(cfg["num_hidden_layers"]), "H": int(cfg["hidden_size"]),
        "nh": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "vd": int(cfg["v_head_dim"]),
        "rank": int(cfg["kv_lora_rank"]), "I": int(cfg["intermediate_size"]),
        "F": int(cfg["moe_intermediate_size"]),
        "E": int(cfg.get("router_experts", held)), "held": held,
        "offset": int(cfg.get("expert_offset", 0)),
        "shared": int(cfg["n_shared_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "Ld": int(cfg["first_k_dense_replace"]), "V": int(cfg["vocab_size"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg.get("norm_topk_prob", True)),
        "theta": float(cfg["rope_theta"]),
        "interleave": bool(cfg.get("rope_interleave", True)),
        "eps": float(cfg["rms_norm_eps"])}


def param_shapes(cfg):
    z = sizes(cfg)
    L, H, nh, I, F, V = z["L"], z["H"], z["nh"], z["I"], z["F"], z["V"]
    Ld, Lm, held, Fs = z["Ld"], z["L"] - z["Ld"], z["held"], z["shared"] * F
    return {
        "embed": (V, H), "head": (H, V), "norm_f": (H,),
        "ln1": (L, H), "q_w": (L, H, nh * (z["nope"] + z["rope"])),
        "kva_w": (L, H, z["rank"] + z["rope"]), "kv_norm": (L, z["rank"]),
        "kvb_w": (L, z["rank"], nh * (z["nope"] + z["vd"])),
        "o_w": (L, nh * z["vd"], H), "ln2": (L, H),
        "ffn_gate": (Ld, H, I), "ffn_up": (Ld, H, I), "ffn_down": (Ld, I, H),
        "router_w": (Lm, H, z["E"]),
        "exp_gate": (Lm, held, H, F), "exp_up": (Lm, held, H, F),
        "exp_down": (Lm, held, F, H),
        "sh_gate": (Lm, H, Fs), "sh_up": (Lm, H, Fs), "sh_down": (Lm, Fs, H)}


def n_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def seed_key(seed):
    """A PRNG key from any whole-number seed, also one past 32 signed bits."""
    seed = int(seed) % (1 << 62)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def init_params(seed, cfg, dtype=F32, shape=None):
    """All weights in one jitted call, in ``dtype``, handed back on the
    host (the module's docstring says why).  ``shape`` is the serving
    cells' bend of the initializer; this family has no such cell."""
    if shape:
        raise ValueError("the deepseek_v3 reference shapes no weights")
    shapes = param_shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def make(key):
        out = {}
        for i, (name, dims) in enumerate(sorted(shapes.items())):
            w = jax.random.normal(jax.random.fold_in(key, i), dims, F32)
            w = w * F32(0.02) + F32(1.0) if name in GAINS else w * F32(std)
            out[name] = w.astype(dtype)
        return out

    return jax.device_get(jax.jit(make)(seed_key(seed)))


# ------------------------------------------------------------------ forward
def dense(x, w):
    """The reference's matrix product.  A control swaps this for a product in
    lower precision (``lower_precision.py``)."""
    return jnp.matmul(x, w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + F32(eps)) * g


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _rotate(x, positions, z):
    """Rotary embedding of ``x`` [B, S, heads, rope] at ``positions`` [S]."""
    d = z["rope"]
    if z["interleave"]:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = F32(1.0) / (F32(z["theta"]) ** (jnp.arange(0, d, 2, dtype=F32)
                                          / F32(d)))
    ang = positions.astype(F32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def _attend(q, k, v, scale):
    """Causal softmax attention, ``q, k`` [B, S, nh, dq], ``v`` [B, S, nh,
    dv], a block of ``ATTN_BLOCK`` (batch, head) rows at a time so that the
    scores of a long sequence fit."""
    B, S, nh, _ = q.shape
    rows = B * nh
    block = math.gcd(rows, ATTN_BLOCK)

    def split(x):
        x = jnp.moveaxis(x, 2, 1).reshape(rows, S, x.shape[-1])
        return x.reshape(rows // block, block, S, x.shape[-1])

    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))

    @jax.checkpoint
    def one(qkv):
        qb, kb, vb = qkv
        s = jnp.einsum("gqd,gkd->gqk", qb, kb) * F32(scale)
        p = jax.nn.softmax(jnp.where(causal, s, F32(-1e30)), axis=-1)
        return jnp.einsum("gqk,gkd->gqd", p, vb)

    out = jax.lax.map(one, (split(q), split(k), split(v)))
    return jnp.moveaxis(out.reshape(B, nh, S, v.shape[-1]), 1, 2)


def _mla(x, lp, z, mm):
    B, S, _ = x.shape
    nh, nope, rd, vd, rank = z["nh"], z["nope"], z["rope"], z["vd"], z["rank"]
    positions = jnp.arange(S)
    q = mm(x, lp["q_w"]).reshape(B, S, nh, nope + rd)
    kva = mm(x, lp["kva_w"])
    kv = mm(_rms(kva[..., :rank], lp["kv_norm"], z["eps"]),
            lp["kvb_w"]).reshape(B, S, nh, nope + vd)
    k_rope = _rotate(kva[..., rank:].reshape(B, S, 1, rd), positions, z)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], positions, z)],
                        -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, S, nh, rd))], -1)
    a = _attend(q, k, kv[..., nope:], (nope + rd) ** -0.5)
    return mm(a.reshape(B, S, nh * vd), lp["o_w"])


def route(x, router_w, b, z, mm):
    """``(expert ids [T, k], weights [T, k])`` of tokens ``x`` [T, H]: the
    selection bias ``b`` [E] chooses, the scores without it weigh."""
    s = jax.nn.sigmoid(mm(x, router_w))
    _, idx = jax.lax.top_k(s + b, z["k"])
    w = jnp.take_along_axis(s, idx, -1)
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + F32(1e-20))
    return idx, w * F32(z["scale"])


def _experts(x, lp, z, mm):
    """The held experts' part of the routed sum plus the shared experts,
    for tokens ``x`` [T, H].  The dense mask: every held expert's SwiGLU of
    every token, the experts side by side along the width (one product
    ``[T, H] x [H, held * F]`` is the held experts' products with their own
    ``[H, F]`` each), weighted by the router's weight where the expert was
    chosen and by 0 where not, and summed by the down product over ``held *
    F``.  Also the tokens each of the router's experts was chosen by."""
    idx, w = route(x, lp["router_w"], lp["router_b"], z, mm)
    load = jnp.sum(idx[:, :, None] == jnp.arange(z["E"])[None, None], (0, 1),
                   dtype=jnp.int32)
    held, H, F = lp["exp_gate"].shape
    chosen = idx[:, :, None] == (jnp.arange(held) + z["offset"])[None, None]
    mine = jnp.sum(jnp.where(chosen, w[:, :, None], F32(0.0)), 1)  # [T, held]
    gate, up = (jnp.moveaxis(lp[k], 0, 1).reshape(H, held * F)
                for k in ("exp_gate", "exp_up"))
    down = lp["exp_down"].reshape(held * F, H)

    @jax.checkpoint
    def rows(block):
        xb, mb = block
        h = jax.nn.silu(mm(xb, gate)) * mm(xb, up)
        h = (h.reshape(-1, held, F) * mb[:, :, None]).reshape(-1, held * F)
        return mm(h, down)

    T = x.shape[0]
    n = T // math.gcd(T, EXPERT_ROWS)
    y = jax.lax.map(rows, (x.reshape(n, T // n, H),
                           mine.reshape(n, T // n, held))).reshape(T, H)
    y = y + _swiglu(x, lp["sh_gate"], lp["sh_up"], lp["sh_down"], mm)
    return y, load


def _layer(x, lp, z, mm, sparse):
    x = x + _mla(_rms(x, lp["ln1"], z["eps"]), lp, z, mm)
    h = _rms(x, lp["ln2"], z["eps"])
    if not sparse:
        return x + _swiglu(h, lp["ffn_gate"], lp["ffn_up"], lp["ffn_down"],
                           mm), None
    B, S, H = h.shape
    y, load = _experts(h.reshape(B * S, H), lp, z, mm)
    return x + y.reshape(B, S, H), load


def no_bias(cfg):
    """The selection bias of every expert layer as a run starts it."""
    z = sizes(cfg)
    return jnp.zeros((z["L"] - z["Ld"], z["E"]), F32)


def hidden(params, ids, cfg, mm=dense, remat=False, bias=None):
    """``(final hidden states [B, S, H], load [expert layers, E])`` in
    float32 for token ids ``[B, S]`` under the selection ``bias`` (zeros if
    none is given).  The leading dense layers one by one, the expert layers
    under ``lax.scan``, so that they compile as one block."""
    z = sizes(cfg)
    p = {k: jnp.asarray(v).astype(F32) for k, v in params.items()}
    Ld = z["Ld"]

    def layer(sparse):
        def body(x, lp):
            return _layer(x, lp, z, mm, sparse)
        return jax.checkpoint(body) if remat else body

    x = p["embed"][ids]
    for i in range(Ld):
        lp = {k: p[k][i] for k in ATTN_KEYS + DENSE_KEYS}
        x, _ = layer(False)(x, lp)
    rest = {k: p[k][Ld:] for k in ATTN_KEYS}
    rest.update({k: p[k] for k in EXPERT_KEYS})
    rest["router_b"] = no_bias(cfg) if bias is None else bias
    x, load = jax.lax.scan(layer(True), x, rest)
    return _rms(x, p["norm_f"], z["eps"]), load


def logits(params, ids, cfg, mm=dense, remat=False):
    h, _ = hidden(params, ids, cfg, mm, remat)
    return mm(h, jnp.asarray(params["head"]).astype(F32))


def balanced(bias, load, cfg):
    """The selection bias after a step that routed ``load`` [expert layers,
    E] tokens: an expert under the mean load gains ``bias_update_speed``, one
    over it loses as much (DeepSeek-V3 section 2.1.2)."""
    mean = jnp.mean(load.astype(F32), -1, keepdims=True)
    return bias + F32(cfg.get("bias_update_speed", 0.0)) * jnp.sign(
        mean - load.astype(F32))


def lm_loss(params, ids, labels, cfg, mm=dense, remat=True, bias=None,
            with_load=False):
    """Mean next-token cross-entropy over all ``B*(S-1)`` positions, as the
    program's model defines its loss when given ``labels``; the head's
    logits ``LOSS_ROWS`` positions at a time.  ``with_load`` hands back
    ``(loss, load)``."""
    h, load = hidden(params, ids, cfg, mm, remat, bias)
    h = h[:, :-1]
    head = jnp.asarray(params["head"]).astype(F32)
    h, want = h.reshape(-1, h.shape[-1]), labels[:, 1:].reshape(-1)
    n = len(want) // math.gcd(len(want), LOSS_ROWS)

    @jax.checkpoint
    def rows(block):
        hb, wb = block
        lg = mm(hb, head)
        picked = jnp.take_along_axis(lg, wb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)

    sums = jax.lax.map(rows, (h.reshape(n, -1, h.shape[-1]),
                              want.reshape(n, -1)))
    loss = jnp.sum(sums) / F32(len(want))
    return (loss, load) if with_load else loss


# ----------------------------------------------------------------- training
def train_steps(params, batches, cfg, hyper, mm=dense):
    """Follow the program's first steps in float32 at ``highest``.

    ``batches`` is a list of ``(ids, labels)``.  Returns each step's loss,
    the first step's gradient (on the host) and the parameters after the
    last step.  The selection bias starts at zeros and moves after every
    step by that step's load (``balanced``); it is no parameter.  At the published widths a step's gradients and activations
    do not fit beside the whole float32 state (16 GB a chip): the
    optimizer's state waits on the host while a gradient is computed, and
    parameters and state are updated in place.
    """
    import importlib

    optim = importlib.import_module(f"{__package__}.{hyper['name']}")
    grad = jax.jit(lambda p, ids, labels, bias: jax.value_and_grad(
        lm_loss, has_aux=True)(p, ids, labels, cfg, mm, bias=bias,
                               with_load=True))
    update = jax.jit(lambda p, g, state, t: optim.update(p, g, state, t, hyper),
                     donate_argnums=(0, 2))
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.array(x, F32) for k, x in params.items()}   # our own
        losses, g1, state, bias = [], None, None, no_bias(cfg)
        for i, (ids, labels) in enumerate(batches):
            (loss, load), g = grad(p, ids, labels, bias)
            bias = balanced(bias, load, cfg)
            losses.append(float(loss))
            if i == 0:
                g1 = jax.device_get(g)
            state = optim.init_state(p, hyper) if state is None \
                else jax.device_put(state)
            p, state = update(p, g, state, F32(i + 1))
            del g
            if i + 1 < len(batches):
                state = jax.device_get(state)
    return losses, g1, p


def leaf_norms(tree, cfg):
    """Norm of every leaf, per layer for a stacked one (an expert leaf's
    held experts together): ``{name: [floats]}`` (a list of one for an
    unstacked leaf)."""
    out = {}
    for k, x in tree.items():
        x = jnp.asarray(x).astype(F32)
        if k in ATTN_KEYS + DENSE_KEYS + EXPERT_KEYS:
            n = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), -1))
        else:
            n = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
        out[k] = [float(a) for a in n]
    return out
