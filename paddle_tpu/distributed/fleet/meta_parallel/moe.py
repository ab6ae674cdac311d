"""Expert parallelism / MoE (reference:
python/paddle/incubate/distributed/models/moe/ — MoELayer with expert
placement, all-to-all dispatch/combine, gshard/switch gating and the
load-balancing aux loss).

TPU-native design: the classic GShard einsum formulation — routing builds
STATIC-shape dispatch/combine tensors (tokens x experts x capacity), expert
FFNs are a single vmapped weight stack with the expert dim laid out over
the mesh's expert axis, and the partitioner materializes the all-to-alls
from the shardings.  No ragged tensors, no per-expert kernel launches —
everything is three einsums and one vmapped matmul pair, exactly what the
MXU wants.

``DroplessMoELayer`` is the other formulation (DeepSeek-V3, arXiv:2412.19437
section 2.1.2): sigmoid scores, top-k with a selection bias, no capacity and
no dropped token.  Tokens are sorted into expert order and the experts are
grouped products (``ops.grouped_matmul``: XLA's ``ragged_dot`` or the repo's
kernel, by the shapes) over the rows each one really got; no ``[G, E, C]``
tensor exists.  The layer is told which experts it holds
(``experts_held``, ``expert_offset``): the router scores all of them, the
layer computes the part of the sum its own experts give.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ....nn import functional as F  # noqa: F401 (activation lookup)
from ....nn.layer import Layer
from ....ops.grouped_matmul import grouped_matmul
from ....profiler import metrics as _metrics
from ....tensor.dispatch import apply as _apply, unwrap
from ....tensor.tensor import Tensor
from ...topology import get_hybrid_communicate_group


def _ep_mesh():
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return None, None
    for ax in ("ep", "sep", "mp", "sharding", "dp"):
        if ax in hcg.mesh.axis_names and hcg.mesh.shape[ax] > 1:
            return hcg.mesh, ax
    return None, None


def top2_gating(logits, capacity, dtype=jnp.float32):
    """GShard top-2 gating: returns (dispatch [G,E,C] bool-ish, combine
    [G,E,C], aux_loss).  G = tokens, E = experts, C = capacity."""
    G, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    idx1 = jnp.argmax(probs, axis=-1)
    mask1 = jax.nn.one_hot(idx1, E, dtype=jnp.float32)
    probs2 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, E, dtype=jnp.float32)

    # aux load-balance loss (Switch/GShard): E * sum_e fraction_e * prob_e
    density = mask1.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux = (density * density_proxy).sum() * E

    # positions within each expert's buffer, first-come-first-served
    pos1 = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1
    mask1 = mask1 * (pos1 < capacity)
    pos_base = jnp.sum(mask1, axis=0, keepdims=True)
    pos2 = (jnp.cumsum(mask2, axis=0) - 1.0) * mask2 + pos_base
    mask2 = mask2 * (pos2 < capacity)

    g1 = (probs * mask1).sum(-1)
    g2 = (probs * mask2).sum(-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    p1 = (pos1 * mask1).sum(-1)
    p2 = (pos2 * mask2).sum(-1)
    disp1 = jax.nn.one_hot(idx1, E, dtype=jnp.float32)[:, :, None] * \
        jax.nn.one_hot(p1.astype(jnp.int32), capacity, dtype=jnp.float32)[:, None, :] * \
        mask1.sum(-1)[:, None, None]
    disp2 = jax.nn.one_hot(idx2, E, dtype=jnp.float32)[:, :, None] * \
        jax.nn.one_hot(p2.astype(jnp.int32), capacity, dtype=jnp.float32)[:, None, :] * \
        mask2.sum(-1)[:, None, None]
    combine = disp1 * g1[:, None, None] + disp2 * g2[:, None, None]
    dispatch = (combine > 0.0).astype(dtype)
    return dispatch, combine.astype(dtype), aux.astype(dtype)


class MoELayer(Layer):
    """Mixture-of-experts FFN block (reference MoELayer).

    Args follow the reference shape: d_model, d_hidden, num_experts, top_k
    (2 supported), capacity_factor.  ``aux_loss`` holds the last forward's
    load-balancing loss (add it to the training loss).
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=2.0, act="gelu", gate=None, experts=None,
                 moe_group=None, **kw):
        super().__init__()
        if top_k != 2:
            raise NotImplementedError("MoELayer implements top-2 (GShard) gating")
        if gate is not None or experts is not None:
            raise NotImplementedError(
                "custom gate/experts modules are not supported; MoELayer owns "
                "a linear gate and a stacked expert FFN (the einsum/EP design)")
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.act_name = act
        from ....nn import initializer as I

        self.gate_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=I.XavierUniform())
        # stacked expert FFNs: [E, d_model, d_hidden], [E, d_hidden, d_model]
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden],
                                        default_initializer=I.XavierUniform())
        self.b1 = self.create_parameter([num_experts, d_hidden], is_bias=True)
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model],
                                        default_initializer=I.XavierUniform())
        self.b2 = self.create_parameter([num_experts, d_model], is_bias=True)
        mesh, ax = _ep_mesh()
        if mesh is not None and num_experts % mesh.shape[ax] == 0:
            for p in (self.w1, self.b1, self.w2, self.b2):
                spec = P(ax, *([None] * (p.ndim - 1)))
                p._value = jax.device_put(p._value, NamedSharding(mesh, spec))
        self.aux_loss = None

    def forward(self, x):
        """x: [B, S, d_model] (or [G, d_model])."""
        orig_shape = x.shape
        E = self.num_experts
        act_name = self.act_name
        cap_f = self.capacity_factor

        def fn(xv, gw, w1, b1, w2, b2):
            lead = xv.shape[:-1]
            d = xv.shape[-1]
            g = 1
            for s in lead:
                g *= s
            tokens = xv.reshape(g, d)
            capacity = max(int(cap_f * g * 2 / E), 4)
            logits = tokens.astype(jnp.float32) @ gw.astype(jnp.float32)
            dispatch, combine, aux = top2_gating(logits, capacity)
            # [G,E,C] x [G,d] -> [E,C,d]  (the all-to-all under EP sharding)
            exp_in = jnp.einsum("gec,gd->ecd", dispatch, tokens.astype(jnp.float32))
            h = jnp.einsum("ecd,edh->ech", exp_in, w1.astype(jnp.float32)) + \
                b1[:, None, :].astype(jnp.float32)
            h = getattr(jax.nn, act_name)(h)
            out = jnp.einsum("ech,ehd->ecd", h, w2.astype(jnp.float32)) + \
                b2[:, None, :].astype(jnp.float32)
            y = jnp.einsum("gec,ecd->gd", combine, out)
            return y.reshape(xv.shape).astype(xv.dtype), aux

        out, aux = _apply(fn, x, self.gate_weight, self.w1, self.b1, self.w2,
                          self.b2, op_name="moe", n_outs=None)
        self.aux_loss = aux
        return out


# ------------------------------------------------------------ dropless MoE
_m_assignments = _metrics.counter(
    "moe.local_assignments",
    "(token, expert) assignments routed to experts this process holds")
_m_load = _metrics.histogram(
    "moe.expert_load_max_over_mean",
    "busiest held expert's assignments over the mean of the held experts, "
    "one observation per expert layer and publish_load()",
    buckets=(1.0, 1.05, 1.1, 1.2, 1.35, 1.5, 2.0, 3.0, 5.0, 10.0, 100.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, order, inv, top_k):
    """``x[order // top_k]``: token rows ``[T, H]`` copied into assignment
    order ``[T * top_k, H]``.  Backward is a gather too (``inv`` undoes
    ``order``), not the scatter-add autodiff would write."""
    return x[order // top_k]


def _dispatch_fwd(x, order, inv, top_k):
    return x[order // top_k], inv


def _dispatch_bwd(top_k, inv, g):
    gx = g[inv].reshape(-1, top_k, g.shape[-1])
    return gx.sum(1, dtype=jnp.float32).astype(g.dtype), None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect_rows(y, order, inv):
    """``y[inv]``: rows in assignment order back into ``(token, choice)``
    order; the backward gathers by ``order``."""
    return y[inv]


def _collect_fwd(y, order, inv):
    return y[inv], order


def _collect_bwd(order, g):
    return g[order], None, None


_collect_rows.defvjp(_collect_fwd, _collect_bwd)


def sigmoid_topk(x, router_w, bias, top_k, scale=1.0, normalize=True,
                 eps=1e-20):
    """DeepSeek-V3's router: ``(expert ids [T, k], weights [T, k] f32)``.
    Scores are ``sigmoid(x W_r)`` in float32 whatever ``x`` is; the k
    experts are the top k of ``score + bias``, the weights come from the
    scores without the bias, normalised over the k (their sum + ``eps``:
    the model states it, 1e-20 for DeepSeek-V3, 1e-6 for LFM2) and
    scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        picked = picked / (picked.sum(-1, keepdims=True) + jnp.float32(eps))
    return idx, picked * jnp.float32(scale)


def routed_experts(x, router_w, bias, w_gate, w_up, w_down, *, top_k,
                   expert_offset=0, scale=1.0, normalize=True, eps=1e-20):
    """The held experts' part of a dropless MoE over tokens ``x`` [T, H]:
    ``(y [T, H], load [router's experts] int32)``, the load being the tokens
    each expert the router scores was chosen by, held here or not.

    ``w_gate``/``w_up`` [held, H, F] and ``w_down`` [held, F, H] are experts
    ``expert_offset .. expert_offset + held`` of the ``router_w.shape[1]``
    the router scores.  An assignment to an expert held elsewhere adds
    nothing here.  Every buffer is as long as all ``T * top_k``
    assignments, so no load drops a token; the grouped products touch only
    the rows that exist."""
    T, H = x.shape
    held = w_gate.shape[0]
    with jax.named_scope("moe_route"):
        idx, weights = sigmoid_topk(x, router_w, bias, top_k, scale,
                                    normalize, eps)
        local = idx - expert_offset
        here = (local >= 0) & (local < held)
        # assignments held elsewhere sort behind the last held expert
        key = jnp.where(here, local, held).reshape(-1).astype(jnp.int32)
        rows = jnp.arange(T * top_k, dtype=jnp.int32)
        _, order = jax.lax.sort((key, rows), num_keys=1)
        _, inv = jax.lax.sort((order, rows), num_keys=1)
        load = (idx.reshape(-1, 1) == jnp.arange(
            router_w.shape[1], dtype=idx.dtype)[None]).sum(0, dtype=jnp.int32)
        counts = load[expert_offset:expert_offset + held]
        exists = (rows < counts.sum())[:, None]
        xs = _dispatch_rows(x, order, inv, top_k)
    with jax.named_scope("moe_experts"):
        # a row past the last group is whatever the kernel left there, in a
        # product's result and in its gradient towards the rows alike: every
        # operand and result is masked, so neither reaches a token
        def real(rows_):
            return jnp.where(exists, rows_, jnp.zeros((), rows_.dtype))

        xs = real(xs)
        h = real(jax.nn.silu(grouped_matmul(xs, w_gate, counts))
                 * grouped_matmul(xs, w_up, counts))
        ys = real(grouped_matmul(h, w_down, counts))
    with jax.named_scope("moe_route"):
        per_choice = _collect_rows(ys, order, inv).reshape(T, top_k, H)
        y = jnp.einsum("tkh,tk->th", per_choice.astype(jnp.float32),
                       jnp.where(here, weights, jnp.float32(0.0)))
    return y.astype(x.dtype), load


def _swiglu(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


class DroplessMoELayer(Layer):
    """Sigmoid-routed experts without dropped tokens, with always-on shared
    experts (DeepSeek-V3 section 2.1.2; HF ``DeepseekV3MoE``).

    ``num_experts`` is the router's width.  The layer holds experts
    ``expert_offset .. expert_offset + experts_held`` (all of them by
    default), as one chip of an expert-parallel group would, and computes
    their part of the sum; the exchange between chips is not here.
    ``num_shared_experts`` shared experts are one SwiGLU of that many times
    ``d_expert``.  ``e_score_correction_bias`` is a buffer: it takes part in
    the selection only and has no gradient.  With ``bias_update_speed`` > 0
    a training call balances the load without an auxiliary loss, as section
    2.1.2 says (DeepSeek-V3 trained at 0.001): after the call every expert
    the router scores that got fewer tokens than the mean gains that much
    bias, every one that got more loses it.  The load is counted over the
    call's tokens and all ``num_experts``, the absent ones too; chips that
    each see a part of a batch would sum their counts first, which is not
    here.
    ``tokens_per_expert`` counts the assignments each held expert has got
    since the layer was built.
    ``norm_topk_eps`` is what the normalisation adds to the picked scores'
    sum (the model's to state).  ``param_init(name, shape)``, where given,
    makes each weight in place of the normal initializer: a model too large
    to be drawn in float32 and cast hands its leaves over in their own type.
    """

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 experts_held=None, expert_offset=0, num_shared_experts=0,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 initializer_range=0.02, bias_update_speed=0.0,
                 norm_topk_eps=1e-20, param_init=None):
        super().__init__()
        from ....nn import initializer as I

        held = num_experts if experts_held is None else int(experts_held)
        if not 0 <= expert_offset <= num_experts - held:
            raise ValueError(
                f"experts {expert_offset}..{expert_offset + held} are not "
                f"among the router's {num_experts}")
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.experts_held, self.expert_offset = held, int(expert_offset)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.norm_topk_eps = float(norm_topk_eps)
        self.bias_update_speed = float(bias_update_speed)
        init = I.Normal(0.0, initializer_range)

        def weight(name, *shape):
            made = init if param_init is None \
                else lambda shape_, dtype: param_init(name, shape_)
            return self.create_parameter(list(shape),
                                         default_initializer=made)

        self.gate_weight = weight("gate_weight", d_model, num_experts)
        self.w_gate = weight("w_gate", held, d_model, d_expert)
        self.w_up = weight("w_up", held, d_model, d_expert)
        self.w_down = weight("w_down", held, d_expert, d_model)
        self.num_shared_experts = int(num_shared_experts)
        if num_shared_experts:
            d_shared = num_shared_experts * d_expert
            self.shared_gate = weight("shared_gate", d_model, d_shared)
            self.shared_up = weight("shared_up", d_model, d_shared)
            self.shared_down = weight("shared_down", d_shared, d_model)
        self.register_buffer("e_score_correction_bias",
                             jnp.zeros((num_experts,), jnp.float32))
        self.register_buffer("tokens_per_expert",
                             jnp.zeros((held,), jnp.int32))
        self._published = None

    def routed(self, x):
        """``(y, this call's load over all the router's experts)`` of the
        held experts alone; no buffer is touched (``count()`` does that), so
        this may run under ``recompute``."""
        top_k, offset = self.top_k, self.expert_offset
        scale, norm = self.routed_scaling_factor, self.norm_topk_prob
        eps = self.norm_topk_eps

        def fn(xv, rw, bias, wg, wu, wd):
            y, load = routed_experts(
                xv.reshape(-1, xv.shape[-1]), rw, bias, wg.astype(xv.dtype),
                wu.astype(xv.dtype), wd.astype(xv.dtype), top_k=top_k,
                expert_offset=offset, scale=scale, normalize=norm, eps=eps)
            return y.reshape(xv.shape), load

        # no op_name: autocast must not round the router's bias, and the
        # products take the activations' type by themselves
        return _apply(fn, x, self.gate_weight, self.e_score_correction_bias,
                      self.w_gate, self.w_up, self.w_down, n_outs=2)

    def shared(self, x):
        @jax.named_scope("moe_shared")
        def fn(xv, wg, wu, wd):
            return _swiglu(xv, wg.astype(xv.dtype), wu.astype(xv.dtype),
                           wd.astype(xv.dtype))

        return _apply(fn, x, self.shared_gate, self.shared_up,
                      self.shared_down)

    def count(self, load):
        """Take a call's load in: the held experts' part joins
        ``tokens_per_expert``, and a training call moves the selection bias
        towards an even load."""
        load = unwrap(load)
        first = self.expert_offset
        self.tokens_per_expert._value = self.tokens_per_expert._value \
            + load[first:first + self.experts_held]
        if self.training and self.bias_update_speed:
            short = jnp.mean(load.astype(jnp.float32)) - load
            self.e_score_correction_bias._value = \
                self.e_score_correction_bias._value \
                + jnp.float32(self.bias_update_speed) * jnp.sign(short)

    def forward(self, x, return_load=False):
        """x: [B, S, d_model] (or [T, d_model]).  With ``return_load`` the
        call's load is handed back and no buffer touched: for a caller under
        ``recompute``, who takes it in outside (``count()``)."""
        y, load = self.routed(x)
        if self.num_shared_experts:
            y = y + self.shared(x)
        if return_load:
            return y, load
        self.count(load)
        return y

    def publish_load(self):
        """What ``tokens_per_expert`` has gained since the last call, into
        ``moe.local_assignments`` and ``moe.expert_load_max_over_mean``.
        Reads the device: ``TrainStep.sync()`` calls it, a step does not."""
        import numpy as np

        now = np.asarray(self.tokens_per_expert._value).astype(np.int32)
        last = np.zeros_like(now) if self._published is None \
            else self._published
        gained = (now - last).astype(np.int64)   # int32 wraps; the gain holds
        self._published = now
        total = int(gained.sum())
        if total > 0:
            _m_assignments.inc(total)
            _m_load.observe(float(gained.max()) * len(gained) / total)
        return gained
