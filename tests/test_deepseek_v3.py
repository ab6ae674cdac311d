"""The DeepSeek-V3 family (Kanana-2-30B-A3B's ``model_type``) at toy sizes on
the CPU in float32, each piece against a plain reference: flash attention
where v's head size is not q's (kernels interpreted), latent attention,
the dropless expert layer and its shares, and the whole model through
``paddle.jit.TrainStep``."""

import importlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed.fleet.meta_parallel import DroplessMoELayer
from paddle_tpu.profiler import metrics as prof_metrics
from paddle_tpu.text.models import DeepseekV3Config, DeepseekV3ForCausalLM
from paddle_tpu.text.models.deepseek_v3 import DeepseekV3Attention

fa = importlib.import_module("paddle_tpu.ops.flash_attention")
moe = importlib.import_module("paddle_tpu.distributed.fleet.meta_parallel.moe")

TOY = dict(vocab_size=97, hidden_size=32, intermediate_size=48,
           moe_intermediate_size=8, num_hidden_layers=3, num_attention_heads=4,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
           v_head_dim=6, n_routed_experts=8, n_shared_experts=2,
           num_experts_per_tok=3)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30)


# ------------------------------------------------ flash attention, d_v != d_qk
@pytest.fixture
def interpreted(monkeypatch):
    real = pl.pallas_call
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))


def _qkvw(b, s, h, d, dv, seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(b, s, h, d), jnp.float32),
            jnp.asarray(rs.randn(b, s, h, d), jnp.float32),
            jnp.asarray(rs.randn(b, s, h, dv), jnp.float32),
            jnp.asarray(rs.randn(b, s, h, dv), jnp.float32))


def _ref_bshd(q, k, v, causal=True):
    b, s, h, d = q.shape
    flat = [jnp.moveaxis(x, 2, 1).reshape(b * h, s, x.shape[3])
            for x in (q, k, v)]
    o = fa._ref_attention(*flat, d ** -0.5, causal)
    return jnp.moveaxis(o.reshape(b, h, s, v.shape[3]), 1, 2)


def _grads(attend):
    def loss(q, k, v, w):
        return jnp.sum(attend(q, k, v) * w)
    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("d,dv", [(192, 128), (24, 16), (128, 256), (64, 64)])
def test_flash_kernels_take_a_value_head_size_of_their_own(interpreted, d, dv):
    """Forward, dk/dv and dq kernels (interpreted) against ``_ref_attention``
    and its autodiff; q/k and v each padded to their own lane multiple."""
    q, k, v, w = _qkvw(1, 256, 2, d, dv)
    got = fa.flash_attention_fn(q, k, v, causal=True)
    assert got.shape == (1, 256, 2, dv)
    _close(got, _ref_bshd(q, k, v))
    for g, want in zip(
            _grads(lambda *a: fa.flash_attention_fn(*a, causal=True))(
                q, k, v, w), _grads(_ref_bshd)(q, k, v, w)):
        _close(g, want, 5e-5)


def test_v_is_never_padded_to_qs_size(interpreted):
    """192 / 128: q and k go to 256 lanes, v and the output stay at 128."""
    seen = []
    real = fa._flash

    def spy(q, k, v, *rest):
        seen.append((q.shape, k.shape, v.shape))
        return real(q, k, v, *rest)

    q, k, v, _ = _qkvw(1, 256, 2, 192, 128)
    with mock.patch.object(fa, "_flash", spy):
        fa.flash_attention_fn(q, k, v, causal=True)
    assert seen == [((2, 256, 256), (2, 256, 256), (2, 256, 128))]


@pytest.mark.parametrize("causal", [True, False])
def test_xla_fallbacks_take_a_value_head_size_of_their_own(causal):
    """Off the chip: ``_ref_attention`` forward and the chunked backward
    behind the same ``custom_vjp``."""
    q, k, v, w = _qkvw(2, 64, 2, 12, 8, seed=1)
    got = fa.flash_attention_fn(q, k, v, causal=causal)
    assert got.shape == (2, 64, 2, 8)

    def flat(x):
        return jnp.moveaxis(x, 2, 1).reshape(4, 64, x.shape[3])

    def chunked(q, k, v):
        o = fa._flash(flat(q), flat(k), flat(v), 12 ** -0.5, causal, 16, 16, 0)
        return jnp.moveaxis(o.reshape(2, 2, 64, 8), 1, 2)

    # _flash's forward is a kernel: take its XLA stand-in for this test
    with mock.patch.object(
            fa, "_flash_fwd",
            lambda q, k, v, scale, causal, bq, bk, off=0, with_lse=False: (
                fa._ref_attention(q, k, v, scale, causal),
                jax.nn.logsumexp(jnp.where(
                    jnp.tril(jnp.ones((64, 64), bool)) | (not causal),
                    jnp.einsum("bqd,bkd->bqk", q, k) * scale, fa.NEG_INF),
                    axis=-1, keepdims=True))):
        grads = _grads(chunked)(q, k, v, w)
    for g, want in zip(grads, _grads(
            lambda *a: _ref_bshd(*a, causal=causal))(q, k, v, w)):
        _close(g, want, 5e-5)


# ------------------------------------------------------------ latent attention
def _plain_mla(x, w, cfg):
    """MLA as HF ``DeepseekV3Attention`` writes it (heads first, the
    interleaved pairs viewed as ``[d/2, 2]`` and transposed), in float32."""
    B, S, _ = x.shape
    nh, nope, rd, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.v_head_dim)
    rank = cfg.kv_lora_rank
    q = (x @ w["q"]).reshape(B, S, nh, nope + rd).transpose(0, 2, 1, 3)
    q_pass, q_rot = q[..., :nope], q[..., nope:]
    ckv = x @ w["kva"]
    c, k_rot = ckv[..., :rank], ckv[..., rank:].reshape(B, 1, S, rd)
    c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True)
                          + cfg.rms_norm_eps) * w["kv_norm"]
    kv = (c @ w["kvb"]).reshape(B, S, nh, nope + vd).transpose(0, 2, 1, 3)
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, rd, 2) / rd))
    ang = np.arange(S)[:, None] * inv[None]
    cos, sin = (np.concatenate([f(ang), f(ang)], -1)[None, None]
                for f in (np.cos, np.sin))

    def rotate(t):
        b, h, s, d = t.shape
        t = t.reshape(b, h, s, d // 2, 2).transpose(0, 1, 2, 4, 3)
        t = t.reshape(b, h, s, d)
        half = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], -1)
        return t * cos + half * sin

    q = jnp.concatenate([q_pass, rotate(q_rot)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(rotate(k_rot), (B, nh, S, rd))], -1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (nope + rd) ** -0.5
    s = jnp.where(np.tril(np.ones((S, S), bool)), s, -1e30)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), kv[..., nope:])
    return o.transpose(0, 2, 1, 3).reshape(B, S, nh * vd) @ w["o"]


def _mla_and_weights():
    paddle.seed(3)
    cfg = DeepseekV3Config(**TOY, initializer_range=0.3)
    att = DeepseekV3Attention(cfg)
    att.kv_a_layernorm.weight._value = 1.0 + 0.1 * jnp.asarray(
        np.random.RandomState(0).randn(cfg.kv_lora_rank), jnp.float32)
    names = {"q": att.q_proj, "kva": att.kv_a_proj_with_mqa,
             "kvb": att.kv_b_proj, "o": att.o_proj,
             "kv_norm": att.kv_a_layernorm}
    return cfg, att, names


def _rope(cfg, S):
    from paddle_tpu.text.models.llama import _rope_cos_sin

    cos, sin = _rope_cos_sin(jnp.arange(S), cfg.qk_rope_head_dim,
                             cfg.rope_theta)
    return paddle.to_tensor(cos), paddle.to_tensor(sin)


def test_mla_forward_and_gradients():
    cfg, att, names = _mla_and_weights()
    x = jnp.asarray(np.random.RandomState(1).randn(2, 12, 32), jnp.float32)
    xt = paddle.to_tensor(x, stop_gradient=False)
    out = att(xt, _rope(cfg, 12))
    w = {k: layer.weight._value for k, layer in names.items()}
    _close(out._value, _plain_mla(x, w, cfg))
    (out * out).sum().backward()
    want = jax.grad(lambda x, w: jnp.sum(_plain_mla(x, w, cfg) ** 2),
                    argnums=(0, 1))(x, w)
    _close(xt.grad._value, want[0], 1e-4)
    for k, layer in names.items():
        _close(layer.weight.grad._value, want[1][k], 1e-4)


def test_mla_rotary_dims_are_interleaved_pairs():
    """With ``rope_interleave`` off the same weights give another answer:
    the pairs are (2i, 2i+1), not (i, i + d/2)."""
    cfg, att, _ = _mla_and_weights()
    x = paddle.to_tensor(np.random.RandomState(1).randn(1, 12, 32)
                         .astype("float32"))
    a = att(x, _rope(cfg, 12))._value
    att.interleave = False
    b = att(x, _rope(cfg, 12))._value
    assert np.abs(np.asarray(a - b)).max() > 1e-3 * np.abs(np.asarray(a)).max()


def test_q_lora_rank_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        DeepseekV3Config(q_lora_rank=1536)


# ----------------------------------------------------------- the expert layer
def _plain_moe(x, layer, experts=None, shared=True):
    """A plain loop over experts: sigmoid scores, the top k of score + bias,
    weights from the scores alone, normalised and scaled."""
    p = {n: np.asarray(t._value, np.float64)
         for n, t in list(layer.named_parameters())
         + list(layer.named_buffers())}
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    s = 1.0 / (1.0 + np.exp(-(x @ p["gate_weight"])))
    idx = np.argsort(-(s + p["e_score_correction_bias"]), -1,
                     kind="stable")[:, :layer.top_k]
    w = np.take_along_axis(s, idx, -1)
    w = layer.routed_scaling_factor * w / (w.sum(-1, keepdims=True) + 1e-20)

    def swiglu(x, g, u, d):
        h = x @ g
        return (h / (1.0 + np.exp(-h)) * (x @ u)) @ d

    y = np.zeros_like(x)
    off = layer.expert_offset
    for e in (range(off, off + layer.experts_held)
              if experts is None else experts):
        mine = np.where(idx == e, w, 0.0).sum(-1)[:, None]
        y += mine * swiglu(x, p["w_gate"][e - off], p["w_up"][e - off],
                           p["w_down"][e - off])
    if shared and layer.num_shared_experts:
        y += swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y, idx


def _layer(held=None, offset=0, shared=2, seed=0, bias=None):
    paddle.seed(seed)
    layer = DroplessMoELayer(16, 8, 8, 3, experts_held=held,
                             expert_offset=offset, num_shared_experts=shared,
                             routed_scaling_factor=2.448,
                             initializer_range=0.5)
    if bias is not None:
        layer.e_score_correction_bias._value = jnp.asarray(bias, jnp.float32)
    return layer


X = np.random.RandomState(5).randn(2, 20, 16).astype("float32")


@pytest.mark.parametrize("bias", [None, "drawn"])
@pytest.mark.parametrize("shared", [0, 2])
def test_expert_layer_against_a_plain_loop(bias, shared):
    if bias == "drawn":
        bias = np.random.RandomState(2).randn(8) * 0.5
    layer = _layer(shared=shared, bias=bias)
    x = paddle.to_tensor(X, stop_gradient=False)
    y = layer(x)
    want, idx = _plain_moe(X, layer)
    _close(y._value.reshape(-1, 16), want)
    counts = np.bincount(idx.reshape(-1), minlength=8)
    assert np.array_equal(layer.tokens_per_expert._value, counts)
    assert counts.sum() == 40 * 3


def test_the_bias_selects_and_does_not_weigh():
    """A large bias on expert 7 puts it among every token's three; its
    weight is still its own sigmoid score's share, not the biased one."""
    bias = np.zeros(8)
    bias[7] = 10.0
    layer = _layer(bias=bias)
    x = jnp.asarray(X.reshape(-1, 16))
    idx, w = moe.sigmoid_topk(x, layer.gate_weight._value,
                              layer.e_score_correction_bias._value, 3, 2.448)
    assert bool(jnp.all(jnp.any(idx == 7, -1)))
    _close(w.sum(-1), np.full(40, 2.448))
    s = jax.nn.sigmoid(x @ layer.gate_weight._value)
    _close(w, 2.448 * jnp.take_along_axis(s, idx, -1)
           / jnp.take_along_axis(s, idx, -1).sum(-1, keepdims=True))
    _close(layer(paddle.to_tensor(X))._value.reshape(-1, 16),
           _plain_moe(X, layer)[0])


def test_expert_layer_gradients():
    layer = _layer()
    x = paddle.to_tensor(X, stop_gradient=False)
    (layer(x) ** 2).sum().backward()
    names = ["gate_weight", "w_gate", "w_up", "w_down", "shared_gate",
             "shared_up", "shared_down"]
    bias = layer.e_score_correction_bias._value

    def plain(x, p):
        x = x.reshape(-1, 16)
        idx, w = moe.sigmoid_topk(x, p["gate_weight"], bias, 3, 2.448)
        y = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])) \
            @ p["shared_down"]
        for e in range(8):
            mine = jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
            y = y + mine * ((jax.nn.silu(x @ p["w_gate"][e])
                             * (x @ p["w_up"][e])) @ p["w_down"][e])
        return jnp.sum(y ** 2)

    p = {n: getattr(layer, n)._value for n in names}
    gx, gp = jax.grad(plain, argnums=(0, 1))(jnp.asarray(X), p)
    _close(x.grad._value, gx, 1e-4)
    for n in names:
        _close(getattr(layer, n).grad._value, gp[n], 1e-4)
    assert layer.e_score_correction_bias.stop_gradient


def test_the_shares_add_up():
    """Four layers holding two experts each (the guide's cut, here 4 chips
    of an 8-expert layer), the shared experts counted once, equal the uncut
    layer: each share routes over all 8 and adds its own experts' part."""
    whole = _layer()
    want = np.asarray(whole(paddle.to_tensor(X))._value, np.float64)
    total = np.asarray(whole.shared(paddle.to_tensor(X))._value, np.float64)
    for chip in range(4):
        part = _layer(held=2, offset=2 * chip, shared=0)
        part.gate_weight._value = whole.gate_weight._value
        for n in ("w_gate", "w_up", "w_down"):
            getattr(part, n)._value = \
                getattr(whole, n)._value[2 * chip:2 * chip + 2]
        y, load = part.routed(paddle.to_tensor(X))
        total += np.asarray(y._value, np.float64)
        # every share counts the load of all 8, and keeps its own two
        assert np.array_equal(load._value, whole.tokens_per_expert._value)
        part.count(load)
        assert np.array_equal(
            part.tokens_per_expert._value,
            whole.tokens_per_expert._value[2 * chip:2 * chip + 2])
        _close(y._value.reshape(-1, 16), _plain_moe(X, part, shared=False)[0])
    _close(total, want)


@pytest.mark.parametrize("held,offset", [(8, 0), (2, 2)])
def test_no_token_is_dropped_at_any_load(held, offset):
    """A bias sends every token to expert 2 (and 3): with all 40 tokens on
    one expert the answer is still the plain loop's, and the count says
    so."""
    bias = np.zeros(8)
    bias[2], bias[3] = 20.0, 10.0
    layer = _layer(held=held, offset=offset, bias=bias)
    y = layer(paddle.to_tensor(X))
    _close(y._value.reshape(-1, 16), _plain_moe(X, layer)[0])
    counts = np.asarray(layer.tokens_per_expert._value)
    assert counts[2 - offset] == 40 and counts[3 - offset] == 40
    assert counts.sum() == (120 if held == 8 else 80)


@pytest.mark.parametrize("held,offset", [(8, 0), (2, 4)])
def test_a_training_call_moves_the_bias_towards_an_even_load(held, offset):
    """DeepSeek-V3 section 2.1.2: after a call every expert of the router's
    8 (held here or not) that got fewer tokens than the mean gains the
    update speed, every one that got more loses it; the weights and an
    ``eval()`` call leave the bias alone."""
    layer = _layer(held=held, offset=offset)
    layer.bias_update_speed = speed = 0.03
    layer.eval()
    layer(paddle.to_tensor(X))
    assert not np.asarray(layer.e_score_correction_bias._value).any()
    layer.train()
    want, idx = _plain_moe(X, layer)
    load = np.bincount(idx.reshape(-1), minlength=8)
    assert load.max() > 15 > load.min()                 # the mean is 15
    _close(layer(paddle.to_tensor(X))._value.reshape(-1, 16), want)
    _close(layer.e_score_correction_bias._value, speed * np.sign(15.0 - load))
    # the next call selects by the moved bias, and the load evens out
    spread = [np.ptp(load)]
    for _ in range(24):
        want, idx = _plain_moe(X, layer)
        _close(layer(paddle.to_tensor(X))._value.reshape(-1, 16), want)
        spread.append(np.ptp(np.bincount(idx.reshape(-1), minlength=8)))
    assert max(spread[-4:]) <= 0.6 * spread[0]


def test_rows_past_the_last_group_never_reach_a_token(monkeypatch):
    """The chip's grouped kernel leaves rows past the last group as they
    were, in its result and in its gradient towards the rows (the CPU's
    writes zeros).  With both poisoned, a share's answer and gradients are
    still the plain loop's."""
    real = jax.lax.ragged_dot

    def past(x, sizes):
        return (jnp.arange(x.shape[0]) >= sizes.sum())[:, None]

    @jax.custom_vjp
    def poisoned(x, w, sizes):
        return jnp.where(past(x, sizes), 1e9, real(x, w, sizes))

    def forward(x, w, sizes):
        return poisoned(x, w, sizes), (x, w, sizes)

    def backward(saved, g):
        x, w, sizes = saved
        dx, dw = jax.vjp(lambda x, w: real(x, w, sizes), x, w)[1](g)
        return jnp.where(past(x, sizes), 1e9, dx), dw, None

    poisoned.defvjp(forward, backward)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    layer = _layer(held=2, offset=2)
    x = paddle.to_tensor(X, stop_gradient=False)
    y = layer(x)
    _close(y._value.reshape(-1, 16), _plain_moe(X, layer)[0])
    (y ** 2).sum().backward()
    monkeypatch.undo()
    clean = _layer(held=2, offset=2)
    xc = paddle.to_tensor(X, stop_gradient=False)
    (clean(xc) ** 2).sum().backward()
    _close(x.grad._value, xc.grad._value, 1e-5)
    for name in ("gate_weight", "w_gate", "w_up", "w_down"):
        _close(getattr(layer, name).grad._value,
               getattr(clean, name).grad._value, 1e-5)


def test_a_share_must_lie_among_the_routers_experts():
    with pytest.raises(ValueError, match="not among"):
        DroplessMoELayer(16, 8, 8, 3, experts_held=4, expert_offset=6)


def test_published_load_is_what_was_gained_since_the_last_time():
    layer = _layer(held=2, offset=2)
    total = prof_metrics.counter("moe.local_assignments")
    load = prof_metrics.histogram("moe.expert_load_max_over_mean")

    def samples():
        return sum(c.count for c in load._children.values())

    before, n = total.total(), samples()
    layer(paddle.to_tensor(X))
    first = layer.publish_load()
    assert first.sum() == layer.tokens_per_expert._value.sum() > 0
    assert total.total() - before == first.sum()
    assert samples() == n + 1
    assert layer.publish_load().sum() == 0          # nothing new, no sample
    assert samples() == n + 1
    # the int32 buffer may wrap; the gain since the last reading holds
    layer.tokens_per_expert._value = jnp.asarray(
        [2 ** 31 - 5, 7], jnp.int32)
    layer.publish_load()
    layer.tokens_per_expert._value = layer.tokens_per_expert._value \
        + jnp.asarray([10, 10], jnp.int32)
    assert list(layer.publish_load()) == [10, 10]


# ------------------------------------------------- the model through TrainStep
def _reference():
    ref = importlib.import_module("chipbench.reference.deepseek_v3")
    family = importlib.import_module("chipbench.models.deepseek_v3")
    cfg = dict(TOY, n_routed_experts=4, router_experts=8, expert_offset=2,
               first_k_dense_replace=1, routed_scaling_factor=2.448,
               rms_norm_eps=1e-6, rope_theta=1000000, initializer_range=0.02)
    return ref, family, cfg


@pytest.mark.parametrize("recompute", [False, True])
def test_one_train_step_loss_and_gradients_leaf_by_leaf(recompute):
    """A share of the toy model (experts 2..5 of 8) through ``TrainStep``:
    the loss and, from AdamW's first moment, every parameter's gradient
    against the plain reference's; the expert layers' counts ride along as
    buffers and ``sync()`` publishes them."""
    ref, family, cfg = _reference()
    params = ref.init_params(11, cfg)
    model = family.build(cfg, params, ref, recompute=recompute)
    model.train()
    optimizer = opt.AdamW(learning_rate=1e-3, beta1=0.9, beta2=0.95,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, optimizer)
    ids = np.random.RandomState(0).randint(0, 97, (2, 24))
    loss = step({"input_ids": paddle.to_tensor(ids),
                 "labels": paddle.to_tensor(ids)})
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(ref.lm_loss)(
            params, jnp.asarray(ids), jnp.asarray(ids), cfg)
    assert abs(float(loss._value) - float(want)) < 1e-5 * float(want)
    before = prof_metrics.counter("moe.local_assignments").total()
    step.sync()
    states = optimizer.state_dict()["states"]
    seen = set()
    for i, (name, _) in enumerate(model.named_parameters()):
        g = family._leaf(grads, name)
        key, _ = family.reference_leaf(name)
        _close(states[str(i)]["m"]._value / 0.1, g, 2e-4)
        seen.add(key)
    assert seen == set(ref.param_shapes(cfg))
    counts = [np.asarray(layer.mlp.tokens_per_expert._value)
              for layer in model.model.layers[1:]]
    assert all(c.shape == (4,) and c.sum() > 0 for c in counts)
    assert prof_metrics.counter("moe.local_assignments").total() - before \
        == sum(c.sum() for c in counts)


def test_the_selection_bias_is_trained_as_the_reference_trains_it():
    """Three steps with ``bias_update_speed`` on: the bias rides through
    ``TrainStep`` as a buffer, moves after every step by the load over all
    8 of the router's experts, and selects in the next step.  Losses and
    the bias of every expert layer against the plain reference's."""
    ref, family, cfg = _reference()
    cfg["bias_update_speed"] = 0.05
    hyper = {"name": "adamw", "learning_rate": 1e-3, "beta1": 0.9,
             "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1}
    params = ref.init_params(11, cfg)
    model = family.build(cfg, params, ref, recompute=True)
    model.train()
    optimizer = opt.AdamW(learning_rate=1e-3, beta1=0.9, beta2=0.95,
                          epsilon=1e-8, weight_decay=0.1,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, optimizer)
    rs = np.random.RandomState(0)
    batches = [(ids, ids) for ids in rs.randint(0, 97, (3, 2, 24))]
    losses = [float(step({"input_ids": paddle.to_tensor(ids),
                          "labels": paddle.to_tensor(ids)})._value)
              for ids, _ in batches]
    step.sync()
    bias = np.stack([np.asarray(layer.mlp.e_score_correction_bias._value)
                     for layer in model.model.layers[1:]])
    # the reference's chain by hand, for the bias it ends with
    optim = importlib.import_module("chipbench.reference.adamw")
    p = {k: jnp.asarray(v) for k, v in params.items()}
    state, want = optim.init_state(p, hyper), ref.no_bias(cfg)
    grad = jax.jit(lambda p, ids, b: jax.value_and_grad(
        ref.lm_loss, has_aux=True)(p, ids, ids, cfg, bias=b, with_load=True))
    with jax.default_matmul_precision("highest"):
        for t, (ids, _) in enumerate(batches):
            (loss, load), g = grad(p, jnp.asarray(ids), want)
            assert abs(float(loss) - losses[t]) < 2e-5 * float(loss)
            assert load.shape == (2, 8) and int(load.sum()) == 2 * 48 * 3
            if t == 1:      # the moved bias selects: zeros give another load
                assert not np.array_equal(
                    load, grad(p, jnp.asarray(ids), ref.no_bias(cfg))[0][1])
            want = ref.balanced(want, load, cfg)
            p, state = optim.update(p, g, state, jnp.float32(t + 1), hyper)
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(bias, want, atol=1e-7)


def test_the_model_returns_logits_without_labels():
    paddle.seed(0)
    model = DeepseekV3ForCausalLM(**TOY).eval()
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 97, (2, 10)))
    assert model(ids).shape == [2, 10, 97]
    assert model(ids, labels=ids).shape == []


# --------------------------------------------------- the names in a device trace
def test_lowered_train_step_names_the_new_scopes():
    """``mla_attention``, ``moe_route``, ``moe_experts`` and ``moe_shared``
    lie under the forward's scope and, as its transpose, in the backward;
    the head keeps ``lm_head_loss``."""
    paddle.seed(0)
    model = DeepseekV3ForCausalLM(recompute=True, **TOY)
    step = paddle.jit.TrainStep(
        model, opt.AdamW(learning_rate=1e-3, parameters=model.parameters()))
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 97, (2, 16)))
    step({"input_ids": ids, "labels": ids})
    args = [step._diff_params, step._opt_state, step._buffers,
            step._frozen_params, step._lr_dev, step._rng_carry]
    text = step._last_fn._jitted.lower(
        *args, *step._last_batch_vals).as_text(debug_info=True)
    for scope in ("mla_attention", "moe_route", "moe_experts", "moe_shared",
                  "lm_head_loss"):
        assert "jvp(forward_loss)" in text and scope in text, scope
        assert any(scope in line and "transpose(jvp(forward_loss))" in line
                   for line in text.splitlines()), scope
    for line in text.splitlines():
        if "ragged_dot" in line and "loc(" in line and "moe_" in line:
            assert "moe_experts" in line


def test_the_flash_kernels_lie_under_mla_attention():
    """Which site carries which name (``tests/test_program_spans.py``'s
    way): the forward kernel is ``flash_fwd`` under ``mla_attention``, the
    two backward kernels carry the scope and no name of their own."""
    spans = importlib.import_module("tests.test_program_spans")
    cfg, att, _ = _mla_and_weights()
    x = jnp.zeros((1, 256, 32), jnp.float32)
    rope = _rope(cfg, 256)

    def loss(x):
        with jax.named_scope("forward_loss"):
            return jnp.sum(att(paddle.to_tensor(x), rope)._value)

    scopes = spans._pallas_scopes(jax.grad(loss), x)
    assert len(scopes) == 3
    assert scopes[0].endswith("mla_attention/flash_fwd")
    assert scopes[0].startswith("jvp(forward_loss)")
    for s in scopes[1:]:
        assert s.startswith("transpose(jvp(forward_loss))")
        assert "mla_attention" in s and "flash_fwd" not in s


# ------------------------------------------- what a recomputed layer keeps
def _toy_gradients(recompute, policy=None):
    """``(flash_fwd kernels in jax.grad's jaxpr, gradients by name)`` of a
    toy model built for this call: JAX caches a custom VJP's traced rules
    by function, so every comparison makes its functions afresh.  The model
    names no policy (``deepseek_v3.py`` calls ``_recompute(layer, ...)``),
    so one is handed in through the name it calls."""
    import functools

    from paddle_tpu.distributed.fleet.utils.recompute import recompute
    from paddle_tpu.framework.state import no_grad_ctx

    spans = importlib.import_module("tests.test_program_spans")
    ds = importlib.import_module("paddle_tpu.text.models.deepseek_v3")
    paddle.seed(0)
    model = DeepseekV3ForCausalLM(recompute=recompute, **TOY)
    model.train()
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 97, (1, 256)))
    params = {k: p._value for k, p in model.named_parameters()}
    buffers = {k: b._value for k, b in model.named_buffers()}

    def loss(p):
        with no_grad_ctx(), model.bind(p, buffers):
            return model(ids, labels=ids)._value

    through = recompute if policy is None else functools.partial(
        recompute, checkpoint_policy=policy)
    with mock.patch.object(ds, "_recompute", through):
        scopes = spans._pallas_scopes(jax.grad(loss), params)
        grads = jax.jit(jax.grad(loss))(params)
    return sum("flash_fwd" in s for s in scopes), grads


@pytest.mark.parametrize("policy,forwards_a_layer", [
    (None, 1), (jax.checkpoint_policies.nothing_saveable, 2)],
    ids=["default", "nothing_saveable"])
def test_a_recomputed_layer_runs_the_flash_forward(interpreted, policy,
                                                   forwards_a_layer):
    """Once under ``recompute()``'s default policy (the kernel's output and
    log-sum-exp are kept, so the recomputed pass drops the call), twice
    where a caller's policy keeps nothing; the gradients are those of the
    model without recomputation, leaf by leaf."""
    layers = TOY["num_hidden_layers"]
    forwards, got = _toy_gradients(True, policy)
    assert forwards == forwards_a_layer * layers
    plain_forwards, want = _toy_gradients(False)
    assert plain_forwards == layers
    assert sorted(got) == sorted(want)
    for name in want:
        _close(got[name], want[name], 1e-5)


def test_a_recomputed_layer_saves_the_two_names_and_its_arguments(
        interpreted, capsys):
    """``print_saved_residuals`` of one recomputed decoder layer: what is
    not an argument (or a weight, a constant of this closure) is the
    kernel's output, lane-padded, and its log-sum-exp as ``[BH, S]``."""
    from paddle_tpu.distributed.fleet.utils.recompute import recompute
    from paddle_tpu.text.models.deepseek_v3 import DeepseekV3DecoderLayer

    paddle.seed(0)
    cfg = DeepseekV3Config(**TOY)
    layer = DeepseekV3DecoderLayer(cfg, 1)
    assert layer.sparse
    cos, sin = (t._value for t in _rope(cfg, 256))
    before = prof_metrics.counter("flash.forward_rules_traced").total()

    def out_sum(x, cos, sin):
        y, _ = recompute(layer, *map(paddle.to_tensor, (x, cos, sin)))
        return jnp.sum(y._value)

    kept = importlib.import_module("tests.test_distributed")._kept(
        capsys, out_sum, jnp.ones((1, 256, 32), jnp.float32), cos, sin)
    heads, lanes = cfg.num_attention_heads, 128
    assert len(kept) == 2, kept
    lse, = [line for line in kept if f"named '{fa.RESIDUAL_NAMES[1]}'" in line]
    assert lse.startswith(f"f32[{heads},256] ")
    out, = [line for line in kept if line is not lse]
    assert out.startswith(f"f32[{heads},256,{lanes}] ")
    assert prof_metrics.counter(
        "flash.forward_rules_traced").total() == before + 1
