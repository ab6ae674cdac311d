"""The LFM2-MoE family (``text/models/lfm2.py``) and its serving through
``ServingEngine`` with per-slot state beside the paged KV
(``serving.adapter.StatedCacheAdapter``), at small sizes in float32 with the
published pattern (2 dense layers, then ``attn conv conv conv``): the
operators against plain loops, the state through tokens and chunks, the
router, the model against ``chipbench/reference/lfm2.py``, and through the
engine: mixed lengths, slot reuse, preemption, the refusals, the GPT
programs' text and the scopes a device trace names."""

import hashlib
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import GPTAdapter, ServingEngine, StatedCacheAdapter
from paddle_tpu.tensor.tensor import Tensor
from paddle_tpu.text.models import GPTForCausalLM, Lfm2MoeConfig
from paddle_tpu.text.models.lfm2 import (Lfm2Attention, Lfm2ShortConv,
                                         published_layer_types, seeded_init)

moe = importlib.import_module("paddle_tpu.distributed.fleet.meta_parallel.moe")
ref = importlib.import_module("chipbench.reference.lfm2")

#: the file of a configuration at toy widths; ten layers as the cell's cut
TOY = {"family": "lfm2", "hidden_size": 32, "intermediate_size": 48,
       "moe_intermediate_size": 8, "num_hidden_layers": 6,
       "layer_types": published_layer_types(6), "num_dense_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
       "num_experts_per_tok": 2, "norm_topk_prob": True,
       "routed_scaling_factor": 1, "use_expert_bias": True,
       "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
       "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
       "max_position_embeddings": 128000, "vocab_size": 211,
       "tie_word_embeddings": True, "initializer_range": 0.02,
       "norm_topk_eps": 1e-6, "serve_positions": 64}
PS, MAXLEN = 4, 64


def test_the_published_pattern():
    assert published_layer_types(10) == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    cfg = Lfm2MoeConfig()
    assert cfg.num_hidden_layers == 40 and cfg.head_dim == 64
    assert cfg.layer_types.count("full_attention") == 10
    assert cfg.layer_types[-2:] == ["full_attention", "conv"]
    with pytest.raises(ValueError, match="layer_types names"):
        Lfm2MoeConfig(num_hidden_layers=4, layer_types=["conv"])


@pytest.fixture(scope="module")
def family():
    """``(model, the reference's params)``: the model holds the
    reference's seeded leaves, as the benchmark's family builds it."""
    models = importlib.import_module("chipbench.models.lfm2")
    params = ref.init_params(2 ** 31 + 7, TOY)
    model = models.build(TOY, params, ref, dtype="float32").eval()
    return model, params


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, TOY["vocab_size"], n)


def _ref_logits(params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, jnp.asarray(ids)[None], TOY)[0])


# ------------------------------------------------------------ the operators
def _conv_layer(seed=1):
    cfg = Lfm2MoeConfig(hidden_size=16, seed=seed)
    return Lfm2ShortConv(cfg, seeded_init(cfg), "c")


def _plain_conv(layer, x):
    """The operator as loops over positions and taps, in numpy."""
    w_in = np.asarray(layer.in_proj.weight._value, np.float64)
    w_out = np.asarray(layer.out_proj.weight._value, np.float64)
    taps = np.asarray(layer.conv_weight._value, np.float64)
    out = np.zeros_like(x, dtype=np.float64)
    for b in range(x.shape[0]):
        bcz = x[b].astype(np.float64) @ w_in
        gate_b, gate_c, z = np.split(bcz, 3, axis=-1)
        s = gate_b * z
        for t in range(x.shape[1]):
            c = np.zeros(x.shape[2])
            for k in range(3):
                src = t - 2 + k
                if src >= 0:
                    c += taps[:, k] * s[src]
            out[b, t] = (gate_c[t] * c) @ w_out
    return out


def test_short_convolution_against_a_plain_loop():
    layer = _conv_layer()
    x = np.random.default_rng(0).normal(size=(2, 9, 16)).astype(np.float32)
    y, state = layer(Tensor(x))
    np.testing.assert_allclose(np.asarray(y._value), _plain_conv(layer, x),
                               rtol=1e-4, atol=1e-6)
    assert state.shape == [2, 2, 16]


@pytest.mark.parametrize("pieces", [
    [1] * 11, [4, 4, 3], [5, 6], [2, 9]],
    ids=["token_by_token", "chunks_padded_last", "two_chunks", "short_first"])
def test_state_carries_the_sequence_through_calls(pieces):
    """Fed piece by piece through the state, every piece right-padded to
    the widest (``valid`` says where it ends), the operator gives what it
    gives for the whole sequence; the pad's lanes never reach the state."""
    layer = _conv_layer(seed=2)
    x = np.random.default_rng(1).normal(size=(1, 11, 16)).astype(np.float32)
    whole, final = layer(Tensor(x))
    width, at, outs = max(pieces), 0, []
    state = Tensor(jnp.zeros((1, 2, 16), jnp.float32))
    for n in pieces:
        piece = np.full((1, width, 16), 7.0, np.float32)     # loud padding
        piece[:, :n] = x[:, at:at + n]
        y, state = layer(Tensor(piece), state,
                         Tensor(jnp.asarray([n], jnp.int32)))
        outs.append(np.asarray(y._value)[:, :n])
        at += n
    np.testing.assert_allclose(np.concatenate(outs, 1),
                               np.asarray(whole._value), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state._value),
                               np.asarray(final._value), rtol=1e-6)


def test_attention_norms_rotates_and_groups_as_the_plain_reference():
    cfg = Lfm2MoeConfig(hidden_size=32, num_attention_heads=4,
                        num_key_value_heads=2, seed=5)
    make = seeded_init(cfg)
    layer = Lfm2Attention(cfg, lambda n, s: (
        1.0 + 0.1 * make(n + ".g", s)) if n.endswith("layernorm.weight")
        else make(n, s), "a")
    x = np.random.default_rng(3).normal(size=(2, 7, 32)).astype(np.float32)
    from paddle_tpu.text.models.llama import _rope_cos_sin

    cos, sin = _rope_cos_sin(jnp.arange(7), cfg.head_dim, cfg.rope_theta)
    got = np.asarray(layer(Tensor(x), (Tensor(cos), Tensor(sin)))._value)
    p = {"a." + k: v._value for k, v in layer.named_parameters()}
    z = dict(ref.sizes(TOY), nh=4, nkv=2, hd=8, theta=cfg.rope_theta)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._attention(jnp.asarray(x), p, "a.", z,
                                         ref.dense))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_router_sigmoid_bias_selects_and_does_not_weigh():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(5, 16)).astype(np.float32)
    w = rng.normal(size=(16, 8)).astype(np.float32)
    bias = np.zeros(8, np.float32)
    bias[3] = 10.0                        # expert 3 is always selected
    idx, wt = moe.sigmoid_topk(jnp.asarray(x), jnp.asarray(w),
                               jnp.asarray(bias), 2, 1.0, True, 1e-6)
    p = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w)))
    idx, wt = np.asarray(idx), np.asarray(wt)
    assert (idx == 3).any(1).all()
    for t in range(5):
        picked = p[t, idx[t]]             # the scores WITHOUT the bias
        np.testing.assert_allclose(wt[t], picked / (picked.sum() + 1e-6),
                                   rtol=1e-5)
    # the reference routes the same way
    z = dict(ref.sizes(TOY), k=2)
    ridx, rwt = ref.route(jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias),
                          z)
    assert np.array_equal(np.sort(np.asarray(ridx), 1), np.sort(idx, 1))
    np.testing.assert_allclose(np.sort(np.asarray(rwt), 1), np.sort(wt, 1),
                               rtol=1e-6)


def test_the_other_family_reads_its_router_as_before():
    """The normalising constant became an argument; left out it is the
    1e-20 DeepSeek-V3's layers have always had, to the last digit."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(9, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=8).astype(np.float32) * 0.1)
    idx, wt = moe.sigmoid_topk(x, w, bias, 3, 2.448)
    scores = jax.nn.sigmoid(jnp.dot(x, w,
                                    precision=jax.lax.Precision.HIGHEST))
    _, want_idx = jax.lax.top_k(scores + bias, 3)
    picked = jnp.take_along_axis(scores, want_idx, -1)
    want = picked / (picked.sum(-1, keepdims=True) + jnp.float32(1e-20)) \
        * jnp.float32(2.448)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.array_equal(np.asarray(wt), np.asarray(want))
    layer = moe.DroplessMoELayer(16, 8, 8, 3)
    assert layer.norm_topk_eps == 1e-20


def test_expert_layer_takes_its_leaves_from_the_model():
    made = []
    layer = moe.DroplessMoELayer(
        16, 8, 4, 2, norm_topk_eps=1e-6,
        param_init=lambda name, shape: made.append((name, tuple(shape)))
        or jnp.zeros(shape, jnp.bfloat16))
    assert made == [("gate_weight", (16, 4)), ("w_gate", (4, 16, 8)),
                    ("w_up", (4, 16, 8)), ("w_down", (4, 8, 16))]
    assert layer.w_gate._value.dtype == jnp.bfloat16


# ------------------------------------------------------ the model, whole
def test_model_logits_equal_the_plain_reference(family):
    model, params = family
    ids = _ids(0, 37)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    want = _ref_logits(params, ids)
    assert np.abs(got - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    # every leaf of the model IS the reference's array: nothing was copied
    named = dict(model.named_parameters())
    assert set(named) | {n for n in params
                         if n.endswith("e_score_correction_bias")} \
        == set(params)
    assert all(named[n]._value is params[n] for n in named)
    bias = dict(model.named_buffers())[
        "model.layers.2.feed_forward.e_score_correction_bias"]
    assert bias._value is params[
        "model.layers.2.feed_forward.e_score_correction_bias"]
    assert float(jnp.abs(bias._value).max()) > 0      # drawn, not zeros


def test_an_evaluating_model_touches_no_buffer(family):
    model, _ = family
    counts = [np.asarray(b._value).copy() for n, b in model.named_buffers()
              if n.endswith("tokens_per_expert")]
    model(paddle.to_tensor(_ids(1, 12)[None]))
    after = [np.asarray(b._value) for n, b in model.named_buffers()
             if n.endswith("tokens_per_expert")]
    assert len(counts) == 4
    assert all(np.array_equal(a, b) for a, b in zip(counts, after))


# ----------------------------------------------------- through the adapter
def _adapter_state(model, slots=3, pages=24):
    adapter = StatedCacheAdapter(model, PS, slots)
    params, bufs = adapter.params_and_buffers()
    return adapter, params, bufs, adapter.init_pools(pages + 1)


def test_pools_hold_the_attention_layers_and_the_state_apart(family):
    model, _ = family
    adapter, _, _, pools = _adapter_state(model)
    kp, vp, state = pools
    assert kp.shape[0] == vp.shape[0] == 1          # 1 attention layer of 6
    assert kp.shape[2:4] == (PS, 2)
    assert state.shape == (5, 3 + 1, 2, 32)         # 5 conv layers, scratch
    assert adapter.page_bytes() == 2 * 1 * PS * 2 * kp.shape[-1] * 4
    assert adapter.state_bytes_per_slot() == 5 * 2 * 32 * 4
    assert adapter.pool_owners() == (("kv.pages", (0, 1)),
                                     ("state.slots", (2,)))
    assert adapter.max_model_len == 128000
    assert GPTAdapter.slot_state is False and adapter.slot_state is True


def test_chunks_then_steps_agree_with_the_full_forward_at_the_logits(family):
    """The programs the engine compiles, called as it calls them: a prompt
    of 21 tokens in chunks of 8 (the last right-padded) into slot 1, then
    decode steps beside an idle lane and a lane in mid-prefill."""
    model, params = family
    adapter, pa, bu, pools = _adapter_state(model)
    ids = _ids(2, 27)
    table = np.full((1, MAXLEN // PS), 24, np.int32)
    table[0, :8] = np.arange(8)
    slot = np.asarray([1], np.int32)
    # whatever the last tenant left must not be read
    pools = pools[:2] + (pools[2] + 100.0,)
    for c0 in (0, 8, 16):
        n = min(8, 21 - c0)
        chunk = np.zeros((1, 8), np.int64)
        chunk[0, :n] = ids[c0:c0 + n]
        logits, *pools = adapter.prefill_chunk(
            pa, bu, chunk, np.asarray([n], np.int32), *pools, table,
            np.asarray([c0], np.int32), slot)
    want = _ref_logits(params, ids)
    assert np.abs(np.asarray(logits)[0] - want[20]).max() < 1e-4
    before = np.asarray(pools[2])
    tables = np.full((3, MAXLEN // PS), 24, np.int32)
    tables[1] = table[0]
    for t in range(21, 27):
        last = np.zeros((3, 1), np.int64)
        last[1, 0] = ids[t]
        lens = np.asarray([0, t, 0], np.int32)
        logits, *pools = adapter.step(pa, bu, last, *pools, tables, lens)
        assert np.abs(np.asarray(logits)[1] - want[t]).max() < 1e-4
    after = np.asarray(pools[2])
    # idle lanes (lens 0) touched the scratch row alone
    assert np.array_equal(after[:, 0], before[:, 0])
    assert np.array_equal(after[:, 2], before[:, 2])
    assert not np.array_equal(after[:, 1], before[:, 1])


def test_a_decode_step_shifts_the_state_by_one(family):
    model, _ = family
    adapter, pa, bu, pools = _adapter_state(model)
    rng = np.random.default_rng(5)
    state = jnp.asarray(rng.normal(size=pools[2].shape).astype(np.float32))
    tables = np.full((3, MAXLEN // PS), 24, np.int32)
    tables[0, :2] = [3, 4]
    out = adapter.step(pa, bu, np.asarray([[5], [0], [0]], np.int64),
                       pools[0], pools[1], state, tables,
                       np.asarray([6, 0, 0], np.int32))
    new = np.asarray(out[3])
    assert np.array_equal(new[:, 0, 0], np.asarray(state)[:, 0, 1])
    assert not np.array_equal(new[:, 0, 1], np.asarray(state)[:, 0, 1])
    assert np.array_equal(new[:, 1:3], np.asarray(state)[:, 1:3])


def test_a_chunk_leaves_the_state_of_its_last_real_lane(family):
    model, _ = family
    adapter, pa, bu, pools = _adapter_state(model)
    ids = _ids(3, 5)
    table = np.full((1, MAXLEN // PS), 24, np.int32)
    table[0, :4] = np.arange(4)
    slot, zero = np.asarray([2], np.int32), np.asarray([0], np.int32)
    exact = adapter.prefill_chunk(pa, bu, ids[None].astype(np.int64),
                                  np.asarray([5], np.int32), *pools, table,
                                  zero, slot)
    padded = np.full((1, 12), 9, np.int64)
    padded[0, :5] = ids
    wide = adapter.prefill_chunk(pa, bu, padded, np.asarray([5], np.int32),
                                 *adapter.init_pools(25), table, zero, slot)
    mono = adapter.prefill(pa, bu, padded, *adapter.init_pools(25), table,
                           np.asarray([5], np.int32), slot)
    for other in (wide, mono):
        np.testing.assert_allclose(np.asarray(other[3])[:, 2],
                                   np.asarray(exact[3])[:, 2], rtol=1e-4,
                                   atol=1e-7)
        np.testing.assert_allclose(np.asarray(other[0]),
                                   np.asarray(exact[0]), atol=1e-5)
    assert float(jnp.abs(exact[3][:, 2]).max()) > 0


# ------------------------------------------------------ through the engine
def _served(model, prompts, new=6, **kw):
    kw.setdefault("num_slots", 3)
    with ServingEngine(model, page_size=PS, max_model_len=MAXLEN,
                       **kw) as eng:
        handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
        return [h.result(timeout=300) for h in handles], eng


def _assert_greedy(params, prompt, tokens):
    """Every served token stands at the reference's best logit, to
    rounding, at its position of prompt + tokens so far."""
    ids = np.concatenate([prompt, tokens])
    lg = _ref_logits(params, ids)
    for j, tok in enumerate(tokens):
        row = lg[len(prompt) - 1 + j]
        assert row.max() - row[tok] < 1e-4, (j, tok, int(row.argmax()))


@pytest.mark.parametrize("chunk", [None, 8], ids=["monolithic", "chunked"])
def test_engine_serves_mixed_lengths_as_the_reference_decodes(family, chunk):
    model, params = family
    assert model.serving_caches()["state_shape"] == (5, 2, 32)
    prompts = [_ids(10 + i, n) for i, n in enumerate((21, 9, 33, 16, 5))]
    outs, eng = _served(model, prompts, prefill_chunk_tokens=chunk)
    assert isinstance(eng._adapter, StatedCacheAdapter)
    for p, out in zip(prompts, outs):
        assert len(out) == 6
        _assert_greedy(params, p, np.asarray(out))
    assert eng.step_traces == 1


@pytest.mark.parametrize("chunk", [None, 8], ids=["monolithic", "chunked"])
def test_a_slots_second_tenant_does_not_see_the_firsts_state(family, chunk):
    model, _ = family
    a, b = _ids(20, 19), _ids(21, 13)
    (alone,), _ = _served(model, [b], num_slots=1,
                          prefill_chunk_tokens=chunk)
    (_, second), eng = _served(model, [a, b], num_slots=1,
                               prefill_chunk_tokens=chunk)
    assert second == alone
    # nor whatever else the row holds: the state pool starts loud
    eng = ServingEngine(model, num_slots=1, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=chunk)
    eng._pools = eng._pools[:2] + (eng._pools[2] + 50.0,)
    with eng:
        assert eng.submit(b, max_new_tokens=6).result(timeout=300) == alone


def test_a_preempted_request_ends_in_the_same_ids(family):
    model, _ = family
    b1, b2, rt = _ids(30, 22), _ids(31, 17), _ids(32, 9)

    def run(preempt):
        with ServingEngine(model, num_slots=2, page_size=PS,
                           max_model_len=MAXLEN, qos=True,
                           prefill_chunk_tokens=8,
                           # a series of its own: the preemption counter is
                           # process-wide and tests/test_qos.py reads "0"
                           replica="lfm2_preempt") as eng:
            h1 = eng.submit(b1, max_new_tokens=24, tier="batch")
            h2 = eng.submit(b2, max_new_tokens=24, tier="batch")
            if preempt:
                deadline = time.time() + 120
                while sum(s is not None and s.produced > 2
                          for s in eng._slots) < 2:
                    assert time.time() < deadline
                    time.sleep(0.002)
                eng.submit(rt, max_new_tokens=4,
                           tier="realtime").result(timeout=300)
            out = h1.result(timeout=300), h2.result(timeout=300)
            return out, h1.preemptions + h2.preemptions

    calm, none = run(False)
    shaken, evicted = run(True)
    assert none == 0 and evicted >= 1
    assert shaken == calm


@pytest.mark.parametrize("kw, names", [
    ({"prefix_sharing": True}, "prefix_sharing"),
    ({"prefix_cache": "radix"}, "cached-prefill"),
    ({"prefix_cache": "radix", "kv_spill": True}, "prefix_sharing"),
    ({"speculative_k": 2}, "speculative_k"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"mesh": jax.devices()[:2]}, "mesh="),
], ids=["prefix_sharing", "prefix_cache", "kv_spill", "speculative",
        "int8_pools", "mesh"])
def test_mechanisms_that_leave_the_state_behind_are_refused(family, kw,
                                                            names):
    model, _ = family
    with pytest.raises(ValueError, match="per-slot state") as err:
        ServingEngine(model, num_slots=2, page_size=PS,
                      max_model_len=MAXLEN, **kw)
    assert names in str(err.value)


def test_the_spill_tier_is_refused_by_its_own_name():
    why = None
    try:
        ServingEngine._refuse_with_slot_state(
            prefix_sharing=False, prefix_cache=None, kv_spill=True,
            speculative_k=0, kv_dtype="native", mesh=None)
    except ValueError as e:
        why = str(e)
    assert why and "kv_spill" in why and "snapshots pages" in why
    ServingEngine._refuse_with_slot_state(
        prefix_sharing=False, prefix_cache=None, kv_spill=False,
        speculative_k=0, kv_dtype="native", mesh=None)


def test_gauge_ledger_and_capacity_count_the_state(family):
    from paddle_tpu.observability import memory as obs_memory
    from paddle_tpu.profiler import metrics as prof_metrics

    model, _ = family
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, replica="lfm2-gauge")
    per_slot = 5 * 2 * 32 * 4
    assert prof_metrics.gauge("serving.state_bytes_per_slot").get(
        replica="lfm2-gauge") == per_slot
    assert prof_metrics.gauge("serving.kv_bytes_per_token").get(
        replica="lfm2-gauge") == eng._bytes_per_page / PS
    bm = eng.block_manager
    assert bm.stats()["state_bytes_per_seq"] == per_slot
    per_seq = bm.pages_for(MAXLEN) * bm.bytes_per_page
    assert bm.max_resident_sequences(MAXLEN, 10 * (per_seq + per_slot)) == 10
    # 10 sequences' pages and ONE state fit 8 sequences with theirs
    assert bm.max_resident_sequences(MAXLEN, 10 * per_seq + per_slot) \
        == (10 * per_seq + per_slot) // (per_seq + per_slot) == 8
    owners = {r["owner"] for r in obs_memory.ledger().report()["owners"]
              if r.get("replica") == "lfm2-gauge"}
    assert {"kv.pages", "state.slots"} <= owners


# ---------------------------------------- what the other family's programs do
_GPT_PROGRAMS = {
    (False, "step"): "e02d9560176def32b9d94c280908ae05e6db763b60f6c54e8ae9ce150556ba05",
    (False, "prefill"): "8b2f6986c8a85159d7f3974b7db8475815099366f37c52fc56ce6f0d1e3c758e",
    (False, "chunk"): "8c53d641d1e4a0b6992660abfda906806aeeba8e7670229871c927b39f6a1085",
    (True, "step"): "0a901c502031105888f951ea2c1ce4e86e8bffa2ea75ebc3a7fe41d0f96ab50c",
    (True, "prefill"): "35c6d5c9e782b04ce827f601baa8422ebab64fd4efa53aa5834440d7d615f359",
    (True, "chunk"): "0bebbb6dba48cf1df6878614dd85ecffbc231a7996c411af8dfe7d74ac6e3f7d",
}


def _engine_programs(eng, guard, width=16):
    """The engine's three programs with the operands it dispatches them
    on: ``{name: (program, args)}``."""
    one, many = ((eng._numeric_inject(1),), (eng._numeric_inject(),)) \
        if guard else ((), ())
    extra = eng._prefill_extra(None, 0)
    table = np.full((1, eng.table_width), eng._scratch, np.int32)
    temps, key = np.zeros((1,), np.float32), eng._base_key
    ids = np.zeros((1, width), np.int64)
    full, none = np.asarray([width], np.int32), np.zeros((1,), np.int32)
    return {
        "step": (eng._step_program()[0], (
            eng._params, eng._bufs, eng._h_last, *eng._pools, eng._h_table,
            eng._h_lens, eng._h_temps, key, *many)),
        "prefill": (eng._prefill_program(width)[0], (
            eng._params, eng._bufs, ids, *eng._pools, table, full, temps,
            key, *extra, *one)),
        "chunk": (eng._prefill_chunk_program(width)[0], (
            eng._params, eng._bufs, ids, full, *eng._pools, table, none,
            temps, key, *extra, *one))}


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
@pytest.mark.parametrize("name", ["step", "prefill", "chunk"])
def test_gpt_programs_keep_the_parents_text(guard, name):
    """The decode, prefill and chunk programs of a GPT engine lower to the
    text they had before the engine learnt of per-slot state (its sha256,
    taken on the parent commit of PR 30 with the script in this test: a
    change to these programs is then a choice, not an accident)."""
    paddle.seed(0)
    model = GPTForCausalLM(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_act="gelu",
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0).eval()
    eng = ServingEngine(model, num_slots=2, page_size=8, max_model_len=64,
                        prefill_chunk_tokens=16, numeric_guard=guard)
    assert eng._prefill_extra(None, 0) == () and eng._slot_state is False
    prog, args = _engine_programs(eng, guard)[name]
    text = prog.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _GPT_PROGRAMS[guard, name]


@pytest.mark.parametrize("name", ["step", "chunk"])
def test_lowered_serving_programs_name_the_four_scopes(family, name):
    model, _ = family
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=8,
                        numeric_guard=True)
    prog, args = _engine_programs(eng, True, width=8)[name]
    text = prog.lower(*args).as_text(debug_info=True)
    for scope in ("short_conv", "gqa_attention", "moe_route", "moe_experts"):
        assert scope in text, scope
    located = [line for line in text.splitlines() if "loc(" in line]
    assert any("ragged_dot" in line and "moe_experts" in line
               for line in located)
    assert any("gqa_attention" in line and f"jit({name})" in line
               for line in located)
    assert any("short_conv" in line and "dot_general" in line
               for line in located)
