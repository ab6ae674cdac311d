"""SmallThinker (``text/models/smallthinker.py``): window layers with rotary
positions beside NoPE full layers, softmax-routed ReGLU experts whose
router reads the attention's input, served through ``ServingEngine`` with
the window layers' pages in a pool of their own
(``serving.block_manager.WindowPages``).  Held to the benchmark's plain
reference (``chipbench/reference/smallthinker.py``) at toy widths."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import ServingEngine, StatedCacheAdapter
from paddle_tpu.serving.block_manager import BlockManager
from paddle_tpu.text.models import SmallThinkerConfig
from paddle_tpu.text.models.smallthinker import SmallThinkerDecoderLayer

moe = importlib.import_module("paddle_tpu.distributed.fleet.meta_parallel.moe")
ref = importlib.import_module("chipbench.reference.smallthinker")
models = importlib.import_module("chipbench.models.smallthinker")

LAYOUT = [0, 1, 1, 1, 0, 1, 1, 1]
WINDOW, PS, MAXLEN, CHUNK = 32, 8, 256, 16
#: the configuration's file at toy widths, the cell's layout and top 6
TOY = {"family": "smallthinker", "vocab_size": 97, "hidden_size": 64,
       "num_hidden_layers": 8, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
       "moe_num_primary_experts": 16, "moe_num_active_primary_experts": 6,
       "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
       "rms_norm_eps": 1e-6, "rope_theta": 1500000, "rope_layout": LAYOUT,
       "sliding_window_layout": LAYOUT, "sliding_window_size": WINDOW,
       "max_position_embeddings": MAXLEN, "tie_word_embeddings": False,
       "initializer_range": 0.2}
SEED = 2 ** 31 + 11


def _build(**changes):
    cfg = dict(TOY, **changes)
    return models.build(cfg, ref.init_params(SEED, TOY), ref,
                        dtype="float32").eval()


@pytest.fixture(scope="module")
def family():
    """``(model, the reference's weights)``: the model holds the
    reference's seeded leaves, as the benchmark's family builds it."""
    return _build(), ref.init_params(SEED, TOY)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, TOY["vocab_size"], n)


def _ref_logits(params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, jnp.asarray(ids)[None], TOY)[0])


def _model_logits(model, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(model(paddle.to_tensor(ids[None]))._value[0])


def test_the_published_defaults():
    cfg = SmallThinkerConfig()
    assert cfg.num_hidden_layers == 52 and cfg.head_dim == 128
    assert cfg.sliding_window_layout[:8] == LAYOUT
    assert cfg.rope_layout == cfg.sliding_window_layout
    assert sum(cfg.sliding_window_layout) == 39
    with pytest.raises(ValueError, match="rope_layout names"):
        SmallThinkerConfig(num_hidden_layers=4, rope_layout=[0])
    with pytest.raises(NotImplementedError, match="softmax router"):
        SmallThinkerConfig(moe_primary_router_apply_softmax=False)


# ------------------------------------------------------------ the operators
def test_softmax_router_renormalises_the_top_k():
    rs = np.random.RandomState(0)
    x, w = rs.randn(9, 12).astype("float32"), rs.randn(12, 16)
    idx, weights = moe.softmax_topk(jnp.asarray(x), jnp.asarray(w), 6)
    p = np.exp(x @ w)
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, -1)[:, :6]
    assert np.array_equal(np.asarray(idx), want)
    picked = np.take_along_axis(p, want, -1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_routing_handed_in_is_the_sigmoid_routing_bit_for_bit():
    """The sigmoid router's own routing, handed in, changes no bit: the
    argument leaves the lfm2 / deepseek_v3 path as it was."""
    rs = np.random.RandomState(1)
    T, H, F, E, k = 10, 16, 8, 8, 2
    x = jnp.asarray(rs.randn(T, H), jnp.float32)
    rw, bias = jnp.asarray(rs.randn(H, E)), jnp.asarray(rs.randn(E) * 0.1)
    wg, wu = (jnp.asarray(rs.randn(E, H, F) * 0.2) for _ in range(2))
    wd = jnp.asarray(rs.randn(E, F, H) * 0.2)
    plain = moe.routed_experts(x, rw, bias, wg, wu, wd, top_k=k, eps=1e-6)
    given = moe.routed_experts(
        x, rw, bias, wg, wu, wd, top_k=k, eps=1e-6,
        routing=moe.sigmoid_topk(x, rw, bias, k, eps=1e-6))
    for a, b in zip(plain, given):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    relu = moe.routed_experts(x, rw, bias, wg, wu, wd, top_k=k, act="relu")
    assert not np.allclose(np.asarray(relu[0]), np.asarray(plain[0]))


def test_model_logits_equal_the_plain_reference(family):
    model, params = family
    ids = _ids(1, 100)
    got, want = _model_logits(model, ids), _ref_logits(params, ids)
    assert np.abs(got - want).max() < 1e-4


def test_the_window_reaches_back_window_keys_and_no_further(family):
    """Moving key 10 moves the window layers' output at positions 10 ..
    10 + WINDOW - 1 only: a one-layer model of a window layer."""
    cfg = dict(TOY, num_hidden_layers=1, rope_layout=[1],
               sliding_window_layout=[1])
    model = models.build(cfg, ref.init_params(SEED, cfg), ref).eval()
    ids = _ids(2, 80)
    other = ids.copy()
    other[10] = (other[10] + 1) % TOY["vocab_size"]
    moved = np.abs(_model_logits(model, ids)
                   - _model_logits(model, other)).max(-1) > 1e-6
    assert moved[10:10 + WINDOW].all() and not moved[:10].any()
    assert not moved[10 + WINDOW:].any()


def _routing_from_b(self, x, rope, cache=None):
    a = self.input_layernorm(x)
    att, pools = self.self_attn(a, rope), None
    h = x + att
    b = self.post_attention_layernorm(h)
    y, _ = self.block_sparse_moe(b, return_load=True,
                                 routing=self.block_sparse_moe.route(b))
    return h + y, pools


@pytest.mark.parametrize("fault", ["rotary_on_nope", "router_from_b"])
def test_the_reference_sees_the_faults(family, monkeypatch, fault):
    """Negative controls: rotary positions on the NoPE layers too, or the
    router reading the experts' input, move the logits far past the
    agreement above."""
    _, params = family
    if fault == "rotary_on_nope":
        model = _build(rope_layout=[1] * 8)
    else:
        model = family[0]
        monkeypatch.setattr(SmallThinkerDecoderLayer, "forward",
                            _routing_from_b)
    ids = _ids(1, 100)
    assert np.abs(_model_logits(model, ids)
                  - _ref_logits(params, ids)).max() > 1e-2


# ----------------------------------------------------- through the adapter
def test_pools_hold_the_window_layers_apart(family):
    model, _ = family
    need = model.serving_caches()
    assert (need["attention_layers"], need["window_layers"],
            need["window"]) == (2, 6, WINDOW)
    adapter = StatedCacheAdapter(model, PS, 3)
    assert adapter.n_pools == 4 and adapter.slot_state is False
    # a chunk of 16 beside a window of 32: 6 pages and one for the edge
    assert adapter.plan_window(3, MAXLEN // PS, CHUNK) == (21, 7)
    kp, vp, kw, vw = adapter.init_pools(41)
    assert kp.shape[:2] == (2, 41) and kw.shape[:2] == (6, 22)
    assert vp.shape == kp.shape and vw.shape == kw.shape
    assert adapter.pool_owners() == (("kv.pages", (0, 1)),
                                     ("kv.window_pages", (2, 3)))
    assert adapter.signature()["window"] == WINDOW


def test_a_sequence_cycles_through_its_window_pages():
    bm = BlockManager(40, PS, window=(14, 7), replica="swa-rows")
    a = bm.allocate(list(range(30)), 30 + 100)
    b = bm.allocate(list(range(5)), 5 + 20)
    assert len(a.window_pages) == 7 and len(b.window_pages) == 4
    assert bm.allocate([1], 100) is None          # 3 window pages left
    row = bm.table_row(a, 130, MAXLEN // PS)
    full, win = row[:32], row[32:]
    assert list(full[:17]) == a.pages and set(full[17:]) == {40}
    assert list(win[:17]) == [a.window_pages[j % 7] for j in range(17)]
    assert set(win[17:]) == {14}
    assert set(bm.scratch_row(32)[32:]) == {14}
    bm.free(a, reached=130)
    bm.free(b, reached=25)
    assert bm.window.used_pages == 0 and bm.used_pages == 0
    assert bm.stats()["window_pages"]["most_per_seq"] == 7


def test_chunks_then_steps_agree_with_the_full_forward_at_the_logits(family):
    """The engine's chunk and step programs, called as it calls them, with
    the table rows its block manager makes: a prompt of 45 tokens in chunks
    of 16, then decode steps to 4.5 windows of context beside an idle lane;
    the window pages of the sequence are reused from position 56 on."""
    model, params = family
    adapter = StatedCacheAdapter(model, PS, 2)
    window_pages, per_seq = adapter.plan_window(2, MAXLEN // PS, CHUNK)
    pa, bu = adapter.params_and_buffers()
    pools = adapter.init_pools(64 + 1)
    bm = BlockManager(64, PS, window=(window_pages, per_seq))
    ids = _ids(3, 150)
    alloc = bm.allocate(ids[:45], 150)
    row = bm.table_row(alloc, 150, MAXLEN // PS)
    want = _ref_logits(params, ids)
    for c0 in (0, 16, 32):
        n = min(CHUNK, 45 - c0)
        chunk = np.zeros((1, CHUNK), np.int64)
        chunk[0, :n] = ids[c0:c0 + n]
        logits, *pools = adapter.prefill_chunk(
            pa, bu, chunk, np.asarray([n], np.int32), *pools, row[None],
            np.asarray([c0], np.int32))
    assert np.abs(np.asarray(logits)[0] - want[44]).max() < 1e-4
    tables = np.stack([bm.scratch_row(MAXLEN // PS), row])
    for t in range(45, 150):
        last = np.zeros((2, 1), np.int64)
        last[1, 0] = ids[t]
        logits, *pools = adapter.step(pa, bu, last, *pools, tables,
                                      np.asarray([0, t], np.int32))
        assert np.abs(np.asarray(logits)[1] - want[t]).max() < 1e-4, t


# ------------------------------------------------------ through the engine
def _served(model, prompts, new, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    with ServingEngine(model, page_size=PS, max_model_len=MAXLEN,
                       **kw) as eng:
        handles = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, new)]
        return [np.asarray(h.result(timeout=600)) for h in handles], eng


def _gaps(params, prompt, tokens):
    """How far below the reference's best logit each served token stands,
    at its position of prompt + tokens so far."""
    lg = _ref_logits(params, np.concatenate([prompt, tokens]))
    rows = lg[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return rows.max(-1) - np.take_along_axis(rows, tokens[:, None], -1)[:, 0]


PROMPTS = [(4, 90), (5, 12), (6, 40)]
NEW = [60, 120, 100]


@pytest.fixture(scope="module")
def served(family):
    """Three requests through two slots: contexts to 150, 132 and 140,
    more than four windows each, in chunks of 16 (a prompt of 12 in one
    prefill)."""
    model, _ = family
    prompts = [_ids(s, n) for s, n in PROMPTS]
    outs, eng = _served(model, prompts, NEW, replica="swa-served")
    return prompts, outs, eng


def test_engine_serves_past_four_windows_as_the_reference(family, served):
    _, params = family
    prompts, outs, eng = served
    assert isinstance(eng._adapter, StatedCacheAdapter)
    for p, out, n in zip(prompts, outs, NEW):
        assert len(out) == n and len(p) + n > 4 * WINDOW
        assert _gaps(params, p, out).max() < 1e-4
    assert eng.step_traces == 1


def test_window_pages_stay_bounded_and_all_come_back(served):
    from paddle_tpu.profiler import metrics as prof_metrics

    _, _, eng = served
    window = eng._bm.window
    assert window.per_seq == 7 and window.num_pages == 2 * 7
    assert window.stats()["most_per_seq"] == 7 and window.used_pages == 0
    assert eng._bm.used_pages == 0
    gauge = prof_metrics.gauge("serving.swa_pages_per_slot_max")
    assert gauge.get(replica="swa-served") == 7
    assert prof_metrics.gauge("serving.swa_pages_in_use").get(
        replica="swa-served") == 0
    # logical pages past the 7 held: 19 - 7, 17 - 7 and 18 - 7
    assert prof_metrics.counter("serving.swa_pages_recycled").get(
        replica="swa-served") == 12 + 10 + 11


def test_a_program_that_ignores_the_window_is_caught(family, served):
    """Negative control: the window layers attending the whole context.
    Past the window the served tokens leave the reference's best."""
    _, params = family
    prompts, _, _ = served
    model = _build(sliding_window_size=MAXLEN)
    outs, _ = _served(model, prompts[:1], NEW[:1], replica="swa-ignored")
    assert _gaps(params, prompts[0], outs[0]).max() > 1e-2


@pytest.mark.parametrize("kw, names", [
    ({"prefix_sharing": True}, "prefix_sharing"),
    ({"prefix_cache": "radix"}, "prefix_cache"),
    ({"speculative_k": 2}, "speculative_k"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"mesh": jax.devices()[:2]}, "mesh="),
], ids=["prefix_sharing", "prefix_cache", "speculative", "int8_pools",
        "mesh"])
def test_mechanisms_that_assume_every_page_are_refused(family, kw, names):
    model, _ = family
    with pytest.raises(ValueError, match="sliding-window") as err:
        ServingEngine(model, num_slots=2, page_size=PS,
                      max_model_len=MAXLEN, **kw)
    assert names in str(err.value)


def test_the_spill_tier_is_refused_by_its_own_name():
    with pytest.raises(ValueError, match="kv_spill: the host spill tier"):
        ServingEngine._refuse_with_window_pages(
            prefix_sharing=False, prefix_cache=None, kv_spill=True,
            speculative_k=0, kv_dtype="native", mesh=None)
    ServingEngine._refuse_with_window_pages(
        prefix_sharing=False, prefix_cache=None, kv_spill=False,
        speculative_k=0, kv_dtype="native", mesh=None)


def test_lowered_serving_programs_name_the_scopes(family):
    model, _ = family
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=CHUNK)
    args = (eng._params, eng._bufs, eng._h_last, *eng._pools, eng._h_table,
            eng._h_lens, eng._h_temps, eng._base_key)
    text = eng._step_program()[0].lower(*args).as_text(debug_info=True)
    for scope in ("swa_attention", "global_attention", "moe_route",
                  "moe_experts"):
        assert scope in text, scope
