"""The Ouro family (``text/models/ouro.py``: a decoder whose layers run
several times over one set of weights) and its serving through
``ServingEngine`` on pages alone (``serving.adapter.StatedCacheAdapter``
without a state), at small sizes in float32: the model against
``chipbench/reference/ouro.py`` (every step's hidden state, the gates, the
exit distribution, the logits), the cache per step, the weights shared, the
paged kernels with a traced layer, the engine under fewer pages than its
slots could fill, what works for a pages-only decoder that is not ``.gpt``
and what is refused by name, and the scopes a device trace names."""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import metrics as prof_metrics
from paddle_tpu.serving import ServingEngine, StatedCacheAdapter
from paddle_tpu.text.models import OuroConfig
from paddle_tpu.text.models.ouro import exit_distribution

pa = importlib.import_module("paddle_tpu.ops.paged_attention")
ref = importlib.import_module("chipbench.reference.ouro")

#: the file of a configuration at toy widths: 3 layers run 4 times
TOY = {"family": "ouro", "hidden_size": 32, "intermediate_size": 48,
       "num_hidden_layers": 3, "num_attention_heads": 4,
       "num_key_value_heads": 4, "head_dim": 8, "hidden_act": "silu",
       "max_position_embeddings": 65536, "rms_norm_eps": 1e-6,
       "rope_theta": 1000000, "rope_scaling": None,
       "tie_word_embeddings": False, "total_ut_steps": 4,
       "early_exit_threshold": 1, "vocab_size": 211,
       "initializer_range": 0.02, "serve_positions": 64}
R, L = 4, 3
PS, MAXLEN = 4, 64
#: the tolerance every logit comparison of this file holds the program to
TOL = 2e-5


@pytest.fixture(scope="module")
def family():
    """``(model, the reference's params)``: the model holds the
    reference's seeded leaves, as the benchmark's family builds it."""
    models = importlib.import_module("chipbench.models.ouro")
    params = ref.init_params(2 ** 31 + 7, TOY)
    model = models.build(TOY, params, ref, dtype="float32").eval()
    return model, params


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, TOY["vocab_size"], n)


def _ref_logits(params, ids, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, jnp.asarray(ids)[None], TOY,
                                     **kw)[0])


def _gap(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / max(1.0, float(np.abs(np.asarray(want)).max())))


# ------------------------------------------------------- the model, whole
def test_the_published_configuration_is_the_default():
    c = OuroConfig()
    assert (c.num_hidden_layers, c.total_ut_steps, c.hidden_size,
            c.intermediate_size, c.vocab_size) == (48, 4, 2048, 5632, 49152)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) \
        == (16, 16, 128)
    assert c.rope_theta == 1e6 and c.tie_word_embeddings is False
    assert c.early_exit_threshold == 1.0


@pytest.mark.parametrize("what", ["hidden_states", "gates",
                                  "exit_distribution", "logits"])
def test_model_equals_the_plain_reference(family, what):
    model, params = family
    ids = _ids(0, 37)
    hidden, gates = model.model(paddle.to_tensor(ids[None]))
    with jax.default_matmul_precision("highest"):
        want_h, want_g = ref.hidden_and_gates(params, jnp.asarray(ids)[None],
                                              TOY)
    if what == "hidden_states":
        assert hidden.shape == [R, 1, 37, 32]
        for t in range(R):          # every step's state, not the last alone
            assert _gap(hidden._value[t], want_h[t]) < TOL, t
        # and the steps differ: the loop is not the identity after step 0
        assert _gap(hidden._value[0], want_h[R - 1]) > 100 * TOL
    elif what == "gates":
        assert gates.shape == [R, 1, 37]
        assert _gap(gates._value, want_g) < TOL
        assert 0 < float(gates._value.min()) \
            and float(gates._value.max()) < 1
    elif what == "exit_distribution":
        got = exit_distribution(gates)
        assert _gap(got, ref.exit_distribution(want_g)) < TOL
        np.testing.assert_allclose(np.asarray(got).sum(0), 1.0, rtol=1e-5)
        lam = np.asarray(want_g)
        np.testing.assert_allclose(np.asarray(got)[1],
                                   lam[1] * (1 - lam[0]), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(got)[3],
            (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-5)
    else:
        got = model(paddle.to_tensor(ids[None]))._value[0]
        assert _gap(got, _ref_logits(params, ids)) < TOL


def test_the_weights_exist_once(family):
    """Three layers' leaves serve twelve layer-steps, and each IS the
    reference's array: nothing was copied."""
    model, params = family
    named = dict(model.named_parameters())
    assert set(named) == set(params) and len(named) == L * 11 + 5
    assert sum(n.startswith("model.layers.") for n in named) == L * 11
    assert all(named[n]._value is params[n] for n in named)
    assert float(jnp.abs(params["model.early_exit_gate.bias"]).max()) > 0
    assert model.serving_caches() == {
        "attention_layers": R * L, "loop_steps": R, "kv_heads": 4,
        "head_dim": 8, "max_positions": 65536, "dtype": jnp.float32}


def test_the_loop_is_traced_once_over_the_layer_bodies(family):
    """One ``scan`` of R iterations whose body holds the L layer bodies:
    a program's size does not grow with the steps."""
    model, _ = family
    ids = jnp.asarray(_ids(1, 9))[None]
    params, bufs = {k: p._value for k, p in model.named_parameters()}, {}

    def forward(params, ids):
        with model.bind(params, bufs):
            return model(paddle.to_tensor(ids))._value

    jaxpr = jax.make_jaxpr(forward)(params, ids)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == R
    body = str(scans[0].params["jaxpr"])
    # q, k, v, o and three of the SwiGLU a layer, and the gate's one
    assert body.count("dot_general") >= L * 7 + 1
    assert body.count("dot_general") < 2 * (L * 9 + 1)


def test_the_eager_tape_differentiates_through_the_loop(family):
    model, params = family
    ids = _ids(2, 11)
    model.train()
    try:
        loss = model(paddle.to_tensor(ids[None]),
                     labels=paddle.to_tensor(ids[None]))
        loss.backward()
        leaf = model.model.layers[1].mlp.up_proj.weight
        got = np.asarray(leaf.grad._value)
    finally:
        model.eval()
        model.clear_gradients()

    def plain(w):
        with jax.default_matmul_precision("highest"):
            lg = ref.logits(dict(params, **{
                "model.layers.1.mlp.up_proj.weight": w}),
                jnp.asarray(ids)[None], TOY)[0]
        logp = jax.nn.log_softmax(lg[:-1], -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(ids)[1:, None], -1))

    want = np.asarray(jax.grad(plain)(
        params["model.layers.1.mlp.up_proj.weight"]))
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-7)


@pytest.mark.parametrize("kw, names", [
    ({"early_exit_threshold": 0.5}, "early_exit_threshold"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"head_dim": 16}, "head_dim"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
], ids=["threshold_under_1", "rope_scaling", "sliding_window", "hidden_act",
        "head_dim", "tied_head"])
def test_what_is_not_built_is_refused_by_name(kw, names):
    with pytest.raises(NotImplementedError, match=names):
        OuroConfig(**dict(TOY, **kw))


# ----------------------------------------------------- through the adapter
def _adapter_state(model, slots=3, pages=24):
    adapter = StatedCacheAdapter(model, PS, slots)
    params, bufs = adapter.params_and_buffers()
    return adapter, params, bufs, adapter.init_pools(pages + 1)


def test_the_adapter_holds_pages_for_every_step_and_layer(family):
    model, _ = family
    adapter, _, _, pools = _adapter_state(model)
    assert len(pools) == 2 and adapter.n_pools == 2
    assert adapter.slot_state is False and adapter.state_shape is None
    assert pools[0].shape == (R * L, 25, PS, 4, 8)
    assert adapter.page_bytes() == 2 * R * L * PS * 4 * 8 * 4
    assert adapter.state_bytes_per_slot() == 0
    assert adapter.pool_owners() == (("kv.pages", (0, 1)),)
    sig = adapter.signature()
    assert (sig["cache_layers"], sig["loop_steps"], sig["num_layers"]) \
        == (R * L, R, R * L)
    assert "state_shape" not in sig
    with pytest.raises(TypeError, match="table"):
        adapter.step({}, {}, None, *pools, None, None, None)


def _chunks_then_steps(adapter, pa_, bu, pools, ids, upto=21, spoil=None):
    """A prompt of ``upto`` tokens in chunks of 8 (the last right-padded),
    then decode steps for the rest of ``ids`` in lane 1 of 3: the logits of
    every position from ``upto - 1`` on.  ``spoil(pools)`` edits the pools
    between the prompt and the decode steps."""
    table = np.full((1, MAXLEN // PS), 24, np.int32)
    table[0, :8] = np.arange(8)
    for c0 in range(0, upto, 8):
        n = min(8, upto - c0)
        chunk = np.zeros((1, 8), np.int64)
        chunk[0, :n] = ids[c0:c0 + n]
        logits, *pools = adapter.prefill_chunk(
            pa_, bu, chunk, np.asarray([n], np.int32), *pools, table,
            np.asarray([c0], np.int32))
    rows = [np.asarray(logits)[0]]
    if spoil is not None:
        pools = spoil(tuple(pools))
    tables = np.full((3, MAXLEN // PS), 24, np.int32)
    tables[1] = table[0]
    for t in range(upto, len(ids)):
        last = np.zeros((3, 1), np.int64)
        last[1, 0] = ids[t]
        logits, *pools = adapter.step(pa_, bu, last, *pools, tables,
                                      np.asarray([0, t, 0], np.int32))
        rows.append(np.asarray(logits)[1])
    return np.stack(rows)


def test_chunks_then_steps_agree_with_the_full_forward_at_the_logits(family):
    model, params = family
    adapter, pa_, bu, pools = _adapter_state(model)
    ids = _ids(2, 27)
    got = _chunks_then_steps(adapter, pa_, bu, pools, ids)
    want = _ref_logits(params, ids)[20:]
    assert _gap(got, want) < TOL


def test_a_reference_that_shares_the_last_steps_cache_fails(family):
    """THE CACHE IS PER STEP.  The variant in which every step attends the
    last step's keys and values (the paper's decode-time sharing, which
    this configuration forbids) misses the tolerance the program meets."""
    model, params = family
    adapter, pa_, bu, pools = _adapter_state(model)
    ids = _ids(2, 27)
    got = _chunks_then_steps(adapter, pa_, bu, pools, ids)
    shared = _ref_logits(params, ids, share_last_kv=True)[20:]
    assert _gap(got, shared) > 1000 * TOL


@pytest.mark.parametrize("step, layer", [(0, 0), (1, 2), (2, 1), (3, 0),
                                         (3, 2)])
def test_every_step_and_layer_reads_a_cache_row_of_its_own(family, step,
                                                           layer):
    """Zeroing row ``step * L + layer`` of both pools after the prompt
    moves the decoded logits, for a row of the first step and of the last
    alike: no (step, layer) reads another's row."""
    model, params = family
    adapter, pa_, bu, pools = _adapter_state(model)
    ids = _ids(2, 24)
    row = step * L + layer

    def spoil(pools):
        return tuple(p.at[row].set(0.0) for p in pools)

    sound = _chunks_then_steps(adapter, pa_, bu, pools, ids)
    spoiled = _chunks_then_steps(adapter, pa_, bu,
                                 adapter.init_pools(25), ids, spoil=spoil)
    assert _gap(sound[0], spoiled[0]) == 0           # the prompt's own
    assert _gap(sound[1:], spoiled[1:]) > 100 * TOL


# ------------------------------------------- the kernels, a traced layer
_GEOMETRIES = {
    # GPTAdapter's pools in the benchmark: 16 heads of 64 in 128 lanes
    "gpt_16_heads": (16, 16, 128, jnp.bfloat16),
    # the hybrid's: 32 query heads in groups of 4 over 8 KV heads
    "hybrid_gqa_8_of_32": (32, 8, 128, jnp.bfloat16),
    # pools the decode kernel's DMAs take no page of: a page a grid step
    "gpt_base_12_heads": (12, 12, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("kernel", ["decode", "chunk", "write"])
@pytest.mark.parametrize("geometry", list(_GEOMETRIES))
def test_kernels_take_a_traced_layer_bit_for_bit(kernel, geometry):
    """The three Pallas entries in interpret mode: layer 2 of 3 as a traced
    int32 scalar gives what the Python int 2 gives, to the last bit."""
    H, HKV, D, dtype = _GEOMETRIES[geometry]
    rng = np.random.default_rng(7)
    layers, P, ps, NP, B, C = 3, 9, 8, 4, 2, 8
    pools = tuple(jnp.asarray(rng.normal(size=(layers, P, ps, HKV, D)),
                              dtype) for _ in range(2))
    table = jnp.asarray(rng.permutation(P - 1)[:B * NP].reshape(B, NP),
                        jnp.int32)
    lens = jnp.asarray([13, 5], jnp.int32)
    scale = 1.0 / math.sqrt(D)
    if kernel == "decode":
        q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)

        def call(layer):
            return pa._paged_decode_pallas(q, pools, (), table, lens + 1,
                                           scale, True, layer)
    elif kernel == "chunk":
        q = jnp.asarray(rng.normal(size=(B, C, H, D)), dtype)

        def call(layer):
            return pa._paged_chunk_pallas(q, pools, (), table, lens, scale,
                                          True, layer)
    else:
        rows = tuple(jnp.asarray(rng.normal(size=(B, C, HKV, D)), dtype)
                     for _ in range(2))

        def call(layer):
            return pa._paged_write_pallas(pools, rows, table, lens, True,
                                          layer)

    fixed = jax.tree_util.tree_leaves(call(2))
    traced = jax.tree_util.tree_leaves(jax.jit(call)(jnp.int32(2)))
    other = jax.tree_util.tree_leaves(call(1))
    for a, b in zip(fixed, traced):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    assert any(not np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
               for a, b in zip(fixed, other))


@pytest.mark.parametrize("tag", ["served", "served_chunk"])
def test_the_seam_takes_a_traced_layer_off_the_chip(tag):
    """The XLA fall-backs index ``pool[layer, table]`` and
    ``pool.at[layer, ...]`` with a traced layer as with an int."""
    from paddle_tpu.tensor.tensor import Tensor

    rng = np.random.default_rng(3)
    C = 1 if tag == "served" else 4
    pools = tuple(jnp.asarray(rng.normal(size=(3, 6, 4, 2, 8)), jnp.float32)
                  for _ in range(2))
    q = jnp.asarray(rng.normal(size=(2, C, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, C, 2, 8)), jnp.float32)
            for _ in range(2))
    table = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lens = jnp.asarray([3, 1], jnp.int32)

    def call(layer):
        att, out = pa.paged_cache_attend(
            Tensor(q), Tensor(k), Tensor(v),
            (tag, layer, tuple(Tensor(p) for p in pools), Tensor(table),
             Tensor(lens)), None)
        return (att._value,) + tuple(p._value for p in out)

    fixed, traced = call(1), jax.jit(call)(jnp.int32(1))
    for a, b in zip(fixed, traced):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
    assert not np.array_equal(np.asarray(fixed[1][1]), np.asarray(pools[0][1]))
    assert np.array_equal(np.asarray(fixed[1][0]), np.asarray(pools[0][0]))


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
    lg = _ref_logits(params, np.concatenate([prompt, tokens]))
    for j, tok in enumerate(tokens):
        row = lg[len(prompt) - 1 + j]
        assert row.max() - row[tok] < 1e-4, (j, tok, int(row.argmax()))


@pytest.mark.parametrize("chunk", [None, 8], ids=["monolithic", "chunked"])
def test_engine_serves_mixed_lengths_as_the_reference_decodes(family, chunk):
    model, params = family
    prompts = [_ids(10 + i, n) for i, n in enumerate((21, 9, 33, 16, 5))]
    outs, eng = _served(model, prompts, prefill_chunk_tokens=chunk)
    # the engine's one rule, no flag: a model that states its caches
    assert isinstance(eng._adapter, StatedCacheAdapter)
    assert eng._slot_state is False and eng._prefill_extra(None, 0) == ()
    for p, out in zip(prompts, outs):
        assert len(out) == 6
        _assert_greedy(params, p, np.asarray(out))
    assert eng.step_traces == 1


def test_chunked_prefill_gives_the_monolithic_tokens(family):
    model, _ = family
    prompts = [_ids(40 + i, n) for i, n in enumerate((29, 13, 22))]
    whole, _ = _served(model, prompts, prefill_chunk_tokens=None)
    pieces, _ = _served(model, prompts, prefill_chunk_tokens=8)
    assert whole == pieces


def test_fewer_pages_than_the_slots_could_fill(family):
    """``num_pages`` a third of full residency (3 slots x 16 pages = 48):
    a request waits in the queue while ``allocate`` returns ``None``, is
    admitted when pages free, and its tokens are those of the engine with
    full residency."""
    model, params = family
    prompts = [_ids(50 + i, n) for i, n in enumerate(
        (30, 22, 27, 18, 25, 31, 12, 20))]
    full, eng = _served(model, prompts, new=10, replica="ouro-full")
    assert eng._num_pages == 48
    blocked = prof_metrics.counter("serving.admissions_blocked")
    assert not blocked.get(replica="ouro-full")
    tight, eng = _served(model, prompts, new=10, num_pages=16,
                         replica="ouro-tight")
    assert tight == full and all(len(t) == 10 for t in tight)
    assert blocked.get(replica="ouro-tight") > 0
    assert eng.block_manager.stats()["used_pages"] == 0
    _assert_greedy(params, prompts[5], np.asarray(tight[5]))


@pytest.mark.parametrize("kw", [
    {"prefix_sharing": True}, {"prefix_cache": "radix"},
    {"prefix_cache": "radix", "kv_spill": True}],
    ids=["prefix_sharing", "radix_cached_prefill", "kv_spill"])
def test_pages_alone_are_shared_and_spilled_as_any_others(family, kw):
    """What is refused a decoder with per-slot state works for one with
    pages alone: a second request with the first's prompt prefix reuses
    (or re-pages) its pages, all 12 rows of them, and decodes the tokens
    it decodes alone."""
    model, _ = family
    base = _ids(60, 24)
    a = np.concatenate([base, _ids(61, 5)])
    b = np.concatenate([base, _ids(62, 7)])
    (alone,), _ = _served(model, [b], num_slots=1)
    with ServingEngine(model, num_slots=1, page_size=PS,
                       max_model_len=MAXLEN, num_pages=24, **kw) as eng:
        eng.submit(a, max_new_tokens=6).result(timeout=300)
        second = eng.submit(b, max_new_tokens=6).result(timeout=300)
        stats = eng.block_manager.stats()
    assert second == alone
    assert stats["prefix_cache"]["hits"] >= 1


@pytest.mark.parametrize("kw, names", [
    ({"speculative_k": 2}, "speculative_k"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"mesh": jax.devices()[:2]}, "mesh="),
], ids=["speculative", "int8_pools", "mesh"])
def test_paths_the_adapter_lacks_are_refused_by_name(family, kw, names):
    model, _ = family
    with pytest.raises(ValueError, match="not supported for this model") \
            as err:
        ServingEngine(model, num_slots=2, page_size=PS,
                      max_model_len=MAXLEN, **kw)
    assert names in str(err.value) and "per-slot state" not in str(err.value)
    assert "StatedCacheAdapter" in str(err.value)


def test_signature_and_gauges_say_the_cache_rows_and_the_steps(family):
    model, _ = family
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, replica="ouro-gauge")
    sig = eng._adapter.signature()
    assert sig["cache_layers"] == R * L and sig["loop_steps"] == R

    def gauge(name):
        return prof_metrics.gauge(name).get(replica="ouro-gauge")

    assert gauge("serving.cache_layers") == R * L
    assert gauge("serving.loop_steps") == R
    # K and V over 12 rows of 4 heads of 8 floats
    assert gauge("serving.kv_bytes_per_token") == 2 * R * L * 4 * 8 * 4
    assert gauge("serving.state_bytes_per_slot") == 0
    # at the published sizes: 1,572,864 B a token
    big = OuroConfig()
    assert 2 * big.total_ut_steps * big.num_hidden_layers \
        * big.num_key_value_heads * big.head_dim * 2 == 1_572_864


@pytest.mark.parametrize("name", ["step", "chunk", "prefill"])
def test_lowered_serving_programs_name_the_scopes(family, name):
    model, _ = family
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=8,
                        numeric_guard=True)
    one, many = (eng._numeric_inject(1),), (eng._numeric_inject(),)
    table = np.full((1, eng.table_width), eng._scratch, np.int32)
    temps, key = np.zeros((1,), np.float32), eng._base_key
    ids = np.zeros((1, 8), np.int64)
    full, none = np.asarray([8], np.int32), np.zeros((1,), np.int32)
    prog, args = {
        "step": (eng._step_program()[0], (
            eng._params, eng._bufs, eng._h_last, *eng._pools, eng._h_table,
            eng._h_lens, eng._h_temps, key, *many)),
        "prefill": (eng._prefill_program(8)[0], (
            eng._params, eng._bufs, ids, *eng._pools, table, full, temps,
            key, *one)),
        "chunk": (eng._prefill_chunk_program(8)[0], (
            eng._params, eng._bufs, ids, full, *eng._pools, table, none,
            temps, key, *one))}[name]
    text = prog.lower(*args).as_text(debug_info=True)
    located = [line for line in text.splitlines() if "loc(" in line]
    for scope in ("loop_step", "gqa_attention"):
        assert any(scope in line for line in located), scope
    # the attention's operations lie inside the loop's body
    assert any("loop_step/gqa_attention/" in line for line in located)
    # said, not hidden: a served program reads the last step's state
    # alone, so the gates are dropped when it is lowered
    assert "exit_gate" not in text


def test_the_dense_program_names_the_exit_gate(family):
    model, _ = family
    params = {k: p._value for k, p in model.named_parameters()}

    def gates(params, ids):
        with model.bind(params, {}):
            return model.model(paddle.to_tensor(ids))[1]._value

    text = jax.jit(gates).lower(
        params, jnp.asarray(_ids(1, 9))[None]).as_text(debug_info=True)
    located = [line for line in text.splitlines() if "loc(" in line]
    assert any("exit_gate" in line for line in located)
    assert any("loop_step" in line for line in located)
