"""Quantized serving (paddle_tpu.serving.quant + ops int8 section):
int8 paged KV pools with parallel scale pools, quant fused into the pool
writes and dequant into the paged attention, the Int8Linear weight path,
the calibration harness, occupancy (>= 1.8x resident slots at a fixed HBM
budget, d=64), the serving.kv_bytes_per_token / serving.pool_bytes
gauges, @int8 perf families, chaos restart of quantized pools — and the
guarantee that the DEFAULT engine stays byte-identical to pre-quant
behavior.  All on the CPU backend with tiny GPTs."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.observability import faults, perf
from paddle_tpu.profiler import metrics as prof_metrics
from paddle_tpu.quantization import (
    Int8Linear, dequantize, quantize, quantize_absmax,
)
from paddle_tpu.resilience.retry import TransientError
from paddle_tpu.serving import BlockManager, ServingEngine
from paddle_tpu.serving.quant import (
    QuantizedGPTAdapter, calibrate, choose_scale, quantize_model_weights,
    top1_agreement,
)
from paddle_tpu.text.models.gpt import GPTForCausalLM

pytestmark = pytest.mark.quant

PS = 8
MAXLEN = 64


def _tiny_gpt(train_steps=5, seed=0, max_pos=MAXLEN):
    paddle.seed(seed)
    m = GPTForCausalLM(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                       num_attention_heads=2, max_position_embeddings=max_pos)
    if train_steps:
        o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters())
        step = paddle.jit.TrainStep(m, o, loss_fn=None)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(1, 96, (8, 20)).astype("int64"))
        for _ in range(train_steps):
            step({"input_ids": ids, "labels": ids})
    return m.eval()


def _cyclic_gpt(seed=1, train_steps=70):
    """Tiny GPT overfit on a cyclic stream: greedy logit gaps are wide, so
    int8 rounding must not flip any token — the agreement fixture.
    (70 steps saturate this 2-layer model; tier-1 wall-clock matters.)"""
    paddle.seed(seed)
    m = GPTForCausalLM(vocab_size=32, hidden_size=48, num_hidden_layers=2,
                       num_attention_heads=2, max_position_embeddings=128)
    period = 6
    cyc = (np.arange(128 + 48) % period + 1).astype("int64")
    o = opt.AdamW(learning_rate=5e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, o, loss_fn=None)
    ids = paddle.to_tensor(np.stack([cyc[i:i + 48] for i in range(6)]))
    for _ in range(train_steps):
        step({"input_ids": ids, "labels": ids})
    return m.eval(), cyc, period


@pytest.fixture(scope="module")
def model():
    return _tiny_gpt()


@pytest.fixture(scope="module")
def cyclic_model():
    return _cyclic_gpt()


def _prompt(n, seed=1, vocab=96):
    return np.random.RandomState(seed).randint(1, vocab, (n,)).tolist()


def _ref_tokens(model, prompt, n):
    ids = paddle.to_tensor(np.asarray([prompt], "int64"))
    out = model.generate(ids, max_new_tokens=n, temperature=0.0,
                         cache_impl="paged", page_size=PS,
                         max_len=len(prompt) + n)
    return [int(t) for t in out.numpy()[0, len(prompt):]]


def _engine_ids(model, prompts, n, **kw):
    with ServingEngine(model, num_slots=min(4, len(prompts)), page_size=PS,
                       max_model_len=MAXLEN, **kw) as eng:
        hs = [eng.submit(p, max_new_tokens=n) for p in prompts]
        return [h.result(timeout=300) for h in hs]


# ======================================================= round-trip units
def test_quantize_absmax_roundtrip_and_grid():
    """The shared grid (quantization.quantize_absmax/dequantize): per-axis
    scales, error bounded by half a grid step, values exactly on the int
    grid, and Int8Linear quantizes onto the SAME grid."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(6, 4, 16).astype("float32"))
    q, scale = quantize_absmax(x, axis=-1)
    assert q.dtype == jnp.int8 and scale.shape == (6, 4, 1)
    err = np.abs(np.asarray(dequantize(q, scale)) - np.asarray(x))
    step = np.asarray(scale)  # one grid step per (row, head)
    assert (err <= step * 0.51 + 1e-7).all()
    assert np.abs(np.asarray(q)).max() <= 127
    # per-tensor spelling (Int8Linear's dynamic-activation path)
    q2, s2 = quantize_absmax(x)
    assert s2.shape == () and np.abs(np.asarray(q2)).max() == 127
    # Int8Linear's weight buffer is quantize() on the same grid
    import paddle_tpu.nn as nn

    paddle.seed(3)
    lin = nn.Linear(8, 8)
    w = lin.weight._value
    s = float(jnp.max(jnp.abs(w))) / 127
    il = Int8Linear(lin, s)
    np.testing.assert_array_equal(np.asarray(il.weight_int8._value),
                                  np.asarray(quantize(w, jnp.float32(s))))


def test_scale_selection_absmax_vs_percentile():
    """choose_scale: absmax covers every value (zero clipping, coarse
    grid); percentile clips the rare outliers for a much finer grid on the
    bulk — the bulk round-trip error drops by roughly the scale ratio
    (the scale-selection satellite; weight calibration picks per layer)."""
    rs = np.random.RandomState(1)
    x = rs.randn(4096).astype("float32")
    x[::512] *= 40.0  # rare outliers stretch the absmax grid 40x
    x = jnp.asarray(x)
    s_abs = choose_scale(x, method="absmax")
    s_pct = choose_scale(x, method="percentile", pct=99.5)
    assert float(s_pct) < 0.2 * float(s_abs)     # much finer grid
    # absmax never clips: max error is half ITS (coarse) grid step
    err_abs = jnp.abs(dequantize(quantize(x, s_abs), s_abs) - x)
    assert float(err_abs.max()) <= float(s_abs) * 0.51
    # on the BULK (values inside the percentile grid) the finer scale wins
    bulk = jnp.abs(x) <= float(s_pct) * 127
    err_pct = jnp.abs(dequantize(quantize(x, s_pct), s_pct) - x)
    mse = lambda e: float(jnp.mean(jnp.where(bulk, e, 0.0) ** 2))  # noqa: E731
    assert mse(err_pct) < 0.1 * mse(err_abs)
    with pytest.raises(ValueError):
        choose_scale(x, method="median")


def test_quantized_pool_writes_roundtrip():
    """prefill/token/chunk writes into the four-pool tuple quantize on the
    way in, agree with each other and round-trip within the per-(slot,
    head) grid bound; only the layer written is touched."""
    from paddle_tpu.ops.paged_attention import (paged_pool_prefill_write,
                                                paged_pool_write)

    rs = np.random.RandomState(2)
    B, S, h, d, ps, P, L = 2, 16, 2, 8, 4, 12, 2
    k = jnp.asarray(rs.randn(B, S, h, d).astype("float32"))
    v = jnp.asarray(rs.randn(B, S, h, d).astype("float32"))
    table = jnp.asarray(
        np.stack([np.arange(0, 4), np.arange(4, 8)]).astype("int32"))

    def pools():
        return (jnp.zeros((L, P, ps, h, d), jnp.int8),) * 2 \
            + (jnp.zeros((L, P, ps, h), jnp.float32),) * 2

    # prefill: whole prompt in one shot, into layer 1
    a = paged_pool_prefill_write(pools(), k, v, table, 1)
    for kv, pool, sp in ((k, a[0], a[2]), (v, a[1], a[3])):
        got = dequantize(pool[1][table].reshape(B, S, h, d),
                         sp[1][table].reshape(B, S, h)[..., None])
        err = np.abs(np.asarray(got) - np.asarray(kv))
        bound = np.abs(np.asarray(kv)).max(-1, keepdims=True) / 127 * 0.51 \
            + 1e-7
        assert (err <= bound).all()
        assert not np.asarray(pool[0]).any() and not np.asarray(sp[0]).any()
    # token-by-token (a chunk of one) and in chunks of 4 (speculative
    # verify) at per-slot positions: the same pool bytes
    for C in (1, 4):
        b = pools()
        for t in range(0, S, C):
            b = paged_pool_write(b, k[:, t:t + C], v[:, t:t + C], table,
                                 jnp.full((B,), t, jnp.int32), 1)
        for x, y in zip(a[:2], b[:2]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(a[2:], b[2:]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y))


def test_quantized_attention_matches_dequantized_reference():
    """paged_attention_quantized == paged_attention over the explicitly
    dequantized pools (the fused dequant changes WHERE the multiply
    happens, not the math), incl. a GQA head layout."""
    from paddle_tpu.ops.paged_attention import (
        paged_attention, paged_attention_quantized, quantize_kv)

    rs = np.random.RandomState(3)
    for H, HKV in ((4, 4), (4, 2)):
        B, d, ps, P, NP = 3, 16, 4, 10, 2
        kv = jnp.asarray(rs.randn(P, ps, HKV, d).astype("float32"))
        vv = jnp.asarray(rs.randn(P, ps, HKV, d).astype("float32"))
        kq, ks = quantize_kv(kv)
        vq, vs = quantize_kv(vv)
        q = jnp.asarray(rs.randn(B, H, d).astype("float32"))
        table = jnp.asarray(rs.permutation(P)[:B * NP].reshape(B, NP)
                            .astype("int32"))
        lens = jnp.asarray(np.array([3, 7, 5], "int32"))
        out_q = paged_attention_quantized(q, kq, vq, ks, vs, table, lens)
        out_ref = paged_attention(q, dequantize(kq, ks[..., None]),
                                  dequantize(vq, vs[..., None]), table, lens)
        np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_ref),
                                   rtol=1e-5, atol=1e-5)


def test_quantized_pallas_kernel_interpret_matches_ref():
    """The dequant-fused Pallas kernel (interpret mode — the same gate the
    bf16 paged kernel clears on CPU) matches the gather+dequant reference:
    the fusion changes where the scale multiply runs, not the output."""
    import math

    from paddle_tpu.ops.paged_attention import (
        _paged_q_flash_pallas, paged_attention_quantized_ref, quantize_kv)

    rs = np.random.RandomState(4)
    B, H, HKV, d, ps, NP = 3, 4, 2, 16, 8, 4
    total = B * NP
    q = jnp.asarray(rs.randn(B, H, d).astype("float32") * 0.5)
    kq, ks = quantize_kv(jnp.asarray(
        rs.randn(total, ps, HKV, d).astype("float32") * 0.5))
    vq, vs = quantize_kv(jnp.asarray(
        rs.randn(total, ps, HKV, d).astype("float32") * 0.5))
    table = jnp.asarray(rs.permutation(total).reshape(B, NP).astype("int32"))
    lens = jnp.asarray(np.array([5, 17, 31], "int32"))
    # the length-bounded kernel: stacked pools and a layer (1 of two, the
    # other's scales poisoned)
    stacked = [jnp.stack([x, x]) for x in (kq, vq)] \
        + [jnp.stack([x * 100.0, x]) for x in (ks, vs)]
    got = np.asarray(_paged_q_flash_pallas(q, *stacked, table, lens,
                                           1.0 / math.sqrt(d), True, 1))
    want = np.asarray(paged_attention_quantized_ref(q, kq, vq, ks, vs,
                                                    table, lens))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ====================================================== engine: bf16 path
def test_default_engine_byte_identical(model):
    """The bf16/native default must be EXACTLY pre-quant behavior: two
    pool arrays, 'native' dtypes in stats, greedy ids byte-equal to
    generate() — the acceptance bar for not perturbing existing serving."""
    prompts = [_prompt(6, 21), _prompt(11, 22)]
    with ServingEngine(model, num_slots=2, page_size=PS,
                       max_model_len=MAXLEN) as eng:
        assert len(eng._pools) == 2
        assert eng.kv_dtype == "native" and eng.weight_dtype == "native"
        hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        got = [h.result(timeout=300) for h in hs]
        st = eng.stats()
    for p, r in zip(prompts, got):
        assert r == _ref_tokens(model, p, 10)
    assert st["pool_dtype"] == str(eng._adapter.dtype)
    # explicit bf16 spelling routes to the same native path
    assert ServingEngine(model, page_size=PS, max_model_len=MAXLEN,
                         kv_dtype="bf16").kv_dtype == "native"
    with pytest.raises(ValueError):
        ServingEngine(model, page_size=PS, max_model_len=MAXLEN,
                      kv_dtype="int4")


def test_int8_engine_serves_and_agrees(cyclic_model):
    """kv_dtype="int8": 4-array pool tuple (int8 payload + f32 scales),
    greedy stream agrees with the full-precision engine at >= 0.99 top-1
    on the calibration-style workload."""
    m, cyc, period = cyclic_model
    prompts = [[int(t) for t in cyc[i % period:i % period + 12]]
               for i in range(3)]
    ref = _engine_ids(m, prompts, 16)
    with ServingEngine(m, num_slots=3, page_size=PS,
                       max_model_len=MAXLEN, kv_dtype="int8") as eng:
        assert len(eng._pools) == 4
        assert eng._pools[0].dtype == jnp.int8
        assert eng._pools[2].dtype == jnp.float32
        assert isinstance(eng._adapter, QuantizedGPTAdapter)
        hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        got = [h.result(timeout=300) for h in hs]
    assert top1_agreement(ref, got) >= 0.99


@pytest.mark.slow
def test_int8_speculative_verify_parity(model):
    """Speculative verify + chunk writes over quantized pools: greedy
    accept-by-argmax is exact, so the int8 speculative engine must be
    BYTE-identical to the int8 non-speculative engine at k=2 and k=4
    (prompts with repetition so drafts actually fire)."""
    base = _prompt(6, 30)
    prompts = [base + base + base[:2], _prompt(9, 31) + base]
    ref = _engine_ids(model, prompts, 14, kv_dtype="int8")
    for k in (2, 4):
        got = _engine_ids(model, prompts, 14, kv_dtype="int8",
                          speculative_k=k)
        assert got == ref, f"k={k}"


@pytest.mark.chaos
def test_chaos_restart_rebuilds_quantized_pools(model):
    """Engine restart with int8 pools: an injected transient decode crash
    rebuilds the quantized pools (int8 payload + scale pools + BlockManager
    byte accounting) and the re-queued requests finish with EXACTLY the
    uninterrupted int8 stream — the agreement guarantee survives recovery."""
    p1, p2 = _prompt(6, 40), _prompt(9, 41)
    ref = _engine_ids(model, [p1, p2], 12, kv_dtype="int8")

    def boom():
        raise TransientError("injected decode crash")

    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, kv_dtype="int8")
    with eng:
        eng.generate(_prompt(4, 42), max_new_tokens=2, timeout=300)  # warm
        bpp0 = eng.stats()["bytes_per_page"]
        faults.inject("serving.step_crash", fn=boom, at_trips={4})
        try:
            h1 = eng.submit(p1, max_new_tokens=12)
            h2 = eng.submit(p2, max_new_tokens=12)
            got = [h1.result(timeout=300), h2.result(timeout=300)]
        finally:
            faults.clear()
        assert eng._engine_restarts == 1
        assert got == ref
        # the rebuilt pools are still the quantized layout, byte for byte
        assert len(eng._pools) == 4 and eng._pools[0].dtype == jnp.int8
        st = eng.block_manager.stats()
        assert st["pool_dtype"] == "int8"
        assert st["bytes_per_page"] == bpp0


# ================================================== occupancy + metrics
def test_int8_fits_1_8x_resident_slots_at_fixed_budget():
    """ISSUE-8 acceptance: at ONE page-pool HBM budget, the int8 layout
    (d bytes payload + 4 bytes scale per position per head) admits >= 1.8x
    the resident sequences of bf16 (2d bytes) — asserted through
    BlockManager capacity math at the production-shaped d=64."""
    paddle.seed(5)
    m = GPTForCausalLM(vocab_size=64, hidden_size=128, num_hidden_layers=1,
                       num_attention_heads=2, max_position_embeddings=64)
    ad = QuantizedGPTAdapter(m, page_size=16)
    assert ad.head_dim == 64
    L, ps, h, d = ad.num_layers, ad.page_size, ad.num_kv_heads, ad.head_dim
    bf16_bpp = 2 * L * ps * h * d * 2          # K+V, bf16 itemsize
    int8_bpp = ad.page_bytes()
    assert int8_bpp == 2 * L * ps * h * (d + 4)
    tokens = 48 + 80                            # prompt + decode worst case
    budget = 64 * bf16_bpp                      # a 64-page bf16 pool
    bm_bf16 = BlockManager(64, 16, bytes_per_page=bf16_bpp,
                           pool_dtype="bfloat16")
    bm_int8 = BlockManager(64, 16, bytes_per_page=int8_bpp,
                           pool_dtype="int8")
    r_bf16 = bm_bf16.max_resident_sequences(tokens, budget_bytes=budget)
    r_int8 = bm_int8.max_resident_sequences(tokens, budget_bytes=budget)
    assert r_int8 >= 1.8 * r_bf16, (r_int8, r_bf16)


def test_block_manager_stats_surface():
    bm = BlockManager(8, 4, bytes_per_page=1024, pool_dtype="int8")
    a = bm.allocate([1, 2, 3, 4, 5], 8)
    st = bm.stats()
    assert st["used_pages"] == 2 and st["pool_dtype"] == "int8"
    assert st["pool_bytes"] == 8 * 1024 and st["used_bytes"] == 2 * 1024
    assert st["kv_bytes_per_token"] == 256.0
    assert bm.max_resident_sequences(8) == 4
    bm.free(a)
    # byte fields absent (None) when the engine never supplied them
    bm2 = BlockManager(4, 4)
    assert bm2.stats()["bytes_per_page"] is None
    with pytest.raises(ValueError):
        bm2.max_resident_sequences(4, budget_bytes=1 << 20)


def test_pool_byte_gauges_and_statusz(model):
    """serving.kv_bytes_per_token and serving.pool_bytes{dtype=} reflect
    the live pools; /statusz carries the BlockManager byte surface."""
    reg = prof_metrics.get_registry()
    with ServingEngine(model, num_slots=2, page_size=PS,
                       max_model_len=MAXLEN, kv_dtype="int8",
                       replica="q0") as eng:
        eng.generate(_prompt(5, 50), max_new_tokens=3, timeout=300)
        bpp = eng.stats()["bytes_per_page"]
        g_tok = reg.get("serving.kv_bytes_per_token").get(replica="q0")
        assert g_tok == bpp / PS
        # one series per pool dtype: int8 payload pages and the f32
        # scale pools are reported separately, and together they cover
        # every live pool byte
        by_dtype = {}
        for p in eng._pools:
            dt = str(p.dtype)
            by_dtype[dt] = by_dtype.get(dt, 0) + int(p.nbytes)
        for dt, nb in by_dtype.items():
            assert reg.get("serving.pool_bytes").get(replica="q0",
                                                     dtype=dt) == nb
        assert by_dtype["float32"] > 0  # scale pools are not dropped
        sz = eng._statusz()
        assert sz["kv_cache"]["pool_dtype"] == "int8"
        assert sz["kv_cache"]["bytes_per_page"] == bpp
        assert sz["kv_dtype"] == "int8"
    # the native engine publishes its own dtype label on the same gauge
    with ServingEngine(model, num_slots=2, page_size=PS,
                       max_model_len=MAXLEN, replica="q1") as eng2:
        dt = str(eng2._adapter.dtype)
        assert reg.get("serving.pool_bytes").get(replica="q1", dtype=dt) \
            == sum(int(p.nbytes) for p in eng2._pools)
        assert eng2.stats()["bytes_per_page"] > bpp  # int8 pages are smaller


# ======================================================== weights + calib
@pytest.mark.slow
def test_weight_int8_path_agreement():
    """weight_dtype="int8": the decoder Linears convert (in place,
    idempotently) to Int8Linear on the shared grid; the converted engine's
    greedy stream agrees >= 0.99 with the pre-conversion reference."""
    m, cyc, period = _cyclic_gpt(seed=7, train_steps=60)
    prompts = [[int(t) for t in cyc[i % period:i % period + 12]]
               for i in range(2)]
    ref = _engine_ids(m, prompts, 14)            # BEFORE conversion
    with ServingEngine(m, num_slots=2, page_size=PS, max_model_len=MAXLEN,
                       kv_dtype="int8", weight_dtype="int8") as eng:
        n_int8 = sum(1 for _, s in m.named_sublayers()
                     if isinstance(s, Int8Linear))
        assert n_int8 == 8                       # qkv/out/ffn1/ffn2 x 2
        assert eng.weight_dtype == "int8"
        hs = [eng.submit(p, max_new_tokens=14) for p in prompts]
        got = [h.result(timeout=300) for h in hs]
    assert top1_agreement(ref, got) >= 0.99
    assert quantize_model_weights(m) == 0        # idempotent


def test_calibrate_harness(cyclic_model):
    """serving.quant.calibrate: reference-first workflow, per-layer KV and
    weight round-trip errors, top-1 agreement, occupancy report (no model
    mutation when weight_dtype is None)."""
    m, cyc, period = cyclic_model
    prompts = [cyc[i % period:i % period + 10] for i in range(3)]
    rep = calibrate(m, prompts, max_new_tokens=12, page_size=PS,
                    num_slots=3)
    assert rep["top1_agreement"] >= 0.99
    assert len(rep["per_layer_kv_error"]) == 2
    assert all(0 < e < 0.05 for e in rep["per_layer_kv_error"])
    assert len(rep["per_layer_weight_error"]) == 8
    assert all(0 < e < 0.05 for e in rep["per_layer_weight_error"].values())
    assert rep["weights_converted"] == 0 and rep["weight_scales"] is None
    assert rep["quantized_stats"]["kv_dtype"] == "int8"
    assert rep["occupancy_ratio"] == pytest.approx(
        rep["kv_bytes_per_token"]["reference"]
        / rep["kv_bytes_per_token"]["int8"])
    assert not any(isinstance(s, Int8Linear)
                   for _, s in m.named_sublayers())


# ================================================ perf families + cluster
def test_quantized_program_families_attributed(model):
    """The int8 engine's warm dispatches land in their OWN perf families
    (decode@int8, prefill/<bucket>@int8) and perf's regime hints recognize
    them — an unquantized bandwidth-bound serving program is told to
    quantize its pools, a quantized one is told the dequant is already
    fused."""
    perf.reset()
    with ServingEngine(model, num_slots=2, page_size=PS,
                       max_model_len=MAXLEN, kv_dtype="int8") as eng:
        eng.generate(_prompt(5, 60), max_new_tokens=3, timeout=300)  # warm
        eng.generate(_prompt(5, 61), max_new_tokens=6, timeout=300)
    fams = {r["program"] for r in perf.snapshot()}
    assert "decode@int8" in fams
    assert any(f.startswith("prefill/") and f.endswith("@int8")
               for f in fams)
    assert perf.is_quantized_family("decode@int8")
    assert not perf.is_quantized_family("decode")
    h_plain = perf.candidate_hint("decode", "bandwidth-bound")
    assert "kv_dtype" in h_plain and "int8" in h_plain
    h_quant = perf.candidate_hint("decode@int8", "bandwidth-bound")
    assert "dequant" in h_quant and "fused" in h_quant
    assert "MXU" in perf.candidate_hint("decode@int8", "compute-bound")
    assert "dequant" in perf.candidate_hint("verify/k4@int8", "unknown")
    # the report names the quantized family (regime is unknown on CPU)
    rep = perf.report(resolve=False)
    assert "decode@int8" in rep


@pytest.mark.slow
def test_cluster_replicas_inherit_kv_dtype(model):
    """Cluster composition: engine kwargs flow to every replica verbatim —
    a kv_dtype="int8" cluster serves through quantized pools on each
    replica with the router untouched.  (slow: cluster startup/teardown —
    the kwargs passthrough itself is engine-level and cheap.)"""
    from paddle_tpu.serving import ServingCluster

    cl = ServingCluster(model, replicas=2, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, kv_dtype="int8",
                        name="qcl")
    with cl:
        hs = [cl.submit(_prompt(5, 70 + i), max_new_tokens=4)
              for i in range(3)]
        for h in hs:
            assert len(h.result(timeout=300)) == 4
        for e in cl.engines:
            assert e.kv_dtype == "int8"
            assert e._pools[0].dtype == jnp.int8
            assert e.stats()["pool_dtype"] == "int8"


# ================================================================ bench
@pytest.mark.slow
def test_bench_serving_quant_arm():
    """bench.py --serving --kv-dtype arm (in-process, tiny config): emits
    the tokens/sec + occupancy + agreement schema; int8 resident slots
    beat the full-precision layout at the shared budget."""
    import bench

    kw = dict(n_requests=6, budget_slots=2, S0=12, page_size=8,
              max_new=24, train_steps=40,
              model_kwargs=dict(vocab_size=64, hidden_size=64,
                                num_hidden_layers=2, num_attention_heads=1,
                                max_position_embeddings=64))
    base = bench._measure_serving_quant(kv_dtype="bf16", **kw)
    quant = bench._measure_serving_quant(kv_dtype="int8", **kw)
    assert base["tokens_per_sec"] > 0 and quant["tokens_per_sec"] > 0
    assert quant["pool_dtype"] == "int8"
    assert quant["bytes_per_page"] < base["bytes_per_page"]
    assert quant["budget_bytes"] == base["budget_bytes"]
    # both arms sized into the SAME budget: int8 runs wider decode waves
    assert quant["num_slots"] >= 1.8 * base["num_slots"]
    assert quant["max_resident_slots_at_budget"] \
        >= 1.8 * base["max_resident_slots_at_budget"]
    agree = top1_agreement(base["ids"], quant["ids"])
    assert agree >= 0.99, agree
