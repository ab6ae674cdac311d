"""ISSUE-14 flash decode + chunked prefill suite (select with -m kern).

Kernel side: interpret-mode parity of the length-bounded flash-decode
Pallas path against the dense references across ragged seq_lens, GQA
group sizes, and int8 pools; empty rows against zeros (the dense
reference's softmax over an all-masked row is uniform, not zero — a
pre-existing ref semantic, so lens=0 rows are not compared with it); and
the dead-page guarantee (garbage written past every row's
length must not move the output by one bit).

Scheduler side: ServingEngine(prefill_chunk_tokens=N) greedy byte-parity
vs the monolithic engine — including a prompt longer than the chunk size
admitted mid-decode-batch — the prefill_chunk/<c> trace plateau,
speculative-k composition, int8-pool composition, and an engine restart
requeuing a half-prefilled chunked slot.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.observability import faults
from paddle_tpu.observability import perf as perf_mod
from paddle_tpu.profiler import metrics as prof_metrics
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models.gpt import GPTForCausalLM

pytestmark = pytest.mark.kern

PS = 8
MAXLEN = 64


# ============================================================ kernel side
def _mk_paged(B=3, H=4, HKV=2, D=16, ps=8, NP=5, lens=(5, 17, 31), seed=0,
              dtype="float32"):
    """Random q + pools + a SHUFFLED page table (the bounded index map
    must chase real indirection, not an identity layout)."""
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    P = B * NP + 1                       # +1 unreferenced page
    q = jnp.asarray(rs.randn(B, H, D), dtype)
    k = jnp.asarray(rs.randn(P, ps, HKV, D), dtype)
    v = jnp.asarray(rs.randn(P, ps, HKV, D), dtype)
    perm = rs.permutation(B * NP).reshape(B, NP).astype(np.int32)
    table = jnp.asarray(perm)
    seq_lens = jnp.asarray(np.asarray(lens, np.int32))
    return q, k, v, table, seq_lens


def _quantize_pools(k, v):
    from paddle_tpu.ops.paged_attention import quantize_kv

    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return kq, vq, ks, vs


#: The decode kernel takes pools whose rows are whole lanes (heads of 128:
#: the served pools') a block of pages at a time (128 keys: 16 pages of 8,
#: 8 of 16, 4 of 32); every other pool is swept a page a grid step.  One
#: choice from the shapes, the same interpreted and compiled, so each case
#: runs the kernel the chip would.
KERNELS = pytest.mark.parametrize(
    "D", [pytest.param(16, id="page_kernel"),
          pytest.param(128, id="decode_kernel")])

#: the decode kernel's edges: tables narrower than a block, not a multiple
#: of one, lengths on a block's edge and one key past it, a length that
#: overruns the table, three heads (f32: the decode kernel; bf16: three
#: heads are no whole tile, so the page kernel attends)
BLOCK_EDGES = {
    "page8_two_blocks": dict(ps=8, NP=20, lens=(128, 129, 160)),
    "page8_overrun": dict(ps=8, NP=20, lens=(127, 161, 400)),
    "page16_two_blocks": dict(ps=16, NP=9, lens=(128, 129, 144)),
    "page16_three_blocks": dict(ps=16, NP=17, lens=(256, 257, 1)),
    "page32_two_blocks": dict(ps=32, NP=5, lens=(128, 129, 160)),
    "page32_overrun": dict(ps=32, NP=5, lens=(33, 160, 999)),
    "narrow_table": dict(ps=16, NP=5, lens=(5, 17, 80)),
    "one_page_table": dict(ps=8, NP=1, lens=(1, 8, 5)),
    "three_heads": dict(ps=16, NP=9, lens=(130, 5, 144), H=3, HKV=3),
    "three_heads_bf16": dict(ps=16, NP=9, lens=(130, 5, 144), H=3, HKV=3,
                             dtype="bfloat16"),
    "gqa_bf16": dict(ps=16, NP=9, lens=(130, 5, 144), H=8, HKV=4,
                     dtype="bfloat16"),
}
BLOCK_EDGES = {name: dict(kw, D=128, H=kw.get("H", 2), HKV=kw.get("HKV", 2))
               for name, kw in BLOCK_EDGES.items()}


def _takes_decode_kernel(q, k, table):
    from paddle_tpu.ops.paged_attention import _decode_blocking

    return _decode_blocking(q, k, table.shape[1]) is not None


@pytest.mark.parametrize("shape", [
    *(dict(lens=lens, D=D) for D in (16, 128)
      for lens in ((5, 17, 31), (8, 16, 39), (1, 1, 1), (3, 40, 25),
                   (40, 40, 40))),
    *(pytest.param(kw, id=name) for name, kw in BLOCK_EDGES.items())])
def test_flash_parity_ragged_lens(shape):
    """Interpret-mode flash kernel vs the dense reference on ragged
    lengths (page-aligned, single-token, and full-table rows) and at the
    edges of the sweep's blocks."""
    from paddle_tpu.ops.paged_attention import (_paged_flash_pallas,
                                                paged_attention_ref)

    q, k, v, table, seq_lens = _mk_paged(**shape)
    assert _takes_decode_kernel(q, k, table) == (
        shape["D"] == 128 and not (q.dtype == "bfloat16"
                                   and k.shape[-2] == 3))
    f32 = [x.astype("float32") for x in (q, k, v)]
    ref = paged_attention_ref(*f32, table, seq_lens, scale=0.25)
    out = _paged_flash_pallas(q, k[None], v[None], table, seq_lens, 0.25, True,
                              0)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref),
        atol=2e-5 if q.dtype == "float32" else 2e-2)


@KERNELS
def test_flash_parity_uses_default_scale(D):
    from paddle_tpu.ops.paged_attention import (paged_attention,
                                                paged_attention_ref)

    q, k, v, table, seq_lens = _mk_paged(lens=(7, 23, 33), seed=3, D=D)
    ref = paged_attention_ref(q, k, v, table, seq_lens)
    out = paged_attention(q, k, v, table, seq_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@KERNELS
@pytest.mark.parametrize("hkv", [1, 2, 4, 8])
def test_flash_gqa_group_sizes(hkv, D):
    """GQA grouping inside the bounded kernel: H=8 query heads over
    HKV in {1, 2, 4, 8} (g = 8, 4, 2, 1) match the grouped reference."""
    from paddle_tpu.ops.paged_attention import (paged_attention,
                                                paged_attention_ref)

    q, k, v, table, seq_lens = _mk_paged(H=8, HKV=hkv, lens=(6, 19, 38),
                                         seed=hkv, D=D)
    ref = paged_attention_ref(q, k, v, table, seq_lens)
    out = paged_attention(q, k, v, table, seq_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("shape", [
    dict(lens=(5, 17, 31)),
    *(pytest.param(BLOCK_EDGES[name], id=name) for name in (
        "page8_overrun", "page16_two_blocks", "page32_two_blocks",
        "narrow_table", "three_heads"))])
def test_flash_int8_parity(shape):
    """The dequant-fused int8 decode matches the quantized dense
    reference (same pools, same scales, same masking)."""
    from paddle_tpu.ops.paged_attention import (
        paged_attention_quantized, paged_attention_quantized_ref)

    q, k, v, table, seq_lens = _mk_paged(seed=7, **shape)
    kq, vq, ks, vs = _quantize_pools(k, v)
    ref = paged_attention_quantized_ref(q, kq, vq, ks, vs, table, seq_lens)
    out = paged_attention_quantized(q, kq, vq, ks, vs, table, seq_lens,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("shape", [
    dict(lens=(0, 7, 40)),
    pytest.param(dict(lens=(0, 7, 40), D=128), id="decode_kernel"),
    pytest.param(dict(ps=16, NP=9, lens=(0, 129, 0), D=128),
                 id="between_rows"),
    pytest.param(dict(ps=16, NP=9, lens=(0, 129, 7), H=3, HKV=3, D=128,
                      dtype="bfloat16"), id="three_heads_bf16")])
def test_flash_empty_rows_match_legacy_kernel(shape):
    """lens=0 rows: the dense reference's all-masked softmax is UNIFORM
    (mean of V — a pre-existing ref semantic), while the kernel emits the
    zeros an empty row must produce (it sweeps one page with every key
    masked), bit-for-bit; the reference everywhere else."""
    from paddle_tpu.ops.paged_attention import (_paged_flash_pallas,
                                                paged_attention_ref)

    q, k, v, table, seq_lens = _mk_paged(seed=11, **shape)
    tol = 2e-5 if q.dtype == "float32" else 2e-2
    flash = np.asarray(
        _paged_flash_pallas(q, k[None], v[None], table, seq_lens, 0.25, True,
                            0))
    flash = flash.astype(np.float32)
    np.testing.assert_array_equal(flash[0], np.zeros_like(flash[0]))
    ref = np.asarray(paged_attention_ref(
        *(x.astype("float32") for x in (q, k, v)), table, seq_lens,
        scale=0.25))
    live = np.asarray(seq_lens) > 0
    np.testing.assert_allclose(flash[live], ref[live], atol=tol)
    np.testing.assert_array_equal(flash[~live], 0.0)


@pytest.mark.parametrize("shape", [
    dict(lens=(5, 17, 31)),
    pytest.param(dict(lens=(5, 17, 31), D=128), id="decode_kernel"),
    pytest.param(dict(ps=16, NP=17, lens=(129, 17, 256), D=128),
                 id="three_blocks")])
def test_flash_dead_pages_never_read(shape):
    """THE flash guarantee: poison every page slot past each row's valid
    length with +/-1e6 garbage — output must not move by one bit (the
    bounded sweep never fetches entries past the row's last valid page
    -- a page a step, it re-presents that page -- and masks them; a kernel
    that still read dead pages would overflow the online softmax)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import _paged_flash_pallas

    lens = shape["lens"]
    q, k, v, table, seq_lens = _mk_paged(seed=13, **shape)
    clean = np.asarray(
        _paged_flash_pallas(q, k[None], v[None], table, seq_lens, 0.25, True,
                            0))
    ps = k.shape[1]
    kp, vp = np.array(k, copy=True), np.array(v, copy=True)
    tab = np.asarray(table)
    for b, ln in enumerate(lens):
        for i in range(tab.shape[1]):
            page = tab[b, i]
            start = i * ps
            # poison every slot of the page at/past this row's length
            for s in range(ps):
                if start + s >= ln:
                    kp[page, s] = 1e6
                    vp[page, s] = -1e6
    poisoned = np.asarray(_paged_flash_pallas(
        q, jnp.asarray(kp)[None], jnp.asarray(vp)[None], table, seq_lens,
        0.25, True, 0))
    np.testing.assert_array_equal(clean, poisoned)


@pytest.mark.slow
def test_flash_parity_sweep():
    """Heavy randomized sweep: shapes x lengths x group sizes x int8."""
    from paddle_tpu.ops.paged_attention import (
        paged_attention, paged_attention_quantized,
        paged_attention_quantized_ref, paged_attention_ref)

    rs = np.random.RandomState(0)
    for trial in range(6):
        B = int(rs.randint(1, 4))
        HKV = int(rs.choice([1, 2, 4]))
        g = int(rs.choice([1, 2, 4]))
        NP = int(rs.randint(2, 7))
        ps = int(rs.choice([4, 8]))
        lens = tuple(int(rs.randint(1, NP * ps + 1)) for _ in range(B))
        q, k, v, table, seq_lens = _mk_paged(
            B=B, H=HKV * g, HKV=HKV, D=16, ps=ps, NP=NP, lens=lens,
            seed=100 + trial)
        ref = paged_attention_ref(q, k, v, table, seq_lens)
        out = paged_attention(q, k, v, table, seq_lens, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)
        kq, vq, ks, vs = _quantize_pools(k, v)
        qref = paged_attention_quantized_ref(q, kq, vq, ks, vs, table,
                                             seq_lens)
        qout = paged_attention_quantized(q, kq, vq, ks, vs, table,
                                         seq_lens, interpret=True)
        np.testing.assert_allclose(np.asarray(qout), np.asarray(qref),
                                   atol=3e-5)


@pytest.mark.parametrize("H,HKV,D,pool,ps,np_,want", [
    # the batch_closed cell: 8 pages of 16 keys, 2 MiB of buffers
    (16, 16, 128, "bfloat16", 16, 64, (8, None)),
    # an mp shard's 4 heads; pages of 8 and of 32 keys
    (4, 4, 128, "bfloat16", 16, 64, (8, None)),
    (16, 16, 128, "bfloat16", 8, 64, (16, None)),
    (16, 16, 128, "bfloat16", 32, 64, (4, None)),
    # a table narrower than a block
    (8, 4, 128, "float32", 16, 3, (3, None)),
    # wide pages: the block is halved until two buffers a pool fit 8 MiB,
    # and a page widened to f32 beside them wants more VMEM than the default
    (64, 64, 256, "float32", 32, 64, (1, 20 << 20)),
    # no DMA takes these a page at a time: rows that are not whole lanes
    # (interpreted too: one choice in both modes), int8 pools (16-lane
    # scale rows), 16-bit heads that are no whole tile
    (16, 16, 64, "bfloat16", 16, 64, None),
    (8, 4, 16, "float32", 8, 5, None),
    (16, 16, 128, "int8", 16, 64, None),
    (3, 3, 128, "bfloat16", 16, 64, None),
    (12, 12, 128, "bfloat16", 16, 64, None),
    (12, 12, 128, "float32", 16, 64, (8, None)),
])
def test_decode_blocking_follows_the_shapes(H, HKV, D, pool, ps, np_, want):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import _decode_blocking

    got = _decode_blocking(
        jax.ShapeDtypeStruct((16, H, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 9, ps, HKV, D), jnp.dtype(pool)), np_)
    if want is None or want[1] is None:
        assert got == want
    else:
        assert got[0] == want[0] and want[1] <= got[1] <= 2 * want[1]


def test_gathered_chunk_attend_matches_rowwise():
    """The CPU chunk-attend fast path (one gather per slot) must equal
    the naive per-position expansion through the dense reference."""
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import (_gathered_attend,
                                                _gathered_chunk_attend)

    rs = np.random.RandomState(5)
    B, C, H, HKV, D, T = 2, 4, 4, 2, 8, 24
    q = jnp.asarray(rs.randn(B, C, H, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, T, HKV, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, T, HKV, D), jnp.float32)
    lens2 = jnp.asarray(rs.randint(1, T + 1, (B, C)).astype(np.int32))
    out = np.asarray(_gathered_chunk_attend(q, k, v, lens2, 0.3))
    for b in range(B):
        for c in range(C):
            row = _gathered_attend(q[b:b + 1, c], k[b:b + 1], v[b:b + 1],
                                   lens2[b:b + 1, c], 0.3)
            np.testing.assert_allclose(out[b, c], np.asarray(row)[0],
                                       atol=2e-5)


# ======================================================== scheduler side
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


@pytest.fixture(scope="module")
def model():
    return _tiny_gpt()


def _prompt(n, seed=1):
    return np.random.RandomState(seed).randint(1, 96, (n,)).tolist()


def _run_engine(model, prompts, budgets, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("page_size", PS)
    kw.setdefault("max_model_len", MAXLEN)
    eng = ServingEngine(model, **kw)
    with eng:
        hs = [eng.submit(p, max_new_tokens=n)
              for p, n in zip(prompts, budgets)]
        out = [h.result(timeout=300) for h in hs]
    return out


def test_chunked_prefill_greedy_byte_parity(model):
    """Chunked vs monolithic greedy parity on a mix of prompts — below,
    at, and well above the chunk size (the long one needs 4 chunks) —
    plus the trace plateau: every chunk of every long prompt reuses ONE
    compiled prefill_chunk program."""
    prompts = [_prompt(30, 2), _prompt(6, 3), _prompt(8, 4), _prompt(27, 5)]
    budgets = [10, 12, 8, 10]
    mono = _run_engine(model, prompts, budgets)
    tr0 = prof_metrics.counter("serving.prefill_chunk_traces").total()
    chunked = _run_engine(model, prompts, budgets, prefill_chunk_tokens=8)
    assert chunked == mono
    # 2 long prompts x ~4 chunks each through ONE trace
    assert prof_metrics.counter(
        "serving.prefill_chunk_traces").total() == tr0 + 1


def test_chunked_prefill_long_prompt_mid_decode_batch(model):
    """A prompt longer than the chunk size admitted while other slots
    are mid-decode: the monolithic engine and the chunked engine agree
    byte-for-byte on every request."""
    shorts = [_prompt(5, 11), _prompt(7, 12)]
    long_p = _prompt(40, 13)

    def run(chunk):
        eng = ServingEngine(model, num_slots=3, page_size=PS,
                            max_model_len=MAXLEN,
                            prefill_chunk_tokens=chunk)
        with eng:
            hs = [eng.submit(p, max_new_tokens=16) for p in shorts]
            # the long prompt arrives once the shorts are decoding (keep
            # the stream iterator alive — abandoning it cancels the
            # request)
            it = hs[0].stream()
            next(it)
            hl = eng.submit(long_p, max_new_tokens=12)
            out = [h.result(timeout=300) for h in hs]
            out.append(hl.result(timeout=300))
            del it
        return out

    assert run(8) == run(None)


def test_chunked_prefill_program_family(model):
    """Chunk programs join the store under the ("serve_prefill_chunk",
    C, ...) key family, and stats() reports the chunk config."""
    from paddle_tpu.text.models._decode import program_store

    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=8)
    with eng:
        eng.generate(_prompt(20, 21), max_new_tokens=4, timeout=300)
        st = eng.stats()
        assert st["prefill_chunk_tokens"] == 8
        assert st["prefilling_slots"] == 0
    keys = [k for k in program_store(model)
            if isinstance(k, tuple) and k and k[0] == "serve_prefill_chunk"]
    assert keys and keys[0][1] == 8


def test_chunked_prefill_rejects_bad_config(model):
    with pytest.raises(ValueError):
        ServingEngine(model, num_slots=2, page_size=PS,
                      max_model_len=MAXLEN, prefill_chunk_tokens=-3)


@pytest.mark.slow
def test_chunked_prefill_speculative_parity(model):
    """speculative_k x chunked prefill: draft/verify over lanes that went
    live from a chunked prefill must still match the plain engine."""
    prompts = [[2, 3, 4] * 6, _prompt(9, 31), _prompt(22, 32)]
    budgets = [12, 10, 10]
    plain = _run_engine(model, prompts, budgets)
    spec_chunk = _run_engine(model, prompts, budgets, speculative_k=4,
                             prefill_chunk_tokens=8)
    assert spec_chunk == plain


@pytest.mark.slow
def test_chunked_prefill_int8_pools_parity(model):
    """served_chunk_q: the quantized engine's chunked prefill matches its
    own monolithic prefill byte-for-byte (int8 vs int8)."""
    prompts = [_prompt(26, 41), _prompt(7, 42)]
    budgets = [10, 10]
    mono = _run_engine(model, prompts, budgets, kv_dtype="int8")
    chunked = _run_engine(model, prompts, budgets, kv_dtype="int8",
                          prefill_chunk_tokens=8)
    assert chunked == mono


def test_restart_requeues_half_prefilled_chunked_slot(model):
    """A TransientError while one slot is MID-CHUNKED-PREFILL: the
    restart requeues it from token 0 (nothing emitted yet), the decoding
    slot requeues with its tokens-so-far, and both finish with the
    uninterrupted greedy ids."""
    from paddle_tpu.resilience.retry import TransientError

    short_p, long_p = _prompt(5, 51), _prompt(40, 52)
    # the short slot must still be decoding when the crash fires (the
    # step-crash site sits in the decode step, which prefill-only
    # iterations skip) — give it a budget far past the crash point
    [ref_short] = _run_engine(model, [short_p], [40], num_slots=2)
    [ref_long] = _run_engine(model, [long_p], [10], num_slots=2)
    requeued0 = prof_metrics.counter("serving.requests_requeued").total()

    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=8)
    seen = {}

    def boom():
        # record whether a slot really was mid-chunked-prefill at the
        # moment of the crash (reads, no locks — safe from the fault fn)
        seen["mid_prefill"] = any(
            s is not None and s.prefilled is not None for s in eng._slots)
        raise TransientError("injected crash mid chunked prefill")

    with eng:
        eng.generate(_prompt(4, 53), max_new_tokens=2, timeout=300)  # warm
        hs = eng.submit(short_p, max_new_tokens=40)
        it = hs.stream()                # keep alive: abandonment cancels
        next(it)                        # short slot is live and decoding
        # the long prompt needs 5 chunks at one chunk per iteration;
        # trip 2 of the (post-_advance_prefills) decode step fires after
        # at most two chunks have landed — deterministically mid-prefill
        hl = eng.submit(long_p, max_new_tokens=10)
        faults.inject("serving.step_crash", fn=boom, at_trips={2})
        try:
            toks_s = hs.result(timeout=300)
            toks_l = hl.result(timeout=300)
        finally:
            faults.clear()
            del it
        assert seen["mid_prefill"] is True
        assert eng._engine_restarts == 1
        assert toks_s == ref_short
        assert toks_l == ref_long
    assert prof_metrics.counter("serving.requests_requeued").total() \
        >= requeued0 + 2


def test_chunked_prefill_cancel_mid_prefill(model):
    """Cancelling a request whose slot is mid-chunked-prefill retires it
    without poisoning the scheduler (pages freed, lane backfills)."""
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=8)
    with eng:
        eng.generate(_prompt(4, 61), max_new_tokens=2, timeout=300)  # warm
        h = eng.submit(_prompt(40, 62), max_new_tokens=10)
        h.cancel()
        # cancel is not an error: result() unblocks with the (empty)
        # partial token list and the handle lands in "cancelled"
        assert h.result(timeout=300) == []
        assert h.status == "cancelled"
        # engine still serves
        out = eng.generate(_prompt(6, 63), max_new_tokens=4, timeout=300)
        assert len(out) == 4


# =================================================== perf-family plumbing
def test_candidate_hint_flash_and_chunk_families():
    """candidate_hint recognizes decode{@int8} / prefill_chunk/<c> — and
    stops suggesting 'chunk the prefill' once a family is chunked.  Decode
    families carry no kernel tag: there is one decode sweep, the
    length-bounded one, on every backend."""
    hint = perf_mod.candidate_hint("prefill/64", "bandwidth-bound",
                                   temp_bytes=9e6, pool_bytes=1e6)
    assert "prefill_chunk_tokens=N" in hint
    hint = perf_mod.candidate_hint("prefill_chunk/32", "bandwidth-bound",
                                   temp_bytes=9e6, pool_bytes=1e6)
    assert "chunk the prefill" not in hint
    assert "lower" in hint and "prefill_chunk_tokens" in hint
    assert 'kv_dtype="int8"' in perf_mod.candidate_hint(
        "decode", "bandwidth-bound")
    assert "dequant already fused" in perf_mod.candidate_hint(
        "decode@int8", "bandwidth-bound")
    assert perf_mod.is_quantized_family("decode@int8")
    assert not perf_mod.is_quantized_family("decode")
    assert perf_mod.is_chunked_prefill_family("prefill_chunk/16@lora-r4")
    assert not perf_mod.is_chunked_prefill_family("prefill/64")


def test_prefill_chunk_family_is_kv_bound():
    assert any(pref == "prefill_chunk/"
               for pref in perf_mod._KV_BOUND_FAMILIES)


def test_engine_prefill_chunk_family_names(model):
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=8)
    assert eng._prefill_chunk_family(8) == "prefill_chunk/8"
    # decode{@int8}{@mpN} on every backend: no kernel tag
    assert eng._decode_family() == "decode"
    q = ServingEngine(model, num_slots=2, page_size=PS, max_model_len=MAXLEN,
                      kv_dtype="int8")
    assert q._decode_family() == "decode@int8"
