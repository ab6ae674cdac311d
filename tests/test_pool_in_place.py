"""The served KV pool is read and written IN PLACE: a layer is an index into
the stacked ``[L, P, ps, h, d]`` pool tuple, never a slice of it.

What the serving programs computed before they did so — slice every layer
out of the pool, write and attend the slice, stack the layers back — is kept
nowhere in the package; it is written out here (the pool writes in numpy)
and every adapter closure must reproduce it bit for bit, pools and logits,
over full-precision and int8 pools.  Then the pieces: a layer's write
leaves every other layer and page as it was, lanes past the table's reach
are dropped, donated pools are consumed, and the Pallas kernels
(interpreted) read layer ``l`` of a stack as the dense references read
``pool[l]``.
"""

import copy
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import random as _rng
from paddle_tpu.framework.state import no_grad_ctx
from paddle_tpu.nn import functional as F
from paddle_tpu.serving.adapter import GPTAdapter
from paddle_tpu.serving.quant import QuantizedGPTAdapter
from paddle_tpu.tensor.tensor import Tensor
from paddle_tpu.text.models import GPTForCausalLM

pa = importlib.import_module("paddle_tpu.ops.paged_attention")

L, HEADS, HD, VOCAB, MAXLEN = 3, 2, 16, 61, 32
PS, NP, PAGES = 4, 8, 25                  # capacity 32 a slot; last = scratch


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return GPTForCausalLM(
        vocab_size=VOCAB, hidden_size=HEADS * HD, num_hidden_layers=L,
        num_attention_heads=HEADS, max_position_embeddings=MAXLEN).eval()


def _adapter(model, kv_dtype):
    return (QuantizedGPTAdapter if kv_dtype == "int8"
            else GPTAdapter)(model, page_size=PS)


def _pools(adapter, rs):
    """Pools with something in every row, so that a row that should have
    stayed is told from one that was zeroed or moved."""
    out = []
    for p in adapter.init_pools(PAGES):
        x = rs.randint(-100, 100, p.shape) if p.dtype == jnp.int8 \
            else rs.rand(*p.shape) + 0.5
        out.append(jnp.asarray(x, p.dtype))
    return tuple(out)


def _table(rs, B):
    return jnp.asarray(rs.permutation(PAGES - 1)[:B * NP].reshape(B, NP),
                       jnp.int32)


# ------------------------------------------------- slice, write, stack
def _np_write(pool, rows, table, lens):
    """rows [B, C, ...] into ONE layer's pool [P, ps, ...] at positions
    lens[b] .. lens[b]+C-1; lanes past the table's reach are dropped."""
    pool = np.array(pool)
    rows, table = np.asarray(rows), np.asarray(table)
    for b in range(rows.shape[0]):
        for t in range(rows.shape[1]):
            pos = int(lens[b]) + t
            if pos < table.shape[1] * PS:
                pool[table[b, pos // PS], pos % PS] = rows[b, t]
    return jnp.asarray(pool)


def _slice_write_stack(adapter, params, bufs, ids, pools, table, lens,
                       pos_ids, tag, lora=None):
    """``GPTAdapter._run`` as it was: layer i gets the slices ``pool[i]``,
    returns new per-layer pools, and the layers are stacked at the end."""
    gpt, n = adapter.gpt, len(pools)
    lens = jnp.asarray(lens, jnp.int32)
    layers = []
    with no_grad_ctx(), _rng.rng_scope(jax.random.key(0)), \
            adapter.model.bind(params, bufs):
        x = gpt.embed(Tensor(ids), Tensor(pos_ids))
        for i, blk in enumerate(gpt.layers):
            mine = [p[i] for p in pools]                          # slice
            residual = x
            qkv = blk.qkv(blk.ln1(x))
            B, S = qkv.shape[:2]
            qkv = qkv.reshape([B, S, HEADS, 3, HD])
            q, k, v = (qkv[:, :, :, j]._value for j in range(3))
            if n == 4:          # int8 payloads, then their scales
                (k8, ks), (v8, vs) = pa.quantize_kv(k), pa.quantize_kv(v)
            rows = (k, v) if n == 2 else (k8, v8, ks, vs)
            if tag == "served" and S > 1:                         # prefill
                attn = F.scaled_dot_product_attention(
                    Tensor(q), Tensor(k), Tensor(v), is_causal=True,
                    dropout_p=0.0, training=False)._value
                pad = -S % PS
                rows = [jnp.pad(r, ((0, 0), (0, pad))
                                + ((0, 0),) * (r.ndim - 2)) for r in rows]
                mine = [_np_write(p, r, table, np.zeros(B, np.int32))
                        for p, r in zip(mine, rows)]              # write
            else:
                mine = [_np_write(p, r, table, lens)
                        for p, r in zip(mine, rows)]              # write
                if tag == "served_chunk":
                    attend = pa.paged_chunk_attend if n == 2 \
                        else pa.paged_chunk_attend_quant
                    attn = attend(q, *mine, table, lens)
                else:
                    attend = pa.paged_attention if n == 2 \
                        else pa.paged_attention_quantized
                    attn = attend(q[:, 0], *mine, table, lens + 1)[:, None]
            attn = Tensor(attn).reshape([B, S, HEADS * HD])
            x = residual + blk.out_proj(attn)
            x = x + blk.ffn2(blk.act(blk.ffn1(blk.ln2(x))))
            layers.append(mine)
        x = gpt.final_ln(x)
        w = gpt.word_embeddings.weight._value
    return x._value, w, tuple(
        jnp.stack([layer[j] for layer in layers]) for j in range(n))  # stack


#: closure -> (B, tokens a row, has nvalid, lens)
CASES = {
    "prefill": (2, 10, False, [10, 7]),            # 10: ends inside a page
    "step": (3, 1, False, [0, 9, 31]),
    "prefill_chunk": (2, 6, True, [3, 26]),        # 26 + 6: just in reach
    "verify": (3, 4, False, [5, 14, 30]),          # 30 + 4: two lanes dropped
    "encode_chunk": (1, 3, False, [8]),
}


def _args(adapter, closure, seed=0):
    rs = np.random.RandomState(seed)
    B, S, has_nvalid, lens = CASES[closure]
    ids = jnp.asarray(rs.randint(1, VOCAB, (B, S)), jnp.int64)
    lead = (ids,) + ((jnp.asarray([S, S - 2][:B], jnp.int32),)
                     if has_nvalid else ())
    return lead, _pools(adapter, rs), _table(rs, B), \
        jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("closure", sorted(CASES))
def test_closure_equals_slice_write_stack_bit_for_bit(model, closure,
                                                      kv_dtype):
    adapter = _adapter(model, kv_dtype)
    reference = copy.copy(adapter)
    reference._run = functools.partial(_slice_write_stack, reference)
    params, bufs = adapter.params_and_buffers()
    lead, pools, table, lens = _args(adapter, closure)
    got = getattr(adapter, closure)(params, bufs, *lead, *pools, table, lens)
    want = getattr(reference, closure)(params, bufs, *lead, *pools, table,
                                       lens)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # and the program did write: some row of every pool changed
    for before, after in zip(pools, got[-len(pools):]):
        assert np.any(np.asarray(before) != np.asarray(after))


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_donated_pools_are_consumed(model, kv_dtype):
    adapter = _adapter(model, kv_dtype)
    params, bufs = adapter.params_and_buffers()
    (last,), pools, table, lens = _args(adapter, "step")
    n = len(pools)
    kept = tuple(jnp.array(p) for p in pools)
    step = jax.jit(adapter.step, donate_argnums=tuple(range(3, 3 + n)))
    out = step(params, bufs, last, *pools, table, lens)
    assert all(p.is_deleted() for p in pools)
    want = adapter.step(params, bufs, last, *kept, table, lens)
    for g, w in zip(out[1:], want[1:]):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=1e-5)


# ------------------------------------------------------------ the writer
def _writers():
    def scatter(pools, rows, table, lens, layer):
        return tuple(pa.paged_table_chunk_write(p, r, table, lens, layer)
                     for p, r in zip(pools, rows))

    def kernel(pools, rows, table, lens, layer):
        return pa._paged_write_pallas(pools, rows, table, lens, True, layer)

    return {"scatter": scatter, "paged_write_kernel": kernel}


def _poisoned(quantized, rs):
    """Pools no write can produce by accident, and the rows to write."""
    shape = (L, PAGES, PS, HEADS, HD)
    if quantized:
        pools = (jnp.full(shape, -128, jnp.int8),) * 2 \
            + (jnp.full(shape[:-1], -7.0, jnp.float32),) * 2
    else:
        pools = (jnp.full(shape, jnp.nan, jnp.float32),) * 2

    def rows(B, C):
        k, v = (jnp.asarray(rs.randn(B, C, HEADS, HD), jnp.float32)
                for _ in range(2))
        return pa._pool_rows(pools, k, v)
    return pools, rows


def _expect_only(pools, got, rows, table, lens, layer):
    """``got`` is ``pools`` but for the in-reach lanes of ``rows`` in
    ``layer``: every other layer, page and row is what it was."""
    for pool, out, r in zip(pools, got, rows):
        want = np.array(pool)
        want[layer] = np.asarray(_np_write(pool[layer], r, table, lens))
        np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("writer", sorted(_writers()))
def test_a_layers_write_leaves_the_other_layers_untouched(writer,
                                                          quantized):
    rs = np.random.RandomState(3)
    pools, rows = _poisoned(quantized, rs)
    table = _table(rs, 2)
    for C, lens in ((1, [0, 13]), (6, [2, 21]), (9, [7, 16])):
        new = rows(2, C)
        lens = jnp.asarray(lens, jnp.int32)
        got = _writers()[writer](pools, new, table, lens, 1)
        _expect_only(pools, got, new, table, lens, 1)
        # the rows are there: the first lane of slot 0, read back
        page, slot = table[0, lens[0] // PS], lens[0] % PS
        np.testing.assert_array_equal(np.asarray(got[0][1, page, slot]),
                                      np.asarray(new[0][0, 0]))


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("writer", sorted(_writers()))
def test_lanes_past_the_tables_reach_are_dropped(writer, quantized):
    """Slot 0 runs out of table after two lanes, slot 1 lies wholly past
    it: their lanes go nowhere — not clamped onto the slot's last row, not
    wrapped onto the pool's last page."""
    rs = np.random.RandomState(4)
    pools, rows = _poisoned(quantized, rs)
    table = _table(rs, 2)
    new = rows(2, 5)
    lens = jnp.asarray([NP * PS - 2, NP * PS + 3], jnp.int32)
    got = _writers()[writer](pools, new, table, lens, 2)
    _expect_only(pools, got, new, table, lens, 2)
    out = np.asarray(got[0])
    written = (out != -128) if quantized else ~np.isnan(out)  # not poison
    assert written[2, table[0, NP - 1], PS - 2:].all()
    assert written.sum() == 2 * HEADS * HD


def test_one_page_under_two_table_entries_keeps_both_writes():
    """Two table entries that are one page (the engine's scratch page under
    a tail that straddles them): the second merge must not undo the first."""
    rs = np.random.RandomState(5)
    pools, rows = _poisoned(False, rs)
    table = jnp.full((1, NP), PAGES - 1, jnp.int32)
    new = rows(1, 3)
    lens = jnp.asarray([PS - 1], jnp.int32)     # slots 3 | 0, 1 of the page
    for writer in _writers().values():
        got = writer(pools, new, table, lens, 0)
        _expect_only(pools, got, new, table, lens, 0)


# --------------------------------------------- kernels read layer l in place
def _stack(rs, quantized, hd=HD):
    shape = (L, PAGES, PS, HEADS, hd)
    if quantized:
        pools = tuple(jnp.asarray(rs.randint(-127, 128, shape), jnp.int8)
                      for _ in range(2))
        scales = tuple(jnp.asarray(rs.rand(*shape[:-1]) * 0.02 + 1e-3,
                                   jnp.float32) for _ in range(2))
    else:
        pools = tuple(jnp.asarray(rs.randn(*shape), jnp.float32)
                      for _ in range(2))
        scales = ()
    return pools, scales


# rows of whole lanes take the decode kernel's own DMAs (f32 pools), every
# other pool the sweep of a page a grid step
@pytest.mark.parametrize("hd", [HD, 128])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("layer", [0, 2])
def test_decode_kernel_reads_layer_l_as_the_reference_reads_pool_l(
        layer, quantized, hd):
    rs = np.random.RandomState(6)
    pools, scales = _stack(rs, quantized, hd)
    q = jnp.asarray(rs.randn(3, HEADS, hd), jnp.float32)
    table = _table(rs, 3)
    lens = jnp.asarray([1, 17, 32], jnp.int32)
    scale = 1.0 / math.sqrt(hd)
    assert (pa._decode_blocking(q, pools[0], NP) is not None) == (
        hd == 128 and not quantized)
    if quantized:
        got = pa._paged_q_flash_pallas(q, *pools, *scales, table, lens, scale,
                                       True, layer)
        want = pa.paged_attention_quantized_ref(
            q, *(a[layer] for a in (*pools, *scales)), table, lens, scale)
    else:
        got = pa._paged_flash_pallas(q, *pools, table, lens, scale, True,
                                     layer)
        want = pa.paged_attention_ref(q, pools[0][layer], pools[1][layer],
                                      table, lens, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # the public entry's own fall-back gathers pool[l][table] the same way
    entry = pa.paged_attention_quantized if quantized else pa.paged_attention
    np.testing.assert_array_equal(
        np.asarray(entry(q, *pools, *scales, table, lens, layer=layer)),
        np.asarray(want))


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("layer", [0, 2])
def test_chunk_kernel_reads_layer_l_as_the_reference_reads_pool_l(
        layer, quantized):
    rs = np.random.RandomState(8)
    pools, scales = _stack(rs, quantized)
    q = jnp.asarray(rs.randn(2, 5, HEADS, HD), jnp.float32)
    table = _table(rs, 2)
    lens = jnp.asarray([0, 22], jnp.int32)
    got = pa._paged_chunk_pallas(q, pools, scales, table, lens,
                                 1.0 / math.sqrt(HD), True, layer)
    entry = pa.paged_chunk_attend_quant if quantized \
        else pa.paged_chunk_attend
    want = entry(q, *(a[layer] for a in (*pools, *scales)), table, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(entry(q, *pools, *scales, table, lens, layer=layer)),
        np.asarray(want))


# ------------------------------------------- whole lanes of the head size
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_lane_wide_pools_serve_what_narrow_ones_do(model, kv_dtype,
                                                   monkeypatch):
    """On the TPU a payload pool's rows are whole 128-lane rows of the head
    size (here 16), so that the device's own layout of the pool is the
    kernels'.  Through the kernels (interpreted), over such pools, every
    closure gives the logits the narrow pools give off the TPU, the rows'
    first lanes hold the same K/V and the lanes behind them zeros."""
    from jax.experimental import pallas as pl

    adapter = _adapter(model, kv_dtype)
    params, bufs = adapter.params_and_buffers()
    narrow, given = {}, {}
    for closure in ("prefill", "step", "prefill_chunk"):
        lead, _, table, lens = given[closure] = _args(adapter, closure)
        pools = adapter.init_pools(PAGES)
        narrow[closure] = getattr(adapter, closure)(
            params, bufs, *lead, *pools, table, lens)

    real = pl.pallas_call
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    assert pa.pool_lane_dim(HD) == 128 and pa.pool_lane_dim(192) == 256
    for closure, want in narrow.items():
        lead, _, table, lens = given[closure]
        pools = adapter.init_pools(PAGES)
        assert pools[0].shape == (L, PAGES, PS, HEADS, 128)
        got = getattr(adapter, closure)(params, bufs, *lead, *pools, table,
                                        lens)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   atol=2e-4, rtol=2e-4)
        # (deeper layers' K/V follow the kernels' rounding: a last bit of
        # f32, one step of the int8 grid)
        for wide, thin in zip(got[1:3], want[1:3]):
            np.testing.assert_allclose(
                np.asarray(wide[..., :HD], np.float32),
                np.asarray(thin, np.float32),
                atol=1 if kv_dtype == "int8" else 1e-5)
            assert not np.asarray(wide[..., HD:]).any()
        for wide, thin in zip(got[3:], want[3:]):          # scale pools
            np.testing.assert_allclose(np.asarray(wide), np.asarray(thin),
                                       atol=1e-6)
