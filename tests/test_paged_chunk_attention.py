"""The chunk attention kernel (``ops.paged_attention._paged_chunk_pallas``):
C query positions of a slot as one query block against the slot's pages.
Run interpreted on the CPU against ``_gathered_chunk_attend`` — the public
entries return their dense path before the kernel off the chip, so this is
the only place the kernel's arithmetic is checked without one."""

import importlib
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

pa = importlib.import_module("paddle_tpu.ops.paged_attention")

PS, NP, D = 8, 40, 32          # 320 tokens a row: C=256 fits unclipped
CAP = PS * NP
#: base lengths a slot can stand at: empty, inside a page, on page edges,
#: and close enough to the table's end that lens + C overruns it (clipped)
BASES = {"empty": 0, "inside": 5, "edge": PS, "edge2": 3 * PS,
         "clipped": CAP - 2, "full": CAP}


def _pools(rs, P, HKV, kind):
    kf = rs.randn(P, PS, HKV, D).astype(np.float32)
    vf = rs.randn(P, PS, HKV, D).astype(np.float32)
    if kind == "int8":
        kq, ks = pa.quantize_kv(jnp.asarray(kf))
        vq, vs = pa.quantize_kv(jnp.asarray(vf))
        return (kq, vq), (ks, vs)
    return (jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16)), ()


def _make(B, C, H, HKV, kind, seed=0):
    rs = np.random.RandomState(seed)
    P = B * NP + 1                                  # last page = scratch
    q = jnp.asarray(rs.randn(B, C, H, D).astype(np.float32))
    pools, scales = _pools(rs, P, HKV, kind)
    table = jnp.asarray(rs.permutation(P - 1).reshape(B, NP), jnp.int32)
    return q, pools, scales, table


def _kernel(q, pools, scales, table, lens):
    fn = pa._paged_chunk_q_flash_pallas if scales \
        else pa._paged_chunk_flash_pallas
    return np.asarray(fn(q, *(a[None] for a in (*pools, *scales)), table,
                         jnp.asarray(lens, jnp.int32),
                         1.0 / math.sqrt(D), True, 0))


def _reference(q, pools, scales, table, lens):
    B, C = q.shape[:2]
    HKV = pools[0].shape[2]
    k, v = (p[table].astype(jnp.float32) for p in pools)
    if scales:
        k = k * scales[0][table][..., None]
        v = v * scales[1][table][..., None]
    lens2 = pa._chunk_lens(jnp.asarray(lens, jnp.int32), C, CAP)
    return np.asarray(pa._gathered_chunk_attend(
        q, k.reshape(B, CAP, HKV, D), v.reshape(B, CAP, HKV, D), lens2,
        1.0 / math.sqrt(D)))


def _lens(B, names):
    return [BASES[n] for n in names][:B]


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("C", [1, 3, 8, 256])
def test_chunk_kernel_matches_gathered(C, B, kind):
    """Every (C, B, pool dtype): the B=4 rows stand at an empty slot, inside
    a page, on a page edge and where lens + C overruns the table."""
    q, pools, scales, table = _make(B, C, 2, 2, kind, seed=C + B)
    lens = _lens(B, ["inside", "empty", "edge2", "clipped"])
    np.testing.assert_allclose(_kernel(q, pools, scales, table, lens),
                               _reference(q, pools, scales, table, lens),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("base", sorted(BASES))
@pytest.mark.parametrize("C", [3, 130])
def test_chunk_kernel_base_lengths(base, C):
    """One slot at each base length; C=130 takes two lane tiles of query
    positions, the second mostly padding."""
    q, pools, scales, table = _make(1, C, 2, 2, "bf16", seed=7)
    lens = [BASES[base]]
    np.testing.assert_allclose(_kernel(q, pools, scales, table, lens),
                               _reference(q, pools, scales, table, lens),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_chunk_kernel_gqa(kind):
    """H = 2 * HKV: query head h reads kv head h // 2."""
    q, pools, scales, table = _make(2, 8, 4, 2, kind, seed=3)
    lens = _lens(2, ["inside", "edge"])
    np.testing.assert_allclose(_kernel(q, pools, scales, table, lens),
                               _reference(q, pools, scales, table, lens),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_scratch_page_tail_is_never_read(kind):
    """A row as the engine builds it: its own pages, then the scratch page
    to the table's width.  The sweep stops at the row's last position, so a
    poisoned scratch page changes nothing (the dense gather would read it:
    the reference gets the clean pools)."""
    C, own = 8, 3
    q, pools, scales, table = _make(2, C, 2, 2, kind, seed=5)
    scratch = pools[0].shape[0] - 1
    table = table.at[:, own:].set(scratch)
    lens = [own * PS - C, PS + 1]          # row 0 ends on its last own page
    want = _reference(q, pools, scales, table, lens)
    bad = jnp.inf if kind == "bf16" else 127
    poisoned = tuple(p.at[scratch].set(bad) for p in pools)
    np.testing.assert_allclose(_kernel(q, poisoned, scales, table, lens),
                               want, rtol=5e-5, atol=5e-5)


def test_position_t_sees_its_own_key_and_no_later_one():
    """Keys 0 .. lens[b]+t reach position t: changing key lens+t moves
    positions >= t only, and a key past the chunk's end moves none."""
    C, base, t = 8, 13, 3
    q, pools, scales, table = _make(1, C, 2, 2, "bf16", seed=11)
    lens = [base]
    out = _kernel(q, pools, scales, table, lens)

    def with_key_changed(pos):
        page, row = int(table[0, pos // PS]), pos % PS
        k = pools[0].at[page, row].add(jnp.bfloat16(3.0))
        v = pools[1].at[page, row].add(jnp.bfloat16(3.0))
        return _kernel(q, (k, v), scales, table, lens)

    moved = with_key_changed(base + t)
    np.testing.assert_array_equal(moved[0, :t], out[0, :t])
    assert all(np.abs(moved[0, c] - out[0, c]).max() > 1e-3
               for c in range(t, C))
    np.testing.assert_array_equal(with_key_changed(base + C), out)


def test_negative_length_row_writes_zeros():
    """A position with no valid key (a slot the caller parks at a negative
    length) writes zeros, and the rows beside it are untouched."""
    q, pools, scales, table = _make(2, 4, 2, 2, "bf16", seed=2)
    out = _kernel(q, pools, scales, table, [-6, 9])
    np.testing.assert_array_equal(out[0, :2], 0.0)   # positions -6, -5, ...
    np.testing.assert_allclose(
        out[1], _reference(q, pools, scales, table, [0, 9])[1],
        rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("C,H,HKV,D,pool,ps,np_,want", [
    # the batch_closed cell
    (256, 16, 16, 64, jnp.bfloat16, 16, 64, (256, 256, 8, None)),
    # speculative verify: one lane tile of mostly padding
    (4, 16, 16, 64, jnp.bfloat16, 16, 64, (128, 128, 8, None)),
    # page 8: capped at 8 in-specs a pool
    (130, 2, 2, 64, jnp.int8, 8, 40, (256, 256, 8, None)),
    # 32 heads of 128: one lane tile, and more VMEM than the default
    (256, 32, 32, 128, jnp.bfloat16, 16, 64, (256, 128, 8, 20 << 20)),
    # a table narrower than a step
    (8, 4, 4, 64, jnp.bfloat16, 16, 3, (128, 128, 3, None)),
])
def test_chunk_blocking_follows_the_shapes(C, H, HKV, D, pool, ps, np_, want):
    got = pa._chunk_blocking(
        jax.ShapeDtypeStruct((1, C, H, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((9, ps, HKV, D), pool), np_)
    assert got[:3] == want[:3]
    assert (got[3] is None) == (want[3] is None)
    assert got[3] is None or want[3] <= got[3] <= 2 * want[3]


@pytest.fixture
def as_tpu(monkeypatch):
    """The public entries ask ``jax.default_backend()``; run what they
    dispatch to interpreted."""
    real = pl.pallas_call
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("sharded", [False, True])
def test_public_entries_reach_the_kernel(as_tpu, kind, sharded):
    """``paged_chunk_attend`` / ``_quant`` on a "TPU": the chunk kernel,
    alone and head-sharded under an mp scope (q is rank 4 there)."""
    from jax.sharding import Mesh

    q, pools, scales, table = _make(2, 3, 4, 4, kind, seed=9)
    lens = jnp.asarray(_lens(2, ["inside", "edge"]), jnp.int32)
    fn = pa.paged_chunk_attend_quant if scales else pa.paged_chunk_attend
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",)) if sharded else None
    with pa.mp_shard_scope(mesh):
        got = jax.jit(fn)(q, *pools, *scales, table, lens)
    np.testing.assert_allclose(np.asarray(got),
                               _reference(q, pools, scales, table, lens),
                               rtol=5e-5, atol=5e-5)


# ------------------------------------------------------ sliding windows
def _window_reference(q, pools, table, lens, window):
    """Independent numpy oracle: position t of slot b attends keys
    ``lens[b] + t - window + 1 .. lens[b] + t``."""
    B, C, H, _ = q.shape
    HKV = pools[0].shape[2]
    g = H // HKV
    k, v = (np.asarray(p, np.float32)[np.asarray(table)].reshape(
        B, CAP, HKV, D) for p in pools)
    out = np.zeros((B, C, H, D), np.float32)
    qn = np.asarray(q, np.float32)
    for b in range(B):
        for t in range(C):
            at = min(lens[b] + t, CAP - 1)
            lo = max(lens[b] + t - window + 1, 0)
            for h in range(H):
                s = k[b, lo:at + 1, h // g] @ qn[b, t, h] / math.sqrt(D)
                p_ = np.exp(s - s.max())
                out[b, t, h] = (p_ / p_.sum()) @ v[b, lo:at + 1, h // g]
    return out


@pytest.mark.parametrize("window", [1, 12, 64, 200])
@pytest.mark.parametrize("C", [3, 130])
def test_windowed_chunk_kernel_matches_reference(window, C):
    """Slots whose window starts before the chunk, inside its first page,
    and (window 12 at base 5: key 0 still visible to the first positions)
    a chunk that straddles the window's first page; the pages wholly
    behind every position's window hold infinities."""
    q, pools, scales, table = _make(4, C, 4, 2, "bf16", seed=window + C)
    lens = [5, 3 * PS, 150, 250 - C]
    want = _window_reference(q, pools, table, lens, window)
    dense = _reference_windowed(q, pools, table, lens, window)
    np.testing.assert_allclose(dense, want, rtol=5e-5, atol=5e-5)
    poisoned = list(pools)
    for b, n in enumerate(lens):
        for i in range(max(n - window + 1, 0) // PS):
            poisoned = [p.at[int(table[b, i])].set(jnp.inf)
                        for p in poisoned]
    got = np.asarray(pa._paged_chunk_flash_pallas(
        q, *(p[None] for p in poisoned), table, jnp.asarray(lens, jnp.int32),
        1.0 / math.sqrt(D), True, 0, window))
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-5)


def _reference_windowed(q, pools, table, lens, window):
    """The public dense path with the window (what the CPU serves)."""
    return np.asarray(pa.paged_chunk_attend(
        q, *pools, table, jnp.asarray(lens, jnp.int32), window=window),
        np.float32)
