"""Paged attention — attention over a block-paged KV cache, and the cache.

Reference analog: the PagedAttention kernels serving stacks use for
KV-cache memory management (and the reference inference engine's fused
decode attention).  TPU-native design: the page table rides the kernels as
SCALAR PREFETCH — Pallas resolves each grid step's HBM block address from
``page_table[b, i]`` *before* the step runs, so pages stream HBM→VMEM with
no gather materialization; online-softmax state (m, l, acc) lives in VMEM
scratch across the page sweep, exactly like this repo's flash kernel
(ops/flash_attention.py).

ONE cache contract, the serving engine's, and one seam the models call
(:func:`paged_cache_attend`): write this layer's K/V chunk, attend against
the pages.  ``generate(cache_impl="paged")`` is the same contract with an
identity page table and every length equal.

Layout:
    pools      (k, v) each [L, P, page_size, HKV, Dp]: every layer's pages
               stacked in one array, shared by all sequences; a layer is an
               index into it: a Python int, or a traced int32 scalar (a
               decoder that runs its layers several times reaches row
               ``step * layers + layer`` from inside a traced loop).  Either
               way it rides the kernels as one more prefetched scalar.  ``Dp`` is the head size in whole lanes on
               the TPU (:func:`pool_lane_dim`).  The int8 cache adds the
               scale pools (ks, vs) [L, P, page_size, HKV] f32 to the tuple.
               (The entries also take ONE layer's [P, page_size, ...] with
               ``layer=None``.)
    q          [B, H, D] one decode token per slot, or a chunk [B, C, H, D]
    page_table [B, NP] int32       page ids per slot (row-padded)
    seq_lens   [B]     int32       valid token count per slot

Four kernel bodies: decode (``_paged_decode_kernel``: a block of pages at
a time by its own DMAs), the decode of pools whose pages no DMA takes, int8
pools among them (``_paged_page_kernel``: a page a grid step, scales
optional), chunk (``_paged_chunk_kernel``) and the writer
(``_paged_write_kernel``).  Off the TPU every public entry is a dense
gather reference (a scatter, for the writer) with identical semantics.
"""

from __future__ import annotations

import functools
import inspect
import math

import jax
import jax.numpy as jnp

from ..profiler import metrics as _metrics
from ..tensor.dispatch import apply as _apply

NEG_INF = -1e30
_LANES = 128

_m_calls = _metrics.counter(
    "paged.kernel_calls_traced",
    "paged attention kernels applied by the programs traced, by kernel "
    "(kernel = decode | chunk): one a layer body; counted when a program "
    "is traced, not when it runs")
_m_bodies = _metrics.counter(
    "paged.kernel_bodies_traced",
    "paged attention kernel bodies traced, by kernel (kernel = decode | "
    "chunk): one a signature (operand shapes and types, static "
    "parameters); every other call takes the body traced then")


def _traced_once(kernel):
    """Decorator: the application of a Pallas kernel to its prepared
    operands, traced ONCE a signature -- the operands' shapes and types,
    the keyword-only parameters (static) and the x64 state at the call.
    Pallas caches no kernel body, so a program of 48 layer bodies traced
    the same kernel 48 times.  ``jax.jit`` keeps the traced application,
    and ``inline=True`` puts its equations into the calling program as
    they are: under the caller's name stack, with no call of their own, so
    the program is the one an uncached call traces and the kernels keep
    their instruction names (JAX's lowering cache then lowers the one
    kernel once a program, too).  The layer is an operand: a traced layer
    stays one, and the constant of an int is made by the caller, as
    before.  The undecorated function is the wrapper's ``__wrapped__``."""
    def wrap(fn):
        static = tuple(n for n, p in inspect.signature(fn).parameters.items()
                       if p.kind is p.KEYWORD_ONLY)

        @functools.wraps(fn)
        def body(*args, **kw):
            _m_bodies.inc(kernel=kernel)
            return fn(*args, **kw)

        cached = jax.jit(body, static_argnames=static, inline=True)

        @functools.wraps(fn)
        def call(*args, **kw):
            _m_calls.inc(kernel=kernel)
            return cached(*args, **kw)
        return call
    return wrap


# ------------------------------------------------------------------ decode
# The length-bounded sweep: a kernel that visits EVERY entry of the table
# width for every row makes a 128-token row in a 2048-token table pay 128
# pages of DMA for 8 pages of data.  The decode kernel walks a row's table
# only as far as the row's length reaches (the scalar-prefetched seq_lens
# bound its loop), a block of pages at a time, each page fetched through
# the page table by a DMA of its own: dead table entries are never read,
# and cost no grid step either -- a row is ONE grid step, whatever the
# table's width.


# ------------------------------------------------- tensor-parallel serving
# ServingEngine(mesh=...) shards q and the page pools on the (KV-)head dim.
# Off-TPU the dense-gather references below are plain jnp — GSPMD partitions
# them from the operand shardings with no help.  The Pallas flash kernels
# can't be GSPMD-partitioned (they bake num_kv_heads from the static
# shape), so under an active scope the TPU entries wrap
# the kernel in shard_map with head-sharded specs: each shard's kernel
# compiles against its LOCAL head count and sweeps only its own pool
# shard's pages.  Per-head attention is embarrassingly parallel and the
# contiguous head split keeps GQA groups whole per shard (q head h reads
# kv head h // g; both sides split at the same head boundaries), so the
# wrapper needs no collectives.  The scope is entered by the serving
# adapter at TRACE time (inside the engine's jit), so the wrapping decision
# bakes into the compiled program.
_MP_SCOPE = [None]  # active (mesh, axis_name) or None


def mp_shard_scope(mesh, axis="model"):
    """Context manager activating head-sharded flash dispatch for the
    paged-attention entries traced inside it.  ``mesh=None`` is a no-op
    scope (the single-device engine pays nothing)."""
    import contextlib

    if mesh is None:
        return contextlib.nullcontext()

    @contextlib.contextmanager
    def scope():
        prev = _MP_SCOPE[0]
        _MP_SCOPE[0] = (mesh, axis)
        try:
            yield
        finally:
            _MP_SCOPE[0] = prev

    return scope()


def _flash_sharded(pallas_fn, q, pools, scales, page_table, seq_lens,
                   scale, interpret, layer):
    """shard_map wrapper for a flash Pallas entry: q and the pools shard
    the head dim, table/lens replicate, out follows q.  ``q`` is the decode
    [B, h, d] or the chunk [B, C, h, d]; ``pools`` are the stacked
    [L, P, ps, h, d] payload arrays, ``scales`` the optional [L, P, ps, h]
    scale pools (quantized path); every shard reads layer ``layer`` of its
    own heads (the layer replicates with the table and the lengths)."""
    from jax.sharding import PartitionSpec as P

    mesh, ax = _MP_SCOPE[0]
    q_spec = P(*(None,) * (q.ndim - 2), ax, None)
    pool_spec = P(None, None, None, ax, None)
    scale_spec = P(None, None, None, ax)
    in_specs = (q_spec,) + (pool_spec,) * len(pools) \
        + (scale_spec,) * len(scales) + (P(), P(), P())

    def local(q_, *rest):
        kv = rest[:len(pools) + len(scales)]
        table_, lens_, layer_ = rest[-3:]
        return pallas_fn(q_, *kv, table_, lens_, scale, interpret, layer_)

    f = jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=q_spec,
                      check_vma=False)
    return f(q, *pools, *scales, page_table, seq_lens, _layer_scalar(layer))


def pool_lane_dim(head_dim):
    """Width of a row of the serving engine's payload pools: the head size,
    on the TPU rounded up to whole 128-lane rows (64 is stored as 128,
    zeros behind it).  The device holds a ``[h, d]`` tile 128 lanes wide
    whatever ``d`` is, and lays an array whose last dim is narrower out
    with ANOTHER dim minor-most (``[L, P, ps, h, 64]``: the page dim),
    which every program would have to convert to the kernels' row-major
    operands on entry and back on exit.  With the padding in the shape the
    device's own layout is the kernels'."""
    if jax.default_backend() != "tpu":
        return head_dim
    return -(-head_dim // _LANES) * _LANES


def _to_lanes(x, width):
    """``x [..., d]`` zero-padded along its last dim to a pool's row width."""
    pad = width - x.shape[-1]
    return x if not pad else jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def _as_stack(pools, layer):
    """``(stacked pools, layer)`` for the kernels, which take the serving
    engine's stacked ``[L, P, ps, ...]`` pools and a layer (an int or a
    traced scalar, handed on as it is): with ``layer`` None the pools are
    ONE layer's ``[P, ps, ...]``, a stack of one."""
    if layer is None:
        return tuple(p[None] for p in pools), 0
    return tuple(pools), layer


def _layer_scalar(layer):
    """The layer as the ``[1]`` int32 the kernels prefetch beside the page
    table and the lengths: a Python int becomes a constant, a traced scalar
    stays one.  ONE form for both, so a program's kernels do not depend on
    which kind of index its model hands the seam."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _gather_pages(pool, table, layer):
    """The dense fall-backs' gather of a table's pages ``[B, NP, ps, ...]``
    out of layer ``layer`` of a stacked pool (None: a one-layer pool)."""
    return pool[table] if layer is None else pool[layer, table]


def _decode_blocking(q, k_pages, NP):
    """Block sizes of the decode sweep from the shapes the kernel is handed:
    ``(pages, vmem_limit)`` -- the pages of one block of the sweep (as many
    as make 128 keys, no more than the table holds, and halved while the
    two buffers of K and V blocks pass 8 MiB), and the VMEM limit to ask
    Mosaic for where wide pages need more than its 16 MiB default
    (``None``: the default does).

    ``None`` where the sweep cannot fetch a page of the pool by a DMA of
    its own, which Mosaic takes only in whole tiles: rows that are not
    whole lanes (a head size that is no multiple of 128), int8 pools
    (their scale pools' rows are ``HKV`` lanes wide), and 16-bit pools
    whose heads are not whole sublane tiles (2, 4 or a multiple of 8: not
    an mp shard's 3, not 12).  Those keep the sweep of one page a grid
    step (:func:`_paged_page_kernel`)."""
    H, D = q.shape[-2:]
    page_size, HKV = k_pages.shape[-3:-1]
    kv_bytes = k_pages.dtype.itemsize
    if D % _LANES or kv_bytes == 1 \
            or (kv_bytes == 2 and HKV not in (2, 4) and HKV % 8):
        return None
    rows = 32 // kv_bytes                 # sublanes of one tile of the pool
    page = page_size * -(-HKV // rows) * rows * D * kv_bytes
    pages = max(1, min(-(-_LANES // page_size), NP))
    while pages > 1 and 4 * pages * page > 8 << 20:
        pages //= 2
    # one page of K and of V widened to f32, its scores and probabilities;
    # q, out and the carried state
    staged = 4 * page_size * -(-HKV // 8) * 8 * D * 4
    state = 5 * (H // HKV) * -(-HKV // 8) * 8 * D * 4
    need = 4 * pages * page + staged + state + (4 << 20)
    return pages, need if need > 16 << 20 else None


def _paged_decode_kernel(pt_ref, lens_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sem, swept, *, page_size,
                         scale, pages, table_pages):
    """Grid (slot b): a slot's whole sweep is one grid step.  ``k_hbm`` /
    ``v_hbm`` are the pools as they lie in HBM, ``[L, P, ps, HKV, D]``, of
    which layer ``layer_ref[0]`` (the third prefetched scalar) is read;
    the scratch: two buffers of ``pages`` pages for each, the DMA
    semaphores ``[buffer, pool]``, and the count of blocks swept so far.

    The sweep walks the slot's table a block of ``pages`` pages at a time,
    as many blocks as the slot's length needs: each LIVE page is fetched
    through the page table by a DMA of its own into one of the two buffers
    while the block before it is attended, and the first block of the NEXT
    slot is fetched during this slot's last, so the buffers alternate
    across slots.  The batch dimension is sequential for that: on a chip
    with two cores the slots no longer partition between them (a v5e has
    one).  Entries past the slot's last page are not fetched at all: their
    place in the buffer keeps what an earlier block left there (zeros at
    first) and every key of it is masked, so dead table entries are never
    read.

    One query row a head, so the products are multiply-and-reduce on the
    VPU over the page AS IT LIES, ``[ps, HKV, D]`` with the heads on the
    sublanes: every kv head at once, no per-head tile, no transposition.
    q comes in as ``[g, HKV, D]`` (query head ``kv * g + r`` is row
    ``[r, kv]``), so a page streams once for all g grouped query heads.
    K/V are read as stored and widened to f32 in VMEM; scores,
    probabilities, statistics and the accumulator are f32, and so are both
    products' operands.  The online-softmax update runs a page at a time,
    so that a page's tensors stay near the register file; the carried
    state is a few tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    g, HKV, D = q_ref.shape[1:]
    layer = layer_ref[0]

    def sweep(slot):
        """``(length, pages)`` of a slot's sweep: a length that overruns
        the table sees the table's keys (callers mask with seq_lens), and
        an empty slot sweeps one page with every key masked, so that each
        grid step fetches and waits alike."""
        seq_len = jnp.minimum(lens_ref[slot], table_pages * page_size)
        return seq_len, jnp.maximum(
            (seq_len + page_size - 1) // page_size, 1)

    def fetch(slot, blk, buf, live, start):
        """Start (or wait for) the DMAs of block ``blk`` of ``slot`` into
        buffer ``buf``: one a pool for each of the block's pages below
        ``live``, the slot's page count."""
        first = blk * pages

        def page(r, carry):
            # to wait for a copy only its shape counts
            at = pt_ref[slot, first + r] if start else 0
            for a, (hbm, buffers) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                copy = pltpu.make_async_copy(
                    hbm.at[layer, at], buffers.at[buf, r], sem.at[buf, a])
                copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pages, live - first), page, 0)

    @pl.when(b == 0)
    def _first():
        swept[0] = 0
        # what no DMA overwrites is multiplied by a probability of zero:
        # it has to be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        fetch(0, 0, 0, sweep(0)[1], True)

    seq_len, live = sweep(b)
    blocks = (live + pages - 1) // pages
    base = swept[0]
    q = q_ref[0].astype(jnp.float32) * jnp.float32(scale)  # [g, HKV, D]

    def block(blk, state):
        buf = (base + blk) % 2

        @pl.when(blk + 1 < blocks)
        def _next_block():
            fetch(b, blk + 1, 1 - buf, live, True)

        @pl.when((blk + 1 == blocks) & (b + 1 < pl.num_programs(0)))
        def _next_slot():
            fetch(b + 1, 0, 1 - buf, sweep(b + 1)[1], True)

        fetch(b, blk, buf, live, False)
        m, l, acc = (list(x) for x in state)
        for r in range(pages):
            k = k_buf[buf, r].astype(jnp.float32)          # [ps, HKV, D]
            v = v_buf[buf, r].astype(jnp.float32)
            pos = (blk * pages + r) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (page_size, 1, 1), 0)
            valid = pos < seq_len                          # [ps, 1, 1]
            for j in range(g):
                s = jnp.sum(k * q[j][None], axis=2, keepdims=True)
                s = jnp.where(valid, s, jnp.float32(NEG_INF))  # [ps, HKV, 1]
                m_new = jnp.maximum(m[j], s.max(axis=0))   # [HKV, 1]
                # an empty slot has no valid key and m_new == NEG_INF,
                # s - m_new == 0: its p must still be 0, so that it ends
                # with l == 0 and writes zeros
                p = jnp.where(valid, jnp.exp(s - m_new[None]),
                              jnp.float32(0.0))
                alpha = jnp.exp(m[j] - m_new)
                l[j] = l[j] * alpha + p.sum(axis=0)
                acc[j] = acc[j] * alpha + (p * v).sum(axis=0)  # [HKV, D]
                m[j] = m_new
        return tuple(m), tuple(l), tuple(acc)

    m, l, acc = jax.lax.fori_loop(0, blocks, block, (
        (jnp.full((HKV, 1), NEG_INF, jnp.float32),) * g,
        (jnp.zeros((HKV, 1), jnp.float32),) * g,
        (jnp.zeros((HKV, D), jnp.float32),) * g))
    swept[0] = base + blocks
    for j in range(g):
        # output stays f32; the wrapper downcasts outside the kernel
        o_ref[0, j] = acc[j] / jnp.maximum(l[j], jnp.float32(1e-30))


def _last_page(seq_len, page_size):
    """Index of the last page a row's sweep must visit (>= 0, so empty
    rows still have a step to finalize on — they write zeros)."""
    return jnp.maximum((seq_len + page_size - 1) // page_size - 1, 0)


def _paged_page_kernel(pt_ref, lens_ref, layer_ref, q_ref, *refs, page_size,
                       scale, num_kv_heads, quantized):
    """Grid (slot b, table entry i): the sweep of ONE page a grid step, for
    the pools of which the decode kernel's DMAs take no page
    (:func:`_decode_blocking`); the layer is the index maps' business.
    ``refs``: the K and V page, (int8 pools:
    their ``[ps, HKV]`` scale tiles, the dequantization fused into the
    loads), the output block, m / l / acc.

    Mosaic discipline (mirrors ops/flash_attention.py): strictly 2-D tiles,
    keepdims reductions, f32 constants, plain-contracting dot_generals
    only.  KV heads run as a STATIC unrolled loop over the page's f32
    ``[page, D]`` tiles, each streamed ONCE and serving all g grouped query
    heads."""
    from jax.experimental import pallas as pl

    k_ref, v_ref = refs[:2]
    ks_ref, vs_ref = refs[2:4] if quantized else (None, None)
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    g = q_ref.shape[1] // num_kv_heads

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, jnp.float32(NEG_INF))
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]
    # finalize at the row's LAST VALID page, not the table edge — steps
    # past it present a repeated (un-fetched) block and do nothing.  The
    # clamp to the grid edge covers rows whose length overruns the table
    # (callers mask with seq_lens).
    last = jnp.minimum(_last_page(seq_len, page_size),
                       pl.num_programs(1) - 1)

    def load(ref, scale_ref, j):
        x = ref[0, :, j, :].astype(jnp.float32)            # [page, D]
        return x * scale_ref[0, :, j:j + 1] if quantized else x

    @pl.when(i * page_size < seq_len)
    def _compute():
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = pos < seq_len                              # [1, page]
        for j in range(num_kv_heads):
            r = slice(j * g, (j + 1) * g)
            q = q_ref[0, r, :].astype(jnp.float32)         # [g, D]
            k = load(k_ref, ks_ref, j)
            v = load(v_ref, vs_ref, j)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * jnp.float32(scale)
            s = jnp.where(valid, s, jnp.float32(NEG_INF))  # [g, page]
            m_prev = m_scr[r, :]                           # [g, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                         # [g, page]
            alpha = jnp.exp(m_prev - m_new)                # [g, 1]
            l_scr[r, :] = l_scr[r, :] * alpha + p.sum(axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [g, D]
            acc_scr[r, :] = acc_scr[r, :] * alpha + pv
            m_scr[r, :] = m_new

    @pl.when(i == last)
    def _fin():
        # empty rows (seq_len == 0) run _init then _fin at step 0 (when
        # blocks execute in definition order) and write zeros.  Output
        # stays f32; the wrapper downcasts outside the kernel.
        o_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], jnp.float32(1e-30))


def _paged_decode_pallas(q, pools, scales, page_table, seq_lens, scale,
                         interpret, layer, name=None):
    """q [B, H, D] against layer ``layer`` of the stacked ``pools`` (K, V:
    [L, P, ps, HKV, D]) and, for int8 pools, ``scales`` (K, V:
    [L, P, ps, HKV]) -> [B, H, D].  The layer (an int or a traced int32
    scalar) is the third prefetched scalar, so a layer is read where it
    lies in the pool: the decode kernel fetches its pages from HBM itself;
    pools whose pages it cannot fetch (:func:`_decode_blocking`) are swept
    a page a grid step, the page a block whose leading dimension of one,
    the layer, the kernel does not see."""
    B, H, D = q.shape
    HKV = pools[0].shape[3]
    blocking = _decode_blocking(q, pools[0], page_table.shape[1])
    if blocking is None:
        operand = q
        paged = (*pools, *(a.astype(jnp.float32) for a in scales))
    else:
        # a few KB around the kernel: query head kv * g + r to row [r, kv]
        operand = jnp.swapaxes(q.reshape(B, HKV, H // HKV, D), 1, 2)
        paged = pools
    # x64 OFF around the call: the framework enables jax_enable_x64 globally
    # (paddle int64 tensor parity), and under it the literal 0s of the
    # BlockSpec index maps trace as i64 constants, which Mosaic fails to
    # legalize (checked against libtpu 0.0.34: "failed to legalize operation
    # 'func.func'" on the index-map transform).  Every dtype in the kernel is
    # pinned, so x32 promotion rules change nothing numerically.
    with jax.enable_x64(False):
        out = _paged_decode_call(
            page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
            _layer_scalar(layer), operand, *paged, scale=scale,
            interpret=interpret, name=name, blocking=blocking)
    if blocking is not None:
        out = jnp.swapaxes(out, 1, 2).reshape(B, H, D)
    return out.astype(q.dtype)


@_traced_once("decode")
def _paged_decode_call(page_table, seq_lens, layer, operand, *paged, scale,
                       interpret, name, blocking):
    """The decode kernel applied to what :func:`_paged_decode_pallas`
    prepares: the int32 table, lengths and ``[1]`` layer, the query rows
    (``[B, H, D]``, or ``[B, g, HKV, D]`` for the kernel of ``blocking``),
    the pools (and float32 scale pools) -> float32 rows in the query's
    shape."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    page_size, HKV = paged[0].shape[2:4]
    NP = page_table.shape[1]
    if blocking is None:
        B, H, D = operand.shape

        def page_spec(pool):
            # the index map clamps the sweep: steps past the row's last
            # valid page re-present it, and a revisited block is not
            # fetched again
            def idx(b, i, pt, ln, ly):
                return (ly[0], pt[b, jnp.minimum(
                    i, _last_page(ln[b], page_size))]) + (0,) * (pool.ndim - 2)
            return pl.BlockSpec((None, 1) + pool.shape[2:], idx)

        grid = (B, NP)
        q_spec = pl.BlockSpec((1, H, D), lambda b, i, pt, ln, ly: (b, 0, 0))
        in_specs = [q_spec] + [page_spec(a) for a in paged]
        scratch = [pltpu.VMEM((H, 1), jnp.float32),
                   pltpu.VMEM((H, 1), jnp.float32),
                   pltpu.VMEM((H, D), jnp.float32)]
        kernel = functools.partial(
            _paged_page_kernel, page_size=page_size, scale=scale,
            num_kv_heads=HKV, quantized=len(paged) > 2)
        # batch rows are independent; the page sweep carries the
        # online-softmax state and stays sequential
        semantics, vmem_limit = ("parallel", "arbitrary"), None
    else:
        pages, vmem_limit = blocking
        B, g, _, D = operand.shape
        grid = (B,)
        q_spec = pl.BlockSpec((1, g, HKV, D),
                              lambda b, pt, ln, ly: (b, 0, 0, 0))
        in_specs = [q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch = [pltpu.VMEM((2, pages) + a.shape[2:], a.dtype)
                   for a in paged] \
            + [pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((1,), jnp.int32)]
        kernel = functools.partial(
            _paged_decode_kernel, page_size=page_size, scale=scale,
            pages=pages, table_pages=NP)
        # sequential: a slot's last block fetches the next slot's first
        semantics = ("arbitrary",)
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
            out_specs=q_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(operand.shape, jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=vmem_limit),
    )(page_table, seq_lens, layer, operand, *paged)


def _paged_flash_pallas(q, k_pages, v_pages, page_table, seq_lens, scale,
                        interpret, layer):
    # This call alone carries no ``name="paged_decode"``: a name is the
    # innermost scope of the kernel's name stack and so becomes its HLO
    # instruction name, and the benchmark's ``paged_decode_roofline`` finds
    # this kernel in the decode program as ``%step.N`` (PERF.md section 7
    # says what has to be repointed first).  ``paged_chunk_attend`` does
    # not come through here: it has a kernel of its own
    # (``_paged_chunk_pallas``) under the scope ``chunk_attention``.
    return _paged_decode_pallas(q, (k_pages, v_pages), (), page_table,
                                seq_lens, scale, interpret, layer)


def _paged_q_flash_pallas(q, k_pages, v_pages, k_scales, v_scales,
                          page_table, seq_lens, scale, interpret, layer):
    return _paged_decode_pallas(q, (k_pages, v_pages), (k_scales, v_scales),
                                page_table, seq_lens, scale, interpret,
                                layer, name="paged_decode_q")


def _gathered_attend(q, k, v, seq_lens, scale):
    """The dense-reference math shared by the bf16 and int8 fallbacks:
    q [B, H, D] against gathered k/v [B, T, HKV, D] masked by seq_lens.

    GQA runs as a grouped einsum over [HKV, g] (query head k*g+j attends
    kv head k, the jnp.repeat convention) — the K/V operands stay at their
    native HKV head count instead of materializing a g×-repeated copy, so
    the CPU/reference path allocates KV bytes once, not per query head."""
    B, H, D = q.shape
    T = k.shape[1]
    HKV = k.shape[2]
    g = H // HKV
    qg = q.reshape(B, HKV, g, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(T)[None, None, None, :]
    s = jnp.where(pos < seq_lens[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def _gathered_chunk_attend(q, k, v, lens2, scale):
    """Chunked twin of :func:`_gathered_attend`: q [B, C, H, D] against
    gathered k/v [B, T, HKV, D], position (b, t) masked to its OWN valid
    length ``lens2[b, t]``.  The point is the gather amortization: the
    slot's pages are gathered ONCE for all C chunk positions, where the
    naive [B*C]-row expansion through the dense reference re-gathers the
    full table width per position (C× the bytes for identical data)."""
    B, C, H, D = q.shape
    T = k.shape[1]
    HKV = k.shape[2]
    g = H // HKV
    qg = q.reshape(B, C, HKV, g, D).astype(jnp.float32)
    s = jnp.einsum("bckgd,btkd->bckgt", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(T)[None, None, None, None, :]
    s = jnp.where(pos < lens2[:, :, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bckgt,btkd->bckgd", p, v.astype(jnp.float32))
    return out.reshape(B, C, H, D).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, page_table, seq_lens,
                        scale=None, layer=None):
    """Dense-gather reference with identical semantics (oracle + fallback).

    GQA: q may carry g*HKV heads against HKV-head pools (q head h attends
    kv head h//g, matching jnp.repeat(kv, g, axis=heads))."""
    B, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    k = _gather_pages(k_pages, page_table, layer)[..., :D]
    v = _gather_pages(v_pages, page_table, layer)[..., :D]
    HKV = k.shape[3]
    return _gathered_attend(q, k.reshape(B, -1, HKV, D),
                            v.reshape(B, -1, HKV, D), seq_lens, scale)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, scale=None,
                    interpret=None, layer=None):
    """Decode attention over a paged KV cache (see module docstring).
    With ``layer`` the pools are the serving engine's stacked
    ``[L, P, ps, HKV, D]`` and that layer of them is attended, in place.

    Uses the length-bounded flash Pallas kernel on TPU (each row's page
    sweep stops at its last valid page — dead table slots cost no DMA);
    dense reference elsewhere.  All rows of ``page_table`` must index
    valid pages (pad rows with any in-range id — padded pages are masked
    by ``seq_lens``).  GQA: q with g*HKV heads against HKV-head pools is
    grouped inside the kernel — each page streams once for all g query
    heads.
    """
    B, H, D = q.shape
    if H % k_pages.shape[-2]:
        raise ValueError(f"q heads {H} not a multiple of kv heads "
                         f"{k_pages.shape[-2]}")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return paged_attention_ref(q, k_pages, v_pages, page_table,
                                       seq_lens, scale, layer)
        interpret = False
    pools, layer = _as_stack((k_pages, v_pages), layer)
    q = _to_lanes(q, k_pages.shape[-1])     # rows as wide as the pool's
    if _MP_SCOPE[0] is not None:
        out = _flash_sharded(_paged_flash_pallas, q, pools, (), page_table,
                             seq_lens, scale, interpret, layer)
    else:
        out = _paged_flash_pallas(q, *pools, page_table, seq_lens, scale,
                                  interpret, layer)
    return out[..., :D]


# ------------------------------------------------------------------ writes
# ONE global pool shared by every sequence through an explicit page table,
# and PER-SLOT lengths: each slot writes and decodes at its own position,
# which is what iteration-level batching needs.
#
# The pool holds every layer, stacked: [L, P, ps, h, d].  Each entry takes
# ``layer`` and reads or writes THAT layer of the stacked pool where it lies
# (``layer=None``: the pool is one layer's [P, ps, h, d]).  Nothing slices a
# layer out or stacks layers back: the kernels get the layer as a leading
# block dimension of one at the block index a prefetched scalar gives (so a
# layer may be a Python int or a traced scalar alike), the dense fall-backs
# gather ``pool[layer, table]``, and a write touches the rows it writes.
# One write mechanism: a chunk of C tokens per slot at the slot's own
# position (a decode token is a chunk of one, a whole prompt a chunk at
# length zero) — a scatter off the TPU, and on it one Pallas call a layer
# over the whole pool tuple, aliased in and out (``_paged_write_pallas``
# under ``paged_pool_write``).


def paged_table_chunk_write(pool, kv, table, lens, layer=None):
    """Write a CHUNK of C tokens per slot at positions ``lens[b] ..
    lens[b]+C-1`` (a prefill chunk; speculative verify: the last sampled
    token plus C-1 draft tokens land in one call).

    pool: [P, ps, *rest], or with ``layer`` the stacked [L, P, ps, *rest]
    of which only that layer's rows are written; kv: [B, C, *rest]; table:
    [B, NP]; lens: [B] int32.  The trailing dims are generic: K/V payload
    pools carry ``[h, d]``, the quantized path's scale pools ``[h]``.
    Lanes past the table's reach (pad drafts of a slot near the model cap)
    are DROPPED, not clamped: a clamp would make the pad lane collide with
    the chunk's own last real write in the same scatter, and duplicate-
    index ``.set`` order is undefined — the junk could win and corrupt the
    final valid position.  In-range junk lanes (rejected drafts) need no
    undo: they sit past the slot's valid length, invisible to ``seq_lens``
    masking, and the next step's write at the rolled-back length
    overwrites them."""
    B, C = kv.shape[:2]
    rest = kv.shape[2:]
    at = () if layer is None else (layer,)
    ps = pool.shape[len(at) + 1]
    NP = table.shape[1]
    pos = lens.astype(jnp.int32)[:, None] \
        + jnp.arange(C, dtype=jnp.int32)[None, :]            # [B, C]
    in_range = pos < jnp.int32(NP * ps)
    pos_c = jnp.minimum(pos, jnp.int32(NP * ps - 1))
    pages = jnp.take_along_axis(table.astype(jnp.int32), pos_c // ps, axis=1)
    # the sentinel is one past the last page: -1 would wrap to it
    pages = jnp.where(in_range, pages, jnp.int32(pool.shape[len(at)]))
    return pool.at[(*at, pages.reshape(-1), (pos_c % ps).reshape(-1))].set(
        kv.reshape((B * C,) + rest).astype(pool.dtype), mode="drop")


def _pad_to_pages(kv, page_size):
    """kv [B, S, *rest] zero-padded along S to whole pages."""
    pad = -kv.shape[1] % page_size
    return jnp.pad(kv, ((0, 0), (0, pad)) + ((0, 0),) * (kv.ndim - 2))


# ---------------------------------------------------------- chunk attention
# C query positions per slot (a prefill chunk, a verify chunk) against the
# slot's pages.  The decode kernel above takes ONE query row per batch row,
# so serving a chunk through it means a [B*C]-row batch that walks the
# same page table C times.  The chunk kernel keeps the slot's whole query block resident instead — its
# block index is constant over the page sweep, so it is fetched once — and
# every K/V page comes into VMEM once per (slot, query tile) and meets all
# of the tile's positions there.
#
# Query positions ride the LANE axis: q goes in as [B, H, D, C], scores are
# [keys, C], the softmax statistics [1, C], the accumulator [D, C].  A page
# of 16 keys on the lane axis would fill 16 of 128 lanes; C fills them, and
# the reductions over keys run down the sublanes.  A step takes as many
# pages as make 128 keys (each page its own in-spec through the page
# table), so the two products of a step have a full contraction tile.
# Position t of slot b sees keys 0 .. lens[b]+t: one mask per step replaces
# the per-row seq_len, and the sweep stops at the page of the tile's LAST
# position: grid steps past it re-present that page's block index, and
# Pallas does not fetch a block whose index repeats.


def _chunk_blocking(q, k_pages, NP):
    """Block sizes from the shapes the kernel is handed: ``(Cp, tile,
    pages, vmem_limit)`` — C padded to whole lane tiles; the query tile of
    one grid step (two lane tiles where the kernel's VMEM then stays under
    12 MiB, else one); the pages of one step (128 keys' worth, at most 8
    in-specs a pool, no more than the table holds); and the VMEM limit to
    ask Mosaic for where a wide model needs more than its 16 MiB default
    (``None``: the default does)."""
    _, C, H, D = q.shape
    page_size, HKV = k_pages.shape[-3:-1]
    kv_bytes = k_pages.dtype.itemsize
    Cp = -(-C // _LANES) * _LANES
    pages = max(1, min(_LANES // page_size, 8, NP))
    T = pages * page_size
    lanes = -(-D // _LANES) * _LANES
    rows = 32 // kv_bytes                 # sublanes of one tile of the pool

    def vmem(tile):
        blocks = H * D * tile * (2 * q.dtype.itemsize + 3 * 4)  # q, out x2; acc
        stats = 2 * H * 8 * tile * 4
        staged = 2 * HKV * T * lanes * 4
        paged = 2 * 2 * T * -(-HKV // rows) * rows * lanes * kv_bytes
        return blocks + stats + staged + paged + (1 << 20)      # + scales

    tile = 2 * _LANES if Cp % (2 * _LANES) == 0 \
        and vmem(2 * _LANES) <= 12 << 20 else _LANES
    need = vmem(tile) + (4 << 20)                               # temporaries
    return Cp, tile, pages, need if need > 16 << 20 else None


def _chunk_last_key(seq_len, j, tile, chunk, capacity):
    """Last key position query tile ``j`` of a slot can see: that of its
    last REAL position (pad positions past ``chunk`` extend no sweep),
    clipped to the table's reach as the dense path clips ``lens2``."""
    return jnp.clip(seq_len + jnp.minimum((j + 1) * tile, chunk) - 1,
                    0, capacity - 1)


def _paged_chunk_kernel(pt_ref, lens_ref, layer_ref, q_ref, *refs, page_size,
                        scale, num_kv_heads, pages, chunk, table_pages,
                        quantized):
    """Grid (slot b, query tile j, page step i); the layer is the index
    maps' business.  ``refs``: ``pages`` K
    page refs, as many V, (int8 pools: as many K-scale and V-scale refs),
    the output block, then the scratch: the step's K and V staged
    head-major in f32, and m / l / acc.  K/V are read as stored and
    widened to f32 in VMEM (dequant fused there); both products take f32
    operands on the MXU, which rounds them to bf16 (exact for the stored
    K, V and a bf16 q; the probabilities lose their low bits); 2-D tiles
    inside the head loop, keepdims reductions, f32 constants."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    ks_refs, vs_refs = ((refs[2 * pages:3 * pages],
                         refs[3 * pages:4 * pages]) if quantized
                        else (None, None))
    o_ref, k_scr, v_scr, m_scr, l_scr, acc_scr = refs[-6:]

    b = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)
    H, tile = q_ref.shape[1], q_ref.shape[3]
    g = H // num_kv_heads
    T = k_scr.shape[1]                         # keys of one step
    capacity = table_pages * page_size

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, jnp.float32(NEG_INF))
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]
    last = _chunk_last_key(seq_len, j, tile, chunk, capacity)

    def stage(pool_refs, scale_refs, scr):
        """The step's pages into ``scr`` [HKV, T, D] f32, head-major: the
        head loop below indexes a LEADING dim (a page holds its heads on
        the sublane axis, where Mosaic takes no dynamic index)."""
        for r in range(pages):
            x = pool_refs[r][0].astype(jnp.float32)        # [ps, HKV, D]
            if quantized:
                x = x * scale_refs[r][0][:, :, None]
            scr[:, r * page_size:(r + 1) * page_size, :] = \
                jnp.swapaxes(x, 0, 1)

    @pl.when(i * T <= last)
    def _compute():
        stage(k_refs, ks_refs, k_scr)
        stage(v_refs, vs_refs, v_scr)
        key = i * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        lim = jnp.minimum(
            seq_len + j * tile
            + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1),
            capacity - 1)
        valid = key <= lim                                 # [T, tile]

        # a loop over kv heads, not an unrolled one: the kernel is traced
        # and lowered once per layer of every program that holds it, and
        # 16 unrolled heads of 8 pages were a thousand equations each time
        @pl.loop(0, num_kv_heads)
        def _kv_head(kv):
            k = k_scr[kv]                                  # [T, D]
            v = v_scr[kv]
            for h in (kv * g + r for r in range(g)):
                q = q_ref[0, h].astype(jnp.float32)        # [D, tile]
                s = jax.lax.dot_general(
                    k, q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * jnp.float32(scale)
                s = jnp.where(valid, s, jnp.float32(NEG_INF))
                m_prev = m_scr[h]                          # [1, tile]
                m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
                # a position with no valid key so far has m_new == NEG_INF
                # and s - m_new == 0: its p must still be 0, so that it
                # ends with l == 0 and writes zeros
                p = jnp.where(valid, jnp.exp(s - m_new), jnp.float32(0.0))
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = l_scr[h] * alpha + p.sum(axis=0, keepdims=True)
                pv = jax.lax.dot_general(
                    v, p, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [D, tile]
                acc_scr[h] = acc_scr[h] * alpha + pv
                m_scr[h] = m_new

    @pl.when(i == last // T)
    def _fin():
        # output stays f32; the wrapper downcasts outside the kernel
        @pl.loop(0, H)
        def _head(h):
            o_ref[0, h] = acc_scr[h] / jnp.maximum(l_scr[h],
                                                   jnp.float32(1e-30))


def _paged_chunk_pallas(q, pools, scales, table, lens, scale, interpret,
                        layer, name=None):
    """q [B, C, H, D] against layer ``layer`` of the stacked ``pools`` (K,
    V: [L, P, ps, HKV, D]) and, for int8 pools, ``scales`` (K, V:
    [L, P, ps, HKV]) -> [B, C, H, D].  The layer is a block dimension of
    one that the kernel does not see, at the block index the third
    prefetched scalar gives (an int or a traced int32 scalar), so a layer
    is read where it lies in the pool."""
    C = q.shape[1]
    blocking = _chunk_blocking(q, pools[0], table.shape[1])
    qt = jnp.transpose(jnp.pad(q, ((0, 0), (0, blocking[0] - C), (0, 0),
                                   (0, 0))), (0, 2, 3, 1))  # [B, H, D, Cp]
    paged = (*pools, *(a.astype(jnp.float32) for a in scales))
    # x64 OFF for the same Mosaic i64-index reason as _paged_decode_pallas
    with jax.enable_x64(False):
        out = _paged_chunk_call(
            table.astype(jnp.int32), lens.astype(jnp.int32),
            _layer_scalar(layer), qt, *paged, scale=scale,
            interpret=interpret, name=name, chunk=C, blocking=blocking)
    return jnp.transpose(out, (0, 3, 1, 2))[:, :C].astype(q.dtype)


@_traced_once("chunk")
def _paged_chunk_call(table, lens, layer, qt, *paged, scale, interpret, name,
                      chunk, blocking):
    """The chunk kernel applied to what :func:`_paged_chunk_pallas`
    prepares: the int32 table, lengths and ``[1]`` layer, the queries
    ``[B, H, D, Cp]`` of a ``chunk`` padded to ``Cp``, the pools (and
    float32 scale pools) -> float32 ``[B, H, D, Cp]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D, Cp = qt.shape
    page_size, HKV = paged[0].shape[2:4]
    NP = table.shape[1]
    _, tile, pages, vmem_limit = blocking

    def page_map(r, rank):
        def idx(b, j, i, pt, ln, ly):
            last = _chunk_last_key(ln[b], j, tile, chunk, NP * page_size)
            return (ly[0],
                    pt[b, jnp.minimum(i * pages + r, last // page_size)]) \
                + (0,) * (rank - 2)
        return idx

    q_spec = pl.BlockSpec((1, H, D, tile),
                          lambda b, j, i, pt, ln, ly: (b, 0, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Cp // tile, -(-NP // pages)),
        in_specs=[q_spec] + [
            pl.BlockSpec((None, 1) + a.shape[2:], page_map(r, a.ndim))
            for a in paged for r in range(pages)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((HKV, pages * page_size, D), jnp.float32),
            pltpu.VMEM((HKV, pages * page_size, D), jnp.float32),
            pltpu.VMEM((H, 1, tile), jnp.float32),
            pltpu.VMEM((H, 1, tile), jnp.float32),
            pltpu.VMEM((H, D, tile), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_chunk_kernel, page_size=page_size, scale=scale,
            num_kv_heads=HKV, pages=pages, chunk=chunk, table_pages=NP,
            quantized=len(paged) > 2),
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, jnp.float32),
        interpret=interpret,
        # slots and query tiles are independent; the page sweep carries
        # the online-softmax state and stays sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
    )(table, lens, layer, qt, *(a for a in paged for _ in range(pages)))


def _paged_chunk_flash_pallas(q, k_pages, v_pages, table, lens, scale,
                              interpret, layer):
    return _paged_chunk_pallas(q, (k_pages, v_pages), (), table, lens, scale,
                               interpret, layer)


def _paged_chunk_q_flash_pallas(q, k_pages, v_pages, k_scales, v_scales,
                                table, lens, scale, interpret, layer):
    return _paged_chunk_pallas(q, (k_pages, v_pages), (k_scales, v_scales),
                               table, lens, scale, interpret, layer,
                               name="paged_chunk_q")


def _chunk_attend(pallas_fn, q, pools, scales, table, lens, layer):
    """The TPU side of both chunk entries: the chunk kernel under the
    scope the benchmark reads it by, head-sharded under an mp scope."""
    D = q.shape[-1]
    scale = 1.0 / math.sqrt(D)
    n = len(pools)
    stacked, layer = _as_stack((*pools, *scales), layer)
    pools, scales = stacked[:n], stacked[n:]
    q = _to_lanes(q, pools[0].shape[-1])    # rows as wide as the pool's
    with jax.named_scope("chunk_attention"):
        if _MP_SCOPE[0] is not None:
            out = _flash_sharded(pallas_fn, q, pools, scales, table, lens,
                                 scale, False, layer)
        else:
            out = pallas_fn(q, *pools, *scales, table, lens, scale, False,
                            layer)
    return out[..., :D]


def _chunk_lens(lens, C, capacity):
    """[B, C] valid lengths of the dense chunk path: position t of slot b
    sees ``lens[b] + t + 1`` keys, clipped to the table's reach."""
    lens2 = lens.astype(jnp.int32)[:, None] + jnp.int32(1) \
        + jnp.arange(C, dtype=jnp.int32)[None, :]
    return jnp.minimum(lens2, jnp.int32(capacity))


def paged_chunk_attend(q, k_pages, v_pages, table, lens, layer=None):
    """Attend C query positions per slot against the global paged pools
    (with ``layer``: that layer of the stacked pools, in place):
    position t of slot b sees tokens ``0 .. lens[b]+t`` (its own K/V
    included — the chunk is written before attending, and within-chunk
    causality falls out of the per-position valid lengths).

    On TPU one chunk kernel (:func:`_paged_chunk_pallas`): a slot's C
    positions are one query block, and each of its K/V pages is walked once
    for all of them.  Elsewhere the dense reference, which gathers the
    slot's pages once for all C positions.

    q: [B, C, H, D] -> [B, C, H, D]."""
    B, C, H, D = q.shape
    if jax.default_backend() == "tpu":
        return _chunk_attend(_paged_chunk_flash_pallas, q,
                             (k_pages, v_pages), (), table, lens, layer)
    NP = table.shape[1]
    ps, HKV = k_pages.shape[-3:-1]
    lens2 = _chunk_lens(lens, C, NP * ps)
    k, v = (_gather_pages(p, table, layer)[..., :D].reshape(
        B, NP * ps, HKV, D) for p in (k_pages, v_pages))
    return _gathered_chunk_attend(q, k, v, lens2, 1.0 / math.sqrt(D))


# --------------------------------------------------- int8 quantized pools
# The quantized serving path (paddle_tpu.serving.quant): K/V page pools
# stored as int8 with a PARALLEL SCALE POOL — one float32 scale per
# (page-slot, kv-head), i.e. each page carries a [ps, h] scale tile next to
# its [ps, h, d] int8 payload, addressed by the SAME page table.  Per-slot
# scales make every write self-contained (a token write never has to
# requantize a page it shares with older tokens), and per-head granularity
# keeps outlier heads from poisoning the grid of quiet ones.  Scale-pool
# overhead is 4/d of the payload (≈6% at d=64) — bytes per token drop
# ~2x vs bf16, ~3.8x vs f32.
#
# Quantization is FUSED into the write ops (the bf16 K/V produced by the
# projection is rounded on the way into the pool scatter) and
# dequantization into the attention consumers: the Pallas kernel multiplies
# each int8 page tile by its scale column in VMEM right after the HBM
# stream-in, so no full-precision copy of the cache ever materializes in
# HBM.  (The off-TPU dense reference dequantizes the GATHERED pages — a
# transient [B, T] working set, still never a full pool copy.)


def quantize_kv(kv, bits=8):
    """Quantize K or V activations onto the pool grid: ``[..., h, d]`` ->
    ``(int8 [..., h, d], float32 scales [..., h])`` — absmax over d per
    position per head (the per-page-slot-per-head layout above)."""
    from .quant import quantize_absmax

    q, scale = quantize_absmax(kv, axis=-1, bits=bits)
    return q, jnp.squeeze(scale, -1)


def _gather_dequant(pages, scales, table, layer, D):
    """A table's int8 pages (their first ``D`` lanes) and their scale
    tiles, gathered and dequantized to f32 [B, NP * ps, HKV, D]: a
    transient [B, T] working set, never a full pool copy."""
    x = _gather_pages(pages, table, layer)[..., :D].astype(jnp.float32) \
        * _gather_pages(scales, table, layer).astype(jnp.float32)[..., None]
    return x.reshape((table.shape[0], -1) + x.shape[-2:])


def _pool_rows(pools, k, v):
    """K and V ``[B, C, h, d]`` as the rows the pool tuple stores, one
    array per pool: themselves for ``(kp, vp)``; for the int8 path's
    ``(kp, vp, ks, vs)`` rounded onto the int8 grid, with their scales.
    Payload rows are filled up with zeros to the pools' row width
    (:func:`pool_lane_dim`)."""
    width = pools[0].shape[-1]          # whole lanes of the head size
    if len(pools) == 2:
        return _to_lanes(k, width), _to_lanes(v, width)
    (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
    return _to_lanes(k, width), _to_lanes(v, width), ks, vs


def _write_page(pt_ref, lens_ref, b, i, page_size, chunk, table_pages):
    """``(page, first lane)`` of write step ``i`` of slot ``b``: the i-th
    page the slot's chunk touches, and the chunk lane that lands in the
    page's slot 0 (negative in the chunk's first page, which it enters part
    of the way down).  Steps past the chunk's last page, or past the
    table's reach, re-present the last page they may touch, so they are
    the same merge once more and dropped lanes are never visited; a slot
    wholly out of reach presents the table's last page and merges nothing
    into it."""
    seq_len = lens_ref[b]
    last = jnp.minimum((seq_len + chunk - 1) // page_size, table_pages - 1)
    j = jnp.minimum(seq_len // page_size + i, last)
    return pt_ref[b, j], j * page_size - seq_len


def _paged_write_kernel(pt_ref, lens_ref, layer_ref, *refs, page_size, chunk,
                        table_pages):
    """Grid (slot b, page step i); the layer is the index maps' business.
    ``refs``: for each pool of the tuple
    the ``page_size`` rows of the (padded) chunk that line up with the
    page's slots, then each pool's page as it is, then each pool's page as
    it shall be (the same HBM: the pools are aliased in and out).  A page
    is loaded, the chunk's lanes are merged into it by a position mask,
    and it is stored; nothing else of the pool is touched."""
    from jax.experimental import pallas as pl

    n = len(refs) // 3
    new_refs, old_refs, out_refs = refs[:n], refs[n:2 * n], refs[2 * n:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    steps = pl.num_programs(1)
    where = functools.partial(_write_page, pt_ref, lens_ref,
                              page_size=page_size, chunk=chunk,
                              table_pages=table_pages)
    page, first = where(b, i)
    # A page is fetched, and written back, when the block index CHANGES
    # from one grid step to the next.  Where the step before presented the
    # same page (a repeated last page; two table entries that are one page,
    # as the scratch page is), its merge is still in the output block and
    # the input block is stale: merge into the output block then.  (A page
    # that two steps APART present — the scratch page under idle slots —
    # keeps whichever merge lands last: it holds junk either way.)
    before = where(jnp.maximum(jnp.where(i == 0, b - 1, b), 0),
                   jnp.where(i == 0, steps - 1, i - 1))[0]
    fresh = ((b == 0) & (i == 0)) | (before != page)

    def merge(base_refs):
        for new_ref, base_ref, out_ref in zip(new_refs, base_refs, out_refs):
            shape = out_ref.shape[1:]                      # [ps, h(, d)]
            lane = first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            valid = (lane >= 0) & (lane < chunk)
            if len(shape) == 3:                            # payload rows
                out_ref[0] = jnp.where(valid, new_ref[0], base_ref[0])
            else:       # scale rows, handed over one [1, h] tile a row
                for r in range(page_size):
                    out_ref[0, r:r + 1, :] = jnp.where(
                        valid[r:r + 1], new_ref[0, r], base_ref[0, r:r + 1, :])

    pl.when(fresh)(lambda: merge(old_refs))
    pl.when(jnp.logical_not(fresh))(lambda: merge(out_refs))


@functools.partial(jax.jit, static_argnums=(4,))
def _paged_write_pallas(pools, rows, table, lens, interpret, layer):
    """``rows`` (one ``[B, C, h(, d)]`` array per pool) into layer ``layer``
    of the stacked ``pools`` at positions ``lens[b] .. lens[b]+C-1``, one
    Pallas call for the whole tuple with every pool aliased to its output:
    the only operations that touch a pool are kernels, each at its pages
    (a scatter into the stacked pool made XLA copy the donated pool before
    the first write and re-lay it around the kernels).  The layer rides as
    a third prefetched scalar and the call is a jitted function of its
    shapes, so a program traces and lowers ONE writer for all its layers
    (a kernel with the layer baked in costs that once a layer: 2 s more of
    set-up for each serving program)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, C = rows[0].shape[:2]
    page_size = pools[0].shape[2]
    NP = table.shape[1]
    where = functools.partial(_write_page, page_size=page_size, chunk=C,
                              table_pages=NP)

    def row_spec(x):
        # the rows that meet a page's slots start at any lane of the chunk,
        # so this operand is indexed by ELEMENT (every dimension, as Mosaic
        # wants it), over a chunk padded by a page at both ends; a row is a
        # MAJOR dimension there (the scales get a unit one behind it)
        def idx(b, i, pt, ln, ly):
            first = where(pt, ln, b, i)[1]
            return (b, jnp.clip(first + page_size, 0, C + page_size)) \
                + (0,) * (x.ndim - 2)
        return pl.BlockSpec(
            tuple(pl.Element(d) for d in (1, page_size) + x.shape[2:]), idx)

    def page_spec(pool):
        def idx(b, i, pt, ln, ly):
            return (ly[0], where(pt, ln, b, i)[0]) + (0,) * (pool.ndim - 2)
        return pl.BlockSpec((None, 1) + pool.shape[2:], idx)

    padded = []
    for x, pool in zip(rows, pools):
        x = jnp.pad(x.astype(pool.dtype), ((0, 0), (page_size, page_size))
                    + ((0, 0),) * (x.ndim - 2))
        padded.append(x if x.ndim == 4 else x[:, :, None, :])
    n = len(pools)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, (C + page_size - 2) // page_size + 1),
        in_specs=[row_spec(x) for x in padded]
        + [page_spec(pool) for pool in pools],
        out_specs=[page_spec(pool) for pool in pools],
    )
    # x64 OFF for the same Mosaic i64-index reason as _paged_decode_pallas.  The
    # name keeps this call out of ``paged_decode_roofline``, which sums the
    # decode program's kernels that carry none.
    with jax.enable_x64(False):
        return tuple(pl.pallas_call(
            functools.partial(_paged_write_kernel, page_size=page_size,
                              chunk=C, table_pages=NP),
            name="paged_write",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
            input_output_aliases={3 + n + k: k for k in range(n)},
            interpret=interpret,
            # sequential: a step may merge into the page the step before
            # it left in the output block
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
        )(table.astype(jnp.int32), lens.astype(jnp.int32),
          _layer_scalar(layer), *padded, *pools))


def _write_sharded(pools, rows, table, lens, layer):
    """shard_map wrapper of the writer under an mp scope: pools
    ``[L, P, ps, h, ...]`` and rows ``[B, C, h, ...]`` shard the head dim,
    table/lens replicate, as in :func:`_flash_sharded`."""
    from jax.sharding import PartitionSpec as P

    mesh, ax = _MP_SCOPE[0]
    n = len(pools)

    def heads_at(x, dim):
        return P(*(ax if d == dim else None for d in range(x.ndim)))

    pool_specs = tuple(heads_at(p, 3) for p in pools)

    def local(*a):
        return _paged_write_pallas(a[:n], a[n:2 * n], a[-3], a[-2], False,
                                   a[-1])

    f = jax.shard_map(
        local, mesh=mesh, out_specs=pool_specs, check_vma=False,
        in_specs=pool_specs + tuple(heads_at(x, 2) for x in rows)
        + (P(), P(), P()))
    return f(*pools, *rows, table, lens, _layer_scalar(layer))


def _pool_write(pools, rows, table, lens, layer):
    if jax.default_backend() == "tpu":
        if _MP_SCOPE[0] is not None:
            return _write_sharded(pools, rows, table, lens, layer)
        return _paged_write_pallas(pools, rows, table, lens, False, layer)
    return tuple(paged_table_chunk_write(pool, x, table, lens, layer)
                 for pool, x in zip(pools, rows))


def paged_pool_write(pools, k, v, table, lens, layer):
    """One layer's K and V chunk ``[B, C, h, d]`` into the serving engine's
    stacked pool tuple at positions ``lens[b] .. lens[b]+C-1`` of each slot
    (a decode token is a chunk of one): ``(kp, vp)``, each
    [L, P, ps, h, d], or with the int8 path's scale pools
    ``(kp, vp, ks, vs)``, for which K and V are quantized on the way in.
    Only the rows written move: every other layer and page of the pools
    stays where it lies (:func:`paged_table_chunk_write` says which lanes
    are dropped).  Returns the pool tuple."""
    return _pool_write(pools, _pool_rows(pools, k, v), table, lens, layer)


def paged_pool_prefill_write(pools, k, v, table, layer):
    """Whole prompts ``[B, S, h, d]`` into their table pages at position 0:
    a chunk at length zero, the last page filled up with zeros.  S is a
    trace-time constant; rows shorter than S are right-padded by the caller
    (the junk tokens go into pages that per-slot ``seq_lens`` masking keeps
    invisible, or into the caller's scratch page)."""
    rows = tuple(_pad_to_pages(x, pools[0].shape[2])
                 for x in _pool_rows(pools, k, v))
    return _pool_write(pools, rows, table,
                       jnp.zeros((k.shape[0],), jnp.int32), layer)


def paged_attention_quantized_ref(q, k_pages, v_pages, k_scales, v_scales,
                                  page_table, seq_lens, scale=None,
                                  layer=None):
    """Dense-gather oracle/fallback for the quantized pools: gather the
    int8 pages AND their scale tiles, dequantize the gathered working set
    (transient [B, T] — never a full pool copy), then the shared reference
    math."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    return _gathered_attend(
        q, _gather_dequant(k_pages, k_scales, page_table, layer, D),
        _gather_dequant(v_pages, v_scales, page_table, layer, D), seq_lens,
        scale)


def paged_attention_quantized(q, k_pages, v_pages, k_scales, v_scales,
                              page_table, seq_lens, scale=None,
                              interpret=None, layer=None):
    """Decode attention over int8 paged pools with dequant fused into the
    kernel (see the section comment above).

    q [B, H, D]; k_pages/v_pages [P, ps, HKV, D] int8; k_scales/v_scales
    [P, ps, HKV] f32 (with ``layer``: that layer of pools stacked
    ``[L, ...]``); page_table [B, NP] int32; seq_lens [B] int32.  Same
    table/masking/GQA contract as :func:`paged_attention`."""
    B, H, D = q.shape
    if H % k_pages.shape[-2]:
        raise ValueError(f"q heads {H} not a multiple of kv heads "
                         f"{k_pages.shape[-2]}")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return paged_attention_quantized_ref(
                q, k_pages, v_pages, k_scales, v_scales, page_table,
                seq_lens, scale, layer)
        interpret = False
    stacked, layer = _as_stack((k_pages, v_pages, k_scales, v_scales), layer)
    q = _to_lanes(q, k_pages.shape[-1])
    if _MP_SCOPE[0] is not None:
        out = _flash_sharded(_paged_q_flash_pallas, q, stacked[:2],
                             stacked[2:], page_table, seq_lens, scale,
                             interpret, layer)
    else:
        out = _paged_q_flash_pallas(q, *stacked, page_table, seq_lens, scale,
                                    interpret, layer)
    return out[..., :D]


def paged_chunk_attend_quant(q, k_pages, v_pages, k_scales, v_scales,
                             table, lens, layer=None):
    """Quantized twin of :func:`paged_chunk_attend` (chunks over int8
    pools): the same chunk kernel with the dequantizing page loads on TPU,
    one gather + dequant per slot elsewhere.
    q: [B, C, H, D] -> [B, C, H, D]."""
    B, C, H, D = q.shape
    if jax.default_backend() == "tpu":
        return _chunk_attend(_paged_chunk_q_flash_pallas, q,
                             (k_pages, v_pages), (k_scales, v_scales),
                             table, lens, layer)
    lens2 = _chunk_lens(lens, C, table.shape[1] * k_pages.shape[-3])
    return _gathered_chunk_attend(
        q, _gather_dequant(k_pages, k_scales, table, layer, D),
        _gather_dequant(v_pages, v_scales, table, layer, D),
        lens2, 1.0 / math.sqrt(D)).astype(q.dtype)


# ---------------------------------------------------------- the cache seam
# What a decoder layer knows of the paged cache: this one call.  The format
# (which pools the tuple holds, who writes, which kernel attends, what a
# length means to each entry) stays in this file.  No scope and no jit of
# its own: the benchmark finds the decode kernel by the instruction name it
# has under NO scope (``%step.N``), the chunk kernel under
# ``chunk_attention``, the writer by its kernel name.


def paged_cache_attend(q, k, v, cache, prefill_attend):
    """One decoder layer's attention through the paged KV cache: this
    layer's K/V chunk goes into the pools, then ``q`` attends the pages.

    q ``[B, C, H, D]``, k / v ``[B, C, HKV, D]`` (Tensors; keys as they
    are to be stored, e.g. already rotated); ``cache`` is ``(tag, layer,
    pools, table, lens)``: the stacked pool tuple (``(kp, vp)``, or the
    int8 cache's ``(kp, vp, ks, vs)``: K/V are quantized on the way in and
    dequantized in the attention), this layer's index into it (a Python
    int, or a traced int32 scalar: ``step * layers + layer`` inside the
    traced loop of a decoder that runs its layers several times), the page
    table ``[B, NP]`` and every slot's length BEFORE this chunk ``[B]``.
    The chunk lands at positions ``lens[b] .. lens[b]+C-1`` and position t
    attends keys ``0 .. lens[b]+t``, its own included.  ``tag``:

    - ``"served"``: one token a slot (C == 1: the decode kernel), or, with
      C > 1, whole right-padded prompts at position 0: their pages are
      written and attention is ``prefill_attend(q, k, v)``, the model's own
      dense causal attention (nothing is in the cache before a prompt);
    - ``"served_chunk"``: C tokens a slot at the slot's own position (a
      prefill chunk, a speculative verify) through the chunk kernel.

    Returns ``(attn [B, C, H, D], pools)``."""
    tag, layer, pools, table, lens = cache
    if tag not in ("served", "served_chunk"):
        raise ValueError(f"unknown paged cache tag {tag!r}")
    if tag == "served" and q.shape[1] > 1:
        attn = prefill_attend(q, k, v)
        # positions past a row's true length write junk into pages that
        # per-slot seq_lens masking (or the engine's scratch page) keeps
        # invisible
        pools = _apply(
            lambda kk, vv, tb, *pl: paged_pool_prefill_write(
                pl, kk, vv, tb, layer),
            k, v, table, *pools, n_outs=None, op_name="paged_write")
        return attn, pools
    pools = _apply(
        lambda kk, vv, tb, ln, *pl: paged_pool_write(
            pl, kk, vv, tb, ln, layer),
        k, v, table, lens, *pools, n_outs=None, op_name="paged_write")
    quantized = len(pools) == 4
    if tag == "served_chunk":
        attend = paged_chunk_attend_quant if quantized else paged_chunk_attend
        attn = _apply(
            lambda qq, tb, ln, *pl: attend(qq, *pl, tb, ln, layer=layer),
            q, table, lens, *pools, op_name="paged_attention")
    else:
        # the decode entries take ONE query row a slot and the length that
        # INCLUDES the token just written
        attend = paged_attention_quantized if quantized else paged_attention
        attn = _apply(
            lambda qq, tb, ln, *pl: attend(
                qq[:, 0], *pl, tb, ln.astype(jnp.int32) + 1,
                layer=layer)[:, None],
            q, table, lens, *pools, op_name="paged_attention")
    return attn, pools
