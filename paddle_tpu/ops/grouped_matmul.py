"""Grouped matrix product — ``x [M, K]`` whose rows lie sorted by group
against ``w [G, K, N]``: rows ``sum(group_sizes[:g]) .. + group_sizes[g]``
are multiplied by ``w[g]``.  The dropless expert layer's three products
(``distributed/fleet/meta_parallel/moe.py`` ``routed_experts``).

ONE seam, :func:`grouped_matmul`, and the shapes choose what runs beneath
it (:func:`_blocking`): XLA's ``jax.lax.ragged_dot``, or this file's kernel
where the rows span several 128-row tiles and a group holds less than one.
There XLA's lowering picks its row tile from ``M`` alone (512 rows for
1,024) and every group pays a whole tile of MXU passes for its few rows
(1.27 ms a product of 1,024 x 2,048 x 1,536 over 64 groups on a v5e, 2.6x
its weights' time).  The kernel keeps a row tile as tall as its budget
allows in VMEM, all of ``M`` where it fits, walks the (row tile, group)
pairs that hold a row, and multiplies only the 128-row products a group's
rows lie in: each group's weights stream once a tile they touch, and the
product costs what streaming them costs (0.54 ms, 91% of HBM's rate).

The arithmetic is ``ragged_dot``'s on the chip: operands as stored, float32
accumulation, the result in the operands' type.  Every row of a group is
computed, no group is skipped.  A row past the last group is ZERO here and
whatever the kernel left there under ``ragged_dot``: callers mask them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..profiler import metrics as _metrics

_m_traced = _metrics.counter(
    "moe.grouped_products_traced",
    "grouped products traced through ops.grouped_matmul, by the kernel the "
    "shapes chose (kernel = tiled | ragged_dot); counted when a program is "
    "traced, not when it runs")

# the MXU's height: a weight tile is pushed once for up to this many rows,
# so a shorter product saves no pass and a taller one costs a group more
_SUB_ROWS = 128
_LANES = 128
# a row tile of x [tm, K] and of the result [tm, tn]; the pipeline holds two
_ROW_BLOCK_BYTES = 4 << 20
# one block of a group's weights [K, tn]; the pipeline holds two
_WEIGHT_BLOCK_BYTES = 8 << 20


def _sublane_rows(dtype):
    """Rows of one sublane tile: 8 of 32 bits, 16 of 16."""
    return 32 // dtype.itemsize


def _blocking(x, w):
    """``(tm, tn)`` of the kernel for ``x [M, K] @ w [G, K, N]``, or None
    where ``ragged_dot`` is the better program: from the shapes and the
    type alone, the same compiled and interpreted.

    The kernel takes products whose rows span more than one 128-row tile
    while the mean group (``M // G``) holds less than one: there a tile
    chosen from ``M`` is mostly padding.  One tile of rows (a decode step)
    and groups of whole tiles (a training step) are what ``ragged_dot`` is
    tiled for.  ``M`` has to be whole sublane tiles of the type.  The row
    tile is all of ``M`` where that fits the budget (no group then lies
    across two tiles and has its weights read twice), else the most whole
    128-row products that do; the column block is the widest divisor of
    ``N`` in whole lanes whose ``[K, tn]`` fits its budget."""
    (M, K), (G, _, N) = x.shape, w.shape
    item = x.dtype.itemsize
    if x.dtype != w.dtype or item not in (2, 4):
        return None
    if M <= _SUB_ROWS or M // G >= _SUB_ROWS:
        return None
    if M % _sublane_rows(x.dtype):
        return None
    cap = _ROW_BLOCK_BYTES // (max(K, N) * item) // _SUB_ROWS * _SUB_ROWS
    widths = [N] if N % _LANES else \
        [t for t in range(N, 0, -_LANES) if N % t == 0]
    fit = [t for t in widths if K * t * item <= _WEIGHT_BLOCK_BYTES]
    if not cap or not fit:
        return None
    return min(M, cap), fit[0]


def _visits(group_sizes, m_tiles, tm):
    """The (row tile, group) pairs that hold a row, in row order, as the
    kernel's grid walks them: ``(group, tile, lo, hi)`` each
    ``[m_tiles + G - 1]`` int32, a visit computing rows ``lo .. hi`` of its
    group where they lie in its tile.  A group spans the tiles its rows
    touch, an empty group none.  There are at most ``m_tiles + G - 1``
    pairs; the visits left over hold no row (``lo == hi``) and walk the
    tiles behind the last group, which the kernel zeroes, under the last
    group's weights, which are not fetched again.  One pass over a
    ``[visits, G]`` mask and two running sums: no gather, no sort."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = starts // tm
    span = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(span, dtype=jnp.int32)    # visits through each group
    begin = upto - span
    v = jnp.arange(m_tiles + G - 1, dtype=jnp.int32)
    mine = (v[:, None] >= begin[None]) & (v[:, None] < upto[None])

    def of_group(a):                    # a[g] of each visit's group, else 0
        return jnp.sum(jnp.where(mine, a[None], 0), axis=1, dtype=jnp.int32)

    live = v < upto[-1]
    groups = jnp.arange(G, dtype=jnp.int32)
    behind = -(-ends[-1] // tm) + v - upto[-1]
    return (jnp.where(live, of_group(groups),
                      jnp.max(jnp.where(sizes > 0, groups, 0))),
            jnp.minimum(jnp.where(live, of_group(first - begin) + v,
                                  behind), m_tiles - 1),
            of_group(starts), of_group(ends))


def _grouped_kernel(g_ref, tile_ref, lo_ref, hi_ref, x_ref, w_ref, o_ref):
    """One visit: the rows ``lo .. hi`` that lie in this row tile times this
    column block of their group's weights, 128 rows a product from the
    sublane tile their first row lies in.  A tile's visits are consecutive
    grid steps, so its block stays in VMEM between them; the first zeroes
    it."""
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    tm, sub, pack = o_ref.shape[0], _SUB_ROWS, _sublane_rows(x_ref.dtype)
    tile = tile_ref[v]
    base = tile * tm
    lo, hi = lo_ref[v], jnp.minimum(hi_ref[v], base + tm)

    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile))
    def _fresh():
        o_ref[...] = jnp.zeros_like(o_ref)

    start = (jnp.maximum(lo - base, 0) // pack) * pack

    def product(i, carry):
        # the last product of a tile is moved up to end with the tile
        at = pl.multiple_of(jnp.minimum(start + i * sub, tm - sub), pack)
        rows = base + at + jax.lax.broadcasted_iota(
            jnp.int32, (sub, o_ref.shape[1]), 0)
        acc = jnp.dot(x_ref[pl.ds(at, sub), :], w_ref[...],
                      preferred_element_type=jnp.float32)
        o_ref[pl.ds(at, sub), :] = jnp.where(
            (rows >= lo) & (rows < hi), acc,
            o_ref[pl.ds(at, sub), :].astype(jnp.float32)).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, (hi - base - start + sub - 1) // sub, product, 0)


def _grouped_pallas(x, w, group_sizes, interpret):
    """The kernel over ``x [M, K]``, ``w [G, K, N]``: grid (column blocks,
    visits); the visits' tiles and groups ride as scalar prefetch, so each
    step's row tile and weight block are fetched from where they lie."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (M, K), (G, _, N) = x.shape, w.shape
    tm, tn = _blocking(x, w)
    m_tiles = -(-M // tm)
    visits = _visits(group_sizes, m_tiles, tm)
    item = x.dtype.itemsize
    # two of each block, the float32 product, and room for Mosaic's own
    vmem = 2 * (tm * K + K * tn + tm * tn) * item + 2 * _SUB_ROWS * tn * 4
    # x64 OFF around the call: see ops/paged_attention.py
    with jax.enable_x64(False):
        return pl.pallas_call(
            _grouped_kernel,
            name="grouped_matmul",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(N // tn, m_tiles + G - 1),
                in_specs=[
                    pl.BlockSpec((tm, K),
                                 lambda n, v, g, t, lo, hi: (t[v], 0)),
                    pl.BlockSpec((None, K, tn),
                                 lambda n, v, g, t, lo, hi: (g[v], 0, n)),
                ],
                out_specs=pl.BlockSpec(
                    (tm, tn), lambda n, v, g, t, lo, hi: (t[v], n))),
            out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
            interpret=interpret,
            # a tile's visits follow one another and share its block
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=max(vmem + (4 << 20), 16 << 20)),
            cost_estimate=pl.CostEstimate(
                flops=2 * (M + G * _SUB_ROWS) * K * N, transcendentals=0,
                bytes_accessed=(G * K * N + (N // tn) * M * K + M * N)
                * item),
        )(*visits, x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tiled(x, w, group_sizes, interpret):
    return _grouped_pallas(x, w, group_sizes, interpret)


def _tiled_fwd(x, w, group_sizes, interpret):
    return _tiled(x, w, group_sizes, interpret), (x, w, group_sizes)


def _tiled_bwd(interpret, saved, g):
    # the kernel has no transposes of its own yet: ragged_dot's serve
    x, w, group_sizes = saved
    _, pull = jax.vjp(lambda x_, w_: jax.lax.ragged_dot(x_, w_, group_sizes),
                      x, w)
    return (*pull(g), None)


_tiled.defvjp(_tiled_fwd, _tiled_bwd)


def grouped_matmul(x, w, group_sizes, *, interpret=None):
    """``x [M, K] @ w [G, K, N]`` by groups of rows -> ``[M, N]`` in
    ``x``'s type, float32 accumulation (see the module docstring).

    The shapes choose the kernel (:func:`_blocking`); off the TPU it is
    ``ragged_dot`` unless ``interpret=True`` asks for the kernel
    interpreted.  Differentiable either way."""
    tiled = _blocking(x, w) is not None and (
        interpret is not None or jax.default_backend() == "tpu")
    _m_traced.inc(kernel="tiled" if tiled else "ragged_dot")
    if not tiled:
        return jax.lax.ragged_dot(x, w, group_sizes)
    return _tiled(x, w, group_sizes, bool(interpret))
