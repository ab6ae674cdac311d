"""``ops/grouped_matmul.py``: the kernel (interpreted here) against a plain
per-group loop in float32, the table of (row tile, group) visits its grid
walks, the rule by which the shapes choose between it and
``jax.lax.ragged_dot``, gradients through the seam, and the counter that says
which was traced."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.profiler import metrics as prof_metrics

gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")


def _traced():
    """``moe.grouped_products_traced`` by kernel: (tiled, ragged_dot)."""
    counter = prof_metrics.counter("moe.grouped_products_traced")
    return tuple(counter.get(kernel=k) or 0 for k in ("tiled", "ragged_dot"))

# the chunk program's ratios (1,024 assignments over 64 experts) at a
# sixteenth of the cell's widths
M, G, K, N = 1024, 64, 128, 256


def _loop(x, w, sizes):
    """Rows of group g times ``w[g]`` in float32; a row in no group is 0."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out, at = np.zeros((x.shape[0], w.shape[2]), np.float32), 0
    for g, n in enumerate(sizes):
        out[at:at + n] = x[at:at + n] @ w[g]
        at += n
    return out


def _sizes(case, m, rng):
    if case == "even":
        return np.full(G, m // G)
    if case == "ragged_with_empty_groups":
        # a group longer than one product of 128 rows among them
        sizes = rng.multinomial(m - 150, rng.dirichlet(np.full(G, 0.4)))
        sizes[sizes.argmax()] += 150
        assert (sizes == 0).any() and sizes.sum() == m
        return sizes
    if case == "one_group_holds_every_row":
        sizes = np.zeros(G, int)
        sizes[37] = m
        return sizes
    if case == "rows_past_the_last_group":
        return rng.multinomial(m - 300, np.full(G, 1 / G))
    if case == "a_group_across_a_tile_boundary":
        # group 3 holds rows 120 .. 400: it lies in the row tiles 0, 1 (and
        # 2, 3 where they are 128 rows), group 4 starts inside a tile
        sizes = np.zeros(G, int)
        sizes[:5] = 40, 40, 40, 280, 24
        sizes[5:] = rng.multinomial(m - 424, np.full(G - 5, 1 / (G - 5)))
        return sizes
    assert case == "no_rows_at_all"
    return np.zeros(G, int)


def _operands(m, dtype, rng):
    return (jnp.asarray(rng.standard_normal((m, K)), dtype),
            jnp.asarray(rng.standard_normal((G, K, N)) * 0.1, dtype))


@pytest.mark.parametrize("row_tile_rows", [1024, 256, 128])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case,m", [
    ("even", 1024), ("ragged_with_empty_groups", 1024),
    ("one_group_holds_every_row", 1024), ("rows_past_the_last_group", 1024),
    ("a_group_across_a_tile_boundary", 1024), ("no_rows_at_all", 1024),
    # a monolithic prefill of 13 pages: 832 rows, the last tile partial
    ("ragged_with_empty_groups", 832), ("rows_past_the_last_group", 832),
])
def test_kernel_is_the_float32_loop(monkeypatch, case, m, dtype,
                                    row_tile_rows):
    """Every row of every group is computed in the operands' type with
    float32 accumulation, a row in no group is zero, whatever the row tile:
    all of M, or tiles that groups lie across."""
    monkeypatch.setattr(gm, "_ROW_BLOCK_BYTES",
                        row_tile_rows * N * jnp.dtype(dtype).itemsize)
    rng = np.random.default_rng(len(case) + m)
    x, w = _operands(m, dtype, rng)
    sizes = _sizes(case, m, rng)
    assert gm._blocking(x, w)[0] == min(m, row_tile_rows)
    y = gm.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32),
                          interpret=True)
    assert y.dtype == dtype and y.shape == (m, N)
    want = _loop(x, w, sizes)
    # one rounding of the float32 sum to the result's type
    tol = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(y, np.float32), want, rtol=tol,
                               atol=tol * np.abs(want).max())
    assert not np.asarray(y, np.float32)[sizes.sum():].any()


def test_kernel_is_no_further_from_the_loop_than_ragged_dot():
    """Same operands, same accumulation: against the float32 loop the
    kernel's largest gap is ``ragged_dot``'s (on the chip too: both read
    0.0142 at the cell's widths, PR 31)."""
    rng = np.random.default_rng(5)
    x, w = _operands(M, jnp.bfloat16, rng)
    sizes = _sizes("ragged_with_empty_groups", M, rng)
    counts = jnp.asarray(sizes, jnp.int32)
    want = _loop(x, w, sizes)
    gap_kernel = np.abs(np.asarray(gm.grouped_matmul(
        x, w, counts, interpret=True), np.float32) - want).max()
    gap_ragged = np.abs(np.asarray(jax.lax.ragged_dot(
        x, w, counts), np.float32) - want).max()
    assert 0 < gap_ragged < 0.05 * np.abs(want).max()
    assert gap_kernel <= gap_ragged


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("tm,m", [(128, 1024), (256, 832), (1024, 1024)])
def test_visits_hold_every_pair_of_tile_and_group_once(seed, tm, m):
    """The grid's table: each (row tile, group) pair that holds a row
    exactly once, in row order, a tile's visits next to one another; what
    is left over holds no row and ends on the last tile, so every tile is
    met (and zeroed) at least once."""
    rng = np.random.default_rng(seed)
    sizes = rng.multinomial(m - 100 * (seed % 3),
                            rng.dirichlet(np.full(G, 0.3 + seed)))
    m_tiles = -(-m // tm)
    g, tile, lo, hi = (np.asarray(a) for a in gm._visits(
        jnp.asarray(sizes, jnp.int32), m_tiles, tm))
    assert len(g) == m_tiles + G - 1
    ends = np.cumsum(sizes)
    want = [(t, e) for e in range(G) if sizes[e]
            for t in range((ends[e] - sizes[e]) // tm,
                           (ends[e] - 1) // tm + 1)]
    live = hi > lo
    assert sorted(want) == want == list(zip(tile[live], g[live]))
    assert (lo[live] == (ends - sizes)[g[live]]).all()
    assert (hi[live] == ends[g[live]]).all()
    assert not live[len(want):].any()
    assert (np.diff(tile) >= 0).all() and (np.diff(tile) <= 1).all()
    assert set(tile) == set(range(m_tiles))
    # a visit without rows fetches no other group's weights
    assert (g[len(want):] == g[len(want) - 1]).all()


@pytest.mark.parametrize("name,m,groups,dtype,blocking", [
    # the hybrid's decode step: one tile of rows
    ("decode_128x64", 128, 64, jnp.bfloat16, None),
    # its chunk of 256 tokens, top-4: all of its rows in one tile, all of
    # a group's columns in one block
    ("chunk_1024x64", 1024, 64, jnp.bfloat16, (1024, 1536)),
    # its monolithic prefill of 13 pages
    ("prefill_832x64", 832, 64, jnp.bfloat16, (832, 1536)),
    # one chip's share of the sparse training step: 3,072 rows a group
    ("training_49152x16", 49152, 16, jnp.bfloat16, None),
    ("float32_chunk", 1024, 64, jnp.float32, (512, 768)),
    ("groups_of_a_whole_tile", 8192, 64, jnp.bfloat16, None),
    ("rows_not_whole_sublane_tiles", 1000, 64, jnp.bfloat16, None),
    ("int8", 1024, 64, jnp.int8, None),
])
def test_the_shapes_choose_the_kernel(name, m, groups, dtype, blocking):
    """By the rows, the groups and the type alone: no flag, no name."""
    x = jax.ShapeDtypeStruct((m, 2048), dtype)
    w = jax.ShapeDtypeStruct((groups, 2048, 1536), dtype)
    assert gm._blocking(x, w) == blocking


def test_a_row_tile_past_its_budget_is_whole_products():
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((64, 2048, 1536), jnp.bfloat16)
    assert gm._blocking(x, w) == (1024, 1536)
    # weights wider than a block's budget are swept by column blocks
    w = jax.ShapeDtypeStruct((64, 2048, 4096), jnp.bfloat16)
    assert gm._blocking(x, w) == (512, 2048)
    assert gm._blocking(x, jax.ShapeDtypeStruct((64, 2048, 1536),
                                                jnp.float32)) is None


def test_the_seam_is_ragged_dot_off_the_chip():
    """``interpret=None`` on a backend that is no TPU: ``ragged_dot``, in
    the lowered text and in the count."""
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((G, K, N), jnp.bfloat16)
    s = jax.ShapeDtypeStruct((G,), jnp.int32)
    before = _traced()
    text = jax.jit(lambda *a: gm.grouped_matmul(*a)).lower(
        x, w, s).as_text(debug_info=True)
    assert "ragged_dot" in text and "tpu_custom_call" not in text
    assert _traced() == (before[0], before[1] + 1)


@pytest.mark.parametrize("m,groups", [(128, 64), (49152, 16)],
                         ids=["decode_step", "training_step"])
def test_programs_that_keep_ragged_dot_keep_their_text(monkeypatch, m,
                                                       groups):
    """Where the shapes keep ``ragged_dot`` the seam adds nothing to the
    program, on the TPU either: the decode step's and the training step's
    jaxprs (forward and gradients) are the ones they were."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shapes = (jax.ShapeDtypeStruct((m, 256), jnp.bfloat16),
              jax.ShapeDtypeStruct((groups, 256, 384), jnp.bfloat16),
              jax.ShapeDtypeStruct((groups,), jnp.int32))

    def grads(product):
        return jax.grad(lambda x, w, s: jnp.sum(
            product(x, w, s).astype(jnp.float32)), argnums=(0, 1))

    for through, plain in ((gm.grouped_matmul, jax.lax.ragged_dot),
                           (grads(gm.grouped_matmul),
                            grads(jax.lax.ragged_dot))):
        assert str(jax.make_jaxpr(through)(*shapes)) \
            == str(jax.make_jaxpr(plain)(*shapes))


def test_products_traced_are_counted_by_kernel():
    """One increment a product TRACED: a second call of a jitted program
    counts nothing."""
    rng = np.random.default_rng(0)
    x, w = _operands(M, jnp.bfloat16, rng)
    counts = jnp.full((G,), M // G, jnp.int32)

    @jax.jit
    def chunk_like(x, w, counts):      # 1,024 rows: the kernel, twice
        h = gm.grouped_matmul(x, w, counts, interpret=True)
        return gm.grouped_matmul(h[:, :K], w, counts, interpret=True)

    @jax.jit
    def step_like(x, w, counts):       # 128 rows: ragged_dot
        return gm.grouped_matmul(x[:128], w, counts // 8, interpret=True)

    before = _traced()
    for _ in range(2):
        chunk_like(x, w, counts).block_until_ready()
        step_like(x, w, counts).block_until_ready()
    assert _traced() == (before[0] + 2, before[1] + 1)


def test_gradients_through_the_seam_are_ragged_dots():
    """The kernel has no backward of its own: under differentiation at a
    shape that takes it, the gradients are ``ragged_dot``'s."""
    rng = np.random.default_rng(1)
    x, w = _operands(M, jnp.float32, rng)
    sizes = _sizes("rows_past_the_last_group", M, rng)
    counts = jnp.asarray(sizes, jnp.int32)
    cot = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)

    def loss(product):
        return lambda x, w: jnp.sum(product(x, w) * cot)

    through = jax.jit(jax.value_and_grad(loss(
        lambda x, w: gm.grouped_matmul(x, w, counts, interpret=True)),
        argnums=(0, 1)))
    plain = jax.jit(jax.value_and_grad(loss(
        lambda x, w: jax.lax.ragged_dot(x, w, counts)), argnums=(0, 1)))
    (value, (dx, dw)), (value_p, (dx_p, dw_p)) = through(x, w), plain(x, w)
    np.testing.assert_allclose(value, value_p, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_p))
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dw_p))


def test_routed_experts_calls_the_seam_under_its_scope(monkeypatch):
    """``routed_experts`` names no kernel: three calls of the seam, all
    under ``moe_experts``."""
    moe = importlib.import_module(
        "paddle_tpu.distributed.fleet.meta_parallel.moe")
    seen = []

    def seam(x, w, sizes, **kw):
        seen.append((x.shape, w.shape, kw))
        return jax.lax.ragged_dot(x, w, sizes)

    monkeypatch.setattr(moe, "grouped_matmul", seam)
    rng = np.random.default_rng(2)
    T, H, F, E, k = 64, 32, 48, 8, 4
    args = (jnp.asarray(rng.standard_normal((T, H)), jnp.float32),
            jnp.asarray(rng.standard_normal((H, E)), jnp.float32),
            jnp.zeros((E,), jnp.float32),
            jnp.asarray(rng.standard_normal((E, H, F)), jnp.float32),
            jnp.asarray(rng.standard_normal((E, H, F)), jnp.float32),
            jnp.asarray(rng.standard_normal((E, F, H)), jnp.float32))
    text = jax.jit(lambda *a: moe.routed_experts(*a, top_k=k)).lower(
        *args).as_text(debug_info=True)
    assert seen == [((T * k, H), (E, H, F), {}), ((T * k, H), (E, H, F), {}),
                    ((T * k, F), (E, F, H), {})]
    lines = [line for line in text.splitlines() if "ragged_dot" in line
             and "loc(" in line]
    assert len(lines) >= 3 and all("moe_experts" in line for line in lines)
