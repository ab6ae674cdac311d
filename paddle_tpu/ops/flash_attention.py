"""Pallas TPU flash attention (reference analog: the CUDA
flash_attn/fused attention kernels under phi/kernels/fusion/ and
incubate.nn.functional.fused_multi_head_attention's attention core).

TPU-native design: one `pallas_call` whose grid walks (batch*heads,
q-blocks, k-blocks) with the online-softmax state (running max, running
denominator, output accumulator) held in VMEM scratch across the k-block
sweep — q/k/v tiles stream HBM→VMEM per block, the two matmuls hit the MXU
at (BLOCK_Q=128, BLOCK_K=128) tiles, and the S x S score matrix never
materializes (memory O(S) instead of O(S^2)).

Backward: two Pallas kernels (dk/dv: grid sweeps q-blocks per k-block;
dq: grid sweeps k-blocks per q-block) that recompute the probabilities from
the forward's saved logsumexp — exact gradients, O(block) memory, both
matmuls per block on the MXU.  Off-TPU (or for shapes the kernels don't
cover) a chunked-XLA backward provides the same math.

``flash_attention_with_lse`` additionally returns the per-row logsumexp and
is differentiable IN BOTH outputs (d/dlse folds into the ds term as
``ds = p * (dp - delta + g_lse) * scale``), which is what ring attention
needs to merge per-ring-step blocks exactly.

Heads of two widths: q and k share one head size ``d``, v and the output
have their own ``dv`` (latent attention: 192 and 128).  Each is padded to
its own lane multiple, v never to q's size; at ``dv == d`` every call is
the one it was.

Both forward rules NAME the two residuals only the kernel can make, the
output and the log-sum-exp (``RESIDUAL_NAMES``).  Outside a
``jax.checkpoint`` a name is the identity and lowers to nothing; inside
one, a policy that saves these names (``fleet.utils.recompute``'s default)
keeps both and the forward kernel drops out of the recomputed pass: they
are the cheapest things in a layer to hold and the dearest to make again.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..profiler import metrics as _metrics

# the names a forward rule gives (o, lse): what a checkpoint policy saves to
# keep the forward kernel out of a recomputed region
RESIDUAL_NAMES = ("flash_out", "flash_lse")

_m_forward_rules = _metrics.counter(
    "flash.forward_rules_traced",
    "forward rules of the flash custom VJPs traced (a kernel call that is "
    "differentiated, its two residuals named); counted when a program is "
    "traced, not when it runs")

# swept on v5e at S=4096: (512, 1024) beats XLA's fused attention 1.7x;
# blocks shrink adaptively for shorter sequences
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
MIN_BLOCK = 128
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
               scale, causal, block_q, block_k, nk, causal_offset=0):
    """causal_offset = sk - sq (bottom-right-aligned mask, matching
    _ref_attention's tril(k=sk-sq) for kv-cache-style sq != sk)."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, jnp.float32(NEG_INF))
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute():
        # constants pinned to f32: under jax_enable_x64 a bare Python float
        # would promote the whole block to f64, which Mosaic can't lower
        q = q_ref[0].astype(jnp.float32)          # [BQ, D]
        k = k_ref[0].astype(jnp.float32)          # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = iq * block_q + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        m_prev = m_scr[:]                          # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                     # [BQ, BK]
        alpha = jnp.exp(m_prev - m_new)            # [BQ, 1]
        l_new = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)           # [BK, DV]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    if causal:
        # skip fully-masked k-blocks (strictly above the diagonal)
        @pl.when(ik * block_k <= iq * block_q + causal_offset + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        # output stays f32; XLA fuses the downcast outside the kernel
        denom = jnp.maximum(l_scr[:], jnp.float32(1e-30))
        o_ref[0] = acc_scr[:] / denom
        lse_ref[0] = m_scr[:] + jnp.log(denom)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, causal_offset=0,
               with_lse=False):
    """q,k: [BH, S, D], v: [BH, S, DV] -> o [BH, S, DV] (and lse
    [BH, S, 1] if with_lse)."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, nk=nk,
                               causal_offset=causal_offset)
    # index-map constants must be i32 and must not be captured tracers:
    # derive the zero from a program id (i32) — under jax_enable_x64 a
    # literal 0 would trace as i64, which Mosaic rejects
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, b * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, b * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, b * 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, b * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, b * 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * sq * sk * (d + dv), transcendentals=bh * sq * sk,
            bytes_accessed=2 * (q.size + k.size + v.size) * q.dtype.itemsize),
    )(q, k, v)
    out = out.astype(q.dtype)
    return (out, lse) if with_lse else out


def _ref_attention(q, k, v, scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


# backward blocks: smaller than the forward's — the bwd kernels hold two
# extra [block, d] accumulators plus three [BQ, BK] intermediates in VMEM
BWD_BLOCK_Q = 256
BWD_BLOCK_K = 512


def _causal_mask(iq, ik, block_q, block_k, causal_offset):
    q_pos = iq * block_q + causal_offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return q_pos >= k_pos


def _fa_bwd_dkdv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, r_ref,
                        dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                        block_q, block_k, nq, causal_offset):
    """Grid (bh, k-blocks, q-blocks): accumulate dk/dv for one k-block
    across the q sweep.  r = delta - g_lse (the combined row correction)."""
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute():
        q = q_ref[0].astype(jnp.float32)           # [BQ, D]
        k = k_ref[0].astype(jnp.float32)           # [BK, D]
        v = v_ref[0].astype(jnp.float32)           # [BK, DV]
        g = g_ref[0].astype(jnp.float32)           # [BQ, DV]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        p = jnp.exp(s - lse_ref[0])                # [BQ, BK], rowwise lse
        if causal:
            mask = _causal_mask(iq, ik, block_q, block_k, causal_offset)
            p = jnp.where(mask, p, jnp.float32(0.0))
        # dv += p^T @ g   (contract over the q dim — no explicit transpose)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - r_ref[0]) * jnp.float32(scale)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ik * block_k <= iq * block_q + causal_offset + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(iq == nq - 1)
    def _fin():
        dk_ref[0] = dk_scr[:]
        dv_ref[0] = dv_scr[:]


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, r_ref,
                      dq_ref, dq_scr, *, scale, causal, block_q, block_k,
                      nk, causal_offset):
    """Grid (bh, q-blocks, k-blocks): accumulate dq for one q-block across
    the k sweep."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        p = jnp.exp(s - lse_ref[0])
        if causal:
            mask = _causal_mask(iq, ik, block_q, block_k, causal_offset)
            p = jnp.where(mask, p, jnp.float32(0.0))
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - r_ref[0]) * jnp.float32(scale)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ik * block_k <= iq * block_q + causal_offset + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ik == nk - 1)
    def _fin():
        dq_ref[0] = dq_scr[:]


def _flash_bwd_pallas(q, k, v, g, lse, r, scale, causal, causal_offset):
    """Pallas backward. q,k: [BH, S, D]; v,g: [BH, S, DV]; lse, r:
    [BH, S, 1] f32.  Returns (dq, dk, dv) in input dtypes."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    bq = min(BWD_BLOCK_Q, sq)
    bk = min(BWD_BLOCK_K, sk)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(sk, bk)

    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, b * 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, b * 0),
                          memory_space=pltpu.VMEM)
    # v and dO walk the grid as k and q do, at v's width
    g_spec = pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, j, b * 0),
                          memory_space=pltpu.VMEM)
    v_spec = pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, i, b * 0),
                          memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, j, b * 0),
                            memory_space=pltpu.VMEM)
    # Neither backward call carries a ``name`` (``flash_dkdv``, ``flash_dq``)
    # yet: a name is the innermost scope of the kernel's name stack and so
    # becomes its HLO instruction name, and the benchmark's two flash
    # rooflines tell backward from forward by the ``%transpose_jvp...`` that
    # the instruction is called without one (PERF.md section 7).
    dkdv = pl.pallas_call(
        functools.partial(_fa_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq,
                          causal_offset=causal_offset),
        grid=(bh, nk, nq),
        in_specs=[q_spec, k_spec, v_spec, g_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, b * 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, i, b * 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, dv), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=bh * sq * sk * (3 * d + 2 * dv),
            transcendentals=bh * sq * sk,
            bytes_accessed=3 * (q.size + k.size + v.size) * q.dtype.itemsize),
    )(q, k, v, g, lse, r)

    q_spec2 = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, b * 0),
                           memory_space=pltpu.VMEM)
    k_spec2 = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, b * 0),
                           memory_space=pltpu.VMEM)
    g_spec2 = pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, b * 0),
                           memory_space=pltpu.VMEM)
    v_spec2 = pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, b * 0),
                           memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, b * 0),
                             memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nk=nk,
                          causal_offset=causal_offset),
        grid=(bh, nq, nk),
        in_specs=[q_spec2, k_spec2, v_spec2, g_spec2, row_spec2, row_spec2],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, b * 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=bh * sq * sk * (2 * d + dv), transcendentals=bh * sq * sk,
            bytes_accessed=3 * (q.size + k.size + v.size) * q.dtype.itemsize),
    )(q, k, v, g, lse, r)
    return dq.astype(q.dtype), dkdv[0].astype(k.dtype), dkdv[1].astype(v.dtype)


def _chunked_attn_bwd(q, k, v, g, scale, causal, causal_offset, chunk,
                      row_corr=None):
    """Exact attention backward, q-chunked: recomputes the softmax per chunk
    so peak memory is O(S * chunk), never the full S x S matrix.
    ``row_corr`` [BH, S, 1] is subtracted inside the ds term (carries the
    -g_lse correction when differentiating the (o, lse) pair)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // chunk
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    scale32 = jnp.float32(scale)

    def body(carry, qi):
        dk_acc, dv_acc = carry
        start = qi * chunk
        qc = jax.lax.dynamic_slice_in_dim(qf, start, chunk, 1)
        do = jax.lax.dynamic_slice_in_dim(gf, start, chunk, 1)
        s = jnp.einsum("bcd,bkd->bck", qc, kf) * scale32
        if causal:
            q_pos = start + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (chunk, sk), 0)
            k_pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, sk), 1)
            s = jnp.where((q_pos >= k_pos)[None], s, jnp.float32(NEG_INF))
        p = jax.nn.softmax(s, axis=-1)
        dv_c = jnp.einsum("bck,bcd->bkd", p, do)
        dp = jnp.einsum("bcd,bkd->bck", do, vf)
        corr = jnp.sum(dp * p, axis=-1, keepdims=True)
        if row_corr is not None:
            corr = corr + jax.lax.dynamic_slice_in_dim(row_corr, start, chunk, 1)
        ds = p * (dp - corr) * scale32
        dq_c = jnp.einsum("bck,bkd->bcd", ds, kf)
        dk_c = jnp.einsum("bck,bcd->bkd", ds, qc)
        return (dk_acc + dk_c, dv_acc + dv_c), dq_c

    zeros = (jnp.zeros((bh, sk, d), jnp.float32),
             jnp.zeros((bh, sk, v.shape[2]), jnp.float32))
    (dk, dv), dq_chunks = jax.lax.scan(body, zeros, jnp.arange(nq))
    dq = jnp.moveaxis(dq_chunks, 0, 1).reshape(bh, sq, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd_dispatch(q, k, v, o, g, lse, g_lse, scale, causal, block_q,
                  causal_offset):
    """delta/r prep + Pallas-vs-chunked-XLA backward selection."""
    sq, sk = q.shape[1], k.shape[1]
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    r = delta if g_lse is None else delta - g_lse.astype(jnp.float32)
    pallas_ok = (jax.default_backend() == "tpu"
                 and sq % min(BWD_BLOCK_Q, sq) == 0
                 and sk % min(BWD_BLOCK_K, sk) == 0
                 and sq % 128 == 0 and sk % 128 == 0)
    if pallas_ok:
        return _flash_bwd_pallas(q, k, v, g, lse, r, scale, causal,
                                 causal_offset)
    chunk = block_q
    while q.shape[1] % chunk:
        chunk //= 2
    row_corr = None if g_lse is None else -g_lse.astype(jnp.float32)
    return _chunked_attn_bwd(q, k, v, g, scale, causal, causal_offset,
                             max(chunk, 1), row_corr=row_corr)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, causal_offset):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, causal_offset)


def _fwd_named(q, k, v, scale, causal, block_q, block_k, causal_offset):
    """``(o, lse)`` of the forward kernel under ``RESIDUAL_NAMES``, for both
    forward rules, which hand them on as outputs AND as residuals: what
    follows the kernel in a recomputed region then reads the saved ones.
    The log-sum-exp is named as ``[BH, S]``: the kernel's ``[BH, S, 1]``
    is held 128 lanes wide on the chip (134 MB where 1 MB is data at
    BH=64, S=4,096), and outside a checkpoint the two reshapes cancel."""
    _m_forward_rules.inc()
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        causal_offset, with_lse=True)
    rows = checkpoint_name(lse.reshape(lse.shape[:2]), RESIDUAL_NAMES[1])
    return checkpoint_name(o, RESIDUAL_NAMES[0]), rows.reshape(lse.shape)


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, causal_offset):
    o, lse = _fwd_named(q, k, v, scale, causal, block_q, block_k,
                        causal_offset)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, causal_offset, res, g):
    q, k, v, o, lse = res
    return _bwd_dispatch(q, k, v, o, g, lse, None, scale, causal, block_q,
                         causal_offset)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, scale, causal, block_q, block_k, causal_offset):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, causal_offset,
                      with_lse=True)


def _flash_lse_vjp_fwd(q, k, v, scale, causal, block_q, block_k, causal_offset):
    o, lse = _fwd_named(q, k, v, scale, causal, block_q, block_k,
                        causal_offset)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_vjp_bwd(scale, causal, block_q, block_k, causal_offset, res, g):
    q, k, v, o, lse = res
    g_o, g_lse = g
    return _bwd_dispatch(q, k, v, o, g_o, lse, g_lse, scale, causal, block_q,
                         causal_offset)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def flash_attention_with_lse(q, k, v, scale, causal, block_q=None,
                             block_k=None):
    """[BH, S, D] block attention returning (o, lse [BH, S, 1] f32),
    differentiable in both outputs — the ring-attention per-step primitive.
    Shapes must already be block-aligned (the ring guarantees this)."""
    bq = block_q or max(MIN_BLOCK, min(DEFAULT_BLOCK_Q,
                                       (q.shape[1] // MIN_BLOCK) * MIN_BLOCK))
    bk = block_k or max(MIN_BLOCK, min(DEFAULT_BLOCK_K,
                                       (k.shape[1] // MIN_BLOCK) * MIN_BLOCK))
    return _flash_lse(q, k, v, scale, causal, bq, bk, k.shape[1] - q.shape[1])


def _pad_to(x, target, axis):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def supported(q_shape, k_shape, causal=False) -> bool:
    """Route sdpa to the Pallas kernel: TPU backend, [B,S,H,D], head_dim a
    lane multiple (or <=128, padded), sequences long enough to win."""
    if jax.default_backend() != "tpu":
        return False
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    b, sq, h, d = q_shape
    sk = k_shape[1]
    if k_shape[2] != h:  # GQA/MQA (h_kv != h_q) not handled by the kernel
        return False
    if d > 256:
        return False
    if sq < 2 * MIN_BLOCK:
        return False
    return True


def flash_attention_bshd(q, k, v, causal=False, scale=None):
    """[B, S, H, D] front-end used by nn.functional.scaled_dot_product_attention."""
    return flash_attention_fn(q, k, v, scale=scale, causal=causal)


def flash_attention_fn(q, k, v, scale=None, causal=False,
                       block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Raw-array flash attention, [B, S, H, D] layout (paddle convention);
    v may have a head size of its own, which is then the output's.

    Pads S to the block size and each head size to the 128-lane tile when
    needed; falls back to the reference einsum path off-TPU, for tiny
    shapes and where keys would have to be padded without a causal mask.
    """
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[3]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # shrink blocks for short sequences (stay 128-aligned)
    block_q = max(MIN_BLOCK, min(block_q, (sq // MIN_BLOCK) * MIN_BLOCK))
    block_k = max(MIN_BLOCK, min(block_k, (sk // MIN_BLOCK) * MIN_BLOCK))
    sq_p = pl.cdiv(sq, block_q) * block_q
    sk_p = pl.cdiv(sk, block_k) * block_k

    def heads_first(x, s_p=None, width=None):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], x.shape[3])
        if s_p is None:
            return x
        return _pad_to(_pad_to(x, s_p, 1), width, 2)

    plat = jax.default_backend()  # tracing-safe (tracers carry no devices)
    # padded keys must not receive weight, and a zero key row scores 0, not
    # -inf: only the causal mask keeps them out
    if plat != "tpu" or sq < 2 * MIN_BLOCK or (sk_p > sk and not causal):
        o = _ref_attention(heads_first(q), heads_first(k), heads_first(v),
                           scale, causal)
        return jnp.moveaxis(o.reshape(b, h, sq, dv), 1, 2)

    def lanes(width):  # lane-align a head size
        return pl.cdiv(width, 128) * 128

    o = _flash(heads_first(q, sq_p, lanes(d)), heads_first(k, sk_p, lanes(d)),
               heads_first(v, sk_p, lanes(dv)), scale, causal, block_q,
               block_k, sk - sq)
    o = o[:, :sq, :dv].reshape(b, h, sq, dv)
    return jnp.moveaxis(o, 1, 2)
