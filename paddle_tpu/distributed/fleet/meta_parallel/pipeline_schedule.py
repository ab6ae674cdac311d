"""SPMD pipeline schedules: stages on a mesh axis, activations rotated with
``lax.ppermute``, tick loop compiled as ``lax.scan`` (O(1) trace/compile in
micro-batch count).

Reference analog: fleet/meta_parallel/pipeline_parallel.py (1F1B Python
schedule driving send_v2/recv_v2 p2p ops per rank) + fleet_executor's
micro-batch task graph (SURVEY.md §2.1).

TPU-native design (SURVEY.md §7 hard-part (a)): all S stages live in ONE
compiled program.  Each pp rank holds its stage's parameters (stacked
pytree, leading dim S laid out P('pp')); activations rotate one hop per
tick over the ICI ring via ``ppermute``.

Three schedules:

- ``gpipe`` (default): M+S-1 ticks scanned; backward DERIVED BY AD (the
  transpose of ppermute is the reverse rotation, so grad-of-scan is
  automatically the mirrored drain-fill pipeline).  Residuals: one stage
  input per tick (with remat), i.e. O(M+S) micro-activations per rank.

- ``interleaved`` (circular/virtual stages): ``layers_per_stage = v`` layer
  chunks per rank, each micro-batch laps the ring v times, chunk-of-S
  injection.  The per-tick compute is ONE virtual stage, so the fill/drain
  bubble costs ~2(S-1) single-chunk ticks instead of GPipe's (S-1) ticks of
  v-chunk compute — the reference's interleaved-1F1B bubble win
  (fleet "virtual pipeline parallel").  Backward by AD of the scan.

- ``spmd_pipeline_1f1b``: explicit forward/backward interleaving with a
  custom VJP whose backward re-runs the forward pipeline tick-aligned with
  the cotangent pipeline (1F1B steady state).  Live state is O(S)
  micro-activations per rank — this is the memory schedule the reference
  hand-codes as 1F1B — at the cost of one extra forward (full remat).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _shard_map(f, mesh, in_specs, out_specs):
    """Fully manual over the mesh: hybrid parallelism inside the body is
    explicit — pp via ppermute here, mp via the TP layers' own psum
    (mp_layers manual mode), dp via the batch specs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def pipeline_tick_stats(n_micro, n_stages, layers_per_stage=1, schedule="gpipe"):
    """Tick counts + bubble fraction, in units of ONE layer-chunk of compute.

    gpipe merges the v chunks of a rank into one stage call, so each of its
    M+S-1 ticks costs v chunk-units; interleaved ticks cost 1 chunk-unit.
    Useful compute is v*M chunk-units per rank either way.
    """
    M, S, v = n_micro, n_stages, layers_per_stage
    if schedule == "interleaved" and v > 1:
        n_chunks = math.ceil(M / S)
        ticks = ((n_chunks - 1) * v * S) + v * S + (S - 1)
        total = ticks  # 1 chunk-unit per tick
    else:
        ticks = M + S - 1
        total = ticks * v
    useful = v * M
    return {"ticks": ticks, "compute_units": total, "useful_units": useful,
            "bubble_fraction": 1.0 - useful / total}


def spmd_pipeline(block_fn, stacked_params, x_micro, mesh, axis="pp",
                  batch_axis=None, remat=True, param_specs=None,
                  schedule="gpipe"):
    """Run ``x_micro`` through S pipeline stages living on mesh axis ``axis``.

    Args:
        block_fn: ``(params_slice, x) -> x`` — one stage's compute.
            ``params_slice`` is the stage's slice of ``stacked_params`` with
            the stage dim REMOVED.  For ``schedule='gpipe'`` a rank's whole
            chunk stack is passed (leading dim L_per_stage kept if the caller
            stacked several layers per stage); for ``schedule='interleaved'``
            one VIRTUAL stage slice [1, ...] is passed per call.
        stacked_params: pytree of arrays with leading dim S (= mesh.shape[axis]);
            an optional second leading dim v = layers-per-stage.
        x_micro: [M, micro_batch, ...] micro-batches.
        mesh: the device mesh (may carry more axes, e.g. dp; they stay
            compiler-partitioned via the batch dims).
        batch_axis: optional mesh axis name to shard the micro-batch dim over
            (data parallel inside each stage).
        remat: checkpoint each stage call (flat activation memory).
        schedule: 'gpipe' | 'interleaved' (circular over the v dim).

    Returns:
        [M, micro_batch, ...] outputs of the final (virtual) stage,
        replicated over ``axis``.
    """
    if schedule not in ("gpipe", "interleaved", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         "(expected 'gpipe', 'interleaved' or '1f1b')")
    if schedule == "1f1b":
        return spmd_pipeline_1f1b(block_fn, stacked_params, x_micro, mesh,
                                  axis=axis, batch_axis=batch_axis,
                                  param_specs=param_specs)
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    if M < S:
        raise ValueError(f"need micro-batches >= stages ({M} < {S})")
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if leaves and leaves[0].shape[0] != S:
        raise ValueError(
            f"stacked_params leading dim {leaves[0].shape[0]} != pipeline degree {S}; "
            "stack layers-per-stage into a second leading dim and loop in block_fn")
    fn = jax.checkpoint(block_fn) if remat else block_fn

    bspec = (None, batch_axis) if batch_axis else (None,)
    in_param_specs = param_specs if param_specs is not None else \
        jax.tree_util.tree_map(lambda _: P(axis), stacked_params)

    if schedule == "interleaved":
        body = _interleaved_body(fn, stacked_params, S, M, axis)
    else:
        body = _gpipe_body(fn, S, M, axis)

    mapped = _shard_map(
        body, mesh,
        in_specs=(in_param_specs, P(*bspec)),
        out_specs=P(*bspec),
    )
    if schedule == "interleaved":
        v = jax.tree_util.tree_leaves(stacked_params)[0].shape[1]
        ticks = v * M + S - 1  # the interleaved body's scan length T
    else:
        ticks = M + S - 1
    _record_pp_bytes(x_micro, S, ticks)
    return mapped(stacked_params, x_micro)


def _record_pp_bytes(x_micro, S, ticks):
    """Observability: one ring hop of a micro-batch per scan tick
    (trace-time accounting — forward-pass bytes the program will move per
    execution; the backward's reverse rotation is not counted).  Routes
    through communication.record_collective_traffic — one schema."""
    try:
        from ...communication import _nbytes, record_collective_traffic

        mb_bytes = _nbytes(
            jax.ShapeDtypeStruct(x_micro.shape[1:], x_micro.dtype))
        record_collective_traffic("pp_ppermute", S, mb_bytes * ticks,
                                  phase="traced")
    except Exception:
        pass


def _gpipe_body(fn, S, M, axis):
    def body(params_local, xs):
        # params_local leaves: [1, ...] (stage dim); xs: [M, micro_local, ...]
        params_here = jax.tree_util.tree_map(lambda v: v[0], params_local)
        idx = lax.axis_index(axis)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            outs, c = carry
            mb = jnp.minimum(t, M - 1)
            inp = jnp.where(idx == 0,
                            lax.dynamic_index_in_dim(xs, mb, 0, keepdims=False),
                            c)
            out = fn(params_here, inp)
            # micro-batch t-(S-1) finishes at tick t on the last stage; the
            # modular slot is only FINALLY written at its real tick (earlier
            # writes to the same slot are overwritten), so no masking needed
            slot = jnp.remainder(t - (S - 1), M)
            outs = lax.dynamic_update_index_in_dim(outs, out, slot, 0)
            c2 = lax.ppermute(out, axis, fwd_perm)
            return (outs, c2), None

        outputs = jnp.zeros((M,) + xs.shape[1:], xs.dtype)
        carry0 = jnp.zeros_like(xs[0])
        (outputs, _), _ = lax.scan(
            tick, (outputs, carry0), jnp.arange(M + S - 1, dtype=jnp.int32))
        # outputs are valid on the last stage only; all_gather + slice
        # replicates them (one ring pass — half the bytes of the mask+psum
        # fan-out, which moves the buffer twice around the ring)
        return lax.all_gather(outputs, axis, axis=0)[S - 1]

    return body


def _interleaved_body(fn, stacked_params, S, M, axis):
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if not leaves or leaves[0].ndim < 2:
        raise ValueError("interleaved schedule needs stacked_params leaves of "
                         "shape [S, layers_per_stage, ...]")
    v = leaves[0].shape[1]
    if M % S:
        raise ValueError(f"interleaved schedule needs micro-batches divisible "
                         f"by stages ({M} % {S})")
    n_chunks = M // S

    def body(params_local, xs):
        # params_local leaves: [1, v, ...]; xs: [M, micro_local, ...]
        params_here = jax.tree_util.tree_map(lambda p: p[0], params_local)
        idx = lax.axis_index(axis)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        T = (n_chunks - 1) * v * S + v * S + (S - 1)

        def tick(carry, t):
            outs, c = carry
            # stream position of the micro-batch arriving at this rank: it
            # entered the ring at e = t - idx (mod the chunk cadence)
            e = t - idx
            live = e >= 0
            e = jnp.maximum(e, 0)
            chunk = e // (v * S)          # which injection chunk
            lap = (e // S) % v            # which circular lap (virtual stage)
            pos = e % S                   # index inside the chunk
            mb = jnp.minimum(chunk * S + pos, M - 1)
            inject = jnp.logical_and(idx == 0, lap == 0)
            inp = jnp.where(inject,
                            lax.dynamic_index_in_dim(xs, mb, 0, keepdims=False),
                            c)
            p_lap = jax.tree_util.tree_map(
                lambda p: lax.dynamic_index_in_dim(p, lap, 0, keepdims=False),
                params_here)
            out = fn(p_lap, inp)
            out = jnp.where(live, out, c * 0)
            # micro-batch mb completes its last virtual stage on rank S-1 at
            # lap v-1; modular slot, final write wins
            slot = jnp.remainder(mb, M)
            is_done = jnp.logical_and(idx == S - 1, lap == v - 1)
            cur = lax.dynamic_index_in_dim(outs, slot, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(is_done & live, out, cur), slot, 0)
            c2 = lax.ppermute(out, axis, fwd_perm)
            return (outs, c2), None

        outputs = jnp.zeros((M,) + xs.shape[1:], xs.dtype)
        carry0 = jnp.zeros_like(xs[0])
        (outputs, _), _ = lax.scan(
            tick, (outputs, carry0), jnp.arange(T, dtype=jnp.int32))
        return lax.all_gather(outputs, axis, axis=0)[S - 1]

    return body


def spmd_pipeline_1f1b(block_fn, stacked_params, x_micro, mesh, axis="pp",
                       batch_axis=None, param_specs=None):
    """GPipe-order forward with an O(S)-memory 1F1B backward.

    Forward: identical schedule to ``spmd_pipeline(..., 'gpipe')`` but wrapped
    in a custom VJP that saves ONLY (params, inputs) — no per-tick residuals.
    Backward: a single scan that runs the RECOMPUTE-forward pipeline and the
    cotangent (backward) pipeline simultaneously, tick-aligned the way the
    reference's 1F1B steady state interleaves one forward and one backward
    per rank per step; stage inputs are retained in a circular buffer of
    depth 2S (the 1F1B in-flight bound) instead of the M+S-1 scan residuals
    AD would keep.
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    if M < S:
        raise ValueError(f"need micro-batches >= stages ({M} < {S})")
    bspec = (None, batch_axis) if batch_axis else (None,)
    in_param_specs = param_specs if param_specs is not None else \
        jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    _record_pp_bytes(x_micro, S, M + S - 1)

    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    rev_perm = [((i + 1) % S, i) for i in range(S)]
    DEPTH = 2 * S  # 1F1B in-flight bound per rank

    def _fwd_tick_inp(xs, idx, c, t):
        mb = jnp.minimum(t, M - 1)
        return jnp.where(idx == 0,
                         lax.dynamic_index_in_dim(xs, mb, 0, keepdims=False), c)

    # forward schedule is EXACTLY the gpipe body (single source of truth);
    # only the backward is custom
    _pipe = _gpipe_body(block_fn, S, M, axis)

    def _pipe_bwd(params_local, xs, gout):
        """Recompute-forward + cotangent pipeline in ONE scan, O(S) buffers.

        Timing: recompute tick for micro-batch m happens at t_f = m + idx (its
        input materializes then); its backward on this rank runs at
        t_b = m + 2(S-1) - idx + (S-1)... expressed relative: the cotangent
        for m enters the LAST stage at tick m + (S-1) (when m's forward
        output is complete) and ppermutes BACKWARD one rank per tick, so
        this rank consumes m's cotangent at t_b = m + (S-1) + (S-1-idx).
        The stage input saved at t_f is needed at t_b; t_b - t_f =
        2(S-1-idx) <= 2S - 2 < DEPTH, so a circular buffer of DEPTH slots
        suffices — the 1F1B window.
        """
        params_here = jax.tree_util.tree_map(lambda p: p[0], params_local)
        idx = lax.axis_index(axis)
        T = M + S - 1 + (S - 1)  # recompute fill + cotangent drain

        gacc0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape[1:], jnp.promote_types(p.dtype, jnp.float32)
                                if jnp.issubdtype(p.dtype, jnp.floating) else p.dtype),
            params_local)
        buf0 = jnp.zeros((DEPTH,) + xs.shape[1:], xs.dtype)
        gx0 = jnp.zeros((M,) + xs.shape[1:],
                        jnp.promote_types(xs.dtype, jnp.float32))

        def tick(carry, t):
            fcarry, bcarry, buf, gacc, gxs = carry
            # ---- recompute-forward half-tick (same schedule as _pipe)
            inp = _fwd_tick_inp(xs, idx, fcarry, t)
            buf = lax.dynamic_update_index_in_dim(
                buf, inp, jnp.remainder(t, DEPTH), 0)
            out = jax.checkpoint(block_fn)(params_here, inp)
            fnext = lax.ppermute(out, axis, fwd_perm)
            # ---- backward half-tick: cotangent for micro-batch m_b arrives
            # here at t; on the last stage it is injected straight from gout
            m_b = t - (S - 1) - (S - 1 - idx)
            live = jnp.logical_and(m_b >= 0, m_b <= M - 1)
            m_b_c = jnp.clip(m_b, 0, M - 1)
            g_in = jnp.where(idx == S - 1,
                             lax.dynamic_index_in_dim(gout, m_b_c, 0,
                                                      keepdims=False).astype(bcarry.dtype),
                             bcarry)
            # the stage input for m_b was saved at recompute tick m_b + idx
            saved = lax.dynamic_index_in_dim(
                buf, jnp.remainder(m_b_c + idx, DEPTH), 0, keepdims=False)
            _, vjp_fn = jax.vjp(lambda p, a: block_fn(p, a), params_here, saved)
            gp, gx = vjp_fn(g_in.astype(saved.dtype))
            gacc = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(live, g, 0).astype(acc.dtype),
                gacc, gp)
            # rank 0's gx is dL/dx for micro-batch m_b
            slot = jnp.remainder(m_b_c, M)
            cur = lax.dynamic_index_in_dim(gxs, slot, 0, keepdims=False)
            gxs = lax.dynamic_update_index_in_dim(
                gxs, jnp.where(jnp.logical_and(live, idx == 0),
                               gx.astype(gxs.dtype), cur), slot, 0)
            bnext = lax.ppermute(jnp.where(live, gx, 0 * gx).astype(bcarry.dtype),
                                 axis, rev_perm)
            return (fnext, bnext, buf, gacc, gxs), None

        bcarry0 = jnp.zeros(xs.shape[1:], jnp.promote_types(xs.dtype, jnp.float32))
        init = (jnp.zeros_like(xs[0]), bcarry0, buf0, gacc0, gx0)
        (_, _, _, gacc, gxs), _ = lax.scan(
            tick, init, jnp.arange(T, dtype=jnp.int32))
        # param grads live per rank (stage dim 1); x grads live on rank 0
        gparams = jax.tree_util.tree_map(
            lambda g, p: g[None].astype(p.dtype), gacc, params_local)
        gxs = lax.psum(jnp.where(idx == 0, gxs, jnp.zeros_like(gxs)), axis)
        return gparams, gxs.astype(xs.dtype)

    @jax.custom_vjp
    def pipe(stacked, xm):
        mapped = _shard_map(_pipe, mesh,
                            in_specs=(in_param_specs, P(*bspec)),
                            out_specs=P(*bspec))
        return mapped(stacked, xm)

    def pipe_fwd(stacked, xm):
        return pipe(stacked, xm), (stacked, xm)

    def pipe_bwd(res, gout):
        stacked, xm = res
        mapped = _shard_map(
            _pipe_bwd, mesh,
            in_specs=(in_param_specs, P(*bspec), P(*bspec)),
            out_specs=(in_param_specs, P(*bspec)))
        return mapped(stacked, xm, gout)

    pipe.defvjp(pipe_fwd, pipe_bwd)
    return pipe(stacked_params, x_micro)
