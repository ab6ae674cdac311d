"""TrainStep — the fused, donated, single-XLA-program training step.

This is the performance contract of the rebuild (SURVEY.md §3.1): the
reference's dygraph step is thousands of per-op kernel launches
(forward dispatch → eager GradNode tape → per-param optimizer ops); the
TPU-native path traces forward + backward + grad-clip + optimizer update
into ONE jitted XLA module, with parameter / optimizer-state / buffer
arrays DONATED so the update is in-place in HBM (no double-buffering OOM).

Eager mode (`loss.backward(); opt.step()`) stays the correctness/debug
path; `TrainStep` (used by `hapi.Model.fit` and directly) is how you train
fast.  Typical use::

    step = paddle.jit.TrainStep(model, opt, loss_fn=nn.CrossEntropyLoss())
    for x, y in loader:
        loss = step(x, y)          # one fused XLA execution
    step.sync()                     # flush state into model/optimizer

Parameters update functionally inside the step; the wrapper rebinds each
``Parameter._value`` on exit, so from the user's side the model mutates
in place exactly like the reference.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import warnings
import weakref
from collections import OrderedDict
from time import perf_counter

import jax
import jax.numpy as jnp

from ..framework import random as _rng
from ..framework.state import no_grad_ctx
from ..observability import numerics as _numerics
from ..observability import perf as _perf
from ..observability import programs as _obs_programs
from ..observability import tracing as _tracing
from ..optimizer.lr import LRScheduler
from ..profiler import events as _prof_events
from ..profiler import metrics as _metrics
from ..tensor.tensor import Tensor

# bf16 datasheet peaks now live in observability.perf (one table feeds the
# MFU gauge here AND the per-program roofline attribution); these aliases
# keep the old spelling working.
_PEAK_BF16_FLOPS = _perf.PEAK_BF16_FLOPS
_peak_flops = _perf.peak_flops

_PERF_INSTANCE_IDS = itertools.count()


class TrainStep:
    """Compile model+loss+optimizer into one donated XLA train step.

    Args:
        model: nn.Layer. Its trainable parameters are updated.
        optimizer: paddle_tpu Optimizer (pure-rule; supplies functional_update).
        loss_fn: callable(outputs, *labels) -> scalar loss Tensor.  If None,
            the model's forward must itself return the scalar loss.
        amp_level: None/'O0', 'O1' or 'O2' — runs forward under
            amp.auto_cast(level, dtype) inside the trace.
        amp_dtype: 'bfloat16' (TPU-first default) or 'float16'.
        donate: donate params/opt-state/buffers to the compiled call
            (halves HBM held across the update; on by default).
        return_outputs: also return the model outputs from each step.
    """

    def __init__(self, model, optimizer, loss_fn=None, amp_level=None,
                 amp_dtype="bfloat16", donate=True, return_outputs=False,
                 accumulate_steps=1, scaler=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.amp_level = None if amp_level in (None, "O0") else amp_level
        self.amp_dtype = amp_dtype
        self.return_outputs = return_outputs and accumulate_steps == 1
        self.accumulate_steps = int(accumulate_steps)
        # fp16 loss scaling as TRACED ops (reference: GradScaler semantics —
        # scale loss, unscale grads, skip the update on inf/nan, dynamic
        # rescale).  The (scale, good, bad, found_inf) carry lives on device
        # and is donated; no per-step host sync.
        self._scaler = scaler if (scaler is not None
                                  and getattr(scaler, "_enable", False)) else None
        if self._scaler is not None:
            s = self._scaler
            self._scaler_state = (jnp.asarray(s._scale, jnp.float32),
                                  jnp.asarray(s._good_steps, jnp.int32),
                                  jnp.asarray(s._bad_steps, jnp.int32),
                                  jnp.zeros((), jnp.bool_))
        else:
            self._scaler_state = None

        named_p = list(model.named_parameters())
        self._pnames = [k for k, _ in named_p]
        self._ptensors = [p for _, p in named_p]
        self._diff = [not p.stop_gradient for _, p in named_p]
        named_b = list(model.named_buffers())
        self._bnames = [k for k, _ in named_b]
        self._btensors = [b for _, b in named_b]

        # live state (jax arrays), rebound into the model after every step.
        # Plain dicts throughout: jit OUTPUTS are plain dicts, and a treedef
        # change (OrderedDict in, dict back in) would retrace on step 2.
        params = dict(
            (k, p._master if p._master is not None else p._value) for k, p in named_p)
        self._master = {k: p._master is not None for k, p in named_p}
        self._buffers = dict((k, b._value) for k, b in named_b)
        # split once: the jitted step takes the diff/frozen dicts wholesale so
        # __call__ does no per-step dict rebuilding
        self._diff_params = dict(
            (k, v) for (k, v), d in zip(params.items(), self._diff) if d)
        self._frozen_params = dict(
            (k, v) for (k, v), d in zip(params.items(), self._diff) if not d)
        self._opt_state = optimizer.functional_init(self._diff_params)
        self._leaf_meta = optimizer.resolve_leaf_meta(
            OrderedDict((k, t) for (k, t), d in zip(zip(self._pnames, self._ptensors),
                                                    self._diff) if d))
        self._step_count = 0
        self._compiled = {}
        # per-instance tag for roofline attribution families: two
        # TrainSteps in one process must not fold their stats (and one
        # cost_analysis) into a shared "train_step/v0".  The finalizer
        # evicts this instance's families when it dies, so TrainStep-in-a-
        # loop processes don't grow the table without bound.
        self._perf_tag = f"train_step/t{next(_PERF_INSTANCE_IDS)}"
        self._perf_prev_family = None  # family that RAN in the last interval
        weakref.finalize(self, _perf.table().drop_prefix, self._perf_tag)
        self._donate = donate
        self._lr_float = None
        self._lr_dev = None
        self._rng_carry = None

        # observability handles (profiler.metrics): compile/retrace events,
        # per-step latency, donated HBM, achieved-FLOPs/MFU
        reg = _metrics.get_registry()
        self._m_compiles = reg.counter(
            "train_step.compiles", "TrainStep XLA program compilations")
        self._m_retraces = reg.counter(
            "train_step.retraces",
            "recompilations after the first variant (input shape/dtype churn)")
        self._m_compile_s = reg.gauge(
            "train_step.compile_seconds",
            "wall time of the last trace+compile (first dispatch of a variant)")
        self._m_step_s = reg.histogram(
            "train_step.step_seconds",
            "wall time between consecutive fused-step dispatches")
        self._m_donated = reg.gauge(
            "train_step.donated_bytes",
            "HBM held by donated params + optimizer state + buffers")
        self._m_flops = reg.gauge(
            "train_step.flops_per_step", "XLA cost_analysis flops of the step")
        self._m_tflops = reg.gauge(
            "train_step.achieved_tflops", "flops_per_step / step wall time")
        self._m_mfu = reg.gauge(
            "train_step.mfu", "achieved FLOP/s over device peak "
            "(PADDLE_PEAK_FLOPS or the chip's bf16 datasheet number)")
        self._retrace_count = 0
        self._flops_per_step = None
        self._last_call_t = None
        self._m_donated.set(self._donated_bytes())

        # ZeRO: group_sharded_parallel marks the optimizer; lay the fresh
        # functional states out over the sharding axis (donation keeps it)
        if getattr(optimizer, "_sharded_states_axis", None):
            from ..distributed.fleet.meta_parallel.sharding import shard_optimizer_states

            shard_optimizer_states(self, optimizer._sharded_states_axis,
                                   mesh=getattr(optimizer,
                                                "_sharded_states_mesh", None))

    def _first_call(self, fn, args):
        """First dispatch of a variant = trace + XLA compile (+ async
        enqueue), under a compile window: TrainStep variants are mints too
        (keyed by their perf family — no model program store), and the
        window puts the seconds JAX reports for the build on the row."""
        win = _obs_programs.ledger().compile_window(
            fn._perf_family, family=fn._perf_family, kind="train_step",
            replica="-", trace_id=_tracing.current_trace_id())
        try:
            with _obs_programs.phase("train_step.first_call"), \
                    _tracing.span("jit.train_step", step=self._step_count,
                                  new_variant=True):
                return fn(*args)
        finally:
            win.close()
            self._m_compiles.inc()
            self._m_compile_s.set(win.wall_s)

    # ------------------------------------------------------------------ call
    def __call__(self, *batch):
        lr_f = self._lr_value()
        if lr_f != self._lr_float:  # upload the lr scalar only when it changes
            self._lr_float = lr_f
            # np scalar, not jnp: a jnp scalar is COMMITTED to one local
            # device, which a multi-process (multi-host) jit rejects; numpy
            # inputs are uncommitted/replicated in both modes
            import numpy as _np

            self._lr_dev = _np.float32(lr_f)
        if self._rng_carry is None:
            # per-step keys are fold_in(base, t) computed INSIDE the program;
            # the (base, counter) carry lives on device and is donated, so a
            # step costs zero host-side RNG dispatches.
            self._rng_carry = (_rng.next_key(), jnp.zeros((), jnp.uint32))
        leaves, treedef = jax.tree_util.tree_flatten(
            batch, is_leaf=lambda x: isinstance(x, Tensor))
        vals = [x._value if isinstance(x, Tensor) else jnp.asarray(x) for x in leaves]
        # numerics probes enter the variant key (ISSUE 13): disabled, the
        # token is 0 and the cached program is byte-identical to a build
        # that never heard of probes; enabled, every cadence-th step
        # dispatches a distinct probed variant that also returns the
        # per-site stats table
        ptok = _numerics.probe_token()
        probed = bool(ptok) and \
            self._step_count % _numerics.probe_cadence() == 0
        avals = (treedef, tuple((v.shape, str(v.dtype)) for v in vals),
                 bool(self.model.training), ptok if probed else 0)
        fn = self._compiled.get(avals)
        new_variant = fn is None
        if new_variant:
            if self._compiled and not any(a[:3] == avals[:3]
                                          for a in self._compiled):
                # a second signature means every step with it pays a full
                # XLA compile — loud by design (the #1 silent perf killer).
                # A probe toggle over an EXISTING signature is intentional
                # and stays quiet.
                self._retrace_count += 1
                self._m_retraces.inc()
                warnings.warn(
                    f"TrainStep retrace #{self._retrace_count}: input "
                    f"signature changed to {avals[1]} "
                    f"(training={avals[2]}); {len(self._compiled)} compiled "
                    "variant(s) already exist.  Each distinct batch "
                    "shape/dtype compiles a new XLA program — pad or bucket "
                    "batches to avoid recompilation.", stacklevel=2)
            fn = self._build(treedef, bool(self.model.training),
                             probes=avals[3])
            fn._perf_family = f"{self._perf_tag}.v{len(self._compiled)}"
            self._compiled[avals] = fn
        # avals only, for dist_main_program re-lowering: holding the real
        # arrays would pin a full batch of HBM for the TrainStep's lifetime.
        # _last_fn is the variant those avals belong to — they move together
        self._last_batch_vals = [jax.ShapeDtypeStruct(v.shape, v.dtype)
                                 for v in vals]
        self._last_fn = fn
        call_args = (self._diff_params, self._opt_state, self._buffers,
                     self._frozen_params, self._lr_dev, self._rng_carry)
        if self._scaler_state is not None:
            call_args += (self._scaler_state,)
        # probed variants take one trailing f32 scalar: 0.0 normally, NaN
        # when the numerics.nan_inject fault site tripped — the program
        # shape never depends on whether a fault is armed
        tail = (_numerics.consume_nan_inject(),) \
            if getattr(fn, "_probed", False) else ()
        t_call = perf_counter()
        if self._last_call_t is not None and not new_variant:
            # steady-state wall time per step (the honest MFU denominator:
            # includes host work between dispatches, excludes compiles)
            dt = t_call - self._last_call_t
            self._m_step_s.observe(dt)
            # per-program roofline attribution: dt covers the interval in
            # which the PREVIOUS dispatch executed, so it is recorded
            # under THAT call's variant family (with alternating bucketed
            # variants, crediting the current fn would swap their seconds)
            if self._perf_prev_family is not None:
                _perf.record(self._perf_prev_family, dt)
            if self._flops_per_step:
                achieved = self._flops_per_step / max(dt, 1e-12)
                self._m_tflops.set(achieved / 1e12)
                peak = _peak_flops()
                if peak:
                    self._m_mfu.set(achieved / peak)
        self._last_call_t = t_call
        self._perf_prev_family = fn._perf_family
        # span per fused step (the dispatch of the compiled call): traced-
        # phase collective events recorded while a new variant traces
        # inherit this trace id, so a step and its collectives correlate in
        # the merged cross-rank timeline; in a jax.profiler trace it is the
        # host's part of a step, on the device events' clock
        if new_variant:
            out = self._first_call(fn, (*call_args, *vals, *tail))
        else:
            with _tracing.span("jit.train_step", step=self._step_count,
                               new_variant=False):
                out = fn(*call_args, *vals, *tail)
        if new_variant:
            self._m_donated.set(self._donated_bytes())
            if (os.environ.get("PADDLE_TRAINSTEP_COST", "0").lower()
                    not in ("", "0", "false", "no")) or _prof_events._ACTIVE:
                self.cost_analysis(_fn=fn)
            # lazy cost for the roofline table: shapes are captured now,
            # the re-lower+compile runs only when the table resolves costs
            fam = fn._perf_family
            if _perf.needs_cost(fam):
                vals_sds = list(self._last_batch_vals)
                # weakrefs: the process-wide perf table must not pin this
                # TrainStep's params/opt-state past its lifetime just
                # because nobody resolved costs yet
                self_ref, fn_ref = weakref.ref(self), weakref.ref(fn)

                def _cost(vals=vals_sds):
                    ts, v = self_ref(), fn_ref()
                    if ts is None or v is None:
                        raise RuntimeError(
                            "TrainStep was garbage-collected before its "
                            "cost_analysis resolved")
                    out = ts.cost_analysis(_fn=v, _vals=vals,
                                           _update_gauges=False)
                    if not out:
                        raise RuntimeError("cost_analysis unavailable")
                    return out["flops"], out["bytes_accessed"]

                _perf.register_cost_thunk(fam, _cost)
            # the next call's inter-step dt would include this compile —
            # restart the steady-state clock
            self._last_call_t = None
        if getattr(fn, "_probed", False):
            (loss, self._diff_params, self._opt_state, self._buffers, outs,
             self._rng_carry, scaler_state, probe_stats) = out
        else:
            loss, self._diff_params, self._opt_state, self._buffers, outs, \
                self._rng_carry, scaler_state = out
            probe_stats = None
        if scaler_state is not None:
            self._scaler_state = scaler_state
        self._step_count += 1
        if probe_stats is not None:
            # device table parked for off-dispatch-path resolution (the
            # PR-7 cost-thunk discipline); maybe_poll() throttles the one
            # host sync + gauge export + anomaly pass
            _numerics.submit(self._perf_tag, fn._site_box[0], probe_stats,
                             step=self._step_count)
            _numerics.maybe_poll()
        self._rebind()
        loss_t = Tensor(loss, stop_gradient=True)
        if self.return_outputs:
            out_tree = jax.tree_util.tree_unflatten(
                fn._tree_box[0], [Tensor(o, stop_gradient=True) for o in outs])
            return loss_t, out_tree
        return loss_t

    def _lr_value(self):
        lr = self.optimizer._lr
        return float(lr()) if isinstance(lr, LRScheduler) else float(lr)

    # --------------------------------------------------------- observability
    def _donated_bytes(self):
        """Bytes of the donated carry (params + opt state + buffers + rng +
        scaler): the HBM the fused step holds across the update."""
        total = 0
        carry = (self._diff_params, self._opt_state, self._buffers,
                 self._rng_carry, self._scaler_state)
        for v in jax.tree_util.tree_leaves(carry):
            try:
                total += int(v.nbytes)
            except Exception:
                pass  # prng keys on some backends hide their bytes
        return total

    def cost_analysis(self, _fn=None, _vals=None, _update_gauges=True):
        """flops / bytes-accessed of the compiled step via XLA cost
        analysis; feeds the flops/MFU gauges.  Runs automatically on each
        compile when PADDLE_TRAINSTEP_COST=1 or a Profiler is recording
        (it re-lowers and compiles the program once more, so it is not free
        — hence the gate); callable explicitly any time after step one.
        ``_vals`` pins the batch avals to lower with (the perf-table cost
        thunks pass the avals captured at the variant's first dispatch, so
        a later variant's batch shape cannot mismatch the program)."""
        # default to the variant that produced _last_batch_vals — pairing
        # an older variant with the newest avals lowers a mismatched
        # program (same defect dist_main_program had)
        fn = _fn if _fn is not None else getattr(
            self, "_last_fn", None) or next(iter(self._compiled.values()),
                                            None)
        vals = _vals if _vals is not None \
            else getattr(self, "_last_batch_vals", None)
        if fn is None or vals is None:
            return None
        try:
            args = [self._diff_params, self._opt_state, self._buffers,
                    self._frozen_params, self._lr_dev, self._rng_carry]
            if self._scaler_state is not None:
                args.append(self._scaler_state)
            tail = [jax.ShapeDtypeStruct((), jnp.float32)] \
                if getattr(fn, "_probed", False) else []
            comp = fn._jitted.lower(*args, *vals, *tail).compile()
            ca = comp.cost_analysis()
            flops = float(ca.get("flops", 0.0))
            out = {"flops": flops,
                   "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
        except Exception:
            return None
        if flops > 0 and _update_gauges:
            # _update_gauges=False: a deferred perf-table cost thunk may
            # resolve an OLD variant while another is training — it must
            # not clobber the live MFU denominator
            self._flops_per_step = flops
            self._m_flops.set(flops)
        return out

    def _build(self, treedef, training, probes=0):
        model = self.model
        loss_fn = self.loss_fn
        pnames, bnames = self._pnames, self._bnames
        amp_level, amp_dtype = self.amp_level, self.amp_dtype
        opt = self.optimizer
        leaf_meta = self._leaf_meta
        self_ref = self

        tree_box = [None]  # out-treedef recorded at trace time, per variant
        # numerics probe plumbing (ISSUE 13): per-layer activation capture
        # rides the nn.Layer tap inside the trace; grads and the loss get
        # explicit rows.  Site names are recorded host-side at trace time
        # (site_box), the stats become one extra [n_sites, 6] f32 output.
        probes = int(probes)
        probe_acts = bool(probes) and self.accumulate_steps == 1
        probe_names = _numerics.layer_names(model) if probes else None
        _pcfg = _numerics.config() if probes else None
        inject_site = getattr(_pcfg, "nan_inject_site", None)
        site_box = [()]   # full site order (acts + loss + grads)
        act_box = [()]    # activation sites recorded by the capture
        use_scaler = self._scaler is not None
        if use_scaler:
            sc = self._scaler
            sc_dynamic = bool(sc._dynamic)
            sc_incr_every = int(sc._incr_every)
            sc_decr_every = int(sc._decr_every)
            sc_incr_ratio = float(sc._incr_ratio)
            sc_decr_ratio = float(sc._decr_ratio)

        def step(diff_params, opt_state, buffers, frozen, lr, rng_carry, *rest):
            if probes:
                inject, rest = rest[-1], rest[:-1]
            else:
                inject = None
            if use_scaler:
                (scale_in, good, bad, _), vals = rest[0], rest[1:]
            else:
                scale_in, vals = None, rest
            base_key, rng_counter = rng_carry
            key = jax.random.fold_in(base_key, rng_counter)
            def loss_of_with(dp, vals, buffers, key):
                bind_p = dict(dp)
                # O2 master weights: compute runs on an amp-dtype cast of the
                # f32 master params; the cast is part of the fused program.
                if amp_level == "O2":
                    jd = jnp.bfloat16 if amp_dtype == "bfloat16" else jnp.float16
                    bind_p = {k: (v.astype(jd)
                                  if jnp.issubdtype(v.dtype, jnp.floating) else v)
                              for k, v in bind_p.items()}
                bind_p.update(frozen)
                from ..amp import auto_cast

                was = model.training
                model.training = training
                cap = None
                try:
                    with contextlib.ExitStack() as _stack:
                        if probe_acts:
                            # per-layer stats (and the nan_inject poison
                            # point) recorded while the traced forward runs
                            cap = _stack.enter_context(_numerics.capture(
                                names=probe_names, inject=inject,
                                inject_site=inject_site))
                        _stack.enter_context(no_grad_ctx())
                        _stack.enter_context(_rng.rng_scope(key))
                        _stack.enter_context(model.bind(bind_p, dict(buffers)))
                        # names the forward and the loss (and, as
                        # transpose(jvp(forward_loss)), their backward) in
                        # every device operation's name stack
                        _stack.enter_context(jax.named_scope("forward_loss"))
                        with auto_cast(enable=amp_level is not None,
                                       level=amp_level or "O1", dtype=amp_dtype):
                            args = jax.tree_util.tree_unflatten(
                                treedef, [Tensor(v) for v in vals])
                            if loss_fn is None:
                                # single-dict batches call as kwargs, so models
                                # with (input_ids, ..., labels=None) signatures
                                # route by name: step({"input_ids": x, "labels": y})
                                if len(args) == 1 and isinstance(args[0], dict):
                                    loss = model(**args[0])
                                else:
                                    loss = model(*args)
                                outs = ()
                            else:
                                x = args[0]
                                xs = tuple(x) if isinstance(x, (tuple, list)) else (x,)
                                outs = model(*xs)
                                loss = loss_fn(outs, *args[1:])
                    newb = {k: model._captured_buffers[k] for k in bnames}
                finally:
                    model.training = was
                if isinstance(loss, dict):  # detection-style loss dicts
                    loss = loss["loss"]
                loss_v = loss._value if isinstance(loss, Tensor) else loss
                out_leaves, out_tree = jax.tree_util.tree_flatten(
                    outs, is_leaf=lambda x: isinstance(x, Tensor))
                tree_box[0] = out_tree
                out_vals = tuple(o._value if isinstance(o, Tensor) else o
                                 for o in out_leaves)
                if cap is not None:
                    act_sites, act_stats = cap.stack()
                    act_box[0] = act_sites
                else:
                    act_stats = None
                return loss_v.astype(jnp.float32), (newb, out_vals, act_stats)

            def loss_of(dp):
                l, aux = loss_of_with(dp, vals, buffers, key)
                if use_scaler:
                    l = l * scale_in  # backprop runs on the scaled loss
                return l, aux

            acc = self_ref.accumulate_steps
            if acc > 1:
                # grad accumulation as ONE program: lax.scan over micro-slices
                # (reference: pipeline/gradient-merge accumulate_steps), grads
                # averaged before a single optimizer update.
                for v in vals:
                    if v.ndim == 0 or v.shape[0] % acc:
                        raise ValueError(
                            f"accumulate_steps={acc} needs every batch input's "
                            f"leading dim divisible by it; got shape {v.shape}")
                micro_vals = tuple(
                    v.reshape((acc, v.shape[0] // acc) + v.shape[1:]) for v in vals)
                micro_keys = jax.random.split(key, acc)

                def body(carry, xs):
                    mv, mk = xs[:-1], xs[-1]
                    g_acc, l_acc, bufs_c = carry
                    def loss_micro(dp):
                        loss_v, (nb, _o, _s) = loss_of_with(dp, mv, bufs_c, mk)
                        if use_scaler:
                            loss_v = loss_v * scale_in
                        return loss_v, nb
                    (l, nb), g = jax.value_and_grad(loss_micro, has_aux=True)(diff_params)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l, nb), None

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.promote_types(p.dtype, jnp.float32)
                                        if jnp.issubdtype(p.dtype, jnp.floating) else p.dtype),
                    diff_params)
                (g_sum, l_sum, newb), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros((), jnp.float32), buffers),
                    micro_vals + (micro_keys,))
                grads = jax.tree_util.tree_map(lambda g: g / acc, g_sum)
                loss, outs, act_stats = l_sum / acc, (), None
            else:
                (loss, (newb, outs, act_stats)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(diff_params)
            if use_scaler:
                inv = 1.0 / scale_in
                loss = loss * inv
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                found = jnp.zeros((), jnp.bool_)
                for g in jax.tree_util.tree_leaves(grads):
                    found = found | ~jnp.all(jnp.isfinite(g))
            with jax.named_scope("optimizer_step"):
                new_p, new_s = opt.functional_update(
                    diff_params, grads, opt_state, lr, leaf_meta=leaf_meta)
            if use_scaler:
                # skip-step: keep old params/opt-state when any grad is
                # non-finite (one jnp.where per leaf; XLA fuses into the copy)
                new_p = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(found, o, n), new_p, diff_params)
                new_s = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(found, o, n), new_s, opt_state)
                if sc_dynamic:
                    bad_n = jnp.where(found, bad + 1, 0).astype(jnp.int32)
                    good_n = jnp.where(found, 0, good + 1).astype(jnp.int32)
                    dec = found & (bad_n >= sc_decr_every)
                    inc = (~found) & (good_n >= sc_incr_every)
                    scale_n = jnp.where(
                        dec, jnp.maximum(scale_in * sc_decr_ratio, 1.0),
                        jnp.where(inc, scale_in * sc_incr_ratio, scale_in))
                    bad_n = jnp.where(dec, 0, bad_n).astype(jnp.int32)
                    good_n = jnp.where(inc, 0, good_n).astype(jnp.int32)
                else:
                    scale_n, good_n, bad_n = scale_in, good, bad
                scaler_out = (scale_n, good_n, bad_n, found)
            else:
                scaler_out = None
            ret = (loss, new_p, new_s, newb, outs,
                   (base_key, rng_counter + 1), scaler_out)
            if not probes:
                return ret
            # assemble the device stats table: activation rows (capture
            # order), the unscaled loss, then one row per grad leaf —
            # "first offending layer" falls out of this ordering
            sites = list(act_box[0])
            rows = [act_stats] if (act_stats is not None and sites) else []
            if _numerics._match("loss"):
                sites.append("loss")
                rows.append(_numerics.stats_row(loss)[None])
            g_rows = []
            for k, g in grads.items():
                nm = "grad/" + k
                if _numerics._match(nm):
                    sites.append(nm)
                    g_rows.append(_numerics.stats_row(g))
            if g_rows:
                rows.append(jnp.stack(g_rows))
            site_box[0] = tuple(sites)
            stats = jnp.concatenate(rows, axis=0) if rows \
                else jnp.zeros((0, _numerics.NSTATS), jnp.float32)
            return ret + (stats,)

        if self._donate:
            donate = (0, 1, 2, 5, 6) if use_scaler else (0, 1, 2, 5)
        else:
            donate = ()
        jitted = jax.jit(step, donate_argnums=donate)

        def runner(*args):
            return jitted(*args)

        runner._tree_box = tree_box
        runner._jitted = jitted  # exposed for lowering/inspection (profiler, tests)
        runner._probed = bool(probes)
        runner._site_box = site_box
        return runner

    # ------------------------------------------------------- multi-host SPMD
    def globalize(self, mesh=None):
        """Make every carried array a GLOBAL ``jax.Array`` so this fused
        step is valid in a multi-process (multi-host) job.

        In multi-process jax, a jit over a mesh spanning processes rejects
        inputs committed to one process's local devices.  Model parameters
        and optimizer state are per-process identical after seeded
        construction, so they become fully-REPLICATED global arrays here
        (already-global sharded leaves — e.g. tensor-parallel weights —
        pass through untouched).  Batch inputs are the caller's job: build
        them with ``jax.make_array_from_process_local_data`` (each process
        feeds its shard of the global batch — what DistributedBatchSampler
        loads).  Single-process: no-op.  Returns self.
        """
        if jax.process_count() == 1:
            return self
        import numpy as _np
        from jax.experimental import multihost_utils as mh
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = mesh or Mesh(_np.asarray(jax.devices()), ("_g",))

        def conv(v):
            if isinstance(v, jax.Array) and not v.is_fully_addressable:
                return v  # already global (sharded or replicated)
            dt = getattr(v, "dtype", None)
            if dt is None or not hasattr(v, "shape"):
                return v
            if jax.dtypes.issubdtype(dt, jax.dtypes.prng_key):
                data = mh.host_local_array_to_global_array(
                    _np.asarray(jax.random.key_data(v)), mesh, P())
                return jax.random.wrap_key_data(data,
                                                impl=jax.random.key_impl(v))
            return mh.host_local_array_to_global_array(
                _np.asarray(v), mesh, P())

        tmap = jax.tree_util.tree_map
        self._diff_params = tmap(conv, self._diff_params)
        self._frozen_params = tmap(conv, self._frozen_params)
        self._buffers = tmap(conv, self._buffers)
        self._opt_state = tmap(conv, self._opt_state)
        if self._scaler_state is not None:
            self._scaler_state = tuple(conv(v) for v in self._scaler_state)
        if self._rng_carry is None:
            self._rng_carry = (_rng.next_key(), jnp.zeros((), jnp.uint32))
        self._rng_carry = (conv(self._rng_carry[0]), conv(self._rng_carry[1]))
        self._rebind()
        return self

    # ------------------------------------------------------------ state sync
    @property
    def _params(self):
        """Merged name->array view (diff + frozen), for state_dict/debug."""
        merged = OrderedDict()
        for k in self._pnames:
            d = self._diff_params
            merged[k] = d[k] if k in d else self._frozen_params[k]
        return merged

    def _rebind(self):
        """Point model Parameters/buffers at the fresh arrays (in-place
        discipline: a handful of attribute writes, no device work)."""
        for k, p in zip(self._pnames, self._ptensors):
            if k not in self._diff_params:
                continue  # frozen params never move
            v = self._diff_params[k]
            if self._master[k]:
                p._master = v
                p._value = v.astype(p._value.dtype)
            else:
                p._value = v
        for k, b in zip(self._bnames, self._btensors):
            b._value = self._buffers[k]

    def sync(self):
        """Flush functional optimizer state back into ``optimizer._states`` so
        eager ``opt.step()`` / ``opt.state_dict()`` see the trained state."""
        diff = [(k, t) for k, t, d in zip(self._pnames, self._ptensors, self._diff) if d]
        states = self._opt_state
        hook = getattr(self.optimizer, "sync_functional_state", None)
        if hook is not None:  # wrapper optimizers (LookAhead) own their layout
            hook(diff, states, self._step_count)
        else:
            for k, t in diff:
                self.optimizer._states[id(t)] = states[k]
            self.optimizer._step_count = self._step_count
        if self._scaler is not None and self._scaler_state is not None:
            s, g, b, _ = self._scaler_state
            self._scaler._scale = float(s)
            self._scaler._good_steps = int(g)
            self._scaler._bad_steps = int(b)
            from .. import amp as _amp

            _amp._m_loss_scale.set(float(s))
        # counts a layer keeps on the device (an expert layer's tokens per
        # expert) reach the metrics registry here, never inside a step
        for layer in self.model.sublayers(include_self=True):
            publish = getattr(layer, "publish_load", None)
            if publish is not None:
                publish()
        return self

    @property
    def found_inf(self):
        """Whether the LAST step skipped its update (traced scaler only)."""
        return (bool(self._scaler_state[3])
                if self._scaler_state is not None else False)

    @property
    def loss_scale(self):
        return (float(self._scaler_state[0])
                if self._scaler_state is not None else 1.0)

    def state_dict(self):
        sd = {"params": dict(self._params), "buffers": dict(self._buffers),
              "opt_state": self._opt_state, "step": self._step_count}
        if self._scaler_state is not None:
            sd["scaler_state"] = self._scaler_state
        return sd

    def set_state_dict(self, sd):
        for k, v in sd["params"].items():
            if k in self._diff_params:
                self._diff_params[k] = v
            else:
                self._frozen_params[k] = v
        self._buffers.update(sd["buffers"])
        self._opt_state = sd["opt_state"]
        self._step_count = sd.get("step", 0)
        if "scaler_state" in sd and self._scaler is not None:
            self._scaler_state = tuple(jnp.asarray(v) for v in sd["scaler_state"])
        self._rebind()


def train_step(model, optimizer, loss_fn=None, **kwargs):
    """Functional spelling: ``step = paddle.jit.train_step(model, opt, loss)``."""
    return TrainStep(model, optimizer, loss_fn, **kwargs)
