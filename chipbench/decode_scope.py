"""Device operations INSIDE executions of one of the engine's programs (by
default the decode program, ``jit_step(...)`` on the ``XLA Modules`` line),
by the program's scopes.

``scope_time.py`` reads a training step, where nothing else runs between two
steps; under the serving engine the chunk and prefill programs run between
decode steps and carry the same scopes (``moe_experts``, ``short_conv``), so
a reader of the decode step keeps only the operations that lie inside one of
its executions.  The readers ``moe_experts_decode_ms``, ``moe_route_decode_ms``,
``short_conv_decode_ms``, ``moe_decode_share`` and ``gqa_decode_roofline``
share it; a reader of another program's share (the chunk program's expert
products, say) hands ``program=`` its name on that line."""
import bisect

from chipbench import trace_reduce, trace_scopes

PROGRAM = "jit_step("


def executions(obs, whole=True, program=PROGRAM):
    """``[(start, end)]`` of ``program``'s executions on the first device,
    sorted: those that lie whole inside the traced window, or
    (``whole=False``) every one that touches it, cut to it."""
    dev = obs.trace.devices()[0]
    runs = [(n, s, d) for n, s, d in obs.trace.modules.get(dev, [])
            if n.startswith(program)]
    if whole:
        return sorted((s, s + d) for _, s, d in runs
                      if s >= obs.t0 and s + d <= obs.t1)
    return sorted((s, s + d)
                  for _, s, d in trace_reduce.clip(runs, obs.t0, obs.t1))


def inside(events, runs):
    """The scoped events ``(name, start, dur, scope)`` that lie whole inside
    one of ``runs``."""
    starts = [a for a, _ in runs]
    out = []
    for e in events:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] + e[2] <= runs[i][1]:
            out.append(e)
    return out


#: what the TPU's compiler makes of ``jax.lax.ragged_dot`` is a Mosaic call
#: whose name stack is gone (``op_name="ragged-dot-none"``, and
#: ``ragged-dot-metadata`` for its group offsets; compiled for a described
#: v5e, PR 30).  They count under the expert layer's scope ONLY where an
#: execution holds exactly as many as the family's costs say its expert
#: layers issue (``costs/<family>.grouped_kernels``): a second user of
#: ragged products, or a lowering under another name, gives ``None`` and
#: not a wrong number
UNSCOPED = {"moe_experts": "ragged-dot"}


def _unscoped(obs, ops, runs, scope):
    """The events the compiler left no name stack that can only be
    ``scope``'s, or ``None`` where their number an execution is not the one
    the family's costs give (the fullest execution is counted: the profiler
    may drop events of one, it invents none)."""
    mark = UNSCOPED[scope]
    found = [e for e in ops
             if mark in e[3] or e[0].startswith("%" + mark)]
    costs = obs.spec.module("costs", obs.config["family"])
    expected = costs.grouped_kernels(obs.config) \
        if hasattr(costs, "grouped_kernels") else None
    starts = [a for a, _ in runs]
    per_run = [0] * len(runs)
    for e in found:
        per_run[bisect.bisect_right(starts, e[1]) - 1] += 1
    obs.host[scope + "_unscoped_kernels"] = {
        "fullest_execution": max(per_run, default=0), "expected": expected}
    if expected is None or max(per_run, default=0) != expected:
        return None
    return found


def scoped(obs, scope, program=PROGRAM):
    """``(events, runs)``: the device operations under ``scope`` inside the
    whole executions ``runs`` of ``program``, by their name stack and, for a
    scope in ``UNSCOPED``, by the compiler's own names.  ``None`` where the
    trace holds no whole execution, no operation there carries the scope,
    or the unscoped kernels are not the ones expected."""
    runs = executions(obs, program=program)
    if not runs:
        return None
    # the host line's facts keep the decode program's names
    per = "_per_decode_step" if program == PROGRAM \
        else f"_per_{program.rstrip('(')}"
    ops = inside(trace_scopes.of(obs), runs)
    events = trace_scopes.under(ops, scope)
    if scope in UNSCOPED:
        by_stack, seen = len(events), set(events)
        more = _unscoped(obs, [e for e in ops if e not in seen], runs, scope)
        if more is None:
            return None
        events = events + more
        # how many the name stack alone finds
        obs.host[scope + "_ops_by_name_stack" + per] = by_stack / len(runs)
    if not events:
        return None
    obs.host[scope + "_ops" + per] = len(events) / len(runs)
    return events, runs


def per_step_ms(obs, scope, program=PROGRAM):
    """Device milliseconds under ``scope`` in one execution of ``program``,
    mean over the whole executions traced; ``None`` where :func:`scoped`
    finds nothing to read."""
    got = scoped(obs, scope, program)
    if got is None:
        return None
    events, runs = got
    return sum(e[2] for e in events) / 1e6 / len(runs)
