"""The program's own host spans in the profiler trace, and the device's idle
time laid against them.

``paddle_tpu.observability.tracing.span()`` writes every span as a
``TraceAnnotation``, so a traced run holds them in the ``/host:CPU`` plane
(``Trace.host``) on the clock of the device's events.  On the engine's
scheduler thread, one tree per turn that does work::

    serving.iteration
      serving.admit            (serving.prefill / serving.prefill_cached)
      serving.prefill_chunk    serving.dispatch, serving.device_wait
      serving.decode_step      serving.dispatch, serving.device_wait
      serving.verify_step      serving.dispatch, serving.device_wait
      serving.emit
    serving.idle_wait          (a turn with nothing queued or in a slot)

and ``jit.train_step`` around ``TrainStep``'s compiled call.  This file is
arithmetic on ``(thread, name, start_ns, dur_ns)`` tuples; a program without
these spans (the parent of PR 24) gives empty lists and ``None``.
"""

from __future__ import annotations

import bisect

from . import trace_reduce

ITERATION = "serving.iteration"
DEVICE_WAIT = "serving.device_wait"
TRAIN_STEP = "jit.train_step"
#: ``serving.*`` spans that other threads than the scheduler's open
NOT_ENGINE = ("serving.submit",)
#: a read-back that ends further than this from every program's end on the
#: device is no pair for the clock offset
PAIR_NS = 20_000_000


def named(trace, name, t0=None, t1=None):
    """Host spans of exactly this name, whole inside ``[t0, t1]`` if given,
    as ``(thread, name, start_ns, dur_ns)`` sorted by start."""
    return [e for e in trace.host if e[1] == name
            and (t0 is None or (e[2] >= t0 and e[2] + e[3] <= t1))]


def engine_spans(trace):
    """Every span the engine's scheduler thread opened."""
    return [e for e in trace.host if e[1].startswith("serving.")
            and e[1] not in NOT_ENGINE]


def inside(spans, outer):
    """The spans on ``outer``'s thread that start inside it."""
    thread, _, s, d = outer
    return [e for e in spans if e[0] == thread and s <= e[2] < s + d]


def iteration_host_ms(trace, t0, t1):
    """Per ``serving.iteration`` whole inside the window: its duration less
    the ``serving.device_wait`` inside it, in milliseconds.  That is the
    scheduler's own time of a turn: admission, building arguments and the
    enqueue, emitting tokens, retiring, gauges and ledgers."""
    waits = named(trace, DEVICE_WAIT)
    return [(it[3] - sum(w[3] for w in inside(waits, it))) / 1e6
            for it in named(trace, ITERATION, t0, t1)]


def clock_offset_ns(trace):
    """Host clock minus device clock, from pairs the program defines: a
    ``serving.device_wait`` ends just after the program it waited for ends
    on the device (its ``XLA Modules`` event), so over all pairs the
    smallest difference of the two ends is the clocks' offset plus the
    read-back's floor.  ``(offset_ns, pairs)``; ``(0, 0)`` without pairs.
    A host instant ``t`` is ``t - offset_ns`` on the device's clock."""
    if not trace.modules:
        return 0, 0
    ends = sorted(s + d for _, s, d in trace.modules[min(trace.modules)])
    diffs = []
    for _, _, s, d in named(trace, DEVICE_WAIT):
        e = s + d
        i = bisect.bisect_left(ends, e)
        near = min(ends[max(i - 1, 0):i + 1], key=lambda m: abs(e - m),
                   default=None)
        if near is not None and abs(e - near) <= PAIR_NS:
            diffs.append(e - near)
    return (min(diffs), len(diffs)) if diffs else (0, 0)


def innermost(spans):
    """One thread's nested spans as disjoint segments ``(start, end,
    name)`` in order, each named after the innermost span open there."""
    out, stack = [], []         # stack of (end, name)
    cursor = None

    def emit(upto):
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][1]))
        cursor = upto

    for _, name, s, d in sorted(spans, key=lambda e: (e[2], -e[3])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        if stack:
            emit(s)
        cursor = s
        stack.append((s + d, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def idle_by_span(trace, t0, t1, offset_ns=0):
    """The first device's idle time inside the window, by the innermost
    engine span under which each idle instant lies once the host's spans
    are shifted onto the device's clock: ``({name: ns}, idle_ns)``.  Idle
    time under no engine span is under ``"no program span"``."""
    dev = trace.devices()[0]
    idle = trace_reduce.gaps(trace_reduce.clip(trace.ops[dev], t0, t1),
                             t0, t1)
    by_thread = {}
    for e in engine_spans(trace):
        by_thread.setdefault(e[0], []).append(
            (e[0], e[1], e[2] - offset_ns, e[3]))
    segments = sorted(seg for spans in by_thread.values()
                      for seg in innermost(spans))
    starts = [s for s, _, _ in segments]
    by, total = {}, 0
    for g0, dur in idle:
        g1 = g0 + dur
        total += dur
        named_ns = 0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segments) and segments[i][0] < g1:
            a, b, name = segments[i]
            over = min(b, g1) - max(a, g0)
            if over > 0:
                by[name] = by.get(name, 0) + over
                named_ns += over
            i += 1
        if dur > named_ns:
            by["no program span"] = by.get("no program span", 0) \
                + dur - named_ns
    return by, total
