"""The benchmark's own host spans, around its calls into each layer.

While the profiler runs, each span is written into the trace as a
``TraceAnnotation`` named ``bench.<what>``, so that the device's idle gaps can
be attributed to what the host was doing on the trace's own clock
(``trace_reduce.idle_gaps``); outside a traced stretch a span costs nothing.
Spans inside the program are a later PR's.
"""

from __future__ import annotations

import contextlib

_TRACING = [False]


def set_tracing(on):
    _TRACING[0] = bool(on)


def span(name):
    if not _TRACING[0]:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)
