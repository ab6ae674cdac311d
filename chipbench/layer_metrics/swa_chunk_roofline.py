"""The window layers' paged chunk kernels' share of the MXU's peak in the
traced window: the operations of the keys each prompt position of a chunk
SEES (``min(position + 1, window)``: ``costs/<family>.swa_chunk_flops``) at
the chip's bfloat16 peak, over the summed device time of those kernels.

The kernels are the Pallas calls under both ``swa_attention`` and
``chunk_attention`` inside whole executions of the chunk program
(``jit_chunk(...)``).  What a chunk held comes from the engine's host span
of its dispatch, ``serving.prefill_chunk``, whose attributes
``chunk_start`` and ``chunk_tokens`` the profiler keeps as stats: the k-th
execution of the window is paired with the k-th dispatch of it, as the
device runs them in the order they were sent (at the window's edges a
pair may be a chunk apart).  ``None`` where the trace holds no such
kernel, no such span, or the family's costs count no window layer."""
import os

from chipbench import peaks, trace_reduce, trace_scopes
from chipbench.decode_scope import executions, inside

SCOPES, PROGRAM, SPAN = ("swa_attention", "chunk_attention"), "jit_chunk(", \
    "serving.prefill_chunk"


def _dispatches(obs):
    """``[(start_ns, chunk_start, chunk_tokens)]`` of the chunk dispatches,
    read once from the trace's file and kept on the ``Trace`` (a hand-built
    ``Trace`` carries its own ``chunk_spans``)."""
    trace = obs.trace
    if getattr(trace, "chunk_spans", None) is None:
        trace.chunk_spans = []
        path = os.path.join(obs.spec.root, ".chipbench_trace", obs.cell.name)
        if os.path.isdir(path):
            from jax.profiler import ProfileData

            data = ProfileData.from_file(trace_reduce.find_xplane(path))
            for plane in data.planes:
                if plane.name != trace_reduce.HOST_PLANE:
                    continue
                for line in plane.lines:
                    for e in line.events:
                        if e.name == SPAN:
                            stats = dict(e.stats)
                            trace.chunk_spans.append((
                                int(e.start_ns), int(stats["chunk_start"]),
                                int(stats["chunk_tokens"])))
        trace.chunk_spans.sort()
    return trace.chunk_spans


def read(obs):
    if obs.peak is None:
        return None
    costs = obs.spec.module("costs", obs.config["family"])
    if not hasattr(costs, "swa_chunk_flops"):
        return None
    runs = executions(obs, program=PROGRAM)
    sent = [d for d in _dispatches(obs) if obs.t0 <= d[0] < obs.t1]
    pairs = min(len(runs), len(sent))
    if not pairs:
        return None
    ops = inside(trace_scopes.of(obs), runs[:pairs])
    for scope in SCOPES:
        ops = trace_scopes.under(ops, scope)
    kernels = [e for e in ops if trace_reduce.MOSAIC in e[0]]
    if not kernels:
        return None
    flops = sum(costs.swa_chunk_flops(obs.config, first, n)
                for _, first, n in sent[:pairs])
    obs.host["swa_chunk_pairs"] = pairs
    obs.host["swa_chunk_kernels"] = len(kernels)
    obs.host["swa_chunk_kernel_ms"] = sum(e[2] for e in kernels) / 1e6 \
        / len(kernels)
    return peaks.share_percent(flops / obs.peak["bf16_flops"],
                               sum(e[2] for e in kernels) / 1e9,
                               "swa_chunk_roofline")
