"""The window layers' paged decode kernels' share of their roofline in the
traced window: the least time the chip could take to read the K and V of
the keys every decoded token's window holds (``min(context, window)`` a
lane), its query row and its output row, and do their QK^T and PV, in every
window layer (``costs/<family>.swa_decode``; bandwidth-bound), over the
summed device time of those kernels.

The kernels are the Pallas calls (``tpu_custom_call``) inside executions of
the decode program (``decode_scope.py``) under the program's scope
``swa_attention``, other than the writer's (``paged_write`` in the name
stack).  The contexts are those of the tokens the host stamped inside the
traced window, which lags the device by a step at either edge.  ``None``
where the trace holds no such kernel or the family's costs count no window
layer."""
from chipbench import decode_scope, peaks, trace_reduce, trace_scopes

SCOPE, WRITER = "swa_attention", "paged_write"


def read(obs):
    contexts = obs.host.get("traced_decode_contexts")
    if not contexts or obs.peak is None:
        return None
    costs = obs.spec.module("costs", obs.config["family"])
    if not hasattr(costs, "swa_decode"):
        return None
    runs = decode_scope.executions(obs, whole=False)
    ops = decode_scope.inside(
        trace_scopes.clip(trace_scopes.of(obs), obs.t0, obs.t1), runs)
    writers = set(trace_scopes.under(ops, WRITER))
    kernels = [e for e in trace_scopes.under(ops, SCOPE)
               if trace_reduce.MOSAIC in e[0] and e not in writers]
    if not kernels:
        return None
    flops, moved = costs.swa_decode(obs.config, contexts)
    least, bound = peaks.roofline_seconds(flops, moved, obs.peak)
    obs.host["swa_decode_roofline_bound"] = bound
    obs.host["swa_decode_kernels"] = len(kernels)
    obs.host["swa_decode_kernel_ms"] = sum(e[2] for e in kernels) / 1e6 \
        / len(kernels)
    return peaks.share_percent(least, sum(e[2] for e in kernels) / 1e9,
                               "swa_decode_roofline")
