"""The paged decode-attention kernel's share of its roofline in the traced
window, for a decoder with grouped KV heads in SOME layers: the least time
the chip could take to read the cached K and V of every token decoded there
and do their QK^T and PV (``costs/paged_decode.step`` at the family's query
heads, KV heads and head size, times its ATTENTION layers:
``costs/<family>.attention_shape``; bandwidth-bound), over the summed device
time of the decode program's attention kernels.

Those kernels are the Pallas calls (``tpu_custom_call``) inside executions
of the decode program (``decode_scope.py``) under the program's scope
``gqa_attention``, other than the writer's (``paged_write`` in the name
stack): the expert layer's grouped products are Mosaic calls too.  The count takes ``head_dim`` lanes a
row where the pool stores whole lanes (64 of 128): 50% is this pool
format's ceiling, as of ``paged_decode_roofline``.  The contexts are those
of the tokens the host stamped inside the traced window, which lags the
device by a step at either edge.  ``None`` where the trace holds no such
kernel or the family's costs name no attention shape."""
from chipbench import decode_scope, peaks, trace_reduce, trace_scopes

SCOPE, WRITER = "gqa_attention", "paged_write"


def read(obs):
    contexts = obs.host.get("traced_decode_contexts")
    if not contexts or obs.peak is None:
        return None
    costs = obs.spec.module("costs", obs.config["family"])
    if not hasattr(costs, "attention_shape"):
        return None
    runs = decode_scope.executions(obs, whole=False)
    ops = decode_scope.inside(
        trace_scopes.clip(trace_scopes.of(obs), obs.t0, obs.t1), runs)
    writers = set(trace_scopes.under(ops, WRITER))
    kernels = [e for e in trace_scopes.under(ops, SCOPE)
               if trace_reduce.MOSAIC in e[0] and e not in writers]
    if not kernels:
        return None
    layers, heads, kv_heads, head_dim = costs.attention_shape(obs.config)
    flops, moved = obs.spec.module("costs", "paged_decode").step(
        contexts, heads, kv_heads, head_dim)
    least, bound = peaks.roofline_seconds(layers * flops, layers * moved,
                                          obs.peak)
    obs.host["gqa_decode_roofline_bound"] = bound
    obs.host["gqa_decode_kernels"] = len(kernels)
    obs.host["gqa_decode_kernel_ms"] = sum(e[2] for e in kernels) / 1e6 \
        / len(kernels)
    return peaks.share_percent(least, sum(e[2] for e in kernels) / 1e9,
                               "gqa_decode_roofline")
