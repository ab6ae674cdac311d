"""The paged decode-attention kernel's share of its roofline in the traced
window: the least time the chip could take to read the cached K and V of
every token decoded there and do their QK^T and PV (``costs/paged_decode``,
all layers; bandwidth-bound), over the kernel's summed device time.

The kernel is the Pallas call inside the decode program: ``XLA Ops`` events
whose HLO text holds ``custom_call_target="tpu_custom_call"`` and whose
instruction is named after the program, ``%step...`` (PERF.md section 7
asks the next tracing PR for a ``named_scope``).  The contexts are those of
the tokens the host stamped inside the traced window, which lags the device
by a step at either edge."""
from chipbench import peaks, trace_reduce

PREFIX = "%step"


def read(obs):
    contexts = obs.host.get("traced_decode_contexts")
    if not contexts or obs.peak is None:
        return None
    events = [e for e in trace_reduce.kernel_events(obs.trace, obs.t0, obs.t1)
              if e[0].startswith(PREFIX)]
    if not events:
        return None
    cfg = obs.config
    heads, layers = int(cfg["n_head"]), int(cfg["n_layer"])
    itemsize = 1 if obs.workload["engine"].get("kv_dtype") == "int8" else 2
    flops, moved = obs.spec.module("costs", "paged_decode").step(
        contexts, heads, heads, int(cfg["n_embd"]) // heads, itemsize)
    least, bound = peaks.roofline_seconds(layers * flops, layers * moved,
                                          obs.peak)
    obs.host["paged_decode_roofline_bound"] = bound
    return peaks.share_percent(least, sum(d for _, _, d in events) / 1e9,
                               "paged_decode_roofline")
