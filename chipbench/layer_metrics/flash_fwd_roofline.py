"""The flash-attention forward kernel's share of its roofline in the traced
steps: the least time the chip could take for the causal QK^T and PV of the
cell's batch at the published head size (``costs/flash.py``; compute-bound
at S=1,024), times the kernel's executions, over their summed device time.

The trace names no kernel: a Pallas kernel is an ``XLA Ops`` event whose HLO
text holds ``custom_call_target="tpu_custom_call"``, named after the JAX
name stack.  The train step's only kernels are flash attention's: the two
backward kernels' instructions are ``%transpose_jvp...`` and the forward's is
whatever else (PERF.md section 7 asks the next tracing PR for a
``named_scope``).  The
program pads the head size 64 to 128 inside the kernel's operands; the
operations counted here are the algorithm's, at 64."""
from chipbench import peaks, trace_reduce

BACKWARD = "%transpose_jvp"


def kernel_events(obs, backward):
    return [e for e in trace_reduce.kernel_events(obs.trace, obs.t0, obs.t1)
            if e[0].startswith(BACKWARD) == backward]


def share(obs, backward, calls_per_cost, what):
    """Least time for the cell's batch (``costs/flash.py``), once for every
    ``calls_per_cost`` kernel executions, over their summed device time."""
    events = kernel_events(obs, backward)
    if not events or obs.peak is None:
        return None
    cfg, wl = obs.config, obs.workload
    heads = int(cfg["n_head"])
    costs = obs.spec.module("costs", "flash")
    flops, moved = (costs.backward if backward else costs.forward)(
        int(wl["batch_size"]), int(wl["seq_len"]), heads,
        int(cfg["n_embd"]) // heads)
    least, bound = peaks.roofline_seconds(flops, moved, obs.peak)
    obs.host[what + "_bound"] = bound
    return peaks.share_percent(least * len(events) / calls_per_cost,
                               sum(d for _, _, d in events) / 1e9, what)


def read(obs):
    return share(obs, False, 1, "flash_fwd_roofline")
