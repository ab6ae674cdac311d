"""The flash-attention forward kernel's share of its roofline in the traced
steps where q/k and v have head sizes of their own (latent attention): the
least time the chip could take for the causal QK^T at 192 and PV at 128 of
the cell's batch (``costs/mla_flash.py``), times the kernel's executions
(a recomputed forward is one), over their summed device time.

The kernels are found by the program's names: a Pallas kernel (its HLO
text holds the Mosaic custom call) whose name stack holds ``mla_attention``;
the forward's holds ``flash_fwd`` besides, the two backward kernels' do
not.  ``None`` where no such kernel ran."""
from chipbench import peaks, trace_reduce, trace_scopes

SCOPE = "mla_attention"
FORWARD = "flash_fwd"


def kernel_events(obs, backward):
    events = trace_scopes.under(
        trace_scopes.clip(trace_scopes.of(obs), obs.t0, obs.t1), SCOPE)
    kernels = [e for e in events if trace_reduce.MOSAIC in e[0]]
    forward = trace_scopes.under(kernels, FORWARD)
    return [e for e in kernels if e not in forward] if backward else forward


def share(obs, backward, calls_per_cost, what):
    events = kernel_events(obs, backward)
    if not events or obs.peak is None:
        return None
    cfg, wl = obs.config, obs.workload
    costs = obs.spec.module("costs", "mla_flash")
    flops, moved = (costs.backward if backward else costs.forward)(
        int(wl["batch_size"]), int(wl["seq_len"]),
        int(cfg["num_attention_heads"]),
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        int(cfg["v_head_dim"]))
    least, bound = peaks.roofline_seconds(flops, moved, obs.peak)
    obs.host[what + "_bound"] = bound
    obs.host[what + "_kernels"] = len(events)
    return peaks.share_percent(least * len(events) / calls_per_cost,
                               sum(e[2] for e in events) / 1e9, what)


def read(obs):
    return share(obs, False, 1, "mla_flash_fwd_roofline")
