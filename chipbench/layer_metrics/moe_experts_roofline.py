"""The expert layers' grouped products' share of their roofline in the
traced steps: the least time the chip could take for the three products of
every expert layer, forward (twice where the cell recomputes) and backward,
at the rows that were really routed to the experts held here
(``costs/moe.py``), over the device time per step under the program's
``moe_experts`` scope.

The rows are the program's own count: what ``moe.local_assignments`` gained
from the end of the compared steps to the window's end
(``models/<family>.py`` ``window_counters()``), over the steps run between
the two (the cell's ``extra_warm_steps`` and the window's) and the expert
layers.  The time under the scope holds the element-wise work between the
products and the rows no expert here got, which the least time does not.
``None`` without the scope or the count."""
from chipbench import peaks, scope_time

SCOPE = "moe_experts"


def read(obs):
    cfg, wl = obs.config, obs.workload
    family = obs.spec.module("models", cfg["family"])
    assigned = getattr(family, "window_counters", dict)().get(
        "moe.local_assignments")
    ms = scope_time.per_step_ms(obs, SCOPE)
    steps = obs.host.get("steps", 0) + int(wl.get("extra_warm_steps", 0))
    if not assigned or not steps or ms is None or obs.peak is None:
        return None
    layers = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    rows = assigned / (steps * layers)
    obs.host["moe_rows_per_layer_step"] = rows
    flops, moved = obs.spec.module("costs", "moe").step(
        rows, int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"]),
        int(cfg["n_routed_experts"]), layers, bool(wl.get("recompute")))
    least, bound = peaks.roofline_seconds(flops, moved, obs.peak)
    obs.host["moe_experts_roofline_bound"] = bound
    return peaks.share_percent(least, ms / 1e3, "moe_experts_roofline")
