"""The looped decoder's decode steps as a share of their roofline in the
traced window: the least time the chip could take for the decode steps
traced there (``costs/<family>.decode_step``: every step streams the
layers' weights ``total_ut_steps`` times and the head once, and reads the
live K and V of its lanes' contexts over all ``steps x layers`` cache rows;
their operations beside; bandwidth-bound at a few lanes), over the device
time of the decode program's executions (``jit_step(...)``,
``decode_scope.py``) that lie whole inside the window.

The count is of the work the equations need, whatever implements it, so it
stays under 100% unless a program skips a step, which the configuration
forbids.  The contexts are those of the tokens the host stamped inside the
traced window, which lags the device by a step at either edge; the steps
are the whole executions.  ``None`` where the trace holds no whole
execution or the family's costs have no ``decode_step``."""
from chipbench import decode_scope, peaks


def read(obs):
    contexts = obs.host.get("traced_decode_contexts")
    if not contexts or obs.peak is None:
        return None
    costs = obs.spec.module("costs", obs.config["family"])
    if not hasattr(costs, "decode_step"):
        return None
    runs = decode_scope.executions(obs)
    if not runs:
        return None
    flops, moved = costs.decode_step(obs.config, contexts, len(runs))
    least, bound = peaks.roofline_seconds(flops, moved, obs.peak)
    obs.host["loop_decode_hbm_roofline_bound"] = bound
    obs.host["loop_decode_steps_traced"] = len(runs)
    obs.host["loop_decode_lanes_mean"] = len(contexts) / len(runs)
    return peaks.share_percent(least, sum(b - a for a, b in runs) / 1e9,
                               "loop_decode_hbm_roofline")
