"""The two flash-attention backward kernels' (dk/dv and dq) share of their
roofline in the traced steps, as ``flash_fwd_roofline`` reads the forward's.
``costs/flash.backward`` counts both kernels' required operations together
(dV, dP, dK, dQ: twice the forward; the scores they recompute are not
counted), so the least time is taken once per pair of executions."""


def read(obs):
    fwd = obs.spec.module("layer_metrics", "flash_fwd_roofline")
    return fwd.share(obs, True, 2, "flash_bwd_roofline")
