"""The two flash-attention backward kernels' (dk/dv and dq) share of their
roofline in the traced steps at latent attention's head sizes, as
``mla_flash_fwd_roofline`` reads the forward's.  ``costs/mla_flash.backward``
counts both kernels' required operations together, so the least time is
taken once per pair of executions."""


def read(obs):
    fwd = obs.spec.module("layer_metrics", "mla_flash_fwd_roofline")
    return fwd.share(obs, True, 2, "mla_flash_bwd_roofline")
