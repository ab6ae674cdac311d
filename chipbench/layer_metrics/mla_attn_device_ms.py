"""Device time per traced step of the operations under the program's
``mla_attention`` scope: latent attention's projections, rotary and flash
kernels, forward, recomputed forward and backward (``scope_time.py``).
``None`` where no operation carries the scope."""
from chipbench import scope_time

SCOPE = "mla_attention"


def read(obs):
    return scope_time.per_step_ms(obs, SCOPE)
