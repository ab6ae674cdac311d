"""Device time, in one execution of the engine's decode program, of the
operations under the program's ``short_conv`` scope: the gated short
convolution of every convolution layer (both projections, the gates, the
taps over the slot's state) (``decode_scope.py``; the chunk program's share
is left out).  ``None`` where no operation inside a decode step carries the
scope."""
from chipbench import decode_scope

SCOPE = "short_conv"


def read(obs):
    return decode_scope.per_step_ms(obs, SCOPE)
