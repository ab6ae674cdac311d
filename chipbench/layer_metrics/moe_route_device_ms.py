"""Device time per traced step of the operations under the program's
``moe_route`` scope: the router's scores, top-k, the two sorts, the gather
of token rows into expert order and back, and the weighted combine, of
every expert layer, forward, recomputed forward and backward
(``scope_time.py``).  ``None`` where no operation carries the scope."""
from chipbench import scope_time

SCOPE = "moe_route"


def read(obs):
    return scope_time.per_step_ms(obs, SCOPE)
