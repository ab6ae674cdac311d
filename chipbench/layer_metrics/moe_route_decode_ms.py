"""Device time, in one execution of the engine's decode program, of the
operations under the program's ``moe_route`` scope: the router's scores,
top-k, the two sorts, the gathers of lanes into expert order and back and the
weighted combine, of every expert layer (``decode_scope.py``; the chunk
program's share is left out).  ``None`` where no operation inside a decode
step carries the scope."""
from chipbench import decode_scope

SCOPE = "moe_route"


def read(obs):
    return decode_scope.per_step_ms(obs, SCOPE)
