"""Device time, in one execution of the engine's decode program, of the
operations under the program's ``moe_experts`` scope: the grouped products
(gate, up, down) of every expert layer over the step's live lanes x
``num_experts_per_tok`` assignments (``decode_scope.py``; the chunk
program's share is left out).  ``None`` where no operation inside a decode
step carries the scope."""
from chipbench import decode_scope

SCOPE = "moe_experts"


def read(obs):
    return decode_scope.per_step_ms(obs, SCOPE)
