"""The expert layers' share of the decode program's device time: the
operations under ``moe_experts`` and ``moe_route`` inside the decode
program's whole executions over those executions' summed device time
(``decode_scope.py``).  It says whether the mechanism does most of a decode
step's work; lower is better at equal work.  ``None`` where either scope
has nothing to read inside a decode step (``decode_scope.scoped``)."""
from chipbench import decode_scope

SCOPES = ("moe_experts", "moe_route")


def read(obs):
    got = [decode_scope.scoped(obs, s) for s in SCOPES]
    if None in got:
        return None
    # an operation carries one of the two scopes, never both
    under = sum(e[2] for events, _ in got for e in events)
    runs = got[0][1]
    return 100.0 * under / sum(b - a for a, b in runs)
