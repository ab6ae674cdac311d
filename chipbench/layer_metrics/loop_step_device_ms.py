"""Device time of ONE pass of the looped decoder's layers inside a decode
step: the operations under the program's scope ``loop_step`` (the body of
the traced loop over steps: the layer bodies and the final norm; it names
the body's operations once and the device runs them ``total_ut_steps``
times) inside executions of the decode program (``decode_scope.py``; the
chunk and prefill programs' share is left out), a decode step, over the
configuration's ``total_ut_steps``.  ``None`` where no operation inside a
decode step carries the scope."""
from chipbench import decode_scope

SCOPE = "loop_step"


def read(obs):
    ms = decode_scope.per_step_ms(obs, SCOPE)
    steps = int(obs.config.get("total_ut_steps", 0))
    if ms is None or steps < 1:
        return None
    return ms / steps
