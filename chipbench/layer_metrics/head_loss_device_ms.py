"""Device time per step of the operations under the program's
``lm_head_loss`` scope (the vocabulary-wide head product and the loss,
forward and backward: the backward's name stack holds the scope inside
``transpose(jvp(...))``), over the ``jit_step(...)`` executions that lie
whole inside the traced window.  A fusion counts where XLA kept the scope
as its name stack, so operations fused across the scope's edge fall on one
side or the other whole.  ``None`` where no operation carries the scope."""
from chipbench import trace_scopes

SCOPE = "lm_head_loss"
PROGRAM = "jit_step("


def read(obs):
    dev = obs.trace.devices()[0]
    steps = [(s, s + d) for n, s, d in obs.trace.modules.get(dev, [])
             if n.startswith(PROGRAM) and s >= obs.t0 and s + d <= obs.t1]
    if not steps:
        return None
    events = trace_scopes.under(
        trace_scopes.clip(trace_scopes.of(obs), min(a for a, _ in steps),
                          max(b for _, b in steps)), SCOPE)
    if not events:
        return None
    obs.host["head_loss_ops_per_step"] = len(events) / len(steps)
    return sum(e[2] for e in events) / 1e6 / len(steps)
