"""Device time per traced step of the operations under one of the program's
scopes (``jax.named_scope``), over the ``jit_step(...)`` executions that lie
whole inside the traced window, as ``head_loss_device_ms`` reads its scope.
The readers of the scopes ``mla_attention``, ``moe_route`` and
``moe_experts`` share it."""
from chipbench import trace_scopes

PROGRAM = "jit_step("


def step_events(obs, scope):
    """``(events under scope inside the whole traced steps, number of those
    steps)``; ``([], 0)`` where the trace holds no whole step."""
    dev = obs.trace.devices()[0]
    steps = [(s, s + d) for n, s, d in obs.trace.modules.get(dev, [])
             if n.startswith(PROGRAM) and s >= obs.t0 and s + d <= obs.t1]
    if not steps:
        return [], 0
    events = trace_scopes.under(
        trace_scopes.clip(trace_scopes.of(obs), min(a for a, _ in steps),
                          max(b for _, b in steps)), scope)
    return events, len(steps)


def per_step_ms(obs, scope):
    """``None`` where no operation carries the scope."""
    events, steps = step_events(obs, scope)
    if not events:
        return None
    obs.host[scope + "_ops_per_step"] = len(events) / steps
    return sum(e[2] for e in events) / 1e6 / steps
