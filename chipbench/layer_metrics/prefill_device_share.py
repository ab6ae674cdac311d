"""Share of the device's busy time in the traced window that went to
ingesting prompts: executions of the engine's ``jit_prefill(...)`` (whole
prompts up to the chunk size) and ``jit_chunk(...)`` programs."""
from chipbench import trace_reduce


def read(obs):
    ms = trace_reduce.module_ms(obs.trace, obs.t0, obs.t1,
                                "jit_prefill(", "jit_chunk(")
    busy = obs.device["busy_s"]
    if not ms or not busy:
        return None
    return 100.0 * sum(ms) / 1e3 / busy
