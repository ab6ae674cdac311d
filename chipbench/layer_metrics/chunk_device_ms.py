"""Device time of one execution of the engine's chunked-prefill program
(``jit_chunk(...)``: one chunk of ``prefill_chunk_tokens`` prompt tokens),
median over the traced window."""
import statistics

from chipbench import trace_reduce


def read(obs):
    ms = trace_reduce.module_ms(obs.trace, obs.t0, obs.t1, "jit_chunk(")
    return statistics.median(ms) if ms else None
