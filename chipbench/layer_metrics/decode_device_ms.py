"""Device time of one execution of the engine's decode program, median over
the traced window: the ``XLA Modules`` events named ``jit_step(...)`` (the
program is ``step`` in ``serving/engine.py``)."""
import statistics

from chipbench import trace_reduce

PROGRAM = "jit_step("


def read(obs):
    ms = trace_reduce.module_ms(obs.trace, obs.t0, obs.t1, PROGRAM)
    return statistics.median(ms) if ms else None
