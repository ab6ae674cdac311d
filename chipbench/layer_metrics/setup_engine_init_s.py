"""Constructing and starting the engine, without the builds: self seconds of the
phases ``serving.engine_init`` (placing the weights and allocating the pools
with it), ``serving.engine_start`` and ``serving.warmup``
(``chipbench/setup_record.py``).  ``None`` for a program without such phases,
and in a cell that builds no engine."""
from chipbench import setup_record


def read(obs):
    return setup_record.value(obs, "setup_engine_init_s")
