"""Executables the process built before the window, compiled or loaded from
the persistent cache, inside a compile window or not: the program's count of
JAX's ``backend_compile_duration`` events (``chipbench/setup_record.py``),
which is the harness's own ``programs_built_in_setup``.  ``None`` for a program
that keeps no such record."""
from chipbench import setup_record


def read(obs):
    return setup_record.value(obs, "setup_programs_built")
