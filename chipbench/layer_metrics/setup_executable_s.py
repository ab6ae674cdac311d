"""The executables' share of set-up: the seconds inside JAX's
``backend_compile_duration`` before the window, XLA's compile where the
persistent cache missed and the key and retrieval where it hit
(``chipbench/setup_record.py``; the split is ``setup_cache`` on the ``host``
line).  ``None`` for a program that keeps no such record."""
from chipbench import setup_record


def read(obs):
    return setup_record.value(obs, "setup_executable_s")
