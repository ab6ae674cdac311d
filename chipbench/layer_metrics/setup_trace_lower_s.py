"""Python's share of every build before the window: the seconds JAX reported
for tracing to a jaxpr and lowering it to a module, summed over everything
the process built from its start to the window's opening
(``chipbench/setup_record.py``).  A hit of the persistent compile cache saves
none of it.  ``None`` for a program that keeps no such record."""
from chipbench import setup_record


def read(obs):
    return setup_record.value(obs, "setup_trace_lower_s")
