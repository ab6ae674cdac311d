"""The package's import: the phase ``startup.import``, from the first line of
``paddle_tpu/__init__.py`` to its last (JAX's import with it where nothing
imported JAX before; ``chipbench/setup_record.py``).  ``None`` for a program
that records no such phase."""
from chipbench import setup_record


def read(obs):
    return setup_record.value(obs, "setup_import_s")
