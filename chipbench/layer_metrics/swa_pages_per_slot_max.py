"""The most pages of the window layers' pool one sequence held, over the
run: the program's gauge ``serving.swa_pages_per_slot_max``
(``serving.block_manager.WindowPages``), read from its metrics registry at
the run's end.  A sequence's bound is ``ceil((window + chunk) /
page_size) + 1``; a reading above it is a fault of the pool.  ``None``
where the program has no such gauge."""
from chipbench import program_counters


def read(obs):
    return program_counters.snapshot().get("serving.swa_pages_per_slot_max")
