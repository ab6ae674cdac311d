"""The program's own counters, read as they are through its metrics
registry (``paddle_tpu.profiler.metrics``).  The benchmark reads; it never
resets or redefines one."""

from __future__ import annotations


def snapshot(prefixes=("serving.", "train_step.", "dataloader.")):
    """``{series name: value}`` summed over labels, for every series of the
    registry whose name starts with one of the prefixes.  A histogram gives
    ``<name>_sum`` and ``<name>_count``."""
    from paddle_tpu.profiler import metrics

    out = {}
    for row in metrics.get_registry().collect():
        name = row["name"]
        if name.endswith("_bucket") or not name.startswith(tuple(prefixes)):
            continue
        try:
            out[name] = out.get(name, 0.0) + float(row["value"])
        except (TypeError, ValueError):
            continue
    return out


def delta(before, after):
    return {k: after[k] - before.get(k, 0.0) for k in after}
