"""How unevenly the window's tokens fell on the experts held here: the
busiest held expert's assignments over the mean of the held experts, mean
over the expert layers.  Read from the program's histogram
``moe.expert_load_max_over_mean`` (one observation per expert layer when
the layers' device counts are read): what it gained by the reading that
follows the window (``models/<family>.py`` ``window_counters()``).  1 is an
even load; the grouped products wait for the busiest expert's rows.
``None`` for a program without the series."""

SERIES = "moe.expert_load_max_over_mean"


def read(obs):
    family = obs.spec.module("models", obs.config["family"])
    gained = getattr(family, "window_counters", dict)()
    n = gained.get(SERIES + "_count", 0)
    if not n:
        return None
    return gained[SERIES + "_sum"] / n
