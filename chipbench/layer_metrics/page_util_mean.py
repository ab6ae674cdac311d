"""Mean share of the KV page pool in use at a decode dispatch over the
measured window: the program's ``serving.step_page_utilization`` histogram
(``BlockManager.utilization()`` once per dispatch), sum over count, in per
cent.  ``None`` for a program without it."""


def read(obs):
    c = obs.host.get("counters", {})
    n = c.get("serving.step_page_utilization_count", 0)
    if not n:
        return None
    return 100.0 * c["serving.step_page_utilization_sum"] / n
