"""Mean host time of one scheduler iteration over the window, from the
program's ``serving.step_seconds`` histogram (sum over count): dispatch,
read-back and bookkeeping of one decode step."""


def read(obs):
    c = obs.host.get("counters", {})
    n = c.get("serving.step_seconds_count", 0)
    if not n:
        return None
    return 1e3 * c["serving.step_seconds_sum"] / n
