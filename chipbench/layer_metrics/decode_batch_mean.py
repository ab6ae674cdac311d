"""Mean number of lanes in one decode (or verify) dispatch over the measured
window: the program's ``serving.decode_batch_size`` histogram, observed once
per dispatch, sum over count.  ``None`` for a program without it."""


def read(obs):
    c = obs.host.get("counters", {})
    n = c.get("serving.decode_batch_size_count", 0)
    if not n:
        return None
    return c["serving.decode_batch_size_sum"] / n
