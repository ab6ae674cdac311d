"""How often a free slot waited for pages, a decode dispatch: the program's
counter ``serving.admissions_blocked`` (one increment each time the
scheduler found the queue's head without the pages it reserves,
``BlockManager.allocate`` returning ``None``) over the count of its
``serving.decode_batch_size`` histogram (observed once a dispatch), in the
measured window.  0 where pages never bound; ``None`` for a program
without the counter or a window without a decode dispatch."""


def read(obs):
    c = obs.host.get("counters", {})
    n = c.get("serving.decode_batch_size_count", 0)
    if not n or "serving.admissions_blocked" not in c:
        return None
    return c["serving.admissions_blocked"] / n
