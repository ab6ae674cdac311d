"""Traffic kind ``closed_loop``: ``clients`` callers, each sending its next
request when its last one finished (batch generation jobs that keep every
slot full).  A slow system receives less load; latencies count from the
moment of sending.

Parameters (the cell's file): ``clients``, ``stagger_s`` (the clients start
one after another over this long, inside set-up, so that they drift apart
before the window opens), and the sizes that ``lengths.py`` reads.

Each client starts at a point of its own slot of ``stagger_s / clients``
that the run's seed draws.  Which scheduler iteration admits which request
follows from those instants, and a window of some tens of requests reads a
per cent apart on two such paths.  Started on a fixed grid, every seed takes
the same few paths on a host that is quiet and its own on one that is not,
so the runs of one check spread by 0.1% on one machine and by 1% on the
next, and no bound fits both.  With the starts drawn from the seed the seeds
of a set always differ by their paths, on every machine alike, and two runs
of one seed still agree as far as the host lets them.
"""

from __future__ import annotations

import numpy as np

from .lengths import Requests


class Source:
    def __init__(self, mix, seed, vocab):
        self.requests = Requests(mix, seed, vocab)
        self.clients = int(mix["clients"])
        self.stagger = float(mix.get("stagger_s", 0.0))
        self._sent = 0
        self._ready = []        # (due, client), kept sorted
        self._stopped = False

    def start(self, now):
        step = self.stagger / max(self.clients, 1)
        within = np.random.default_rng(
            [self.requests.seed, self.clients]).random(self.clients)
        self._ready = [(now + (i + float(u)) * step, i)
                       for i, u in enumerate(within)]

    def due(self, now):
        """Requests to send at ``now``: ``(due, client, prompt, max_new)``."""
        out = []
        while self._ready and self._ready[0][0] <= now \
                and not self._stopped:
            due, client = self._ready.pop(0)
            prompt, max_new = self.requests.get(self._sent)
            self._sent += 1
            out.append((due, client, prompt, max_new))
        return out

    def done(self, client, now):
        """The client's request finished: its next one is due now."""
        self._ready.append((now, client))

    def stop(self):
        self._stopped = True

    def prompt_lengths(self):
        return self.requests.prompt_lengths()

    def lateness(self):
        return None

    def describe(self):
        return dict(self.requests.describe(), kind="closed_loop",
                    clients=self.clients, sent=self._sent)
