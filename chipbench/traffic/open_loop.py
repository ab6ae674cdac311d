"""Traffic kind ``open_loop``: requests sent on a schedule whether or not
earlier ones have finished (independent users).  Latencies count from the
instant a request was DUE, so a stall is charged to the requests it delays,
and how late the generator itself ran is reported.

Parameters: ``rate_per_s`` (mean arrivals a second), ``burst`` (requests per
arrival instant: 1 is a Poisson process, k sends k together at ``rate/k``
instants a second), ``schedule_seed`` (the arrival instants are the same for
every seed of the run, as the sizes are; the ids change), and the sizes
that ``lengths.py`` reads.
"""

from __future__ import annotations

import numpy as np

from .lengths import Requests


def arrival_offsets(mix, horizon_s):
    """Arrival instants, seconds from the start, up to ``horizon_s``."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    burst = int(mix.get("burst", 1))
    rate = float(mix["rate_per_s"]) / burst
    out, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= horizon_s:
            return out
        out.extend([t] * burst)


class Source:
    def __init__(self, mix, seed, vocab, horizon_s=3600.0):
        self.requests = Requests(mix, seed, vocab)
        self.offsets = arrival_offsets(mix, float(mix.get("horizon_s",
                                                          horizon_s)))
        self._sent = 0
        self._t0 = None
        self._late = []
        self._stopped = False

    def start(self, now):
        self._t0 = now

    def due(self, now):
        out = []
        while not self._stopped and self._sent < len(self.offsets) \
                and self._t0 + self.offsets[self._sent] <= now:
            due = self._t0 + self.offsets[self._sent]
            prompt, max_new = self.requests.get(self._sent)
            out.append((due, self._sent, prompt, max_new))
            self._late.append(now - due)
            self._sent += 1
        return out

    def done(self, client, now):
        pass

    def stop(self):
        self._stopped = True

    def prompt_lengths(self):
        return self.requests.prompt_lengths()

    def lateness(self):
        """How late the generator ran: mean and worst seconds between a
        request's due instant and its sending."""
        if not self._late:
            return None
        return {"mean_s": float(np.mean(self._late)),
                "max_s": float(np.max(self._late))}

    def describe(self):
        return dict(self.requests.describe(), kind="open_loop",
                    sent=self._sent, lateness=self.lateness())
