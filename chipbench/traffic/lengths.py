"""Request sizes and token ids, from the parameters of a traffic mix.

Every seed gets the SAME sequence of (prompt, output) lengths, drawn once
from the mix's own ``lengths_seed``: a seed that changed the sizes, or (where
a window holds some tens of requests) only their order, would change the
work of the window, and runs would differ by the draw, not by the system.
Token ids are drawn from the run's seed.
"""

from __future__ import annotations

import math

import numpy as np


def _lognormal(rng, n, median, sigma, lo, hi):
    x = np.exp(rng.normal(math.log(median), sigma, n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def length_pool(mix):
    """``(prompt_len, output_len)`` pairs of the mix, ``pool_size`` of them:
    lognormal by median and sigma, clipped, the output cut so that prompt +
    output fits ``max_total``."""
    rng = np.random.default_rng(int(mix["lengths_seed"]))
    n = int(mix["pool_size"])
    p, o = mix["prompt"], mix["output"]
    prompts = _lognormal(rng, n, p["median"], p["sigma"], p["min"], p["max"])
    outputs = _lognormal(rng, n, o["median"], o["sigma"], o["min"], o["max"])
    outputs = np.minimum(outputs, int(mix["max_total"]) - prompts)
    if (outputs < 1).any():
        raise ValueError("a prompt leaves no room for one output token")
    return list(zip(prompts.tolist(), outputs.tolist()))


class Requests:
    """The mix's requests: ``get(i)`` is the i-th request sent, ``(prompt
    ids, max_new_tokens)``, its sizes the pool's i-th and its ids this
    seed's; past the pool's end the sizes repeat with fresh ids."""

    def __init__(self, mix, seed, vocab):
        self.pool = length_pool(mix)
        self.seed = int(seed) % (1 << 62)
        self.vocab = int(vocab)

    def get(self, i):
        plen, olen = self.pool[i % len(self.pool)]
        rng = np.random.default_rng([self.seed, i])
        # id 0 is the program's pad id; real prompts do not hold it
        return rng.integers(1, self.vocab, plen, dtype=np.int64), int(olen)

    def prompt_lengths(self):
        """Every prompt length the mix can send, ascending."""
        return sorted({a for a, _ in self.pool})

    def describe(self):
        p = np.array([a for a, _ in self.pool])
        o = np.array([b for _, b in self.pool])
        return {"pool": len(self.pool), "prompt_mean": float(p.mean()),
                "prompt_p95": float(np.percentile(p, 95)),
                "output_mean": float(o.mean()),
                "output_p95": float(np.percentile(o, 95))}
