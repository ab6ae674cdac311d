"""Rate and tail arithmetic over one measured window.

Every end-to-end number is taken over the whole window: a rate is all the
work of the window over the window's seconds, a tail is the tail of all
requests or all gaps.  No medians of chunks, no trimming.
"""

from __future__ import annotations

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks, as ``numpy.percentile`` gives it; ``None`` of nothing."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


class Request:
    """What the benchmark keeps of one served request: host-clock stamps in
    seconds.  ``due`` is when it was due to be sent (closed loop: when it was
    sent).  ``ok`` is False for a request that failed, was refused or never
    finished."""

    __slots__ = ("due", "submitted", "admitted", "token_times", "finished",
                 "ok", "prompt_len", "n_out")

    def __init__(self, due, submitted, admitted, token_times, finished, ok,
                 prompt_len=0, n_out=0):
        self.due = due
        self.submitted = submitted
        self.admitted = admitted
        self.token_times = list(token_times)
        self.finished = finished
        self.ok = ok
        self.prompt_len = prompt_len
        self.n_out = n_out


def serve_window(requests, t_open, t_close):
    """The serving window's numbers from every request's stamps.

    - ``ttft_ms``: for every request due in the window, first token minus
      due time; a request with no first token counts as the worst seen, or
      as the window's length where none has one.
    - ``itl_ms``: every gap between consecutive tokens of every request
      whose later token falls in the window, pooled.
    - ``tokens``: output tokens stamped inside the window.
    """
    ttft, missing, gaps, tokens, queue = [], 0, [], 0, []
    for r in requests:
        in_window = t_open <= r.due < t_close
        if in_window:
            if r.token_times:
                ttft.append((r.token_times[0] - r.due) * 1e3)
            else:
                missing += 1
            if r.admitted is not None:
                queue.append((r.admitted - r.submitted) * 1e3)
        prev = None
        for t in r.token_times:
            if t_open <= t < t_close:
                tokens += 1
                if prev is not None:
                    gaps.append((t - prev) * 1e3)
            prev = t
    worst = max(ttft) if ttft else (t_close - t_open) * 1e3
    ttft += [worst] * missing
    return {"ttft_ms": ttft, "itl_ms": gaps, "tokens": tokens,
            "queue_ms": queue, "seconds": t_close - t_open}


def serve_metrics(requests, t_open, t_close):
    w = serve_window(requests, t_open, t_close)
    out = {"serve_tok_s": w["tokens"] / w["seconds"]}
    if w["ttft_ms"]:
        out["ttft_p95_ms"] = percentile(w["ttft_ms"], 95)
    if w["itl_ms"]:
        out["itl_p95_ms"] = percentile(w["itl_ms"], 95)
    return out, w


def train_metrics(step_ends, samples_per_step, t_open):
    """``train_ips``: samples of every step that completed in the window over
    the window's seconds; the window closes when the last step's loss is
    ready (``step_ends[-1]``), so a stall anywhere lengthens it."""
    if not step_ends:
        return {}
    seconds = step_ends[-1] - t_open
    return {"train_ips": len(step_ends) * samples_per_step / seconds}
