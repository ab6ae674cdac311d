"""p95, over every request sent in the window, of first token minus the
instant it was sent: the time to first token of a closed loop that keeps
every slot full.  There the system runs at capacity by construction and a
window holds some tens of requests, so this tail swings with the smallest
change and stands among the per-layer metrics, not under a bound."""


def read(obs):
    return obs.end_to_end.get("ttft_p95_ms")
