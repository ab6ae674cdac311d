"""p95 of admission wait: ``admitted_at - submitted_at`` of every request due
in the window, the program's own stamps on the handle."""
from chipbench import stats


def read(obs):
    waits = obs.host.get("queue_ms")
    return stats.percentile(waits, 95) if waits else None
