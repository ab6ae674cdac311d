"""p95 of every gap between consecutive tokens of every request, all gaps
of the window pooled (``stats.serve_window``).  In a closed loop that keeps
every slot full the gaps come in modes (a decode step alone, with one chunk
call before it, with two), and this cell's p95 lies on the edge between two
of them, so it swings by a fifth between runs that serve the same tokens a
second: a per-layer metric here, not one under a bound."""


def read(obs):
    return obs.end_to_end.get("itl_p95_ms")
