"""Share of the first device's idle time in the traced window that lies
under a span of the engine's scheduler thread, once the host's spans are
shifted onto the device's clock (``program_spans.clock_offset_ns``: the
device's clock ran 1.1 ms ahead of the host's in PR 23's trace, and the gaps
to attribute are 2-5 ms).  The table by innermost span, in seconds, and the
offset go to ``obs.host`` so that the run's ``host`` line prints them; an
iteration's own name there is its self time.  ``None`` for a program that
writes no such span."""
from chipbench import program_spans


def read(obs):
    if not program_spans.engine_spans(obs.trace):
        return None
    offset, pairs = program_spans.clock_offset_ns(obs.trace)
    by, idle = program_spans.idle_by_span(obs.trace, obs.t0, obs.t1, offset)
    obs.host["program_clock_offset_ms"] = offset / 1e6
    obs.host["program_clock_pairs"] = pairs
    obs.host["idle_by_program_span"] = {
        k: v / 1e9 for k, v in sorted(by.items(), key=lambda kv: -kv[1])}
    if not idle:
        return None
    return 100.0 * (idle - by.get("no program span", 0)) / idle
