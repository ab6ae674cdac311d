"""The scheduler's own time of one turn: mean over the ``serving.iteration``
spans whole inside the traced window of the span's duration less the
``serving.device_wait`` inside it (``program_spans.iteration_host_ms``).
What is left is the host work the device may have to wait for: admission,
argument handling and enqueue (``serving.dispatch``), emitting and retiring
(``serving.emit``), gauges and ledgers.  The mean duration and the count of
every engine span of the window go to ``obs.host`` for the run's ``host``
line.  ``None`` for a program that writes no such span."""
import statistics

from chipbench import program_spans, trace_reduce


def read(obs):
    ms = program_spans.iteration_host_ms(obs.trace, obs.t0, obs.t1)
    if not ms:
        return None
    spans = trace_reduce.time_by_name(
        (n, s, d) for _, n, s, d in program_spans.engine_spans(obs.trace)
        if s >= obs.t0 and s + d <= obs.t1)
    obs.host["engine_span_ms"] = {
        name: [total / n / 1e6, n] for name, (total, n) in sorted(spans.items())}
    obs.host["sched_host_ms_median"] = statistics.median(ms)
    return statistics.fmean(ms)
