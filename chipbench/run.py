"""One run of one cell.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that refuses to start unless JAX's default backend is a TPU
with the chips the cell asks for.  It builds the model from the seed, warms
exactly the cell's shapes (set-up), measures for ``--seconds``, checks what
the timed path produced against the plain reference, prints its detail on
earlier lines and, as the last line of standard output, one JSON object with
the keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` too in a traced run) and, last, ``compared``: each number
that decided ``correct`` beside its limit.

This file knows no cell, configuration or metric by name: ``spec.py`` says
where each named thing lives.
"""

from __future__ import annotations

import time

T0 = time.time()            # process start, as near as Python gives it

import argparse             # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402

from . import spans, spec as _spec, trace_reduce    # noqa: E402
from .peaks import peaks                            # noqa: E402

#: exit code where JAX finds no TPU or too few chips (2 and 3 are the chip
#: tool's own)
NO_CHIP = 4


def log(msg):
    print(f"[chipbench] {msg}", flush=True)


class NoChip(RuntimeError):
    pass


def device_facts(chips):
    """The device as JAX reports it; raises where the default backend is no
    TPU or holds fewer chips than the cell asks for.  No fall-back."""
    import jax

    if jax.default_backend() != "tpu":
        raise NoChip(f"JAX's default backend is {jax.default_backend()!r}, "
                     "not 'tpu': a timing is taken on the chip or not at all")
    if jax.device_count() < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees "
                     f"{jax.device_count()}")
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def claim_chip(spec, chips):
    """Point JAX at the compile cache, load the program and look for the
    chips: the device's facts, or ``NoChip``.  The cache sits at a fixed path
    inside the checkout unless the machine names one; the program takes the
    same variable.  A checkout without the program fails on the import."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(spec.root, ".jax_cache"))
    import paddle_tpu  # noqa: F401
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return device_facts(chips)


class CompileCounter:
    """Counts through ``jax.monitoring`` the programs JAX built (``n``: a
    backend compile or a load from the persistent cache, either builds an
    executable) and how many of them the persistent cache served
    (``hits``).  One for the process (``shared``): JAX keeps a listener
    for good, and a run reads only how far the counts moved."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    _shared = None

    @classmethod
    def shared(cls):
        if cls._shared is None:
            cls._shared = cls()
        return cls._shared

    def __init__(self):
        import jax

        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1


class RunContext:
    """What an entry driver gets: the cell's data, the seed, the clocks, and
    the profiler's switch."""

    def __init__(self, spec, cell, seed, seconds, trace, t0, device=None):
        self.spec, self.cell = spec, cell
        self.config, self.workload = cell.config, cell.workload
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.t0 = t0
        self.device = device or {"platform": "cpu", "kind": "cpu", "count": 1}
        self.chips = cell.chips
        self.trace_seconds = float(cell.workload.get("trace_seconds", 3.0))
        self.trace_dir = os.path.join(spec.root, ".chipbench_trace",
                                      cell.name)
        self.host = {}          # host-side observations for the readers
        self.kept = {}          # what the comparison used (readings tool)
        self.setup_s = None
        self.memory_peak_bytes = None
        self.compiles = CompileCounter.shared()
        self._tracing = False

    def module(self, kind, name):
        return self.spec.module(kind, name)

    # -- clocks
    def open_window(self):
        """Set-up ends here: everything since the process started."""
        now = time.time()
        self.setup_s = now - self.t0
        self.host.update(t_open=now, programs_built_in_setup=self.compiles.n,
                         cache_hits_in_setup=self.compiles.hits)
        return now

    # -- profiler
    def start_trace(self):
        """Start the profiler if this is a traced run.  The traced segment
        follows the measured window, so the window's numbers are the same in
        both kinds of run."""
        if not self.trace:
            return False
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        spans.set_tracing(True)
        self._tracing = True
        return True

    def stop_trace(self):
        if self._tracing:
            import jax

            spans.set_tracing(False)
            jax.profiler.stop_trace()
            self._tracing = False

    # -- memory
    def read_memory_peak(self):
        """Peak bytes on the fullest chip, read before the reference runs."""
        import jax

        peak = 0
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = peak
        return peak


class Observed:
    """What a per-layer metric's reader gets."""

    def __init__(self, ctx, trace, end_to_end, peak, device):
        self.cell, self.device = ctx.cell, device
        self.config, self.workload = ctx.config, ctx.workload
        self.trace, self.host = trace, ctx.host
        self.end_to_end, self.peak = end_to_end, peak
        self.spec = ctx.spec
        if trace is not None:
            self.t0, self.t1 = trace_reduce.window(trace)


def run_cell(spec, cell, seed, seconds, trace, t0=None, device=None,
             kept=None):
    """Everything of a run but the look for a chip.  Returns the result
    line's object.  ``kept``, a dict, receives what the comparison used
    (``tools/readings.py`` puts a control in the program's place on it)."""
    ctx = RunContext(spec, cell, seed, seconds, trace, t0 or time.time(),
                     device)
    if kept is not None:
        ctx.kept = kept
    entry = spec.module("entries", cell.workload["entry"])
    out = entry.run(ctx)
    ctx.stop_trace()

    end_to_end = dict(out["end_to_end"], setup_s=ctx.setup_s)
    checks = list(out["checks"])
    device = dict(ctx.device, memory_peak_bytes=ctx.memory_peak_bytes or 0)
    result = {"correct": None, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": device}
    if trace:
        tr = trace_reduce.load(ctx.trace_dir)
        log(f"trace lines: { {f'{p}|{l}': n for (p, l), n in tr.lines.items() if n} }")
        obs = Observed(ctx, tr, end_to_end, peaks(device["kind"])
                       if device["platform"] == "tpu" else None, device)
        busy, window = trace_reduce.busy_seconds(tr, obs.t0, obs.t1)
        device.update(busy_s=busy, window_s=window)
        log("trace summary " + json.dumps(
            trace_reduce.summary(tr, obs.t0, obs.t1)))
        for m in cell.per_layer():
            value = spec.module("layer_metrics", m["name"]).read(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr, obs.t0, obs.t1),
            "idle_gaps": trace_reduce.idle_gaps(tr, obs.t0, obs.t1)}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        for m in cell.end_to_end():
            if end_to_end.get(m["name"]) is None:
                raise RuntimeError(f"the run gave no {m['name']}")
            result["metrics"][m["name"]] = {"value": end_to_end[m["name"]],
                                            "unit": m["unit"]}
    result["correct"] = bool(all(c["ok"] for c in checks)
                             and result["failed"] == 0)
    result["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                          for c in checks}
    log(f"end_to_end {json.dumps(end_to_end)}")
    log("host " + json.dumps(
        {k: v for k, v in ctx.host.items()
         if not (isinstance(v, list) and len(v) > 16)}, default=str))
    for c in checks:
        print(f"[chipbench] compared {c['name']}: {c['value']!r} "
              f"(limit {c['limit']!r}) {'ok' if c['ok'] else 'NOT OK'}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = _spec.Spec()
    cell = spec.cell(args.workload)
    try:
        device = claim_chip(spec, cell.chips)
    except NoChip as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return NO_CHIP
    result = run_cell(spec, cell, args.seed, args.seconds, bool(args.trace),
                      t0=T0, device=device)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
