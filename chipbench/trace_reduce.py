"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Every PR computes the same number in the same way, so this reduction lives
with the benchmark.  It works on a plain intermediate form, lists of
``(name, start_ns, dur_ns)``, so that its arithmetic can be checked on known
numbers (``tests/test_trace_reduce.py``) and on the small recorded trace
beside it (``data/``).

What a v5e trace holds (looked at by hand, PR 23; PERF.md section 5): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per execution of a compiled program, named ``jit_<fn>(<fingerprint>)``) and
``XLA Ops`` (one event per HLO operation or fusion inside it; a Pallas kernel
shows under the name of its custom call), and one plane ``/host:CPU`` with a
line per host thread, which holds JAX's own TraceMe events
(``PjitFunction(<fn>)``) and the benchmark's ``TraceAnnotation`` spans.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: a Pallas kernel is an ``XLA Ops`` event whose HLO text holds this
MOSAIC = 'custom_call_target="tpu_custom_call"'
#: spans the benchmark writes itself start with this
BENCH_PREFIX = "bench."


class Trace:
    """The intermediate form.  ``ops`` and ``modules`` map a device index to
    a list of ``(name, start_ns, dur_ns)`` sorted by start; ``host`` is a list
    of ``(thread, name, start_ns, dur_ns)``."""

    def __init__(self, ops=None, modules=None, host=None, lines=None):
        self.ops = {k: sorted(v, key=lambda e: e[1])
                    for k, v in (ops or {}).items()}
        self.modules = {k: sorted(v, key=lambda e: e[1])
                        for k, v in (modules or {}).items()}
        self.host = sorted(host or [], key=lambda e: e[2])
        #: every (plane, line) name seen with its number of events
        self.lines = lines or {}

    def devices(self):
        return sorted(self.ops)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    """Read an ``.xplane.pb`` (or the directory the profiler wrote it under)
    with nothing but JAX."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops, modules, host, lines = {}, {}, [], {}
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            lines[(plane.name, line.name)] = len(events)
            if plane.name.startswith(DEVICE_PLANE):
                dev = int(plane.name[len(DEVICE_PLANE):].split()[0])
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(events)
            elif plane.name == HOST_PLANE:
                host.extend((line.name, n, s, d) for n, s, d in events
                            if d > 0)
    return Trace(ops, modules, host, lines)


# ---------------------------------------------------------------- arithmetic
def clip(events, t0, t1):
    """Events cut to the window ``[t0, t1)``."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_ns(events):
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(events, t0, t1):
    """The idle intervals ``(start, dur)`` of ``[t0, t1)`` that no event
    covers, longest first."""
    out, cursor = [], t0
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s > cursor:
            out.append((cursor, min(s, t1) - cursor))
        cursor = max(cursor, s + d)
        if cursor >= t1:
            break
    if cursor < t1:
        out.append((cursor, t1 - cursor))
    return sorted((g for g in out if g[1] > 0), key=lambda g: -g[1])


def time_by_name(events):
    """Summed duration and count per event name."""
    out = {}
    for name, _, d in events:
        tot, n = out.get(name, (0, 0))
        out[name] = (tot + d, n + 1)
    return out


def short_name(name, limit=160):
    """A device operation's name as the trace gives it is the whole HLO
    instruction; for a list that people read: its name, result type and
    opcode (``fusion.12 bf16[4,8] fusion``)."""
    if name.startswith("%") and " = " in name:
        head, _, rest = name[1:].partition(" = ")
        depth, end = 0, len(rest)
        for i, c in enumerate(rest):            # the type may be a tuple
            depth += c in "([{"
            depth -= c in ")]}"
            if c == " " and depth == 0:
                end = i
                break
        kind = rest[:end].split("{", 1)[0] if not rest.startswith("(") \
            else "tuple"
        opcode = rest[end + 1:].split("(", 1)[0]
        name = " ".join(x for x in (head, kind, opcode) if x)
    return name[:limit]


def matching(events, *needles):
    """Events whose name holds any of the needles (case-insensitive)."""
    low = [n.lower() for n in needles]
    return [e for e in events if any(n in e[0].lower() for n in low)]


def kernel_events(trace, t0, t1):
    """The first device's Pallas kernel executions inside the window."""
    dev = trace.devices()[0]
    return clip(matching(trace.ops[dev], MOSAIC), t0, t1)


def module_ms(trace, t0, t1, *programs):
    """Device milliseconds of every execution, inside the window, of the
    compiled programs whose ``XLA Modules`` name starts with one of
    ``programs`` (``"jit_step("``), on the first device."""
    dev = trace.devices()[0]
    return [d / 1e6 for n, _, d in clip(trace.modules.get(dev, []), t0, t1)
            if n.startswith(programs)]


def window(trace):
    """The traced window: from the first to the last benchmark span on the
    host, or the extent of the device events where there is none."""
    spans = [(s, s + d) for _, n, s, d in trace.host
             if n.startswith(BENCH_PREFIX + "window")]
    if spans:
        return min(a for a, _ in spans), max(b for _, b in spans)
    ev = [e for v in trace.ops.values() for e in v]
    if not ev:
        raise ValueError("the trace holds no device operation")
    return min(s for _, s, _ in ev), max(s + d for _, s, d in ev)


def busy_seconds(trace, t0=None, t1=None):
    """Seconds in which an operation ran on the device inside the window,
    averaged over the chips used, and the window's length."""
    if t0 is None:
        t0, t1 = window(trace)
    if not trace.ops:
        raise ValueError("the trace holds no device operation")
    busy = [union_ns(clip(ev, t0, t1)) for ev in trace.ops.values()]
    return sum(busy) / len(busy) / 1e9, (t1 - t0) / 1e9


_LONG_NS = 50_000_000


def _host_index(trace):
    """Host events split for look-up: the few long ones are scanned, the many
    short ones are found by bisection on their start."""
    idx = getattr(trace, "_host_idx", None)
    if idx is None:
        long_ = [e for e in trace.host if e[3] > _LONG_NS]
        short = [e for e in trace.host if e[3] <= _LONG_NS]
        idx = trace._host_idx = (long_, short, [e[2] for e in short])
    return idx


def host_cover(trace, t):
    """What the host was doing at instant ``t``: the benchmark's own
    innermost span if one covers it, else the innermost TraceMe event of any
    thread, else ``"no host span"``."""
    import bisect

    long_, short, starts = _host_index(trace)
    lo = bisect.bisect_left(starts, t - _LONG_NS)
    hi = bisect.bisect_right(starts, t)
    best = None
    for thread, name, s, d in long_ + short[lo:hi]:
        if not (s <= t <= s + d) or name.startswith(BENCH_PREFIX + "window"):
            continue
        mine = name.startswith(BENCH_PREFIX)
        key = (mine, -d)
        if best is None or key > best[0]:
            best = (key, name if mine else f"{name} [{thread}]")
    return best[1] if best else "no host span"


def idle_gaps(trace, t0=None, t1=None, top=10):
    """The longest idle gaps of the first device, each attributed to what the
    host was doing at its middle; gaps with the same attribution are summed.
    Returns ``[[what, seconds], ...]``, largest first."""
    if t0 is None:
        t0, t1 = window(trace)
    dev = trace.devices()[0]
    by = {}
    for s, d in gaps(clip(trace.ops[dev], t0, t1), t0, t1)[:2000]:
        what = host_cover(trace, s + d // 2)
        by[what] = by.get(what, 0) + d
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]


def top_ops(trace, t0=None, t1=None, top=10):
    """The device operations that took most time on the first device:
    ``[[name, seconds], ...]``."""
    if t0 is None:
        t0, t1 = window(trace)
    dev = trace.devices()[0]
    by = time_by_name(clip(trace.ops[dev], t0, t1))
    ranked = sorted(by.items(), key=lambda kv: -kv[1][0])[:top]
    return [[short_name(k), v[0] / 1e9] for k, v in ranked]


def summary(trace, t0=None, t1=None, top=40):
    """What a reader looks at by hand before writing a metric against the
    trace: per line of the first device the names that took most time, as
    ``[name, seconds, count]``, and the benchmark's own host spans."""
    if t0 is None:
        t0, t1 = window(trace)
    dev = trace.devices()[0]

    def ranked(events):
        by = time_by_name(clip(events, t0, t1))
        return [[k, v[0] / 1e9, v[1]] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1][0])[:top]]

    bench = time_by_name([(n, s, d) for _, n, s, d in trace.host
                          if n.startswith(BENCH_PREFIX)])
    kernels = time_by_name(
        (n.split(" = ", 1)[0].rstrip("0123456789."), s, d)
        for n, s, d in kernel_events(trace, t0, t1))
    return {"ops": ranked(trace.ops[dev]),
            "kernels": {k: [v[0] / 1e9, v[1]] for k, v in kernels.items()},
            "modules": ranked(trace.modules.get(dev, [])),
            "bench_spans": {k: [v[0] / 1e9, v[1]] for k, v in bench.items()}}
