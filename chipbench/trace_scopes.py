"""The name the program gave to each device operation, from the same
``.xplane.pb`` that ``trace_reduce.load`` reads.

``trace_reduce`` keeps an ``XLA Ops`` event's name, start and duration,
which is all that ``jax.profiler.ProfileData`` hands out.  The file holds
more: each event points at an ``XEventMetadata`` whose stat ``tf_op`` is the
operation's JAX name stack as XLA kept it (``op_name`` in the HLO metadata),
``jit(step)/transpose(jvp(forward_loss))/lm_head_loss/dot_general:``.  A
``jax.named_scope`` or a ``pallas_call(name=...)`` in the program is one
component of that path, so a reader finds "the operations under
``lm_head_loss``" or "the kernel named ``chunk_attention``" whatever their
HLO instructions are called (looked at on the v5e, PR 24; PERF.md section 3).

Read with nothing but the standard library: a decoder of the protobuf wire
format for the six messages of ``xplane.proto`` that lie on the way (their
field numbers below are that file's).  Only device planes are decoded; the
host plane, the large one, is skipped by its name.
"""

from __future__ import annotations

import re

from . import trace_reduce

SCOPE_STAT = "tf_op"


# ------------------------------------------------------------- wire format
def _varint(buf, pos):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos, end):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, ``(start, end)`` for a length-delimited one."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire == 1:
            value, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif wire == 5:
            value, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """A ``map<int64, Message>`` entry: its key and its value's extent."""
    key = value = None
    for no, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _event_metadata(buf, span, scope_stat, stat_names):
    """``(name, scope)`` of one XEventMetadata (name 2, stats 5; an XStat's
    metadata_id 1, str_value 5, ref_value 7)."""
    name, scope = "", ""
    for no, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 5:
            stat = dict(_fields(buf, *v))
            if stat.get(1) != scope_stat:
                continue
            if 5 in stat:
                scope = _text(buf, stat[5])
            elif 7 in stat:
                scope = stat_names.get(stat[7], "")
    return name, scope


def _device_plane(buf, parts):
    """``[(name, start_ns, dur_ns, scope)]`` of a device plane's ``XLA Ops``
    line (XPlane: lines 3, event_metadata 4, stat_metadata 5; XLine: name
    2, timestamp_ns 3, events 4; XEvent: metadata_id 1, offset_ps 2,
    duration_ps 3)."""
    stat_names = {}
    for span in parts.get(5, ()):
        key, value = _map_entry(buf, span)
        if value is not None:
            stat_names[key] = next(
                (_text(buf, v) for no, v in _fields(buf, *value) if no == 2),
                "")
    scope_stat = next((k for k, n in stat_names.items() if n == SCOPE_STAT),
                      None)
    meta = {}
    for span in parts.get(4, ()):
        key, value = _map_entry(buf, span)
        if value is not None:
            meta[key] = _event_metadata(buf, value, scope_stat, stat_names)
    out = []
    for span in parts.get(3, ()):
        line_name, t_line, events = "", 0, []
        for no, v in _fields(buf, *span):
            if no == 2:
                line_name = _text(buf, v)
            elif no == 3:
                t_line = v
            elif no == 4:
                events.append(v)
        if line_name != trace_reduce.OPS_LINE:
            continue
        for ev in events:
            f = dict(_fields(buf, *ev))
            name, scope = meta.get(f.get(1), ("", ""))
            out.append((name, t_line + f.get(2, 0) // 1000,
                        f.get(3, 0) // 1000, scope))
    return out


def load(path):
    """``{device index: [(name, start_ns, dur_ns, scope)]}``, sorted by
    start, of every ``/device:TPU:<n>`` plane of an ``.xplane.pb`` (or of
    the directory the profiler wrote it under).  ``scope`` is ``""`` where
    XLA kept no name stack for the operation."""
    import os

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for no, plane in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        parts = {}
        for fno, v in _fields(buf, *plane):
            if fno == 2:
                parts["name"] = _text(buf, v)
            elif fno in (3, 4, 5):
                parts.setdefault(fno, []).append(v)
        name = parts.get("name", "")
        if not name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        dev = int(name[len(trace_reduce.DEVICE_PLANE):].split()[0])
        out.setdefault(dev, []).extend(_device_plane(buf, parts))
    return {d: sorted(ev, key=lambda e: e[1]) for d, ev in out.items()}


# ------------------------------------------------------------ for a reader
def of(obs):
    """The first device's scoped operations of the run's trace, read once
    and kept on the ``Trace`` (a hand-built ``Trace`` may carry its own
    ``scoped`` list).  ``[]`` where the trace's file is not there to read
    or names no operation."""
    import os

    trace = obs.trace
    if getattr(trace, "scoped", None) is None:
        trace.scoped = []
        trace_dir = os.path.join(obs.spec.root, ".chipbench_trace",
                                 obs.cell.name)
        if os.path.isdir(trace_dir):
            by_device = load(trace_dir)
            if by_device:
                trace.scoped = by_device[min(by_device)]
    return trace.scoped


def under(events, scope):
    """The events whose name stack holds ``scope`` as a whole component,
    bare or inside a transform (``transpose(jvp(forward_loss))``)."""
    word = re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")
    return [e for e in events if word.search(e[3])]


def clip(events, t0, t1):
    """Scoped events cut to ``[t0, t1)``, as ``trace_reduce.clip`` cuts
    plain ones."""
    out = []
    for name, s, d, scope in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a, scope))
    return out
