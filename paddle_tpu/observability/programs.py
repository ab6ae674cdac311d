"""Program-lifecycle observability: who compiled what, who paid, and how
to never pay twice.

The compiled-program store (:func:`~paddle_tpu.text.models._decode
.program_store`) is keyed by phase x shape bucket x sampler x kv_dtype x
chunk size x k_pad x ('mp', N) — every axis mints a first-dispatch
trace+compile stall, and before this module the only evidence was a
suppressed watchdog and a perf call counter.  Two pieces close the gap:

:class:`ProgramLedger` (process-wide singleton, :func:`ledger`)
    Every mint — serving-engine store keys, ``decode_loop`` generate
    programs, ``jit`` TrainStep variants — lands one row: store key,
    perf family, replica, device, cold-vs-warm provenance, the observed
    compile wall, the seconds JAX reported for the build that happened
    (tracing, lowering, XLA's compile or the load from the persistent
    cache: :class:`BuildRecord`) and the **trace id of the request that
    paid it**.  A lazy per-row analysis thunk (PR-7/12 machinery —
    :func:`~paddle_tpu.observability.perf.jit_analysis_thunk`) resolves
    executable size and cost/memory analysis on demand, never on the
    scrape path.  The ledger exports ``programs.{compiled_total,
    compile_seconds,stall_seconds}{family=,replica=}`` counters plus a
    ``programs.compile_in_progress`` gauge, renders the ``/statusz``
    ``programs`` section (key table sorted by compile seconds,
    cold-start totals, live store size), and drops ONE flight-recorder
    dump per cold-start episode whose stall exceeds
    ``PADDLE_COLD_START_BUDGET_S`` (default 30s, <=0 disables).

    The engine's first-dispatch sites open a :meth:`compile window
    <ProgramLedger.compile_window>` around the stall: the window drives
    the watchdog's compile suppression (``engine._compiling``),
    increments the in-progress gauge so a wedged compile is
    distinguishable from a wedged scheduler on ``/statusz``, and
    accumulates the stall onto every waiting
    :class:`~paddle_tpu.serving.engine.RequestHandle` — giving each
    request the TTFT decomposition ``queue_s / compile_s / prefill_s``
    and letting the SLO accountant label misses caused purely by
    compile as ``cause=cold_start``.

:class:`WarmupManifest`
    Observation turned into warm restarts: :meth:`WarmupManifest
    .capture` snapshots a live store's key set to JSON;
    ``ServingEngine.warmup(manifest)`` (and ``ReplicaPool(warmup=...)``
    replica spin-up) replays each key with inert dispatches ahead of
    admission, so the first real request serves with zero new traces.
    ``bench.py --serving --warmup`` measures the cold-vs-warm
    first-token gap in subprocess arms and ``perf_baselines.json``
    gates ``warm_traces == 0`` as an invariant.

:class:`BuildRecord` (process-wide, :func:`record`)
    What this process built and when, kept whether or not anything
    traces: one ``jax.monitoring`` listener of each kind (a build starts,
    how long it took, the cache served it), registered when the package
    is imported, keep an entry for every program's jaxpr trace, lowering
    and executable (compiled, or loaded from the persistent cache) that
    JAX reports, and :func:`phase` adds
    the start-up phases (import, engine construction, start, manifest
    replay, a ``TrainStep`` variant's first call).  An event belongs to
    the compile window open on its thread, else to its ``fun_name``.
    :meth:`ProgramLedger.builds` is the one query over it; ``/statusz``
    prints it under ``programs.start_up``.

Scrape-path rule (PR-3): :meth:`ProgramLedger.statusz` reads plain
fields under the ledger lock — it never lowers, compiles, or touches an
engine lock, so ``/statusz`` stays bounded while a compile is in flight.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
import weakref

import jax

from ..profiler import metrics as _metrics
from . import tracing as _tracing

__all__ = [
    "ProgramLedger", "WarmupManifest", "BuildRecord", "ledger", "record",
    "phase", "reset", "encode_key", "decode_key",
]

_LEDGER = None
_LOCK = threading.Lock()
_PROVIDER_REGISTERED = False

#: flight-recorder budget for a single cold-start stall (seconds);
#: overridable via ``PADDLE_COLD_START_BUDGET_S``, <=0 disables.
DEFAULT_COLD_START_BUDGET_S = 30.0


def _budget_from_env():
    raw = os.environ.get("PADDLE_COLD_START_BUDGET_S")
    if raw is None:
        return DEFAULT_COLD_START_BUDGET_S
    try:
        v = float(raw)
    except ValueError:
        return DEFAULT_COLD_START_BUDGET_S
    return v if v > 0 else None


# ------------------------------------------------------------- key encoding
def encode_key(key):
    """Store keys are nested tuples of JSON scalars (str/int/float/bool).
    JSON has no tuple, so tuples encode as lists and :func:`decode_key`
    turns every list back into a tuple — exact round-trip because no
    store key contains a real list."""
    if isinstance(key, (tuple, list)):
        return [encode_key(k) for k in key]
    if key is None or isinstance(key, (str, int, float, bool)):
        return key
    raise TypeError(f"program key element {key!r} is not JSON-encodable")


def decode_key(obj):
    if isinstance(obj, list):
        return tuple(decode_key(o) for o in obj)
    return obj


def _fmt_key(key):
    """Human-oriented rendering for /statusz rows."""
    return repr(key)


# ------------------------------------------------------------- build record
#: the seconds JAX reports for a build that really happened
#: (``jax/_src/dispatch.py``, ``compiler.py``): tracing to a jaxpr, lowering
#: it to a module, and ``compile_or_get_cached`` (XLA's compile on a miss of
#: the persistent cache, the key and the retrieval on a hit)
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_EXECUTABLE = ("compile", "cache_load")
_FOLDED = "(folded)"
#: rows of ``start_up.programs`` that ``/statusz`` prints, costliest first
_STATUSZ_ROWS = 32


def _row_seconds(row):
    return row["trace_s"] + row["lower_s"] + row["compile_s"] \
        + row["cache_load_s"]

#: one entry of the record.  A build: ``kind`` of :attr:`BuildRecord.KINDS`,
#: ``name`` JAX's ``fun_name``, ``owner`` the key of the compile window open
#: on the thread (None outside any), ``split`` its seconds by kind, in the
#: order of ``KINDS``: its own, and those of the traces and lowerings that
#: ran inside it and were merged into it.  A phase: ``kind`` ``"phase"``,
#: ``owner`` the phase it nests in, ``split`` None.  ``end`` is
#: ``time.time()``, ``seconds`` the duration as reported, ``self_s`` that
#: less every entry that ran inside it on the same thread and is on the
#: list itself: self seconds add up and count nothing twice.
_Event = collections.namedtuple(
    "_Event", "end kind seconds self_s name owner thread split")


class _Open:
    """A build or a phase that has started on a thread and not ended."""

    __slots__ = ("kind", "name", "inside", "split", "hit")

    def __init__(self, kind, name=None):
        self.kind, self.name = kind, name
        self.inside = 0.0       # seconds of what ran inside it so far
        self.split = [0.0] * len(BuildRecord.KINDS)     # merged into it
        self.hit = False        # a compile the persistent cache served


class BuildRecord:
    """What a process built and when: every jaxpr trace, lowering and
    executable JAX reported, and every :func:`phase`, in one bounded list
    (older entries folded into totals).  Nothing here lowers or compiles;
    the listeners run only when JAX builds something.

    JAX announces each of the three durations when it STARTS too (a
    scalar, :meth:`enter`), so a thread's open builds and phases are a
    stack and what runs inside what is known, not guessed from clocks.  A
    trace or a lowering inside another build never reaches the list: its
    seconds go into the build around it, kept by kind (a jitted callee's
    trace inside its caller's, a lowering rule's inside a lowering: the
    looped decoder's step program holds tens of thousands).  An executable
    built inside a trace, and anything inside a phase, is an entry of its
    own, and its seconds come off what holds it."""

    KINDS = ("trace", "lower", "compile", "cache_load")

    def __init__(self, limit=8192, registry=None):
        reg = registry or _metrics.get_registry()
        self._m_build_s = reg.counter(
            "programs.build_seconds",
            "seconds JAX reported for builds, by phase=trace|lower|compile|"
            "cache_load (compile: XLA on a miss of the persistent cache; "
            "cache_load: key and retrieval on a hit)")
        self._m_built = reg.counter(
            "programs.built_total",
            "executables built, by source=compiled|cache")
        self._m_phase_s = reg.gauge(
            "startup.phase_seconds",
            "wall seconds of a start-up phase's latest run, by phase=")
        self.limit = int(limit)
        self._lock = threading.Lock()
        self._events = collections.deque()
        # what fell off the list: [entries, executables, cache hits,
        # seconds by kind], the phases by (name, parent) as [n, seconds,
        # self_s], and the (first, last) end among them
        self._folded = [0, 0, 0, [0.0] * len(self.KINDS)]
        self._folded_phases = {}
        self._folded_ends = None
        self._tls = threading.local()

    # ------------------------------------------------------------- writers
    def _stack(self):
        return self._tls.__dict__.setdefault("stack", [])

    def _append(self, ev):
        with self._lock:
            self._events.append(ev)
            if len(self._events) > self.limit:
                self._fold(self._events.popleft())

    def _fold(self, old):
        """Past the bound the oldest entry goes into totals: a build by
        kind, a phase by its name (start-up's phases are never lost)."""
        f = self._folded
        f[0] += 1
        if old.kind == "phase":
            p = self._folded_phases.setdefault((old.name, old.owner),
                                               [0, 0.0, 0.0])
            p[0] += 1
            p[1] += old.seconds
            p[2] += old.self_s
        else:
            f[1] += old.kind in _EXECUTABLE
            f[2] += old.kind == "cache_load"
            for i, s in enumerate(old.split):
                f[3][i] += s
        first = self._folded_ends[0] if self._folded_ends else old.end
        self._folded_ends = (first, old.end)

    def enter(self, kind):
        """JAX starts a trace, a lowering or ``compile_or_get_cached`` on
        this thread."""
        self._stack().append(_Open(kind))

    def cache_hit(self):
        """The persistent cache served the executable this thread is
        building (the event fires inside ``backend_compile_duration``)."""
        stack = self._stack()
        if stack and stack[-1].kind == "compile":
            stack[-1].hit = True

    def built(self, kind, seconds, name):
        """One duration JAX reported on this thread, just now."""
        end = time.time()
        stack = self._stack()
        # its own start, unless the listeners were registered inside it
        me = stack.pop() if stack and stack[-1].kind == kind else _Open(kind)
        if me.hit:
            # nothing was compiled: all of it is what the hit cost
            kind = "cache_load"
        fresh_s = max(seconds - me.inside, 0.0)
        me.split[self.KINDS.index(kind)] += fresh_s
        wins = getattr(self._tls, "windows", None)
        win = wins[-1] if wins else None
        if win is not None:
            win._split[kind] += fresh_s
            if kind in _EXECUTABLE:
                win._split["n"] += 1
        self._m_build_s.inc(fresh_s, phase=kind)
        if kind in _EXECUTABLE:
            self._m_built.inc(
                1, source="cache" if kind == "cache_load" else "compiled")
        around = stack[-1] if stack else None
        if around is not None:
            around.inside += seconds
            if around.kind != "phase" and kind not in _EXECUTABLE:
                for i, s in enumerate(me.split):
                    around.split[i] += s
                return
        self._append(_Event(end, kind, seconds, sum(me.split), name or "?",
                            None if win is None else win._key,
                            threading.get_ident(), tuple(me.split)))

    @contextlib.contextmanager
    def phase(self, name):
        stack = self._stack()
        parent = next((o.name for o in reversed(stack)
                       if o.kind == "phase"), None)
        me = _Open("phase", name)
        stack.append(me)
        t0 = time.time()
        try:
            with _tracing.span(name):
                yield
        finally:
            seconds = time.time() - t0
            # what a failed build inside it left open goes with it
            del stack[stack.index(me):]
            if stack:
                stack[-1].inside += seconds
            self.add_phase(name, parent, t0, seconds, seconds - me.inside)

    def add_phase(self, name, parent, start, seconds, self_s=None):
        """A phase that ended on this thread ``seconds`` after ``start``
        (:meth:`phase` calls it; the package's import, which starts before
        this module exists and builds nothing, calls it directly)."""
        self_s = seconds if self_s is None else max(self_s, 0.0)
        self._append(_Event(start + seconds, "phase", seconds, self_s, name,
                            parent, threading.get_ident(), None))
        self._m_phase_s.set(seconds, phase=name)

    def _open_window(self, win):
        self._tls.__dict__.setdefault("windows", []).append(win)

    def _close_window(self, win):
        wins = getattr(self._tls, "windows", None)
        if wins and win in wins:        # closed on the thread that opened it
            wins.remove(win)

    # --------------------------------------------------------------- query
    def builds(self, since=None, until=None):
        """Everything that ENDED inside ``[since, until]`` (``time.time()``
        seconds; None: unbounded): seconds by kind, executables built and
        how many the persistent cache served, the rows by program (the
        compile window's key where one was open, else ``fun_name``) and the
        phases with their self times.  Plain reads under the lock."""
        with self._lock:
            events = [e for e in self._events
                      if (since is None or e.end >= since)
                      and (until is None or e.end <= until)]
            f_n, f_built, f_hits, f_split = self._folded
            f_split = list(f_split)
            f_phases = {k: list(v) for k, v in self._folded_phases.items()}
            ends = self._folded_ends
        seconds = dict.fromkeys(self.KINDS, 0.0)
        programs, phases = {}, {}
        built = hits = 0

        def add(name, split, executables, cached):
            r = programs.setdefault(name, {
                "n": 0, "hits": 0, "trace_s": 0.0, "lower_s": 0.0,
                "compile_s": 0.0, "cache_load_s": 0.0})
            r["n"] += executables
            r["hits"] += cached
            for kind, s in zip(self.KINDS, split):
                r[kind + "_s"] += s
                seconds[kind] += s

        def phase(name, parent, n, total, self_s):
            p = phases.setdefault(name, {
                "n": 0, "seconds": 0.0, "self_s": 0.0, "parent": parent})
            p["n"] += n
            p["seconds"] += total
            p["self_s"] += self_s

        # what was folded cannot be split: it counts where all of it ended
        # inside the interval
        if ends is not None and (since is None or ends[0] >= since) \
                and (until is None or ends[1] <= until):
            if f_built or any(f_split):
                add(_FOLDED, f_split, f_built, f_hits)
                built, hits = f_built, f_hits
            for (name, parent), (n, total, self_s) in f_phases.items():
                phase(name, parent, n, total, self_s)
        for e in events:
            if e.kind == "phase":
                phase(e.name, e.owner, 1, e.seconds, e.self_s)
                continue
            add(e.name if e.owner is None else _fmt_key(e.owner), e.split,
                e.kind in _EXECUTABLE, e.kind == "cache_load")
            built += e.kind in _EXECUTABLE
            hits += e.kind == "cache_load"
        return {"seconds": seconds, "executables": built, "cache_hits": hits,
                "programs": programs, "phases": phases,
                "events": len(events), "folded": f_n}


def _on_start(event, value, **_):
    kind = _DURATIONS.get(event)
    if kind is not None:
        _RECORD.enter(kind)


def _on_duration(event, seconds, fun_name=None, **_):
    kind = _DURATIONS.get(event)
    if kind is not None:
        # a trace reports ``f``, its lowering and executable ``jit(f)``
        if fun_name and fun_name.startswith("jit(") and fun_name[-1] == ")":
            fun_name = fun_name[4:-1]
        _RECORD.built(kind, seconds, fun_name)


def _on_event(event, **_):
    if event == _CACHE_HIT:
        _RECORD.cache_hit()


#: the process's record; the listeners below are registered when this module
#: is first imported, which the package's import does, so that a process's
#: first program is counted.  JAX keeps a listener for good: one of each
#: kind (where a build starts, how long it took, that the cache served it).
_RECORD = BuildRecord()
jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def record() -> BuildRecord:
    return _RECORD


def phase(name):
    """A start-up phase, recorded whether or not anything traces: it IS
    ``tracing.span(name)`` (a ``TraceAnnotation`` in a running profiler
    trace, a ``Span`` under an armed ``Tracer``), and it leaves ``(name,
    parent, start, seconds, thread)`` in the process's :class:`BuildRecord`
    and sets ``startup.phase_seconds{phase=}``.  Context manager and
    decorator.  For what runs once a process or once an engine."""
    return _RECORD.phase(name)


# ------------------------------------------------------------------ entries
class ProgramEntry:
    """One minted program.  Plain record; mutated only under the ledger
    lock except ``analysis`` (write-once from resolve)."""

    __slots__ = ("key", "family", "replica", "device", "kind", "warm",
                 "build_s", "compile_s", "trace_s", "lower_s",
                 "backend_compile_s", "cache_load_s", "cache_hit",
                 "trace_id", "minted_at", "analysis", "analysis_error",
                 "_thunk", "_sid")

    def __init__(self, key, family, replica, device, kind, warm, sid):
        self.key = key
        self.family = family
        self.replica = replica
        self.device = device
        self.kind = kind            # "serving" | "generate" | "train_step"
        self.warm = warm            # True: found pre-traced (manifest/sibling)
        self.build_s = 0.0          # closure construction (host, cheap)
        self.compile_s = None       # observed first-dispatch stall (wall)
        # what JAX reported for the build inside that stall (the compile
        # window's events); cache_hit stays None until a window measured it
        self.trace_s = self.lower_s = 0.0
        self.backend_compile_s = self.cache_load_s = 0.0
        self.cache_hit = None       # every executable came from the cache
        self.trace_id = None        # request trace id that paid the stall
        self.minted_at = time.time()
        self.analysis = None        # resolved jit_analysis_thunk dict
        self.analysis_error = None
        self._thunk = None          # lazy — never run on the scrape path
        self._sid = sid             # id(program_store) owning this key

    def row(self):
        r = {"key": _fmt_key(self.key), "family": self.family,
             "replica": self.replica, "device": self.device,
             "kind": self.kind,
             "cold": "warm" if self.warm else "cold",
             "build_s": round(self.build_s, 6),
             "compile_s": round(self.compile_s, 6)
             if self.compile_s is not None else None,
             "trace_id": self.trace_id}
        if self.cache_hit is not None:
            r.update(trace_s=round(self.trace_s, 6),
                     lower_s=round(self.lower_s, 6),
                     backend_compile_s=round(self.backend_compile_s, 6),
                     cache_load_s=round(self.cache_load_s, 6),
                     cache_hit=self.cache_hit)
        if self.analysis is not None:
            a = self.analysis
            r.update(executable_bytes=a.get("executable_bytes"),
                     flops=a.get("flops"),
                     bytes_accessed=a.get("bytes_accessed"))
        elif self.analysis_error is not None:
            r["analysis_error"] = self.analysis_error
        elif self._thunk is not None:
            r["analysis"] = "pending"
        return r


# ----------------------------------------------------------- compile window
class _NoopWindow:
    """Warm dispatch: nothing to account, nothing to suppress."""

    __slots__ = ()

    def attach(self, program, args):
        pass

    def close(self, traced=False):
        pass


_NOOP_WINDOW = _NoopWindow()


class CompileWindow:
    """Open around a first dispatch that is expected to trace+compile.

    While open it (a) marks ``engine._compiling`` so the serving
    watchdog/health/deadline paths know the stall is a compile, not a
    wedge, and (b) holds ``programs.compile_in_progress`` up — the
    ledger, not the engine, is now the authority on "a compile is in
    flight".  ``close(traced=...)`` releases both and, when the dispatch
    really traced, records the stall: ledger row + metrics + the
    per-request ``compile_s`` attribution for every handle that waited.
    Every build JAX reports on the opening thread while the window is the
    innermost one lands in ``_split``, and from there on the row: tracing,
    lowering and the compile run synchronously inside the dispatching call.
    """

    __slots__ = ("_led", "_key", "_family", "_replica", "_device", "_kind",
                 "_store", "_owner", "_handles", "_engine", "_program",
                 "_args", "_trace_id", "_split", "_t0", "_closed", "wall_s")

    def __init__(self, led, key, family, replica, device, kind, store,
                 owner, handles, engine, trace_id=None):
        self._led = led
        self._key = key
        self._family = family
        self._replica = replica
        self._device = device
        self._kind = kind
        self._store = store
        self._owner = owner
        self._handles = tuple(handles or ())
        self._engine = engine
        self._program = None
        self._args = None
        self._trace_id = trace_id
        self._split = dict(dict.fromkeys(BuildRecord.KINDS, 0.0), n=0)
        self._closed = False
        self.wall_s = None
        led._window_open(engine, replica)
        led._record._open_window(self)
        self._t0 = time.perf_counter()

    def attach(self, program, args):
        """Shapes for the lazy analysis thunk — captured now (cheap),
        lowered/compiled only when someone resolves."""
        self._program = program
        self._args = args

    def close(self, traced=True):
        if self._closed:
            return
        self._closed = True
        self.wall_s = elapsed = time.perf_counter() - self._t0
        self._led._record._close_window(self)
        self._led._window_close(self._engine, self._replica)
        if traced:
            self._led.record_compile(
                self._key, elapsed, family=self._family,
                replica=self._replica, device=self._device, kind=self._kind,
                store=self._store, owner=self._owner, handles=self._handles,
                trace_id=self._trace_id, program=self._program,
                args=self._args, build=self._split)


# ------------------------------------------------------------------- ledger
class ProgramLedger:
    """Process-wide accounting of compiled-program mints.  See module
    docstring.  All methods are thread-safe; rows are keyed by
    ``(id(store), key)`` so two models with coincidentally equal keys
    don't alias."""

    def __init__(self, registry=None, record=None):
        reg = registry or _metrics.get_registry()
        self._record = record if record is not None else _RECORD
        self._m_compiled = reg.counter(
            "programs.compiled_total",
            "programs traced+compiled (one per cold mint)")
        self._m_compile_s = reg.counter(
            "programs.compile_seconds",
            "what building the windowed programs cost: the trace, lower, "
            "compile and cache_load seconds JAX reported inside their "
            "compile windows (no device wait, no read-back)")
        self._m_stall_s = reg.counter(
            "programs.stall_seconds",
            "first-dispatch wall that live requests actually waited on "
            "(the compile window's wall: the build and whatever else the "
            "dispatching call waited for)")
        self._m_inprog = reg.gauge(
            "programs.compile_in_progress",
            "compile windows currently open (a wedged compile shows "
            "here; a wedged scheduler does not)")
        self._lock = threading.RLock()
        self._entries = {}        # (sid, key) -> ProgramEntry
        self._owners = {}         # sid -> weakref(owner model) | None
        self._open_total = 0
        self._open_by_engine = {}   # id(engine) -> open-window count
        self._dumped = set()        # (sid, key) that already cost a dump
        self.budget_s = _budget_from_env()
        self.cold_dumps = 0

    # ------------------------------------------------------------- windows
    def compile_window(self, key, *, family, replica="0", device=None,
                       kind="serving", store=None, owner=None, handles=(),
                       engine=None, cold=True, trace_id=None):
        """Open a compile window around a first dispatch.  ``cold=False``
        returns a shared no-op (the steady-state path pays one branch
        and an attribute load, nothing else)."""
        if not cold:
            return _NOOP_WINDOW
        return CompileWindow(self, key, family, replica, device, kind,
                             store, owner, handles, engine, trace_id)

    def _window_open(self, engine, replica):
        with self._lock:
            self._open_total += 1
            if engine is not None:
                eid = id(engine)
                self._open_by_engine[eid] = \
                    self._open_by_engine.get(eid, 0) + 1
                engine._compiling = True
        self._m_inprog.inc(1, replica=str(replica))

    def _window_close(self, engine, replica):
        with self._lock:
            self._open_total = max(0, self._open_total - 1)
            if engine is not None:
                eid = id(engine)
                n = self._open_by_engine.get(eid, 0) - 1
                if n <= 0:
                    self._open_by_engine.pop(eid, None)
                    engine._compiling = False
                else:
                    self._open_by_engine[eid] = n
        self._m_inprog.inc(-1, replica=str(replica))

    def compiling(self, engine=None):
        """Is a compile window open (for ``engine``, or anywhere)?  The
        watchdog consults this instead of trusting a flag the engine
        forgot to clear."""
        with self._lock:
            if engine is None:
                return self._open_total > 0
            return self._open_by_engine.get(id(engine), 0) > 0

    def in_progress(self):
        with self._lock:
            return self._open_total

    # -------------------------------------------------------------- records
    def record_mint(self, key, *, family, replica="0", device=None,
                    kind="serving", store=None, owner=None, build_s=0.0,
                    warm=False):
        """A program entered the store (or a TrainStep minted a variant).
        Creates the row; the compile window (or :meth:`record_compile`)
        fills in the observed stall."""
        sid = id(store) if store is not None else None
        with self._lock:
            ent = self._entries.get((sid, key))
            if ent is None:
                ent = ProgramEntry(key, family, str(replica), device, kind,
                                   warm, sid)
                self._entries[(sid, key)] = ent
                if sid is not None and sid not in self._owners:
                    try:
                        self._owners[sid] = weakref.ref(owner) \
                            if owner is not None else None
                    except TypeError:
                        self._owners[sid] = None
            ent.build_s += float(build_s)
        _ensure_provider()
        return ent

    def record_compile(self, key, stall_s, *, family, replica="0",
                       device=None, kind="serving", store=None, owner=None,
                       trace_id=None, handles=(), program=None, args=None,
                       build=None):
        """An observed first-dispatch stall.  Fills the mint row (creates
        it if the mint site predates the ledger), bumps the counters,
        attributes the stall to every waiting request handle, arms the
        lazy analysis thunk, and fires the one-per-episode cold-start
        flight dump when the stall blows the budget.  ``build`` is what
        the compile window collected of the build inside the stall
        (seconds by :attr:`BuildRecord.KINDS`, ``n`` executables); a
        caller that timed a stall by hand has none."""
        stall_s = float(stall_s)
        build = build or {}
        built_s = sum(build.get(k, 0.0) for k in BuildRecord.KINDS)
        ent = self.record_mint(key, family=family, replica=replica,
                               device=device, kind=kind, store=store,
                               owner=owner)
        paid = None
        for h in handles:
            if h is None:
                continue
            if paid is None:
                paid = getattr(h, "trace_id", None)
            # bill TTFT only to pre-first-token waiters: a stall AFTER a
            # request's first token delays its ITL, not its TTFT, and must
            # not make the decomposition sum past the observed TTFT
            if getattr(h, "first_token_at", None) is not None:
                continue
            try:
                h.compile_s += stall_s
            except AttributeError:
                continue
        if trace_id is None:
            trace_id = paid
        with self._lock:
            ent.warm = False
            ent.device = device if device is not None else ent.device
            ent.compile_s = (ent.compile_s or 0.0) + stall_s
            if build:
                ent.trace_s += build["trace"]
                ent.lower_s += build["lower"]
                ent.backend_compile_s += build["compile"]
                ent.cache_load_s += build["cache_load"]
                # every executable of the window out of the cache (a
                # window that built none: a re-trace served in memory)
                ent.cache_hit = bool(build["n"]) and \
                    ent.backend_compile_s == 0.0
            if trace_id is not None:
                ent.trace_id = trace_id
            if program is not None and ent._thunk is None:
                try:
                    from . import perf as _perf

                    ent._thunk = _perf.jit_analysis_thunk(program, args)
                except Exception:
                    ent._thunk = None
        labels = {"family": family, "replica": str(replica)}
        self._m_compiled.inc(1, **labels)
        self._m_compile_s.inc(built_s, **labels)
        if any(h is not None for h in handles):
            self._m_stall_s.inc(stall_s, **labels)
        self._maybe_dump(ent, stall_s)
        return ent

    def _maybe_dump(self, ent, stall_s):
        budget = self.budget_s
        if budget is None or stall_s <= budget:
            return
        dkey = (ent._sid, ent.key)
        with self._lock:
            if dkey in self._dumped:
                return
            self._dumped.add(dkey)
            self.cold_dumps += 1
        try:
            from . import flight_recorder as _flight

            rec = _flight.get_flight_recorder()
            # "program_kind", not "kind": record(kind, name, **data) owns
            # the bare name
            extra = {"key": _fmt_key(ent.key), "family": ent.family,
                     "replica": ent.replica, "stall_s": round(stall_s, 3),
                     "budget_s": budget, "trace_id": ent.trace_id,
                     "program_kind": ent.kind}
            rec.record("programs", "cold_start", **extra)
            rec.dump("cold_start", extra=extra)
        except Exception:
            pass  # forensics must never take down serving

    # ------------------------------------------------------------ analysis
    def resolve_analysis(self):
        """Run every pending analysis thunk NOW (re-lower + backend
        compile per entry, for executable size, flops and bytes —
        tooling/test path, never the scrape path; the build's own seconds
        are on the row since its window closed).  Failures are recorded on
        the row and not retried."""
        with self._lock:
            pending = [e for e in self._entries.values()
                       if e._thunk is not None and e.analysis is None
                       and e.analysis_error is None]
        n = 0
        for ent in pending:
            try:
                ent.analysis = ent._thunk()
                n += 1
            except Exception as exc:  # dead weakref, backend quirk, ...
                ent.analysis_error = f"{type(exc).__name__}: {exc}"
        return n

    # -------------------------------------------------------------- queries
    def rows(self, store=None, replica=None):
        """Ledger rows (dicts), most expensive compile first."""
        sid = id(store) if store is not None else None
        with self._lock:
            ents = [e for e in self._entries.values()
                    if (store is None or e._sid == sid)
                    and (replica is None or e.replica == str(replica))]
        ents.sort(key=lambda e: -(e.compile_s or 0.0))
        return [e.row() for e in ents]

    def entry(self, key, store=None):
        sid = id(store) if store is not None else None
        with self._lock:
            return self._entries.get((sid, key))

    def builds(self, since=None, until=None):
        """What the process built and which phases it went through inside
        ``[since, until]``: :meth:`BuildRecord.builds`."""
        return self._record.builds(since, until)

    def _live_store_size(self):
        """Total keys across live stores the ledger has seen.  Lazy
        import: _decode imports observability, not vice versa at module
        scope."""
        total = 0
        with self._lock:
            owners = list(self._owners.values())
        try:
            from ..text.models._decode import program_store
        except Exception:
            return None
        for ref in owners:
            model = ref() if ref is not None else None
            if model is None:
                continue
            store = program_store(model)
            if store:
                total += len(store)
        return total

    def statusz(self):
        """The /statusz ``programs`` section.  Plain-field reads only —
        bounded even while a compile window is open."""
        with self._lock:
            ents = list(self._entries.values())
            in_prog = self._open_total
            dumps = self.cold_dumps
        cold = [e for e in ents if not e.warm and e.compile_s is not None]
        ents.sort(key=lambda e: -(e.compile_s or 0.0))
        start_up = self.builds()
        rows = start_up["programs"]
        start_up["programs"] = dict(sorted(
            rows.items(), key=lambda kv: -_row_seconds(kv[1]))[:_STATUSZ_ROWS])
        start_up["programs_shown"] = f"{len(start_up['programs'])} of {len(rows)}"
        return {
            "entries": len(ents),
            "store_size": self._live_store_size(),
            "cold_starts": len(cold),
            "compile_seconds_total": round(sum(
                e.trace_s + e.lower_s + e.backend_compile_s + e.cache_load_s
                for e in ents), 6),
            "stall_seconds_total": round(sum(
                e.compile_s or 0.0 for e in ents), 6),
            "start_up": start_up,
            "compile_in_progress": in_prog,
            "cold_start_budget_s": self.budget_s,
            "cold_start_dumps": dumps,
            "programs": [e.row() for e in ents],
        }

    def reset(self):
        """Tests: drop rows/episodes (metrics and provider survive)."""
        with self._lock:
            self._entries.clear()
            self._owners.clear()
            self._dumped.clear()
            self._open_by_engine.clear()
            self._open_total = 0
            self.cold_dumps = 0
            self.budget_s = _budget_from_env()


# ----------------------------------------------------------------- manifest
class WarmupManifest:
    """A program store's key set, serializable — capture on a warm
    process, replay on a cold one (``ServingEngine.warmup``) so the
    first real request never pays a trace.

    ``meta`` is free-form provenance (e.g. the engine stamps its adapter
    signature so a manifest captured for one model geometry is refused
    by another)."""

    SCHEMA = "paddle_tpu/warmup-manifest/v1"

    def __init__(self, keys=(), meta=None):
        self.keys = [tuple(k) if isinstance(k, (list, tuple)) else (k,)
                     for k in keys]
        self.meta = dict(meta or {})

    @classmethod
    def capture(cls, model, meta=None):
        """Snapshot the live store key set of ``model``.  Keys that are
        not JSON-encodable (exotic axes) are skipped and listed in
        ``meta['skipped']`` rather than poisoning the manifest."""
        from ..text.models._decode import program_store

        store = program_store(model)
        keys, skipped = [], []
        for k in (store or {}):
            try:
                encode_key(k)
            except TypeError:
                skipped.append(repr(k))
                continue
            keys.append(k)
        m = cls(keys, meta=meta)
        if skipped:
            m.meta["skipped"] = skipped
        return m

    # ---------------------------------------------------------------- json
    def to_json(self):
        return {"schema": self.SCHEMA,
                "keys": [encode_key(k) for k in self.keys],
                "meta": self.meta}

    @classmethod
    def from_json(cls, obj):
        if obj.get("schema") != cls.SCHEMA:
            raise ValueError(
                f"not a warmup manifest (schema={obj.get('schema')!r})")
        return cls([decode_key(k) for k in obj.get("keys", [])],
                   meta=obj.get("meta"))

    def save(self, path):
        path = os.fspath(path)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path):
        with open(os.fspath(path)) as f:
            return cls.from_json(json.load(f))

    def __len__(self):
        return len(self.keys)

    def __iter__(self):
        return iter(self.keys)

    def __repr__(self):
        return f"WarmupManifest({len(self.keys)} keys)"


# ---------------------------------------------------------------- singleton
def ledger() -> ProgramLedger:
    global _LEDGER
    if _LEDGER is None:
        with _LOCK:
            if _LEDGER is None:
                _LEDGER = ProgramLedger()
    return _LEDGER


def _ensure_provider():
    """Register the /statusz ``programs`` section once, lazily on first
    mint — a process that never compiles never grows the key."""
    global _PROVIDER_REGISTERED
    if _PROVIDER_REGISTERED:
        return
    with _LOCK:
        if _PROVIDER_REGISTERED:
            return
        from . import telemetry as _telemetry

        _telemetry.add_status_provider(
            "programs", lambda: ledger().statusz())
        _PROVIDER_REGISTERED = True


def reset():
    """Tests: drop ledger rows and cold-start episodes (the singleton
    and its provider survive)."""
    if _LEDGER is not None:
        _LEDGER.reset()
