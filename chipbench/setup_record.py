"""Set-up as the program itself recorded it: what the process built (the
seconds JAX reported for every trace, lowering and executable, compiled or
loaded from the persistent cache) and the start-up phases it went through,
between the process's start and the window's opening.

``paddle_tpu.observability.programs.ledger().builds(since, until)`` is the
one query; the reference's programs are built after the window and stay out
by time.  A program without the query (the parent of PR 34) gives ``None``
everywhere.  The five ``setup_*`` readers are one line each over
:func:`value`; the first of them to run also puts on the run's ``host``
line:

- ``setup_builds``: the 16 costliest programs, ``{name: [executables,
  trace_s, lower_s, compile_s, cache_load_s, cache hits]}`` (a name is the
  compile window's key where one was open, else JAX's ``fun_name``)
- ``setup_phases``: ``{phase: [seconds, self seconds]}``
- ``setup_cache``: ``{hits, misses, compile_s, cache_load_s}``
- ``setup_unaccounted_s``: ``setup_s`` less the import, the engine's phases
  and every build: what is left is the harness's (the claim of the chip,
  seeded weights, the warm-up requests' and first steps' own run time, the
  mix's ramp)
"""

from __future__ import annotations

#: rows of ``setup_builds``
TOP = 16
IMPORT = "startup.import"
#: construction (its children ``.weights``, ``.pools`` with it), start and
#: the manifest replay
ENGINE = ("serving.engine_init", "serving.engine_start", "serving.warmup")


def query():
    """The program's ``builds`` query, or ``None`` where it has none."""
    try:
        from paddle_tpu.observability import programs
    except ImportError:
        return None
    return getattr(programs.ledger(), "builds", None)


def _row_seconds(row):
    return row["trace_s"] + row["lower_s"] + row["compile_s"] \
        + row["cache_load_s"]


def summary(obs):
    """``{metric name: value}`` for the five readers, computed once a run
    (kept on ``obs``); ``None`` without the query or the window's clock."""
    if not hasattr(obs, "_setup_summary"):
        obs._setup_summary = _summary(obs)
    return obs._setup_summary


def _summary(obs):
    builds = query()
    t_open, setup_s = obs.host.get("t_open"), obs.end_to_end.get("setup_s")
    if builds is None or t_open is None or setup_s is None:
        return None
    got = builds(since=t_open - setup_s, until=t_open)
    sec, phases, rows = got["seconds"], got["phases"], got["programs"]
    imported = phases.get(IMPORT, {}).get("self_s", 0.0)
    engine = sum(p["self_s"] for name, p in phases.items()
                 if name.startswith(ENGINE))
    top = sorted(rows.items(), key=lambda kv: -_row_seconds(kv[1]))[:TOP]
    obs.host.update(
        setup_builds={name: [r["n"]] + [round(r[k], 4) for k in (
            "trace_s", "lower_s", "compile_s", "cache_load_s")] + [r["hits"]]
            for name, r in top},
        setup_phases={name: [round(p["seconds"], 4), round(p["self_s"], 4)]
                      for name, p in phases.items()},
        setup_cache={"hits": got["cache_hits"],
                     "misses": got["executables"] - got["cache_hits"],
                     "compile_s": round(sec["compile"], 4),
                     "cache_load_s": round(sec["cache_load"], 4)},
        setup_unaccounted_s=setup_s - imported - engine - sum(sec.values()))
    return {"setup_trace_lower_s": sec["trace"] + sec["lower"],
            "setup_executable_s": sec["compile"] + sec["cache_load"],
            "setup_programs_built": float(got["executables"]),
            "setup_engine_init_s": engine,
            "setup_import_s": imported}


def value(obs, name):
    """One of the five; ``None`` where nothing was recorded (a sum of
    nought is nothing read, not a reading of 0.0)."""
    got = summary(obs)
    return (got[name] or None) if got else None
