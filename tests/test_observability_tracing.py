"""paddle_tpu.observability — distributed tracing, flight recorder,
watchdogs, live telemetry (the PR-3 tentpole), all on the 8-device CPU
mesh: trace-id propagation engine→decode, cross-rank merge clock
alignment, watchdog firing under injected collective hang / scheduler
wedge, flight-record dump on a simulated crash, the /metrics /healthz
/statusz endpoints, and the disabled-path overhead guard."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import (
    faults, flight_recorder, telemetry, tracing, watchdog,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_observability_state():
    """Every test leaves the module-global sinks disarmed."""
    yield
    faults.clear()
    if tracing.get_tracer() is not None:
        tracing.get_tracer().stop()
    flight_recorder.disable()
    wd = watchdog.get_collective_watchdog()
    if wd is not None:
        wd.stop()
    telemetry.shutdown()


# ================================================================= tracing
def test_span_nesting_ids_and_inheritance():
    tr = tracing.Tracer().start()
    with tracing.span("outer", foo=1) as outer:
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            assert tracing.current_trace_id() == outer.trace_id
        tracing.event("tick")
    tr.stop()
    assert [s.name for s in tr.spans] == ["inner", "tick", "outer"]
    assert len({s.trace_id for s in tr.spans}) == 1
    assert len(tr.spans[0].trace_id) == 32  # 16-byte OTLP hex
    assert len(tr.spans[0].span_id) == 16
    assert tr.spans[-1].duration > 0


def test_explicit_trace_id_roots_new_trace():
    tr = tracing.Tracer().start()
    tid = tracing.new_trace_id()
    with tracing.span("request", trace_id=tid) as sp:
        assert sp.trace_id == tid
        with tracing.span("child") as ch:
            assert ch.trace_id == tid
    tr.stop()
    assert {s.trace_id for s in tr.spans} == {tid}


def test_span_disabled_is_only_a_trace_annotation():
    """With no sink armed span() builds no Span: the region is the
    profiler's own annotation (an inactive check outside a profiler
    session), and a lazy attribute is never computed."""
    assert tracing.get_tracer() is None and not tracing.enabled()
    cm = tracing.span("anything", lambda: 1 / 0, big=list(range(5)))
    assert type(cm) is jax.profiler.TraceAnnotation
    assert not isinstance(cm, tracing.Span)
    with cm:
        assert tracing.current_span() is None
    assert tracing.event("anything") is None


def test_disabled_path_overhead_guard():
    """The hot-path contract: with no sink armed, the instrumentation is
    one flag read (+ a singleton return when span() is called at all).
    Generous absolute bound so CI jitter can't flake it: 200k guarded
    checks + 20k no-op spans in well under a second."""
    assert not tracing.enabled()
    t0 = time.perf_counter()
    for _ in range(200_000):
        if tracing._ACTIVE:  # the guard every instrumented site uses
            raise AssertionError
    for _ in range(20_000):
        with tracing.span("x"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"disabled-path instrumentation took {dt:.3f}s"


def test_span_feeds_record_event_tree():
    """Spans wrap the PR-1 host event tree: with a Profiler recording,
    span names appear in the op summary."""
    from paddle_tpu.profiler import Profiler

    tr = tracing.Tracer().start()
    prof = Profiler(device_trace=False)
    prof.start()
    with tracing.span("traced.region"):
        pass
    prof.stop()
    tr.stop()
    assert "traced.region" in prof._op_table()


def test_otlp_export_shape(tmp_path):
    tr = tracing.Tracer(rank=3).start()
    linked = [tracing.new_trace_id(), tracing.new_trace_id()]
    with tracing.span("op", attempt=2, ratio=0.5, tags=["a", "b"],
                      links=linked):
        pass
    tr.stop()
    path = tr.export_otlp(str(tmp_path / "otlp.json"))
    doc = json.load(open(path))
    rs = doc["resourceSpans"][0]
    res_attrs = {a["key"]: a["value"] for a in rs["resource"]["attributes"]}
    assert res_attrs["service.name"] == {"stringValue": "paddle_tpu"}
    assert res_attrs["process.rank"] == {"intValue": "3"}
    sp = rs["scopeSpans"][0]["spans"][0]
    assert sp["name"] == "op" and len(sp["traceId"]) == 32
    assert int(sp["endTimeUnixNano"]) >= int(sp["startTimeUnixNano"])
    keys = {a["key"] for a in sp["attributes"]}
    assert {"attempt", "ratio", "tags", "rank"} <= keys
    # linked trace ids land in the OTLP Span.links field, not an attribute
    assert "links" not in keys
    assert [ln["traceId"] for ln in sp["links"]] == linked


def test_train_step_span_and_traced_collective_inheritance():
    """TrainStep opens a per-step span; traced-phase collective events
    recorded during a trace inherit the enclosing span's trace id."""
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import communication as comm
    from paddle_tpu.distributed.collective import get_default_group

    tr = tracing.Tracer().start()
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    o = opt.Momentum(learning_rate=0.01, momentum=0.9,
                     parameters=m.parameters())
    step = paddle.jit.TrainStep(m, o, loss_fn=nn.CrossEntropyLoss())
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8).astype("float32"))
    y = paddle.to_tensor(np.asarray([0, 1, 2, 3], "int64"))
    step(x, y)
    step(x, y)
    steps = tr.find("jit.train_step")
    assert len(steps) == 2
    assert [s.attrs["step"] for s in steps] == [0, 1]
    assert steps[0].attrs["new_variant"] and not steps[1].attrs["new_variant"]

    # the traced-phase hook every collective wrapper calls at trace time
    with tracing.span("train.trace") as sp:
        comm._record_collective("all_reduce", get_default_group(),
                                np.zeros(4, np.float32), phase="traced")
    tr.stop()
    ev = tr.find("collective.all_reduce")[-1]
    assert ev.trace_id == sp.trace_id and ev.parent_id == sp.span_id
    assert ev.attrs["phase"] == "traced" and ev.attrs["nranks"] == 8


# =========================================================== rank merging
def test_merge_rank_traces_clock_alignment(tmp_path):
    """8 per-rank trace files with skewed wall-clock anchors merge into
    one timeline: exact offset arithmetic, monotonic timestamps, one pid
    per rank."""
    offsets = {}
    for r in range(8):
        tr = tracing.Tracer(rank=r).start()
        with tracing.span("step", rank=r):
            time.sleep(0.002)
        tr.stop()
        # simulate skewed process clocks: rank r's anchor drifts +0.25r s
        tr.clock_unix += 0.25 * r
        offsets[r] = 0.25 * r
        tr.export_chrome(str(tmp_path / f"rank{r}_spans.json"))

    merged = tracing.merge_rank_traces(str(tmp_path),
                                       out_path=str(tmp_path / "merged.json"))
    assert merged["metadata"]["merged_ranks"] == list(range(8))
    evs = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    assert len(evs) == 8
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts), "merged timestamps must be monotonic"
    base = merged["metadata"]["clock_base_unix_time"]
    for r in range(8):
        raw = json.load(open(tmp_path / f"rank{r}_spans.json"))
        local_ts = raw["traceEvents"][0]["ts"]
        expect = local_ts + (raw["metadata"]["clock"]["unix_time"] - base) * 1e6
        got = next(e["ts"] for e in evs if e["pid"] == r)
        assert got == pytest.approx(expect, abs=1e-3)
    # the written file round-trips
    disk = json.load(open(tmp_path / "merged.json"))
    assert disk["metadata"]["merged_ranks"] == list(range(8))


def test_merge_accepts_profiler_exports(tmp_path):
    """Profiler.export stamps rank + clock anchor, so per-rank profiler
    chrome traces merge through the same path as tracer exports."""
    from paddle_tpu.profiler import Profiler

    prof = Profiler(device_trace=False)
    prof.start()
    with paddle.profiler.RecordEvent("prof_region"):
        time.sleep(0.001)
    prof.stop()
    p1 = prof.export(str(tmp_path / "rank_prof.json"))
    meta = json.load(open(p1))["metadata"]
    assert "clock" in meta and "rank" in meta

    tr = tracing.Tracer().start()
    with tracing.span("span_region"):
        pass
    tr.stop()
    p2 = tr.export_chrome(str(tmp_path / "rank_spans.json"))

    merged = tracing.merge_rank_traces([p1, p2])
    names = {e["name"] for e in merged["traceEvents"] if e.get("ph") != "M"}
    assert {"prof_region", "span_region"} <= names
    ts = [e["ts"] for e in merged["traceEvents"] if e.get("ph") != "M"]
    assert ts == sorted(ts)


# ====================================================== serving propagation
MAXLEN = 64
PS = 8


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    from paddle_tpu.text.models.gpt import GPTForCausalLM

    return GPTForCausalLM(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                          num_attention_heads=2,
                          max_position_embeddings=MAXLEN).eval()


def test_trace_id_propagates_engine_to_decode(model):
    from paddle_tpu.serving import ServingEngine

    tr = tracing.Tracer().start()
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN)
    with eng:
        h1 = eng.submit([1, 2, 3, 4], max_new_tokens=3)
        h2 = eng.submit([5, 6, 7], max_new_tokens=4)
        h1.result(timeout=300)
        h2.result(timeout=300)
    tr.stop()

    submits = tr.find("serving.submit")
    assert {s.trace_id for s in submits} >= {h1.trace_id, h2.trace_id}
    prefills = tr.find("serving.prefill")
    assert {s.trace_id for s in prefills} >= {h1.trace_id, h2.trace_id}
    by_id = {s.trace_id: s for s in prefills}
    assert by_id[h1.trace_id].attrs["request_id"] == h1.request_id
    steps = tr.find("serving.decode_step")
    assert steps, "decode iterations must be spanned"
    linked1 = [s for s in steps if h1.trace_id in s.attrs["links"]]
    linked2 = [s for s in steps if h2.trace_id in s.attrs["links"]]
    # h1 produces 3 tokens (1 from prefill) -> >= 2 decode iterations
    assert len(linked1) >= 2 and len(linked2) >= 3
    assert any(h1.trace_id in s.attrs["links"]
               and h2.trace_id in s.attrs["links"] for s in steps), \
        "continuous batching: one iteration serves both requests"


def test_request_handles_get_distinct_trace_ids(model):
    from paddle_tpu.serving.engine import RequestHandle

    ids = {RequestHandle(i, 1).trace_id for i in range(32)}
    assert len(ids) == 32


# ================================================================ watchdogs
def test_collective_watchdog_fires_on_injected_hang(tmp_path):
    import paddle_tpu.distributed as dist

    rec = flight_recorder.enable(dir=str(tmp_path))
    # warm the program first: the FIRST dispatch of a signature is compile,
    # deliberately not watchdogged (compile-stall suppression)
    dist.all_reduce(paddle.to_tensor(np.ones((8, 4), "float32")))
    wd = watchdog.CollectiveWatchdog(deadline_s=0.25, poll_s=0.05).start()
    faults.inject("collective_hang", seconds=1.0)
    from paddle_tpu.profiler import metrics as prof_metrics

    fires = prof_metrics.get_registry().counter("observability.watchdog_fires")
    n0 = fires.get(kind="collective", op="all_reduce") or 0
    try:
        x = paddle.to_tensor(np.ones((8, 4), "float32"))
        out = dist.all_reduce(x)  # hangs ~1s inside the watchdog bracket
    finally:
        faults.clear()
        wd.stop()
    # the collective still completes correctly after the hang
    np.testing.assert_allclose(out.numpy()[0], 8.0)
    assert len(wd.fired) == 1
    fire = wd.fired[0]
    assert fire["op"] == "all_reduce" and fire["nranks"] == 8
    assert fire["ranks_missing"] == [1, 2, 3, 4, 5, 6, 7]
    assert fire["age_s"] >= 0.25
    assert (fires.get(kind="collective", op="all_reduce") or 0) == n0 + 1
    # flight dump written, naming the stuck op via the open span
    assert fire["dump_path"] and os.path.exists(fire["dump_path"])
    doc = json.load(open(fire["dump_path"]))
    assert doc["reason"] == "collective_watchdog"
    assert doc["extra"]["op"] == "all_reduce"
    assert any(s["name"] == "collective.all_reduce"
               for s in doc["open_spans"])
    assert rec.last_dump_path == fire["dump_path"]


def test_collective_watchdog_quiet_on_fast_ops():
    import paddle_tpu.distributed as dist

    wd = watchdog.CollectiveWatchdog(deadline_s=5.0, poll_s=0.05).start()
    try:
        x = paddle.to_tensor(np.ones((8, 2), "float32"))
        dist.all_reduce(x)
        dist.barrier()
        time.sleep(0.2)
    finally:
        wd.stop()
    assert wd.fired == [] and wd.inflight() == []


def test_serving_watchdog_fires_on_injected_scheduler_wedge(model, tmp_path):
    from paddle_tpu.serving import ServingEngine

    flight_recorder.enable(dir=str(tmp_path))
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN)
    with eng:
        # warm first: prefill/decode compile stalls would trip a short
        # deadline for the "right" mechanical reason but the wrong cause
        eng.generate([1, 2, 3, 4], max_new_tokens=2, timeout=300)
        wd = watchdog.ServingWatchdog(eng, deadline_s=0.3,
                                      poll_s=0.05).start()
        faults.inject("serving.scheduler_wedge", seconds=30.0)
        h = eng.submit([1, 2, 3], max_new_tokens=2)
        t0 = time.time()
        while not wd.fired and time.time() - t0 < 10:
            time.sleep(0.05)
        assert wd.fired, "watchdog must fire while the scheduler is wedged"
        fire = wd.fired[0]
        assert fire["age_s"] >= 0.3
        assert fire["stats"]["queue_depth"] >= 1
        assert fire["dump_path"] and os.path.exists(fire["dump_path"])
        doc = json.load(open(fire["dump_path"]))
        assert doc["reason"] == "serving_watchdog"
        # un-wedge: the request then completes normally
        faults.clear()
        assert len(h.result(timeout=300)) == 2
        wd.stop()


# ========================================================== flight recorder
def test_flight_ring_is_bounded_and_dumps(tmp_path):
    rec = flight_recorder.FlightRecorder(dir=str(tmp_path), capacity=16)
    for i in range(100):
        rec.record("event", f"e{i}", i=i)
    snap = rec.snapshot()
    assert len(snap) == 16 and snap[-1]["name"] == "e99"
    path = rec.dump("unit_test", extra={"k": "v"})
    doc = json.load(open(path))
    assert doc["schema"] == "paddle_tpu.observability.flight.v1"
    assert doc["reason"] == "unit_test" and doc["extra"] == {"k": "v"}
    assert len(doc["events"]) == 16


def test_flight_dump_on_unhandled_exception(tmp_path):
    rec = flight_recorder.enable(dir=str(tmp_path))
    with tracing.span("about_to_fail"):
        pass
    try:
        raise RuntimeError("boom for forensics")
    except RuntimeError:
        path = flight_recorder.handle_exception(*sys.exc_info())
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["reason"] == "unhandled_exception"
    assert "boom for forensics" in doc["extra"]["exception"]
    assert any(e["name"] == "about_to_fail" for e in doc["events"])
    assert rec.last_dump_path == path


_CRASH_SCRIPT = r"""
import os, signal
os.environ.pop("XLA_FLAGS", None)
import jax
jax.config.update("jax_platforms", "cpu")
from paddle_tpu import observability as obs
# PADDLE_FLIGHT_DIR is set: import already armed the ring + handlers
assert obs.flight_recorder.enabled()
tr = obs.tracing.Tracer().start()
with obs.span("doomed_op", step=7):
    pass
print("READY", flush=True)
os.kill(os.getpid(), signal.SIGTERM)   # simulated crash
raise SystemExit("unreachable")
"""


def test_flight_dump_on_sigterm_crash(tmp_path):
    """Real signal path: a subprocess arms the recorder from the env,
    records spans, SIGTERMs itself — the dump lands in PADDLE_FLIGHT_DIR
    and the process still dies by SIGTERM."""
    script = tmp_path / "crash.py"
    script.write_text(_CRASH_SCRIPT)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PADDLE_FLIGHT_DIR"] = str(tmp_path / "flight")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, env=env, timeout=240)
    assert "READY" in r.stdout, r.stderr
    assert r.returncode == -signal.SIGTERM, (r.returncode, r.stderr)
    dumps = sorted((tmp_path / "flight").glob("flight_*_signal_SIGTERM_*.json"))
    assert dumps, "SIGTERM must leave a flight record"
    doc = json.load(open(dumps[0]))
    assert doc["reason"] == "signal_SIGTERM"
    assert any(e["name"] == "doomed_op" for e in doc["events"])


# ================================================================ telemetry
def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return (resp.status, resp.headers.get("Content-Type", ""),
                    resp.read())
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type", ""), e.read()


def test_telemetry_endpoints_with_engine(model, tmp_path):
    from paddle_tpu.serving import ServingEngine

    flight_recorder.enable(dir=str(tmp_path))
    eng = ServingEngine(model, num_slots=2, page_size=PS, max_model_len=MAXLEN,
                        telemetry_port=0)  # ephemeral port via ctor wiring
    with eng:
        eng.generate([1, 2, 3, 4], max_new_tokens=2, timeout=300)
        srv = telemetry.get_server()
        assert srv is not None and srv.port
        code, ctype, body = _get(srv.url + "/metrics")
        text = body.decode()
        assert code == 200 and ctype.startswith("text/plain")
        assert "# TYPE serving_ttft_seconds histogram" in text
        assert "serving_queue_depth" in text

        code, ctype, body = _get(srv.url + "/healthz")
        hz = json.loads(body)
        assert code == 200 and hz["status"] == "ok"
        assert hz["pid"] == os.getpid()

        code, ctype, body = _get(srv.url + "/statusz")
        sz = json.loads(body)
        assert code == 200
        # provider registration is keyed by replica id (default "0")
        assert sz["serving/0"]["num_slots"] == 2
        assert sz["serving/0"]["started"] is True
        assert len(sz["serving/0"]["slots"]) == 2
        assert "queue_depth" in sz["serving/0"]
        assert "page_utilization" in sz["serving/0"]
        assert sz["flight_recorder_armed"] is True
        assert isinstance(sz["in_flight_spans"], list)

        status, _, _ = _get(srv.url + "/nope")
    assert status == 404


def test_telemetry_statusz_shows_slot_table_mid_flight(model):
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(model, num_slots=2, page_size=PS, max_model_len=MAXLEN)
    with eng:
        srv = telemetry.serve(0)
        telemetry.add_status_provider("serving", eng._statusz)
        # deterministic mid-flight snapshot: park the scheduler INSIDE its
        # third loop iteration (after the prefill token + one decode step,
        # long before the 40-token budget) so the slot is guaranteed
        # occupied while we scrape — with warm cached programs the whole
        # request can otherwise finish between injection polls
        import threading

        release = threading.Event()
        faults.inject("serving.scheduler_wedge",
                      fn=lambda: release.wait(60), at_trips={3})
        try:
            h = eng.submit([1, 2, 3, 4, 5], max_new_tokens=40)
            t0 = time.time()
            while not faults.trip_count("serving.scheduler_wedge") \
                    and time.time() - t0 < 120:
                time.sleep(0.005)
            assert faults.trip_count("serving.scheduler_wedge"), \
                "scheduler never reached the wedge hook"
            assert h.token_ids, "no tokens before the parked iteration"
            _, _, body = _get(srv.url + "/statusz")
            rows = [s for s in json.loads(body)["serving"]["slots"] if s]
            assert rows, "slot table empty while a request is mid-decode"
            assert rows[0]["request_id"] == h.request_id
            assert rows[0]["trace_id"] == h.trace_id
            assert rows[0]["produced"] >= 1
        finally:
            release.set()
            faults.clear()
        h.cancel()


def test_metrics_endpoint_matches_registry_exporter():
    from paddle_tpu.profiler import metrics as prof_metrics

    reg = prof_metrics.get_registry()
    reg.counter("observability.test_scrape", "scrape parity probe").inc(3)
    try:
        srv = telemetry.serve(0)
        _, _, body = _get(srv.url + "/metrics")
        assert "observability_test_scrape 3" in body.decode()
    finally:
        # the registry is the process's: a probe left behind fails
        # test_metric_families_match_readme_reference on the same worker
        reg._metrics.pop("observability.test_scrape", None)


def test_fault_with_times_and_seconds_still_cancellable():
    """A times=1 fault popped on its final trip must still release its
    in-flight sleep when clear() is called."""
    import threading

    faults.inject("unit.hang", seconds=30.0, times=1)
    t0 = time.time()
    done = threading.Event()
    threading.Thread(target=lambda: (faults.maybe("unit.hang"),
                                     done.set())).start()
    time.sleep(0.1)   # the trip popped the spec and is now sleeping
    faults.clear("unit.hang")
    assert done.wait(5), "clear() must release the exhausted fault's sleep"
    assert time.time() - t0 < 5
