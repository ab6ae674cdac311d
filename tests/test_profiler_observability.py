"""Observability layer: host event tree, summary tables, scheduler
semantics, metrics registry + exporters, TrainStep accounting, collective
byte accounting, dataloader stall split, MetricsLoggerCallback, and the
bench --emit-metrics JSONL round trip.  All on the 8-device CPU mesh."""

import json
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
import paddle_tpu.profiler as profiler
from paddle_tpu.profiler import events as prof_events
from paddle_tpu.profiler import metrics as prof_metrics


def _tiny_step(b=16, din=8, ncls=4):
    paddle.seed(7)
    m = nn.Sequential(nn.Linear(din, 16), nn.ReLU(), nn.Linear(16, ncls))
    o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, o, loss_fn=nn.CrossEntropyLoss())
    x = paddle.to_tensor(np.random.RandomState(0).randn(b, din).astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(1).randint(0, ncls, (b,)).astype("int64"))
    return step, x, y


# --------------------------------------------------------------- event tree
def test_event_tree_nesting():
    col = prof_events.EventCollector().start()
    try:
        with prof_events.RecordEvent("outer"):
            with prof_events.RecordEvent("inner"):
                pass
            with prof_events.RecordEvent("inner"):
                pass
    finally:
        col.stop()
    assert len(col.roots) == 1
    outer = col.roots[0]
    assert outer.name == "outer"
    assert [c.name for c in outer.children] == ["inner", "inner"]
    assert all(c.duration <= outer.duration for c in outer.children)
    agg = col.op_summary()
    assert agg["inner"]["calls"] == 2
    assert agg["outer"]["calls"] == 1


def test_layer_and_op_events_only_when_active():
    m = nn.Linear(4, 4)
    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    m(x)  # not profiling: no collector, no events
    assert prof_events.active_collector() is None
    col = prof_events.EventCollector().start()
    try:
        m(x)
    finally:
        col.stop()
    names = [ev.name for r in col.roots for ev in r.walk()]
    assert "Linear" in names
    assert "linear" in names  # dispatch-level op under the layer region
    lin = [r for r in col.roots if r.name == "Linear"][0]
    assert any(c.name == "linear" for c in lin.children)


# ------------------------------------------------------------ summary table
def test_summary_table_from_trainstep_run(capsys):
    step, x, y = _tiny_step()
    p = profiler.Profiler()
    p.start()
    for _ in range(3):
        float(step(x, y))
        p.step(num_samples=16)
    p.stop()
    text = p.summary()
    assert "jit.train_step" in text and "Calls" in text and "Ratio (%)" in text
    # per-op rows from the traced forward appear in the table
    assert "Linear" in text or "linear" in text

    # sort orders: total desc by default; calls desc; name asc
    def rows(t):
        return [l.split()[0] for l in t.splitlines()
                if l and not l.startswith("-") and "Calls" not in l
                and "avg step" not in l]

    by_total = rows(p.summary(sorted_by="total"))
    assert by_total, "summary table must have rows"
    by_name = rows(p.summary(sorted_by="name"))
    assert by_name == sorted(by_name)
    by_calls = p.summary(sorted_by="calls")
    first_row = [l for l in by_calls.splitlines()
                 if l and not l.startswith("-") and "Calls" not in l
                 and "avg step" not in l][0]
    max_calls = max(d["calls"] for d in p._op_table().values())
    assert f" {max_calls} " in " " + " ".join(first_row.split()) + " "


# ---------------------------------------------------------------- scheduler
def test_scheduler_record_and_return_fires_on_trace_ready():
    delivered = []
    sched = profiler.make_scheduler(closed=1, ready=1, record=2, repeat=1)
    p = profiler.Profiler(scheduler=sched,
                          on_trace_ready=lambda prof: delivered.append(prof._step))
    p.start()
    for i in range(6):
        p.step()
        if i == 3:
            # the RECORD_AND_RETURN step (index 3) must have delivered as
            # soon as step() ended it — NOT at stop()
            assert delivered == [4]
    p.stop()
    assert delivered == [4], "repeat=1: exactly one cycle, delivered mid-run"


def test_make_scheduler_repeat_honored():
    s = profiler.make_scheduler(closed=1, ready=0, record=1, repeat=2)
    states = [s(i) for i in range(8)]
    assert states[1] == profiler.ProfilerState.RECORD_AND_RETURN
    assert states[3] == profiler.ProfilerState.RECORD_AND_RETURN
    # after 2 cycles: closed forever (previously repeat was ignored)
    assert all(st == profiler.ProfilerState.CLOSED for st in states[4:])


def test_export_protobuf_is_distinct_and_writes_summary(tmp_path):
    assert profiler.export_protobuf is not profiler.export_chrome_tracing
    p = profiler.Profiler(on_trace_ready=profiler.export_protobuf(str(tmp_path)))
    p.start()
    for _ in range(2):
        p.step(num_samples=4)
    p.stop()
    path = p._last_protobuf_path
    assert path and os.path.exists(path) and path.endswith("_profile_summary.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"].startswith("paddle_tpu.profiler.summary")
    assert len(data["steps"]) == 2
    assert data["steps"][0]["num_samples"] == 4


def test_step_info_skips_none_sample_steps():
    p = profiler.Profiler(timer_only=True)
    p.start()
    import time

    for i in range(4):
        time.sleep(0.01)
        # every other step reports samples; None steps must not dilute ips
        p.step(num_samples=100 if i % 2 == 0 else None)
    info = p.step_info()
    assert "avg step" in info and "samples/sec" in info
    ips = float(info.split(",")[1].split()[0])
    # 100 samples per ~10ms sampled step => ~10k/s; diluting by the None
    # steps would halve it.  Generous bounds for CI jitter.
    assert 2000 < ips < 50000
    p.stop()


def test_chrome_trace_export_and_load_roundtrip(tmp_path):
    step, x, y = _tiny_step()
    p = profiler.Profiler()
    p.start()
    float(step(x, y))
    p.step()
    p.stop()
    path = p.export(str(tmp_path / "trace.json"))
    res = profiler.load_profiler_result(path)
    assert res.events, "exported trace must carry host events"
    agg = res.op_summary()
    assert "jit.train_step" in agg
    rows = res.summary(sorted_by="total")
    assert rows[0]["total"] >= rows[-1]["total"]
    # directory form also resolves
    p2 = profiler.Profiler()
    p2.start()
    p2.stop()
    path2 = p2.export(str(tmp_path / "x_chrome_trace.json"))
    assert profiler.load_profiler_result(str(tmp_path)).path == path2


# ---------------------------------------------------------- metrics registry
def test_metrics_counter_gauge_labels():
    reg = prof_metrics.MetricsRegistry()
    c = reg.counter("requests", "total requests")
    c.inc(op="read")
    c.inc(2, op="read")
    c.inc(op="write")
    assert c.get(op="read") == 3
    assert c.get(op="write") == 1
    with pytest.raises(ValueError):
        c.labels(op="read").inc(-1)
    g = reg.gauge("temp")
    g.set(3.5, zone="a")
    g.inc(0.5, zone="a")
    assert g.get(zone="a") == 4.0
    # same name, different kind -> loud error
    with pytest.raises(TypeError):
        reg.gauge("requests")


def test_metrics_histogram_quantiles():
    reg = prof_metrics.MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in [0.005, 0.05, 0.05, 0.5, 2.0]:
        h.observe(v)
    child = h.labels()
    assert child.count == 5
    assert abs(child.sum - 2.605) < 1e-9
    assert child.quantile(0.0) == 0.005
    assert child.quantile(1.0) == 2.0
    assert child.quantile(0.5) == 0.05
    assert child.bucket_counts == [1, 2, 1, 1]


def test_prometheus_text_format_golden():
    reg = prof_metrics.MetricsRegistry()
    reg.counter("ops_total", "ops served").inc(3, op="relu")
    reg.gauge("mfu").set(0.42)
    h = reg.histogram("step_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.to_prometheus()
    expected = (
        "# HELP ops_total ops served\n"
        "# TYPE ops_total counter\n"
        'ops_total{op="relu"} 3\n'
        "# TYPE mfu gauge\n"
        "mfu 0.42\n"
        "# TYPE step_seconds histogram\n"
        'step_seconds_bucket{le="0.1"} 1\n'
        'step_seconds_bucket{le="1.0"} 2\n'
        'step_seconds_bucket{le="+Inf"} 2\n'
        "step_seconds_sum 0.55\n"
        "step_seconds_count 2\n")
    assert text == expected


def test_metrics_thread_safety():
    import threading

    reg = prof_metrics.MetricsRegistry()
    c = reg.counter("n").labels()
    h = reg.histogram("h").labels()

    def work():
        for _ in range(5000):
            c.inc()
            h.observe(0.01)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # += is not atomic in CPython; the per-child lock must not lose updates
    assert c.value == 20000
    assert h.count == 20000 and abs(h.sum - 200.0) < 1e-6
    # histograms report observed sums through the public accessors
    assert reg.get("h").total() == h.sum
    assert reg.get("h").get() == h.sum


def test_prometheus_escapes_label_values():
    reg = prof_metrics.MetricsRegistry()
    reg.counter("jobs").inc(name='run "a"\nx')
    line = [l for l in reg.to_prometheus().splitlines()
            if l.startswith("jobs{")][0]
    assert line == 'jobs{name="run \\"a\\"\\nx"} 1'


def test_export_handler_dir_honored_from_start(tmp_path):
    # the device trace must land in the handler's dir from the FIRST
    # cycle, not only after on_trace_ready first fires
    h = profiler.export_chrome_tracing(str(tmp_path))
    p = profiler.Profiler(on_trace_ready=h)
    assert p._export_dir == str(tmp_path)


def test_prometheus_sanitizes_dotted_names():
    reg = prof_metrics.MetricsRegistry()
    reg.gauge("train_step.mfu").set(0.5)
    text = reg.to_prometheus()
    # dotted registry names are illegal in the prom exposition format
    assert "train_step_mfu 0.5" in text
    assert "train_step.mfu" not in text
    # JSONL keeps the readable dotted spelling
    assert any(r["name"] == "train_step.mfu" for r in reg.collect())


def test_metrics_jsonl_roundtrip(tmp_path):
    reg = prof_metrics.MetricsRegistry()
    reg.counter("a").inc(5, kind="x")
    reg.gauge("b").set(1.5)
    path = reg.export_jsonl(str(tmp_path / "m.jsonl"))
    rows = prof_metrics.load_jsonl(path)
    by_name = {r["name"]: r for r in rows}
    assert by_name["a"]["value"] == 5 and by_name["a"]["labels"] == {"kind": "x"}
    assert by_name["b"]["value"] == 1.5 and by_name["b"]["kind"] == "gauge"
    # append mode accumulates snapshots
    reg.export_jsonl(path)
    assert len(prof_metrics.load_jsonl(path)) == 4


def test_metrics_flusher_env_gated(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_METRICS_DIR", str(tmp_path))
    prof_metrics.get_registry().gauge("flush_probe").set(1.0)
    prof_metrics.flush()
    assert os.path.exists(tmp_path / "metrics.jsonl")
    assert os.path.exists(tmp_path / "metrics.prom")
    assert "flush_probe" in (tmp_path / "metrics.prom").read_text()
    # the probe is this test's own: left in the process-wide registry it
    # fails the README drift guard where both run in one worker
    prof_metrics.get_registry()._metrics.pop("flush_probe", None)


# ------------------------------------------------------- TrainStep accounting
def test_trainstep_compile_and_retrace_counters(monkeypatch):
    reg = prof_metrics.get_registry()

    def total(name):
        m = reg.get(name)
        return m.total() if m else 0.0

    step, x, y = _tiny_step()
    compiles0, retraces0 = total("train_step.compiles"), total("train_step.retraces")
    float(step(x, y))
    assert total("train_step.compiles") == compiles0 + 1
    assert total("train_step.retraces") == retraces0
    assert reg.get("train_step.compile_seconds").get() > 0
    assert step._retrace_count == 0

    # same signature: no new compile
    float(step(x, y))
    assert total("train_step.compiles") == compiles0 + 1

    # batch-size change: retrace + loud warning
    x2 = paddle.to_tensor(np.random.RandomState(2).randn(8, 8).astype("float32"))
    y2 = paddle.to_tensor(np.random.RandomState(3).randint(0, 4, (8,)).astype("int64"))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        float(step(x2, y2))
    assert any("TrainStep retrace" in str(ww.message) for ww in w)
    assert total("train_step.retraces") == retraces0 + 1
    assert step._retrace_count == 1

    # dtype change: another retrace
    y3 = paddle.to_tensor(np.random.RandomState(3).randint(0, 4, (8,)).astype("int32"))
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("ignore")
        float(step(x2, y3))
    assert step._retrace_count == 2
    assert total("train_step.retraces") == retraces0 + 2

    assert step._donated_bytes() > 0
    assert reg.get("train_step.donated_bytes").get() > 0


def test_trainstep_cost_analysis_and_mfu(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINSTEP_COST", "1")
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e12")
    reg = prof_metrics.get_registry()
    step, x, y = _tiny_step()
    float(step(x, y))
    ca = step.cost_analysis()
    assert ca is not None and ca["flops"] > 0
    assert step._flops_per_step == ca["flops"]
    assert reg.get("train_step.flops_per_step").get() == ca["flops"]
    for _ in range(3):
        float(step(x, y))
    assert reg.get("train_step.mfu").get() > 0
    assert reg.get("train_step.achieved_tflops").get() > 0
    # step latency histogram saw the steady-state steps
    h = reg.get("train_step.step_seconds")
    assert h is not None and h.labels().count >= 2


# --------------------------------------------------------------- collectives
def test_collective_byte_accounting_eager_mesh():
    import paddle_tpu.distributed as dist

    reg = prof_metrics.get_registry()

    def total(name, **labels):
        m = reg.get(name)
        return m.get(**labels) or 0.0 if m else 0.0

    g = dist.collective.get_default_group()
    n = g.nranks
    assert n == 8, "conftest pins an 8-device CPU mesh"
    labels = {"op": "all_reduce", "phase": "eager", "nranks": n}
    calls0 = total("collective.calls", **labels)
    bytes0 = total("collective.bytes", **labels)
    v = paddle.to_tensor(np.ones((n, 4), "float32"))
    dist.all_reduce(v)
    assert total("collective.calls", **labels) == calls0 + 1
    assert total("collective.bytes", **labels) == bytes0 + n * 4 * 4
    np.testing.assert_allclose(v.numpy(), np.full((n, 4), n, "float32"))
    # latency histogram records eager dispatches
    h = reg.get("collective.latency_seconds")
    assert h is not None and h.labels(op="all_reduce").count >= 1


def test_new_group_lifecycle_metrics():
    import paddle_tpu.distributed as dist

    reg = prof_metrics.get_registry()
    g = dist.collective.new_group([0, 1, 2, 3])
    created = reg.get("collective.groups_created")
    assert created is not None and created.get(nranks=4) >= 1
    active = reg.get("collective.groups_active").get()
    dist.collective.destroy_process_group(g)
    assert reg.get("collective.groups_active").get() == active - 1


# ---------------------------------------------------------------- dataloader
def test_dataloader_stall_accounting():
    from paddle_tpu.io import DataLoader, TensorDataset

    reg = prof_metrics.get_registry()

    def total(name):
        m = reg.get(name)
        return m.total() if m else 0.0

    ds = TensorDataset([np.arange(32, dtype="float32").reshape(16, 2),
                        np.arange(16, dtype="int64")])
    loader = DataLoader(ds, batch_size=4)
    wait0, batches0 = total("dataloader.host_wait_seconds"), total("dataloader.batches")
    seen = 0
    for batch in loader:
        seen += 1
    assert seen == 4
    assert total("dataloader.batches") == batches0 + 4
    assert total("dataloader.host_wait_seconds") > wait0
    assert total("dataloader.consumer_seconds") >= 0


# ------------------------------------------------------ MetricsLoggerCallback
def test_metrics_logger_callback_fit(tmp_path, capsys):
    from paddle_tpu.callbacks import MetricsLoggerCallback
    from paddle_tpu.io import TensorDataset

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    model = paddle.Model(net)
    model.prepare(optimizer=opt.Adam(learning_rate=1e-3,
                                     parameters=net.parameters()),
                  loss=nn.CrossEntropyLoss())
    ds = TensorDataset([np.random.RandomState(0).randn(16, 4).astype("float32"),
                        np.random.RandomState(1).randint(0, 2, (16,)).astype("int64")])
    cb = MetricsLoggerCallback(log_dir=str(tmp_path))
    model.fit(ds, batch_size=8, epochs=2, verbose=0, shuffle=False,
              callbacks=[cb])
    out = capsys.readouterr().out
    assert "observability | epoch" in out
    rows = [json.loads(l) for l in
            (tmp_path / "train_metrics.jsonl").read_text().splitlines()]
    assert len(rows) == 2
    assert rows[0]["steps"] == 2 and "loss" in rows[0]
    assert rows[0]["train_step.compiles"] >= 1  # first epoch compiled
    assert rows[1]["train_step.compiles"] == 0  # second epoch reused it
    assert (tmp_path / "metrics.prom").exists()


# ---------------------------------------------------------- bench emit path
def test_bench_emit_metrics_roundtrip(tmp_path):
    import bench

    reg = prof_metrics.MetricsRegistry()
    result = {"metric": "resnet50_train_imgs_per_sec", "value": 123.4,
              "vs_baseline": 1.18,
              "roofline": {"matmul_bf16_tflops_measured": 90.1},
              "attention_pallas_vs_xla": [{"seq": 1024, "speedup": 2.5}],
              "note": "strings are skipped"}
    path = bench.emit_metrics(result, out_dir=str(tmp_path), registry=reg)
    rows = prof_metrics.load_jsonl(path)
    by_path = {r["labels"]["path"]: r["value"] for r in rows
               if r["name"] == "bench"}
    assert by_path["value"] == 123.4
    assert by_path["vs_baseline"] == 1.18
    assert by_path["roofline.matmul_bf16_tflops_measured"] == 90.1
    assert by_path["attention_pallas_vs_xla.0.speedup"] == 2.5
    assert "note" not in by_path
    assert "bench" in (tmp_path / "metrics.prom").read_text()
