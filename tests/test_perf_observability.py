"""Per-program roofline attribution, request SLO accounting, and the
bench regression gate (ISSUE 7).

Suite marker: ``perf``.  Everything here runs on the CPU mesh with tiny
models; heavyweight arms stay in the bench, not the test suite.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import faults, perf, slo, telemetry, tracing
from paddle_tpu.profiler import metrics as prof_metrics

pytestmark = pytest.mark.perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MAXLEN = 64
PS = 8


@pytest.fixture(autouse=True)
def _clean_perf_state(monkeypatch):
    """Known roofline ceilings for every test (the BENCH_r04-measured
    v5e numbers: ridge ≈ 278 FLOP/byte — far above any paged-decode
    intensity, so decode classifies bandwidth-bound exactly as the real
    chip measured), and a fresh attribution table."""
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "126.8e12")
    monkeypatch.setenv("PADDLE_HBM_GBS", "456")
    perf.reset()
    yield
    perf.reset()
    faults.clear()
    if tracing.get_tracer() is not None:
        tracing.get_tracer().stop()
    telemetry.shutdown()


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    from paddle_tpu.text.models.gpt import GPTForCausalLM

    return GPTForCausalLM(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                          num_attention_heads=2,
                          max_position_embeddings=MAXLEN).eval()


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ======================================================== program table unit
def test_program_table_record_and_derived_rates():
    t = perf.ProgramTable(registry=prof_metrics.MetricsRegistry())
    t.record("decode", 0.5, calls=10)
    t.record("decode", 0.5, calls=10)
    t.set_cost("decode", flops_per_call=1e9, bytes_per_call=1e9)
    [row] = t.snapshot()
    assert row["calls"] == 20 and row["device_seconds"] == 1.0
    # 1e9 flops x 20 calls / 1s = 20 GFLOP/s; same for bytes
    assert row["achieved_tflops"] == pytest.approx(0.02)
    assert row["achieved_gbs"] == pytest.approx(20.0)
    assert row["intensity_flop_per_byte"] == pytest.approx(1.0)
    # intensity 1 << ridge 278 -> bandwidth-bound; fraction vs 456 GB/s
    assert row["regime"] == "bandwidth-bound"
    assert row["frac_of_peak"] == pytest.approx(20e9 / 456e9)


def test_classify_regimes_and_ceiling_precedence(monkeypatch):
    # ridge = 126.8e12 / 456e9 ~ 278 FLOP/byte
    assert perf.classify(1e9, 1e9) == "bandwidth-bound"
    assert perf.classify(1e12, 1e9) == "compute-bound"
    assert perf.classify(None, 1e9) == "unknown"
    # explicit measured ceiling beats the env value
    perf.set_hbm_ceiling(1.0)  # 1 GB/s -> ridge 126800 -> everything bw-bound
    try:
        assert perf.hbm_ceiling() == pytest.approx(1e9)
        assert perf.classify(1e12, 1e9) == "bandwidth-bound"
    finally:
        perf.set_hbm_ceiling(None)
    assert perf.hbm_ceiling() == pytest.approx(456e9)
    monkeypatch.delenv("PADDLE_HBM_GBS")
    # CPU mesh, no datasheet entry, no override -> unknown regime
    assert perf.hbm_ceiling() is None
    assert perf.classify(1e9, 1e9) == "unknown"


def test_report_names_top_candidates():
    t = perf.ProgramTable(registry=prof_metrics.MetricsRegistry())
    t.record("decode", 2.0, calls=100)
    t.record("prefill/64", 0.5, calls=4)
    t.set_cost("decode", 1e9, 1e9)            # bandwidth-bound
    t.set_cost("prefill/64", 1e13, 1e9)       # compute-bound
    rep = t.report(top=2, resolve=False)
    assert "decode" in rep and "prefill/64" in rep
    # sorted by device time: decode is candidate #1
    assert rep.index("1. decode") < rep.index("2. prefill/64")
    assert "HBM-bound" in rep and "compute-bound" in rep


def test_resolve_costs_runs_thunks_once_and_keeps_errors():
    t = perf.ProgramTable(registry=prof_metrics.MetricsRegistry())
    calls = []
    t.record("good", 1.0)
    t.register_cost_thunk("good", lambda: (calls.append(1), (2e9, 4e9))[1])
    t.record("bad", 1.0)

    def boom():
        raise RuntimeError("no cost for you")

    t.register_cost_thunk("bad", boom)
    t.resolve_costs()
    t.resolve_costs()  # idempotent: thunks consumed, errors not retried
    assert calls == [1]
    rows = {r["program"]: r for r in t.snapshot()}
    assert rows["good"]["flops_per_call"] == pytest.approx(2e9)
    assert rows["good"]["intensity_flop_per_byte"] == pytest.approx(0.5)
    assert rows["bad"]["cost"].startswith("error:")


# ================================================================= SLO unit
def test_slo_policy_evaluate_all_checks():
    pol = slo.SLOPolicy(ttft_s=1.0, itl_s=0.5, e2e_s=10.0)
    tl = slo.RequestTimeline(submitted_at=0.0,
                             token_times=(0.5, 0.8, 1.2), finished_at=1.3)
    rep = pol.evaluate(tl)
    assert rep.met and rep.good_tokens == 3 and rep.itl_violations == 0
    assert rep.ttft == pytest.approx(0.5)
    # TTFT miss
    rep = pol.evaluate(slo.RequestTimeline(0.0, (1.5, 1.6), 1.7))
    assert not rep.met and not rep.ttft_ok and rep.good_tokens == 0
    # one slow inter-token gap
    rep = pol.evaluate(slo.RequestTimeline(0.0, (0.5, 1.4, 1.5), 1.6))
    assert not rep.met and rep.itl_violations == 1
    assert rep.itl_max == pytest.approx(0.9)
    # e2e miss
    rep = pol.evaluate(slo.RequestTimeline(0.0, (0.5, 0.9), 11.0))
    assert not rep.met and not rep.e2e_ok
    # unconfigured checks never fail
    rep = slo.SLOPolicy().evaluate(slo.RequestTimeline(0.0, (9.0,), 9.5))
    assert rep.met


def test_slo_window_rates_formula():
    rows = [(0.0, 2.0, 10, 10, True), (1.0, 4.0, 10, 0, False)]
    rates = slo.SLOAccountant.window_rates(rows, objective=0.9)
    assert rates["attainment"] == pytest.approx(0.5)
    assert rates["burn_rate"] == pytest.approx(0.5 / 0.1)
    assert rates["window_span_s"] == pytest.approx(4.0)
    assert rates["tokens_per_sec"] == pytest.approx(20 / 4.0)
    assert rates["goodput_tokens_per_sec"] == pytest.approx(10 / 4.0)


def test_slo_histogram_buckets_align_with_targets():
    edges = slo.slo_histogram_buckets((0.01, 0.1, 1.0), 0.2)
    assert {0.1, 0.2, 0.4}.issubset(edges)
    assert edges == tuple(sorted(edges))


def test_histogram_buckets_configurable_per_metric():
    reg = prof_metrics.MetricsRegistry()
    h = reg.histogram("t.lat", buckets=(0.1, 1.0))
    assert h.buckets == (0.1, 1.0)
    # a second caller's edges MERGE (two engines with different SLO
    # thresholds both keep their alignment), unobserved children rebuilt
    h2 = reg.histogram("t.lat", buckets=(0.05, 0.2, 1.0))
    assert h2 is h and h.buckets == (0.05, 0.1, 0.2, 1.0)
    h.observe(0.15)
    # re-edge after observations: observed child keeps its edges, loudly
    with pytest.warns(UserWarning, match="cannot be rebinned"):
        h.set_buckets((0.5,))
    c = h.labels()
    assert c.buckets == (0.05, 0.1, 0.2, 1.0) and c.count == 1
    # fresh child (new labelset) uses the new edges
    assert h.labels(replica="9").buckets == (0.5,)


# =================================================== engine attribution e2e
def test_engine_program_table_and_decode_bandwidth_bound(model):
    """The acceptance shape: after a serving run with two prefill buckets,
    the table shows >=3 program families with device time, the decode
    family resolves cost_analysis and classifies bandwidth-bound (as
    BENCH_r04 measured), and /statusz serves the table."""
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, telemetry_port=0)
    rs = np.random.RandomState(0)
    with eng:
        # two requests per prefill bucket: the second dispatch of each
        # family is warm and lands in the table (compiles are excluded)
        for S0 in (5, 17, 5, 17):
            eng.generate(rs.randint(1, 90, (S0,)), max_new_tokens=6,
                         timeout=600)
        rows = {r["program"]: r for r in perf.snapshot(resolve=True)}
        with_time = [f for f, r in rows.items()
                     if r["calls"] > 0 and r["device_seconds"] > 0]
        assert {"prefill/8", "prefill/24", "decode"}.issubset(set(with_time))
        assert len(with_time) >= 3
        dec = rows["decode"]
        assert dec["flops_per_call"] and dec["bytes_per_call"]
        assert dec["achieved_gbs"] > 0
        assert dec["regime"] == "bandwidth-bound"
        assert 0 < dec["frac_of_peak"] < 1
        # prefill buckets resolved too, and are also HBM-bound here
        assert rows["prefill/8"]["regime"] == "bandwidth-bound"

        # the /statusz program table (costs already resolved above)
        srv = telemetry.get_server()
        code, body = _get(srv.url + "/statusz")
        assert code == 200
        sz = json.loads(body)["perf_programs"]
        assert sz["hbm_gbs"] == pytest.approx(456.0)
        progs = {p["program"]: p for p in sz["programs"]}
        assert {"prefill/8", "prefill/24", "decode"}.issubset(progs)
        assert progs["decode"]["regime"] == "bandwidth-bound"
        assert progs["decode"]["achieved_gbs"] > 0
        # sorted by total device time, descending
        times = [p["device_seconds"] for p in sz["programs"]]
        assert times == sorted(times, reverse=True)
    # perf.program.* metrics exported
    reg = prof_metrics.get_registry()
    assert reg.get("perf.program.calls").get(program="decode") > 0
    assert reg.get("perf.program.device_seconds").get(program="decode") > 0
    assert reg.get("perf.program.achieved_gbs").get(program="decode") > 0

    rep = perf.report(resolve=False)
    assert "decode" in rep and "bandwidth-bound" in rep
    assert "Top kernel/fusion candidates" in rep


def test_train_step_variants_attributed():
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt

    paddle.seed(0)
    m = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 8))
    o = opt.Momentum(learning_rate=0.01, momentum=0.9,
                     parameters=m.parameters())
    step = paddle.jit.TrainStep(m, o, loss_fn=nn.CrossEntropyLoss())
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(8, 16).astype("float32"))
    y = paddle.to_tensor(rs.randint(0, 8, (8,)).astype("int64"))
    for _ in range(4):
        step(x, y)
    fam = next(iter(step._compiled.values()))._perf_family
    assert fam.startswith("train_step/t") and fam.endswith(".v0")
    rows = {r["program"]: r for r in perf.snapshot(resolve=True)}
    st = rows[fam]
    assert st["calls"] >= 2 and st["device_seconds"] > 0
    assert st["flops_per_call"] > 0 and st["bytes_per_call"] > 0
    assert st["regime"] in ("bandwidth-bound", "compute-bound")
    # a SECOND TrainStep over a different model gets its own family —
    # its stats and cost_analysis never fold into the first's
    m2 = nn.Sequential(nn.Linear(16, 8))
    o2 = opt.Momentum(learning_rate=0.01, momentum=0.9,
                      parameters=m2.parameters())
    step2 = paddle.jit.TrainStep(m2, o2, loss_fn=nn.CrossEntropyLoss())
    for _ in range(3):
        step2(x, y)
    fam2 = next(iter(step2._compiled.values()))._perf_family
    assert fam2 != fam
    rows = {r["program"]: r for r in perf.snapshot()}
    assert rows[fam2]["calls"] >= 1


# ========================================================== engine SLO e2e
def test_engine_slo_gauges_byte_consistent_with_timelines(model):
    """Mixed greedy/temperature batch: every exported SLO gauge/counter
    equals the value recomputed from the raw per-request timelines."""
    from paddle_tpu.serving import ServingEngine, SLOPolicy

    pol = SLOPolicy(ttft_s=120.0, itl_s=60.0, e2e_s=600.0, objective=0.9,
                    window=32)
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, slo=pol, replica="slo_t1")
    rs = np.random.RandomState(1)
    with eng:
        handles = [
            eng.submit(rs.randint(1, 90, (6,)), max_new_tokens=8),
            eng.submit(rs.randint(1, 90, (6,)), max_new_tokens=5,
                       temperature=0.8),
            eng.submit(rs.randint(1, 90, (10,)), max_new_tokens=7,
                       temperature=0.6),
            eng.submit(rs.randint(1, 90, (4,)), max_new_tokens=6),
        ]
        for h in handles:
            h.result(timeout=600)

    reps = [pol.evaluate(slo.timeline_of(h)) for h in handles]
    rows = [(h.submitted_at, h.finished_at, r.tokens, r.good_tokens, r.met)
            for h, r in zip(handles, reps)]
    want = slo.SLOAccountant.window_rates(rows, pol.objective)

    reg = prof_metrics.get_registry()

    def g(name):
        return reg.get(name).get(replica="slo_t1")

    assert g("serving.slo.attainment") == want["attainment"]
    assert g("serving.slo.burn_rate") == want["burn_rate"]
    assert g("serving.slo.goodput_tokens_per_sec") == \
        want["goodput_tokens_per_sec"]
    assert g("serving.slo.tokens_per_sec") == want["tokens_per_sec"]
    assert g("serving.slo.tokens") == sum(r.tokens for r in reps)
    met_n = sum(1 for r in reps if r.met)
    assert reg.get("serving.slo.requests").get(
        replica="slo_t1", met="true") == (met_n or None)
    if met_n < len(reps):
        assert reg.get("serving.slo.requests").get(
            replica="slo_t1", met="false") == len(reps) - met_n
    assert g("serving.slo.good_tokens") == \
        (sum(r.good_tokens for r in reps) or None)
    # generous targets on an idle box: everything should have met
    assert want["attainment"] == 1.0
    assert want["goodput_tokens_per_sec"] == want["tokens_per_sec"] > 0

    acct = eng.slo_accountant
    s = acct.summary()
    assert s["evaluated"] == len(handles) and s["met"] == met_n
    assert s["window"]["attainment"] == want["attainment"]


def test_engine_slo_impossible_target_burns_budget(model):
    from paddle_tpu.serving import ServingEngine, SLOPolicy

    pol = SLOPolicy(ttft_s=1e-9, objective=0.9)
    # num_slots=2 on purpose: shares the module's compiled program family
    # instead of minting a num_slots=1 pool-shape variant
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, slo=pol, replica="slo_t2")
    with eng:
        eng.generate([1, 2, 3], max_new_tokens=4, timeout=600)
    reg = prof_metrics.get_registry()
    assert reg.get("serving.slo.attainment").get(replica="slo_t2") == 0.0
    assert reg.get("serving.slo.burn_rate").get(replica="slo_t2") == \
        pytest.approx(1.0 / (1.0 - 0.9))
    assert reg.get("serving.slo.goodput_tokens_per_sec").get(
        replica="slo_t2") == 0.0
    assert reg.get("serving.slo.requests").get(
        replica="slo_t2", met="false") == 1


def test_slo_aligned_histogram_buckets_answer_target_fraction(model):
    """With an SLO set, the ttft/itl histograms carry the exact threshold
    as a bucket edge — the satellite's 'fraction under target from
    Prometheus alone'."""
    from paddle_tpu.serving import ServingEngine, SLOPolicy

    pol = SLOPolicy(ttft_s=33.0, itl_s=7.5)
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, slo=pol, replica="slo_t3")
    with eng:
        eng.generate([1, 2, 3, 4], max_new_tokens=4, timeout=600)
    reg = prof_metrics.get_registry()
    ttft = reg.get("serving.ttft_seconds").labels(replica="slo_t3")
    itl = reg.get("serving.inter_token_seconds").labels(replica="slo_t3")
    assert 33.0 in ttft.buckets and 16.5 in ttft.buckets
    assert 7.5 in itl.buckets and 3.75 in itl.buckets and 15.0 in itl.buckets
    # and the Prometheus rendering exposes the edge
    srv = telemetry.serve(0)
    code, body = _get(srv.url + "/metrics")
    assert code == 200
    assert 'serving_ttft_seconds_bucket{le="33.0",replica="slo_t3"}' \
        in body.decode()


# =========================================== telemetry under load (locking)
def test_scrape_bounded_while_engine_mid_decode_and_locked(model):
    """Regression guard for the PR-3 signal-path rule: a /metrics +
    /statusz scrape completes in bounded time while the engine is parked
    mid-iteration AND the test thread holds the engine's scheduler lock —
    i.e. no provider takes that lock across a render."""
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, telemetry_port=0)
    with eng:
        srv = telemetry.get_server()
        release = threading.Event()
        faults.inject("serving.scheduler_wedge",
                      fn=lambda: release.wait(60), at_trips={3})
        try:
            h = eng.submit([1, 2, 3, 4, 5], max_new_tokens=40)
            t0 = time.time()
            while not faults.trip_count("serving.scheduler_wedge") \
                    and time.time() - t0 < 120:
                time.sleep(0.005)
            assert faults.trip_count("serving.scheduler_wedge")
            with eng._lock:  # the scheduler/admission lock, held by US
                t0 = time.time()
                code_s, body_s = _get(srv.url + "/statusz")
                code_m, body_m = _get(srv.url + "/metrics")
                elapsed = time.time() - t0
            assert code_s == 200 and code_m == 200
            assert elapsed < 5.0, f"scrape took {elapsed:.1f}s under lock"
            sz = json.loads(body_s)
            assert "perf_programs" in sz  # the table renders mid-flight too
            assert sz["serving/0"]["active_slots"] >= 1
        finally:
            release.set()
            faults.clear()
        h.cancel()
    # the scrape timed itself
    reg = prof_metrics.get_registry()
    c = reg.get("telemetry.scrape_seconds")
    assert c.get(path="/statusz") is not None
    assert c.get(path="/metrics") is not None


# ====================================================== cluster SLO + spans
def test_cluster_slo_and_route_decision_span_attrs(model, tmp_path):
    """Cluster-wide SLO accounting on the outer handles, and the
    RouteDecision riding cluster.route spans as real attributes in the
    OTLP export (the failover-forensics satellite)."""
    from paddle_tpu.serving import ServingCluster, SLOPolicy

    pol = SLOPolicy(ttft_s=120.0, itl_s=60.0, objective=0.9)
    tr = tracing.Tracer().start()
    cluster = ServingCluster(model, replicas=2, num_slots=2, page_size=PS,
                             max_model_len=MAXLEN, slo=pol,
                             name="perftest", replica_prefix="pf")
    rs = np.random.RandomState(2)
    with cluster:
        handles = [cluster.submit(rs.randint(1, 90, (6,)), max_new_tokens=4)
                   for _ in range(3)]
        for h in handles:
            h.result(timeout=600)
        # scrape-safety under the CLUSTER lock too (stats() is lockless)
        srv = telemetry.serve(0)
        with cluster._lock:
            t0 = time.time()
            code, body = _get(srv.url + "/statusz")
            elapsed = time.time() - t0
        assert code == 200 and elapsed < 5.0
        sz = json.loads(body)["cluster/perftest"]
        assert sz["slo"]["window"]["attainment"] == 1.0
    tr.stop()

    # cluster accountant consistent with the outer timelines
    reps = [pol.evaluate(slo.timeline_of(h)) for h in handles]
    rows = [(h.submitted_at, h.finished_at, r.tokens, r.good_tokens, r.met)
            for h, r in zip(handles, reps)]
    want = slo.SLOAccountant.window_rates(rows, pol.objective)
    reg = prof_metrics.get_registry()
    assert reg.get("serving.slo.attainment").get(cluster="perftest") \
        == want["attainment"] == 1.0
    assert reg.get("serving.slo.goodput_tokens_per_sec").get(
        cluster="perftest") == want["goodput_tokens_per_sec"]

    spans = tr.find("cluster.route")
    assert len(spans) == 3
    for s in spans:
        assert {"affine", "hit", "reason", "policy",
                "replica"}.issubset(s.attrs)
        assert isinstance(s.attrs["hit"], bool)
        assert s.attrs["policy"] == "affinity"

    # the decision fields survive OTLP export as real span attributes
    path = tr.export_otlp(str(tmp_path / "otlp.json"))
    doc = json.load(open(path))
    otlp = [sp for sp in
            doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
            if sp["name"] == "cluster.route"]
    assert len(otlp) == 3
    keys = {a["key"] for a in otlp[0]["attributes"]}
    assert {"affine", "hit", "reason", "policy", "replica"}.issubset(keys)
    hit_attr = next(a for a in otlp[0]["attributes"] if a["key"] == "hit")
    assert "boolValue" in hit_attr["value"]


# ============================================================ regression gate
def _run_gate(*args):
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        *args], capture_output=True, text=True, cwd=REPO)
    line = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else "{}"
    return r.returncode, json.loads(line)


def _bench_result(scale):
    """A ``python bench.py`` result line in the shape main() prints, with
    SYNTHETIC values (``scale`` moves the throughput-like leaves)."""
    return {
        "metric": "resnet50_train_imgs_per_sec", "value": 2000.0 * scale,
        "vs_baseline": 1.0, "device_kind": "synthetic",
        "roofline": {"matmul_bf16_tflops_measured": 100.0,
                     "hbm_gbs_measured": 400.0},
        "resnet50_mfu": {"mfu_vs_peak": 0.25},
        "step_cost_fw_vs_raw": {
            "resnet_fw": {"gflops": 3000.0, "gbytes_accessed": 50.0},
            "resnet_raw": {"gflops": 3000.0, "gbytes_accessed": 50.0}},
        "batch_sweep": {"b256_imgs_per_sec": 2000.0 * scale,
                        "b256_vs_baseline": 1.0},
        "bert_base_finetune": {
            "metric": "ernie3_base_ft_samples_per_sec",
            "value": 800.0 * scale, "vs_baseline": 1.0,
            "mfu": {"achieved_tflops": 60.0, "mfu_vs_peak": 0.3}},
        "allreduce": {"value": None, "n_devices": 1},
        "attention_pallas_vs_xla": [
            {"seq": sq, "speedup_fwd": 1.5, "speedup_fwdbwd": 1.5}
            for sq in (1024, 2048, 4096)],
        "decode_gpt_base": {"dense_cache": 200.0 * scale,
                            "paged_cache": 200.0 * scale,
                            "paged_vs_dense": 1.0},
    }


def _driver_artifact(path, result):
    """The driver's wrapper around one bench run: it keeps only the LAST
    bytes of the output, so the JSON line arrives head-truncated (cut here
    inside step_cost_fw_vs_raw, mid-key) and ``parsed`` is null."""
    text = json.dumps(result)
    cut = text.index('"resnet_raw"') + 5
    path.write_text(json.dumps({"n": 0, "cmd": "python bench.py", "rc": 0,
                                "tail": text[cut:], "parsed": None}))
    return str(path)


def test_check_regressions_truncated_trajectory_passes(tmp_path):
    base = _driver_artifact(tmp_path / "base.json", _bench_result(1.0))
    cur = _driver_artifact(tmp_path / "cur.json", _bench_result(1.25))
    rc, verdict = _run_gate("--check-regressions", base, "--current", cur)
    assert rc == 0
    assert verdict["pass"] is True and verdict["checked"] >= 8
    assert verdict["regressions"] == []
    # the driver artifacts are head-truncated tails: recovery is flagged
    assert verdict["baseline_recovered_partial"] is True
    by_name = {r["metric"]: r for r in verdict["results"]}
    assert by_name["bert_base_finetune.value"]["status"] == "ok"
    assert by_name["bert_base_finetune.value"]["baseline"] == 800.0
    assert by_name["bert_base_finetune.value"]["current"] == 1000.0
    # leaves whose path prefix went with the head are not gated under a
    # shorter, aliased path
    assert "value" not in by_name and "vs_baseline" not in by_name


def test_check_regressions_catches_injected_regression(tmp_path):
    import bench

    base = _driver_artifact(tmp_path / "base.json", _bench_result(1.0))
    m, meta = bench.load_bench_metrics(base)
    assert meta["complete"] is False
    bad = {"bert_base_finetune": {
        "value": m["bert_base_finetune.value"] * 0.8,   # injected -20%
        "vs_baseline": m["bert_base_finetune.vs_baseline"],
        "mfu": {"mfu_vs_peak": m["bert_base_finetune.mfu.mfu_vs_peak"]}}}
    p = tmp_path / "current.json"
    p.write_text(json.dumps(bad))
    rc, verdict = _run_gate("--check-regressions", base,
                            "--current", str(p))
    assert rc == 1
    assert verdict["pass"] is False
    assert "bert_base_finetune.value" in verdict["regressions"]
    # a wide-open tolerance waves the same delta through
    rc, verdict = _run_gate("--check-regressions", base,
                            "--current", str(p), "--tolerance", "0.5")
    assert rc == 0 and verdict["pass"] is True


def test_check_regressions_nothing_comparable_is_an_error(tmp_path):
    p = tmp_path / "empty.json"
    p.write_text(json.dumps({"unrelated": 1.0}))
    rc, verdict = _run_gate("--check-regressions", str(p),
                            "--current", str(p))
    assert rc == 2 and "error" in verdict


def test_builtin_spec_subset_of_perf_baselines():
    """The builtin emergency fallback must never drift from the
    authoritative perf_baselines.json."""
    import bench

    with open(os.path.join(REPO, "perf_baselines.json")) as f:
        authoritative = json.load(f)["metrics"]
    for name, spec in bench._DEFAULT_METRIC_SPECS.items():
        assert name in authoritative, name
        auth = authoritative[name]
        for k, v in spec.items():
            assert auth[k] == v, (name, k)


def test_tail_recovery_drops_truncated_prefix_subtree(tmp_path):
    import bench

    doc = {"metric": "x", "value": 12.5,
           "nested": {"deep": {"a": 1.0, "value": 2.0}, "c": 3.0},
           "arr": [{"s": 4.0}, {"s": 5.0}], "last": 6.0}
    text = json.dumps(doc)
    # cut INSIDE the deep dict (mid-key), like the driver's tail clipping
    cut = text.index('"value": 2.0') - 1
    obj, complete = bench._recover_tail_json(text[cut:])
    assert complete is False
    p = tmp_path / "trunc.json"
    p.write_text(json.dumps({"n": 1, "tail": text[cut:]}))
    flat, meta = bench.load_bench_metrics(str(p))
    assert meta["complete"] is False
    # true top-level keys after the cut survive with correct paths...
    assert flat["arr.0.s"] == 4.0 and flat["arr.1.s"] == 5.0
    assert flat["last"] == 6.0
    # ...but the truncated subtree is EXCLUDED: its "value": 2.0 lost the
    # "nested.deep" prefix and must not alias the top-level gate metric
    # "value" (12.5, itself lost with the head)
    assert "value" not in flat and "c" not in flat
    # an intact one-line result parses completely
    obj, complete = bench._recover_tail_json("noise\n" + text + "\n")
    assert complete is True and obj == doc


def test_generate_decode_family_recorded(model):
    """The generate() path attributes its pipelined loop per token."""
    ids = paddle.to_tensor(np.asarray([[3, 5, 7, 9]], dtype="int64"))
    model.generate(ids, max_new_tokens=6, temperature=0.0,
                   cache_impl="paged", page_size=PS, max_len=32)
    model.generate(ids, max_new_tokens=6, temperature=0.0,
                   cache_impl="paged", page_size=PS, max_len=32)  # warm
    rows = {r["program"]: r for r in perf.snapshot()}
    gd = rows.get("generate.decode")
    assert gd is not None
    assert gd["calls"] == 6 and gd["device_seconds"] > 0
