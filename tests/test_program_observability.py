"""Program-lifecycle observability (ISSUE 16): the compile ledger,
cold-start TTFT forensics and warmup manifests.

Suite marker: ``progs``.  The in-budget tests share ONE compiled tiny
engine (module fixture) plus pure-unit ledger/manifest checks; the
engine-family matrix (int8 / chunked / speculative / mp) compiles fresh
engines and is marked ``slow``.
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
from paddle_tpu.observability import (
    flight_recorder, programs, telemetry,
)
from paddle_tpu.observability.programs import WarmupManifest
from paddle_tpu.profiler import metrics as prof_metrics
from paddle_tpu.text.models._decode import program_store

pytestmark = pytest.mark.progs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MAXLEN = 64
PS = 8
PROMPT = [1, 2, 3, 4]


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    from paddle_tpu.text.models.gpt import GPTForCausalLM

    return GPTForCausalLM(vocab_size=96, hidden_size=32,
                          num_hidden_layers=2, num_attention_heads=2,
                          max_position_embeddings=MAXLEN).eval()


@pytest.fixture(autouse=True)
def _flight_dir(tmp_path):
    rec = flight_recorder.get_flight_recorder()
    old_dir, old_last = rec.dir, rec.last_dump_path
    rec.dir = str(tmp_path / "flight")
    yield
    rec.dir, rec.last_dump_path = old_dir, old_last
    telemetry.shutdown()


@pytest.fixture(scope="module")
def model():
    return _tiny_gpt()


@pytest.fixture(scope="module")
def engine(model):
    """ONE compiled tiny engine shared by the in-budget tests.  The
    ledger is reset FIRST so this module's rows account exactly this
    store; the cold first request's handle is kept for the TTFT
    decomposition tests."""
    programs.ledger().reset()
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN)
    with eng:
        h = eng.submit(PROMPT, max_new_tokens=6)
        ids = h.result(timeout=600)
        eng._test_cold_handle = h
        eng._test_cold_ids = list(ids)
        yield eng


# ======================================================= unit: keys/manifest
def test_key_encode_decode_roundtrip():
    keys = [
        ("serve_step", 2, 8, (2, 17, 8, 2, 16), "float32", (0, 1.0)),
        ("prefill", 32, ("mp", 2), None, True),
        ("decode", 1, 64, "bf16"),
    ]
    for k in keys:
        assert programs.decode_key(programs.encode_key(k)) == k
    with pytest.raises(TypeError):
        programs.encode_key(("x", object()))


def test_manifest_json_roundtrip(tmp_path):
    keys = [("serve_step", 2, 8), ("prefill", 32)]
    m = WarmupManifest(keys, meta={"adapter": {"n": 1}})
    p = m.save(tmp_path / "man.json")
    m2 = WarmupManifest.load(p)
    assert m2.keys == [tuple(k) for k in keys]
    assert m2.meta == {"adapter": {"n": 1}}
    assert len(m2) == 2 and list(m2) == m2.keys


def test_manifest_rejects_wrong_schema():
    with pytest.raises(ValueError, match="schema"):
        WarmupManifest.from_json({"schema": "something/else", "keys": []})


def test_manifest_capture_skips_unencodable(model):
    store = program_store(model)
    bad = ("bad_key", object())
    store[bad] = (None, [0])
    try:
        m = WarmupManifest.capture(model)
        assert bad not in m.keys
        assert any("bad_key" in s for s in m.meta.get("skipped", []))
        assert all(isinstance(k, tuple) for k in m.keys)
    finally:
        del store[bad]


# ==================================================== unit: windows/watchdog
def test_compile_window_drives_engine_flag_and_gauge():
    led = programs.ledger()
    reg = prof_metrics.get_registry()

    class FakeEngine:
        _compiling = False

    e = FakeEngine()
    assert not led.compiling(e)
    win = led.compile_window(("unit_win", 1), family="unit", replica="u",
                             engine=e, cold=True)
    try:
        assert e._compiling is True
        assert led.compiling(e) and led.compiling()
        assert led.in_progress() >= 1
        g = reg.get("programs.compile_in_progress").labels(replica="u")
        assert g.value >= 1
    finally:
        win.close(traced=False)
    assert e._compiling is False
    assert not led.compiling(e)
    assert reg.get("programs.compile_in_progress").labels(
        replica="u").value == 0
    # traced=False: no ledger row was minted for the key
    assert led.entry(("unit_win", 1)) is None
    # close is idempotent
    win.close(traced=True)
    assert led.entry(("unit_win", 1)) is None


def test_warm_window_is_noop_singleton():
    led = programs.ledger()
    w1 = led.compile_window(("k",), family="f", cold=False)
    w2 = led.compile_window(("k2",), family="f", cold=False)
    assert w1 is w2
    w1.attach(None, None)  # all no-ops
    w1.close()
    assert led.in_progress() == 0


def test_watchdog_consults_ledger_not_stale_flag():
    """The watchdog's compile suppression reads the ledger, so an engine
    flag wedged True (the pre-ledger failure mode) cannot silence it."""
    from paddle_tpu.observability import watchdog as wd

    led = programs.ledger()

    class FakeEngine:
        _compiling = True  # stale — no window is actually open

    e = FakeEngine()
    assert not led.compiling(e)
    src = wd.__file__
    with open(src) as f:
        body = f.read()
    assert "ledger().compiling" in body  # the monitor consults the ledger


def test_ttft_billing_skips_post_first_token_handles():
    """A stall AFTER a request's first token is ITL, not TTFT: only
    pre-first-token waiters accumulate compile_s."""
    led = programs.ledger()

    class H:
        first_token_at = None
        compile_s = 0.0
        trace_id = "payer"

    fresh, served = H(), H()
    served.first_token_at = time.time()
    led.record_compile(("unit_bill",), 1.5, family="unit",
                       handles=(fresh, served))
    assert fresh.compile_s == pytest.approx(1.5)
    assert served.compile_s == 0.0
    ent = led.entry(("unit_bill",))
    assert ent.trace_id == "payer"
    assert ent.compile_s == pytest.approx(1.5)


def test_cold_start_flight_dump_once_per_episode(tmp_path):
    led = programs.ledger()
    old = led.budget_s
    led.budget_s = 0.01
    try:
        d0 = led.cold_dumps
        led.record_compile(("unit_dump",), 5.0, family="unit")
        led.record_compile(("unit_dump",), 5.0, family="unit")  # same episode
        assert led.cold_dumps == d0 + 1
        path = flight_recorder.get_flight_recorder().last_dump_path
        assert path and os.path.exists(path)
        body = open(path).read()
        assert "cold_start" in body and "unit_dump" in body
    finally:
        led.budget_s = old


# ============================================================ ledger: engine
def test_ledger_accounts_every_store_key(engine, model):
    led = programs.ledger()
    store = program_store(model)
    rows = led.rows(store=store)
    assert len(store) == 2  # prefill bucket + decode step
    row_keys = {r["key"] for r in rows}
    for k in store:
        assert repr(k) in row_keys
    for r in rows:
        assert r["family"]
        assert r["kind"] == "serving"
        assert r["cold"] == "cold"
        assert r["compile_s"] is not None and r["compile_s"] > 0
        assert r["device"]
    fams = {r["family"] for r in rows}
    assert engine._decode_family() in fams


def test_cold_ttft_decomposition_sums(engine):
    h = engine._test_cold_handle
    bd = h.ttft_breakdown()
    assert bd["cold"] is True
    assert bd["compile_s"] > 0
    assert bd["queue_s"] >= 0 and bd["prefill_s"] >= 0
    assert bd["queue_s"] + bd["compile_s"] + bd["prefill_s"] == \
        pytest.approx(bd["ttft_s"], abs=1e-9)
    assert bd["trace_id"] == h.trace_id
    # the ledger knows who paid: some row carries this request's trace id
    led = programs.ledger()
    payers = {r["trace_id"] for r in led.rows()}
    assert h.trace_id in payers


def test_warm_request_pays_nothing(engine, model):
    led = programs.ledger()
    store = program_store(model)
    t0 = engine.program_traces()
    rows0 = len(led.rows(store=store))
    h = engine.submit(PROMPT, max_new_tokens=4)
    h.result(timeout=600)
    assert engine.program_traces() == t0      # zero new traces
    assert len(led.rows(store=store)) == rows0
    bd = h.ttft_breakdown()
    assert bd["cold"] is False and bd["compile_s"] == 0.0


def test_ttft_cold_histogram_labels_cold_requests(engine):
    reg = prof_metrics.get_registry()
    cold = reg.get("serving.ttft_cold_seconds").labels(replica="0")
    warm_total = reg.get("serving.ttft_seconds").labels(replica="0")
    # exactly the compile-paying request(s) land in the cold family
    assert 1 <= cold.count < warm_total.count


def test_programs_metrics_exported(engine):
    reg = prof_metrics.get_registry()
    fam = engine._decode_family()
    assert reg.get("programs.compiled_total").labels(
        family=fam, replica="0").value >= 1
    assert reg.get("programs.compile_seconds").labels(
        family=fam, replica="0").value > 0
    # the decode-step stall had waiting requests -> stall_seconds too
    assert reg.get("programs.stall_seconds").labels(
        family=fam, replica="0").value > 0


def test_statusz_programs_section(engine, model):
    srv = telemetry.serve(0)
    code, body = _get(srv.url + "/statusz")
    assert code == 200
    sec = json.loads(body)["programs"]
    assert sec["entries"] >= 2
    assert sec["store_size"] >= 2
    assert sec["cold_starts"] >= 2
    assert sec["compile_in_progress"] == 0
    assert sec["compile_seconds_total"] > 0
    row_keys = {r["key"] for r in sec["programs"]}
    for k in program_store(model):       # every live key accounted
        assert repr(k) in row_keys
    # sorted by compile seconds, most expensive first
    cs = [r["compile_s"] or 0.0 for r in sec["programs"]]
    assert cs == sorted(cs, reverse=True)


def test_scrape_bounded_under_open_compile_window(engine):
    """PR-3 rule: /statusz and /metrics render in bounded time while a
    compile window is open — and the open window is VISIBLE."""
    srv = telemetry.serve(0)
    led = programs.ledger()
    win = led.compile_window(("scrape_probe",), family="probe",
                             replica="probe", cold=True)
    try:
        t0 = time.time()
        code_s, body_s = _get(srv.url + "/statusz")
        code_m, body_m = _get(srv.url + "/metrics")
        elapsed = time.time() - t0
        assert code_s == 200 and code_m == 200
        assert elapsed < 5.0, f"scrape took {elapsed:.1f}s under compile"
        sec = json.loads(body_s)["programs"]
        assert sec["compile_in_progress"] >= 1
        assert "programs_compile_in_progress" in body_m.decode()
    finally:
        win.close(traced=False)


def test_analysis_resolves_off_scrape_path(engine, model):
    """The build's seconds are on the row since its window closed (the
    suite's persistent cache is off: every executable was compiled);
    what resolves on demand is size, flops and bytes, and no seconds."""
    led = programs.ledger()
    store = program_store(model)
    measured = [r for r in led.rows(store=store) if "backend_compile_s" in r]
    assert len(measured) == len(store), measured
    for r in measured:
        assert r["backend_compile_s"] > 0 and r["trace_s"] > 0
        assert r["lower_s"] > 0 and r["cache_load_s"] == 0
        assert r["cache_hit"] is False
        assert r["trace_s"] + r["lower_s"] + r["backend_compile_s"] \
            <= r["compile_s"]
    assert led.resolve_analysis() >= 1
    resolved = [r for r in led.rows(store=store) if "flops" in r]
    assert resolved
    for r, was in zip(resolved, measured):
        assert r["flops"] is None or r["flops"] >= 0
        assert r["backend_compile_s"] == was["backend_compile_s"]


# ======================================= the build record: what JAX reported
def _fresh_jit(scale=2.0, name="probe_fn"):
    """A jitted function no cache of this process has seen: a new function
    object every call (the same HLO for the same ``scale``)."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        return jnp.tanh(x @ x.T).sum() * scale
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _x():
    import jax.numpy as jnp

    return jnp.ones((7, 5), jnp.float32)


def test_window_owns_its_threads_builds():
    """Events inside a window land on its row and not under ``fun_name``;
    a jit outside any window is tallied under its function's name."""
    led = programs.ledger()
    x = _x()
    t0 = time.time()
    _fresh_jit(3.0, "outside_any_window")(x)
    win = led.compile_window(("win_owns",), family="probe")
    _fresh_jit(4.0, "inside_the_window")(x)
    win.close()
    got = led.builds(since=t0)
    rows = got["programs"]
    assert "inside_the_window" not in rows
    own = rows[repr(("win_owns",))]
    assert own["n"] == 1 and own["hits"] == 0
    assert own["trace_s"] > 0 and own["lower_s"] > 0 and own["compile_s"] > 0
    loose = rows["outside_any_window"]
    assert loose["n"] == 1 and loose["trace_s"] > 0 and loose["compile_s"] > 0
    assert got["executables"] == 2 and got["cache_hits"] == 0
    r = led.entry(("win_owns",)).row()
    assert r["trace_s"] == pytest.approx(own["trace_s"], abs=1e-5)
    assert r["backend_compile_s"] == pytest.approx(own["compile_s"], abs=1e-5)
    assert r["cache_hit"] is False and r["cache_load_s"] == 0
    assert r["trace_s"] + r["lower_s"] + r["backend_compile_s"] \
        <= r["compile_s"]
    reg = prof_metrics.get_registry()
    assert reg.get("programs.build_seconds").labels(phase="trace").value > 0
    assert reg.get("programs.built_total").labels(
        source="compiled").value >= 2


def test_another_threads_builds_are_not_the_windows():
    led = programs.ledger()
    x = _x()
    t0 = time.time()
    win = led.compile_window(("win_alone",), family="probe")
    th = threading.Thread(
        target=lambda: _fresh_jit(5.0, "on_another_thread")(x))
    th.start()
    th.join(timeout=120)
    assert not th.is_alive()
    win.close()
    rows = led.builds(since=t0)["programs"]
    assert rows["on_another_thread"]["n"] == 1
    assert repr(("win_alone",)) not in rows
    r = led.entry(("win_alone",)).row()
    assert r["trace_s"] == r["lower_s"] == r["backend_compile_s"] == 0
    assert r["cache_hit"] is False and r["compile_s"] > 0


def test_persistent_cache_hit_reads_as_a_load(tmp_path):
    """With a compile cache of its own, the second build of the same
    program in this process is a hit: nothing compiled, the whole of
    ``backend_compile_duration`` is what the load cost."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    led = programs.ledger()
    x = _x()
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (True, str(tmp_path / "cache"), 0.0, 0)):
            jax.config.update(k, v)
        cc.reset_cache()
        t0 = time.time()
        for i in range(2):
            win = led.compile_window(("cached", i), family="probe")
            _fresh_jit(6.0, "cached_twice")(x)
            win.close()
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        cc.reset_cache()
    miss, hit = (led.entry(("cached", i)).row() for i in range(2))
    assert miss["cache_hit"] is False and miss["backend_compile_s"] > 0
    assert miss["cache_load_s"] == 0
    assert hit["cache_hit"] is True and hit["cache_load_s"] > 0
    assert hit["backend_compile_s"] == 0
    assert hit["trace_s"] > 0 and hit["lower_s"] > 0    # a hit saves neither
    got = led.builds(since=t0)
    assert got["executables"] == 2 and got["cache_hits"] == 1
    assert got["programs"][repr(("cached", 1))]["hits"] == 1


def test_builds_until_leaves_out_what_ended_later():
    led = programs.ledger()
    x = _x()
    t0 = time.time()
    _fresh_jit(7.0, "before_the_mark")(x)
    mark = time.time()
    _fresh_jit(8.0, "after_the_mark")(x)
    early = led.builds(since=t0, until=mark)
    assert "before_the_mark" in early["programs"]
    assert "after_the_mark" not in early["programs"]
    assert early["executables"] == 1
    late = led.builds(since=mark)
    assert "after_the_mark" in late["programs"]
    assert "before_the_mark" not in late["programs"]
    both = led.builds(since=t0)
    for kind, s in both["seconds"].items():
        assert s == pytest.approx(
            early["seconds"][kind] + late["seconds"][kind])


def test_callee_builds_merge_into_their_caller():
    """JAX says when a build starts too, so what runs inside what is known:
    a trace or a lowering inside another build never reaches the list (the
    looped decoder's step program holds tens of thousands of jitted
    callees' traces), its seconds kept by kind in the build around it; an
    executable built inside a trace is an entry of its own and its seconds
    come off."""
    rec = programs.BuildRecord(limit=8)
    rec.enter("trace")                      # the caller's trace starts
    for _ in range(5_000):
        rec.enter("trace")
        rec.built("trace", 1e-6, "callee")
    for kind in ("trace", "lower", "compile"):      # an eager constant
        rec.enter(kind)
        rec.built(kind, 0.002, "eager_constant")
    rec.built("trace", 0.1, "caller")
    rec.enter("lower")
    rec.enter("trace")                      # a lowering rule, traced
    rec.built("trace", 0.003, "a_lowering_rule")
    rec.built("lower", 0.05, "caller")
    got = rec.builds()
    assert got["events"] == 3 and got["folded"] == 0
    assert set(got["programs"]) == {"caller", "eager_constant"}
    caller = got["programs"]["caller"]
    # the caller's 100 ms less the constant's 6, which holds 4 of
    # tracing and lowering merged back in, and the rule's 3
    assert caller["trace_s"] == pytest.approx(0.1 - 0.006 + 0.002 + 0.003)
    assert caller["lower_s"] == pytest.approx(0.002 + 0.05 - 0.003)
    assert caller["n"] == 0 and got["executables"] == 1
    assert got["programs"]["eager_constant"] == {
        "n": 1, "hits": 0, "trace_s": 0.0, "lower_s": 0.0,
        "compile_s": 0.002, "cache_load_s": 0.0}
    assert sum(got["seconds"].values()) == pytest.approx(0.15)
    # a window's split counts every second once, merged or not, and a
    # cache hit announced inside the compile makes it a load
    led = programs.ProgramLedger(record=rec)
    win = led.compile_window(("merged",), family="probe")
    rec.enter("trace")
    rec.enter("trace")
    rec.built("trace", 0.005, "callee")
    rec.built("trace", 0.02, "caller")
    rec.enter("compile")
    rec.cache_hit()
    rec.built("compile", 0.01, "caller")
    win.close()
    ent = led.entry(("merged",))
    assert ent.trace_s == pytest.approx(0.02) and ent.cache_hit is True
    assert ent.cache_load_s == 0.01 and ent.backend_compile_s == 0.0
    # a phase takes what ran inside it off its self time and keeps it
    with rec.phase("probe.holds_a_build"):
        time.sleep(0.02)
        rec.enter("trace")
        rec.built("trace", 0.015, "inside")
    ph = rec.builds()["phases"]["probe.holds_a_build"]
    assert ph["self_s"] == pytest.approx(ph["seconds"] - 0.015)
    assert "inside" in rec.builds()["programs"]
    assert rec._stack() == []


def test_record_is_bounded_and_its_totals_hold():
    rec = programs.BuildRecord(limit=8)
    rec.add_phase("startup.import", None, time.time() - 0.5, 0.25)
    for i in range(30):
        rec.built(("trace", "lower", "compile")[i % 3], 0.0005, f"fn{i // 3}")
    assert len(rec._events) == 8
    got = rec.builds()
    assert got["events"] == 8 and got["folded"] == 23
    # a phase is folded by its name, never lost
    assert got["phases"]["startup.import"] == {
        "n": 1, "seconds": 0.25, "self_s": 0.25, "parent": None}
    assert got["seconds"] == pytest.approx(
        {"trace": 0.005, "lower": 0.005, "compile": 0.005, "cache_load": 0.0})
    assert got["executables"] == 10
    assert got["programs"]["(folded)"]["n"] + sum(
        r["n"] for k, r in got["programs"].items() if k != "(folded)") == 10
    # an interval that cuts through what was folded cannot split it
    cut = rec.builds(since=rec._events[0].end)
    assert "(folded)" not in cut["programs"] and cut["events"] == 8
    assert not cut["phases"]


def test_compile_seconds_is_the_build_not_the_wall():
    """A window that also waited on a result (since PR 33 the engine's
    closes after a read-back): the counter gets what building cost."""
    led = programs.ledger()
    reg = prof_metrics.get_registry()
    x = _x()
    labels = dict(family="probe_waits", replica="w")
    win = led.compile_window(("win_waits",), family="probe_waits",
                             replica="w")
    _fresh_jit(9.0, "then_it_waits")(x).block_until_ready()
    time.sleep(0.3)                     # the read-back's stand-in
    win.close()
    r = led.entry(("win_waits",)).row()
    built = r["trace_s"] + r["lower_s"] + r["backend_compile_s"] \
        + r["cache_load_s"]
    assert 0 < built <= r["compile_s"] - 0.3
    assert win.wall_s == pytest.approx(r["compile_s"], abs=1e-5)
    assert reg.get("programs.compile_seconds").labels(
        **labels).value == pytest.approx(built, abs=1e-5)


def test_phase_nests_with_self_times_and_is_a_span():
    from paddle_tpu.observability import tracing

    led = programs.ledger()
    x = _x()
    tr = tracing.Tracer().start()
    t0 = time.time()
    try:
        with programs.phase("probe.outer"):
            time.sleep(0.05)
            with programs.phase("probe.outer.inner"):
                time.sleep(0.05)
                _fresh_jit(10.0, "inside_a_phase")(x)
    finally:
        tr.stop()
    got = led.builds(since=t0)
    outer, inner = (got["phases"][n]
                    for n in ("probe.outer", "probe.outer.inner"))
    assert inner["parent"] == "probe.outer" and outer["parent"] is None
    assert outer["n"] == inner["n"] == 1
    assert outer["seconds"] >= inner["seconds"] >= 0.05
    assert outer["self_s"] == pytest.approx(
        outer["seconds"] - inner["seconds"], abs=1e-6)
    built = sum(got["seconds"].values())
    assert built > 0
    assert inner["self_s"] == pytest.approx(inner["seconds"] - built,
                                            abs=1e-6)
    # phases and builds add up to the outer wall: nothing counted twice
    assert outer["self_s"] + inner["self_s"] + built == pytest.approx(
        outer["seconds"], abs=1e-6)
    reg = prof_metrics.get_registry()
    assert reg.get("startup.phase_seconds").labels(
        phase="probe.outer").value == pytest.approx(outer["seconds"])
    # the same region is a span of the armed tracer, parent and child
    (so,), (si,) = tr.find("probe.outer"), tr.find("probe.outer.inner")
    assert si.parent_id == so.span_id and si.trace_id == so.trace_id
    # and a decorator, for what wraps a whole function
    @programs.phase("probe.decorated")
    def body(a, b=2):
        return a + b
    assert body(1, b=3) == 4
    assert led.builds(since=t0)["phases"]["probe.decorated"]["n"] == 1


def test_engine_phases_and_start_up_on_statusz(model):
    """Construction and start leave their phases behind, the pools' and
    the weights' as children; /statusz prints the record under start_up."""
    from paddle_tpu.serving import ServingEngine

    led = programs.ledger()
    t0 = time.time()
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN)
    eng.start()
    eng.stop()
    ph = led.builds(since=t0)["phases"]
    init = ph["serving.engine_init"]
    for child in ("serving.engine_init.pools", "serving.engine_init.weights"):
        assert ph[child]["parent"] == "serving.engine_init"
        assert 0 <= ph[child]["seconds"] <= init["seconds"]
    assert init["self_s"] <= init["seconds"] - sum(
        ph[c]["seconds"] for c in ph if c.startswith("serving.engine_init."))\
        + 1e-6
    assert ph["serving.engine_start"]["seconds"] > 0
    sec = led.statusz()["start_up"]
    assert set(sec["seconds"]) == set(programs.BuildRecord.KINDS)
    assert "serving.engine_init" in sec["phases"]
    assert len(sec["programs"]) <= 32 and sec["executables"] >= 1
    json.dumps(sec)


# ================================================== manifest: warm restarts
def test_manifest_warm_restart_zero_traces(engine, model, tmp_path):
    """The tentpole invariant: capture -> save -> load -> warmup on a
    fresh same-seed model -> the first real request dispatches with ZERO
    new traces and byte-identical greedy output."""
    man = engine.capture_manifest()
    assert len(man) == len(program_store(model)) == 2
    assert man.meta.get("adapter")
    path = man.save(tmp_path / "manifest.json")

    m2 = _tiny_gpt()
    from paddle_tpu.serving import ServingEngine

    e2 = ServingEngine(m2, num_slots=2, page_size=PS, max_model_len=MAXLEN)
    info = e2.warmup(path)
    assert info["warmed"] == 2 and info["skipped"] == 0
    t0 = e2.program_traces()
    with e2:
        h = e2.submit(PROMPT, max_new_tokens=6)
        ids = list(h.result(timeout=600))
    assert e2.program_traces() - t0 == 0     # the asserted invariant
    assert h.compile_s == 0.0
    assert ids == engine._test_cold_ids      # byte-identical greedy
    # warmed rows carry provenance: warm, paid by "warmup"
    led = programs.ledger()
    rows = led.rows(store=program_store(m2))
    assert len(rows) == 2
    assert all(r["trace_id"] == "warmup" for r in rows)


def test_warmup_refuses_mismatched_adapter(tmp_path, model):
    man = WarmupManifest.capture(model,
                                 meta={"adapter": {"page_size": 999}})
    path = man.save(tmp_path / "bad.json")
    m2 = _tiny_gpt()
    from paddle_tpu.serving import ServingEngine

    e2 = ServingEngine(m2, num_slots=2, page_size=PS, max_model_len=MAXLEN)
    with pytest.raises(ValueError, match="adapter"):
        e2.warmup(path)


def test_warmup_after_start_raises(engine):
    with pytest.raises(RuntimeError, match="start"):
        engine.warmup(WarmupManifest())


def test_warmup_skips_unknown_keys(model, tmp_path):
    """Foreign keys (another engine geometry) are skipped, not fatal."""
    man = WarmupManifest([("no_such_phase", 1, 2)])
    m2 = _tiny_gpt()
    from paddle_tpu.serving import ServingEngine

    e2 = ServingEngine(m2, num_slots=2, page_size=PS, max_model_len=MAXLEN)
    info = e2.warmup(man)
    assert info["warmed"] == 0 and info["skipped"] == 1


@pytest.mark.slow
def test_replica_pool_warm_spinup(engine, tmp_path):
    """ReplicaPool(warmup=...) replays the manifest on spin-up: the
    fresh pool's first request on a replica mints zero traces."""
    from paddle_tpu.serving.cluster import ReplicaPool

    path = engine.capture_manifest().save(tmp_path / "pool.json")
    m2 = _tiny_gpt()
    pool = ReplicaPool(m2, replicas=1, num_slots=2, page_size=PS,
                       max_model_len=MAXLEN, warmup=str(path))
    assert pool.warmup_manifest is not None
    with pool:
        e = pool.engines[0]
        t0 = e.program_traces()
        h = e.submit(PROMPT, max_new_tokens=4)
        h.result(timeout=600)
        assert e.program_traces() - t0 == 0
        assert h.compile_s == 0.0


# ===================================================== slow: family matrix
def _matrix_engine_case(model, **kw):
    """Fresh engine under kw; returns (ledger rows for its store, store)."""
    from paddle_tpu.serving import ServingEngine

    with ServingEngine(model, num_slots=2, page_size=PS,
                       max_model_len=MAXLEN, **kw) as eng:
        h = eng.submit(PROMPT, max_new_tokens=8)
        h.result(timeout=600)
    store = program_store(model)
    return programs.ledger().rows(store=store), store


@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    {"kv_dtype": "int8"},
    {"prefill_chunk_tokens": 8},
    {"speculative_k": 2},
], ids=["int8", "chunked", "speculative"])
def test_ledger_accounts_engine_family_matrix(kw):
    m = _tiny_gpt()
    rows, store = _matrix_engine_case(m, **kw)
    assert len(rows) == len(store) >= 2
    keys = {r["key"] for r in rows}
    for k in store:
        assert repr(k) in keys
    assert all(r["compile_s"] is not None for r in rows)


@pytest.mark.slow
def test_ledger_accounts_mp_engine():
    """mp=2 engine in a forced-host-device subprocess: every SPMD store
    key lands a ledger row (store size == row count)."""
    body = r"""
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models.gpt import GPTForCausalLM
from paddle_tpu.text.models._decode import program_store
from paddle_tpu.observability import programs

paddle.seed(0)
m = GPTForCausalLM(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                   num_attention_heads=2,
                   max_position_embeddings=64).eval()
import jax
with ServingEngine(m, num_slots=2, page_size=8, max_model_len=64,
                   mesh=list(jax.devices())) as eng:
    h = eng.submit([1, 2, 3, 4], max_new_tokens=6)
    h.result(timeout=600)
store = program_store(m)
rows = programs.ledger().rows(store=store)
assert len(rows) == len(store) >= 2, (len(rows), len(store))
keys = {r["key"] for r in rows}
assert all(repr(k) in keys for k in store)
print("WORKER_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", REPO)
    proc = subprocess.run([sys.executable, "-c", body],
                          capture_output=True, text=True, timeout=560,
                          env=env)
    assert proc.returncode == 0 and "WORKER_OK" in proc.stdout, (
        f"worker failed\n--- stdout ---\n{proc.stdout}\n"
        f"--- stderr ---\n{proc.stderr}")


# ================================================== train_step / generate
@pytest.mark.slow
def test_train_step_mints_ledger_rows():
    import paddle_tpu.optimizer as opt

    led = programs.ledger()
    before = {r["key"] for r in led.rows()}
    paddle.seed(0)
    import paddle_tpu.nn as nn

    m = nn.Linear(8, 4)
    o = opt.Momentum(learning_rate=0.01, momentum=0.9,
                     parameters=m.parameters())
    step = paddle.jit.TrainStep(m, o, loss_fn=nn.CrossEntropyLoss())
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8)
                         .astype("float32"))
    y = paddle.to_tensor(np.array([0, 1, 2, 3], dtype="int64"))
    step((x,), y)
    new = [r for r in led.rows() if r["key"] not in before
           and r["kind"] == "train_step"]
    assert new, led.rows()
    assert new[0]["compile_s"] > 0
    # the variant's first call ran under a compile window and a phase
    assert new[0]["trace_s"] > 0 and new[0]["backend_compile_s"] > 0
    assert new[0]["trace_s"] + new[0]["lower_s"] \
        + new[0]["backend_compile_s"] <= new[0]["compile_s"]
    assert "train_step.first_call" in led.builds()["phases"]


@pytest.mark.slow
def test_generate_decode_mints_ledger_row():
    led = programs.ledger()
    m = _tiny_gpt(seed=1)
    ids = paddle.to_tensor(np.array([[1, 2, 3, 4]], dtype="int64"))
    m.generate(ids, max_new_tokens=4, temperature=0.0, cache_impl="paged",
               page_size=PS, max_len=32)
    rows = [r for r in led.rows(store=program_store(m))
            if r["kind"] == "generate"]
    assert rows, led.rows()
    assert rows[0]["family"] == "generate.decode"
    assert rows[0]["compile_s"] is not None and rows[0]["compile_s"] > 0
