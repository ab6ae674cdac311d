"""paddle_tpu.resilience — async checkpointing (atomic commit, checksum
manifest, corruption fallback, partial-save GC), failure classification +
jittered/capped backoff, the recovery supervisors, fault plans, emergency
checkpoints, and the /healthz aggregation.

End-to-end chaos runs (train loop + serving workload through injected
failures) live in tests/test_chaos.py; this file covers the mechanisms.
"""

import json
import os
import signal
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import faults, watchdog
from paddle_tpu.profiler import metrics as prof_metrics
from paddle_tpu.resilience import (
    AsyncCheckpointManager, CheckpointCorruptionError, CollectiveTimeoutError,
    PreemptionError, RecoverySupervisor, RetryPolicy, TransientError,
    arm_emergency_checkpoint, classify_failure, corrupt_checkpoint,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _state(seed=0):
    rs = np.random.RandomState(seed)
    return {
        "model": {"w": paddle.to_tensor(rs.randn(3, 4).astype("float32")),
                  "b": np.arange(4, dtype="int64")},
        "step_count": 7,
        "lr": 0.125,
        "tag": "resilience",
        "shape": (3, 4),
        "none": None,
        "np_scalar": np.float32(2.5),
    }


def _assert_state_roundtrip(out, seed=0):
    ref = _state(seed)
    assert isinstance(out["model"]["w"], paddle.Tensor)
    np.testing.assert_allclose(out["model"]["w"].numpy(),
                               ref["model"]["w"].numpy())
    np.testing.assert_array_equal(out["model"]["b"].numpy(), ref["model"]["b"])
    assert out["step_count"] == 7 and out["lr"] == 0.125
    assert out["tag"] == "resilience" and out["none"] is None
    assert out["shape"] == (3, 4)          # tuples survive as tuples
    assert out["np_scalar"] == np.float32(2.5)
    assert out["np_scalar"].dtype == np.float32


# ==================================================== async checkpointing
def test_async_save_restore_roundtrip(tmp_path):
    with AsyncCheckpointManager(tmp_path / "ckpt") as mgr:
        assert mgr.latest_step() is None and mgr.restore() is None
        mgr.save(3, _state())           # async; returns before the write
        mgr.wait_until_finished()
        assert mgr.all_steps() == [3]
        ok, problems = mgr.verify(3)
        assert ok, problems
        _assert_state_roundtrip(mgr.restore())
        step, out = mgr.restore_latest_valid()
        assert step == 3
        _assert_state_roundtrip(out)


def test_save_interval_and_rotation(tmp_path):
    mgr = AsyncCheckpointManager(tmp_path / "ckpt", max_to_keep=2,
                                 save_interval_steps=2)
    st = _state()
    assert not mgr.save(3, st)                  # off-interval: skipped
    assert mgr.save(3, st, force=True)          # force overrides
    for s in (4, 6, 8):
        assert mgr.save(s, st, block=True)
    assert mgr.all_steps() == [6, 8]            # rotation kept the last 2
    mgr.close()


def test_partial_save_gc_and_atomic_commit(tmp_path):
    d = tmp_path / "ckpt"
    mgr = AsyncCheckpointManager(d)
    mgr.save(1, _state(), block=True)
    # a crashed writer's leftovers: a partial tmp dir is NOT a checkpoint
    # and a fresh manager garbage-collects it
    orphan = d / "step_00000099.tmp-12345"
    orphan.mkdir()
    (orphan / "arrays.npz").write_bytes(b"partial garbage")
    assert mgr.all_steps() == [1]               # never listed
    mgr.close()
    mgr2 = AsyncCheckpointManager(d)
    assert not orphan.exists()                  # GC'd at startup
    assert mgr2.all_steps() == [1]
    mgr2.close()


@pytest.mark.parametrize("mode", ["flip", "truncate"])
def test_corruption_detected_and_falls_back(tmp_path, mode):
    """The satellite acceptance: damage the NEWEST checkpoint's bytes; the
    manager must detect it via the checksum manifest, quarantine it, and
    fall back to the previous valid step."""
    corrupt0 = prof_metrics.counter("resilience.checkpoint_corruptions").total()
    mgr = AsyncCheckpointManager(tmp_path / "ckpt")
    mgr.save(1, _state(seed=1), block=True)
    mgr.save(2, _state(), block=True)
    corrupt_checkpoint(mgr, mode=mode)
    ok, problems = mgr.verify(2)
    assert not ok and problems
    with pytest.raises(CheckpointCorruptionError):
        mgr.restore(2)
    step, out = mgr.restore_latest_valid()
    assert step == 1
    np.testing.assert_allclose(out["model"]["w"].numpy(),
                               _state(seed=1)["model"]["w"].numpy())
    # corrupt step quarantined off the step list, visible as *.corrupt-*
    assert mgr.all_steps() == [1]
    assert any(".corrupt-" in n for n in os.listdir(mgr.directory))
    assert prof_metrics.counter(
        "resilience.checkpoint_corruptions").total() > corrupt0
    mgr.close()


def test_every_checkpoint_corrupt_returns_none(tmp_path):
    mgr = AsyncCheckpointManager(tmp_path / "ckpt")
    mgr.save(1, _state(), block=True)
    corrupt_checkpoint(mgr, step=1)
    assert mgr.restore_latest_valid() == (None, None)
    mgr.close()


# ========================================================= classification
def test_classify_failure():
    assert classify_failure(TransientError("x")) == "transient"
    assert classify_failure(PreemptionError("x")) == "transient"
    assert classify_failure(CollectiveTimeoutError("x")) == "transient"
    assert classify_failure(TimeoutError("x")) == "transient"
    assert classify_failure(ConnectionResetError("x")) == "transient"
    # jax-runtime-shaped messages classify by pattern
    assert classify_failure(RuntimeError("DEADLINE EXCEEDED: barrier")) \
        == "transient"
    assert classify_failure(RuntimeError("host was preempted")) == "transient"
    assert classify_failure(RuntimeError("coordination service shutting "
                                         "down")) == "transient"
    # program bugs are fatal: restarting replays the crash
    assert classify_failure(ValueError("shape mismatch")) == "fatal"
    assert classify_failure(ZeroDivisionError()) == "fatal"


def test_retry_policy_backoff_jitter_and_cap():
    # no jitter: exact exponential, capped
    p = RetryPolicy(base_delay=1.0, max_delay=5.0, jitter=0.0)
    assert [p.delay(a) for a in (1, 2, 3, 4, 10)] == [1.0, 2.0, 4.0, 5.0, 5.0]
    # seeded jitter is deterministic and bounded
    a = RetryPolicy(base_delay=1.0, max_delay=60.0, jitter=0.5, seed=7)
    b = RetryPolicy(base_delay=1.0, max_delay=60.0, jitter=0.5, seed=7)
    da = [a.delay(i) for i in range(1, 8)]
    assert da == [b.delay(i) for i in range(1, 8)]
    for i, d in enumerate(da, start=1):
        base = min(2.0 ** (i - 1), 60.0)
        assert 0.5 * base - 1e-9 <= d <= min(1.5 * base, 60.0) + 1e-9
    # the cap binds even with jitter pushing up
    c = RetryPolicy(base_delay=10.0, max_delay=12.0, jitter=1.0, seed=0)
    assert all(c.delay(i) <= 12.0 for i in range(1, 20))
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.5)


# ============================================================ supervisors
def test_recovery_supervisor_restarts_transient_and_surfaces_fatal(tmp_path):
    mgr = AsyncCheckpointManager(tmp_path / "ckpt")
    n0 = prof_metrics.counter("resilience.restarts").get(
        kind="transient", supervisor="recovery") or 0
    calls = []

    def flaky(start, state):
        calls.append(start)
        mgr.save(len(calls), {"attempt": len(calls)}, block=True)
        if len(calls) < 3:
            raise PreemptionError("host going away")
        return "done"

    sup = RecoverySupervisor(
        mgr, policy=RetryPolicy(base_delay=0.01, max_delay=0.02, seed=0),
        max_transient_restarts=5)
    assert sup.run(flaky) == "done"
    assert sup.restarts == {"transient": 2, "fatal": 0}
    # each retry resumed from the checkpoint the failed attempt wrote
    assert calls == [0, 1, 2]
    assert (prof_metrics.counter("resilience.restarts").get(
        kind="transient", supervisor="recovery") or 0) == n0 + 2
    assert prof_metrics.get_registry().get(
        "resilience.backoff_seconds").labels().count >= 2

    def broken(start, state):
        raise ValueError("a real bug")

    with pytest.raises(ValueError):  # fatal: no restart by default
        RecoverySupervisor(mgr, max_transient_restarts=5).run(broken)
    mgr.close()


def test_recovery_supervisor_budget_exhaustion(tmp_path):
    mgr = AsyncCheckpointManager(tmp_path / "ckpt")
    sup = RecoverySupervisor(
        mgr, policy=RetryPolicy(base_delay=0.001, jitter=0.0),
        max_transient_restarts=2)

    def always_preempted(start, state):
        raise PreemptionError("again")

    with pytest.raises(PreemptionError):
        sup.run(always_preempted)
    assert sup.restarts["transient"] == 3  # budget 2 + the surfaced one
    mgr.close()


def test_recovery_supervisor_falls_back_over_corrupt_checkpoint(tmp_path):
    mgr = AsyncCheckpointManager(tmp_path / "ckpt")
    mgr.save(1, {"v": 10}, block=True)
    mgr.save(2, {"v": 20}, block=True)
    corrupt_checkpoint(mgr)  # newest (2) is damaged
    seen = []

    def train(start, state):
        seen.append((start, state["v"] if state else None))
        return "ok"

    RecoverySupervisor(mgr).run(train)
    assert seen == [(1, 10)]  # resumed from the previous VALID step
    mgr.close()


def test_elastic_supervisor_jitter_cap_and_metrics(tmp_path):
    """Satellite: ElasticSupervisor backoff gains jitter + cap and emits
    resilience.restarts / resilience.backoff_seconds."""
    from paddle_tpu.distributed.elastic import ElasticSupervisor

    mgr = AsyncCheckpointManager(tmp_path / "ckpt")
    n0 = prof_metrics.counter("resilience.restarts").get(
        kind="unclassified", supervisor="elastic") or 0
    bh = prof_metrics.get_registry().histogram("resilience.backoff_seconds")
    c0 = bh.labels().count
    calls = []

    def flaky(start, state):
        calls.append(start)
        if len(calls) < 3:
            raise RuntimeError("boom")
        return 0

    sup = ElasticSupervisor(mgr, max_restarts=5, backoff_seconds=0.01,
                            max_backoff_seconds=0.02, jitter=0.5, seed=1)
    assert sup.run(flaky) == 0
    assert len(calls) == 3
    assert (prof_metrics.counter("resilience.restarts").get(
        kind="unclassified", supervisor="elastic") or 0) == n0 + 2
    assert bh.labels().count == c0 + 2
    # the policy caps delays at max_backoff_seconds
    assert all(sup.policy.delay(i) <= 0.02 for i in range(1, 10))
    mgr.close()


# ============================================================ fault plans
def test_fault_plan_scheduled_and_scoped():
    fired = []
    plan = faults.FaultPlan(seed=3).add(
        "unit.plan_site", fn=lambda: fired.append(1), at_trips={2, 5})
    with plan:
        assert faults.armed("unit.plan_site")
        for _ in range(6):
            faults.maybe("unit.plan_site")
        desc = plan.describe()
    assert fired == [1, 1]
    assert not faults.armed("unit.plan_site")   # scope exit disarms
    assert desc[0]["site"] == "unit.plan_site" and desc[0]["trips"] == 2
    # trips survive the scope exit (the documented post-run report)
    assert plan.describe()[0]["trips"] == 2
    faults.maybe("unit.plan_site")              # disarmed: no-op
    assert fired == [1, 1]


def test_fault_plan_probabilistic_is_deterministic():
    def run(seed):
        hits = []
        with faults.FaultPlan(seed=seed).add(
                "unit.prob_site", fn=lambda: hits.append(1),
                probability=0.3):
            pattern = []
            for _ in range(40):
                n = len(hits)
                faults.maybe("unit.prob_site")
                pattern.append(len(hits) > n)
        return pattern

    p1, p2, p3 = run(11), run(11), run(12)
    assert p1 == p2                 # same seed -> same trip pattern
    assert p1 != p3                 # different seed -> decorrelated
    assert 0 < sum(p1) < 40         # actually probabilistic


def test_fault_every_and_times():
    fired = []
    faults.inject("unit.every_site", fn=lambda: fired.append(1), every=3,
                  times=2)
    try:
        for _ in range(12):
            faults.maybe("unit.every_site")
    finally:
        faults.clear("unit.every_site")
    assert fired == [1, 1]          # calls 3 and 6, then times=2 disarms


def test_describe_lists_armed_faults():
    faults.inject("unit.describe_site", seconds=0.0, times=7)
    try:
        rows = faults.describe()
        row = next(r for r in rows if r["site"] == "unit.describe_site")
        assert row["times"] == 7 and row["trips"] == 0 and not row["fn"]
    finally:
        faults.clear("unit.describe_site")
    assert all(r["site"] != "unit.describe_site" for r in faults.describe())


# ==================================================== emergency + healthz
def test_watchdog_fire_triggers_emergency_checkpoint(tmp_path):
    """Detection-to-recovery wiring: a collective watchdog fire must
    persist an emergency checkpoint through the registered listener."""
    mgr = AsyncCheckpointManager(tmp_path / "ckpt")
    n0 = prof_metrics.counter("resilience.emergency_saves").total()
    disarm = arm_emergency_checkpoint(
        mgr, lambda: (42, {"w": np.ones(3, "float32")}), signals=())
    wd = watchdog.CollectiveWatchdog(deadline_s=0.05, poll_s=0.02).start()
    group = types.SimpleNamespace(id=0, nranks=2, ranks=[0, 1], rank=0)
    token = wd.begin("all_reduce", group)
    try:
        t0 = time.time()
        while not wd.fired and time.time() - t0 < 10:
            time.sleep(0.02)
        assert wd.fired, "watchdog never fired"
        t0 = time.time()
        while 42 not in mgr.all_steps() and time.time() - t0 < 10:
            time.sleep(0.02)
    finally:
        wd.end(token)
        wd.stop()
        disarm()
    assert 42 in mgr.all_steps()
    ok, problems = mgr.verify(42)
    assert ok, problems
    out = mgr.restore(42)
    np.testing.assert_allclose(out["w"].numpy(), 1.0)
    assert prof_metrics.counter("resilience.emergency_saves").total() > n0
    # once disarmed, a second fire saves nothing new
    steps_before = mgr.all_steps()
    watchdog._notify_fire("collective", {"op": "x"})
    assert mgr.all_steps() == steps_before
    mgr.close()


_SIGTERM_WORKER = r"""
import os, signal, sys
os.environ.pop("XLA_FLAGS", None)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from paddle_tpu.resilience import (AsyncCheckpointManager,
                                   arm_emergency_checkpoint)

mgr = AsyncCheckpointManager(os.environ["CKPT_DIR"])
state = {"w": np.full((4,), 3.0, "float32"), "step": 11}
arm_emergency_checkpoint(mgr, lambda: (11, state), signals=("SIGTERM",))
print("ARMED", flush=True)
os.kill(os.getpid(), signal.SIGTERM)   # preemption notice
import time
time.sleep(30)                          # must never get here
"""


def test_sigterm_triggers_emergency_checkpoint_then_dies(tmp_path):
    """SIGTERM (the preemption notice) commits an emergency checkpoint and
    the process still dies with SIGTERM (handler chains to the default)."""
    script = tmp_path / "worker.py"
    script.write_text(_SIGTERM_WORKER)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["CKPT_DIR"] = str(tmp_path / "ckpt")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert "ARMED" in r.stdout, r.stdout + r.stderr
    assert r.returncode == -signal.SIGTERM, (r.returncode, r.stderr)
    mgr = AsyncCheckpointManager(tmp_path / "ckpt")
    assert mgr.all_steps() == [11]
    out = mgr.restore(11)
    np.testing.assert_allclose(out["w"].numpy(), 3.0)
    assert out["step"] == 11
    mgr.close()


def test_healthz_aggregates_worst_component_state():
    from paddle_tpu.observability import telemetry

    srv = telemetry.TelemetryServer(port=0).start()
    try:
        code, doc = srv._healthz()
        base = doc["status"]
        telemetry.add_health_provider(
            "unit_component", lambda: {"state": "degraded",
                                       "reasons": ["queue_pressure"]})
        code, doc = srv._healthz()
        assert code == 200 and doc["status"] == "degraded"
        assert doc["components"]["unit_component"]["reasons"] \
            == ["queue_pressure"]
        telemetry.add_health_provider(
            "unit_component", lambda: {"state": "draining", "reasons": []})
        code, doc = srv._healthz()
        assert code == 503 and doc["status"] == "draining"
        # a provider that raises reads as error (503), never a crash
        telemetry.add_health_provider("unit_component",
                                      lambda: 1 / 0)
        code, doc = srv._healthz()
        assert code == 503 and doc["status"] == "error"
        telemetry.remove_health_provider("unit_component")
        code, doc = srv._healthz()
        assert doc["status"] == base
    finally:
        telemetry.remove_health_provider("unit_component")
        srv.stop()


def test_statusz_lists_armed_fault_hooks():
    from paddle_tpu.observability import telemetry

    srv = telemetry.TelemetryServer(port=0).start()
    faults.inject("unit.statusz_site", seconds=0.0, times=3)
    try:
        sz = srv._statusz()
        sites = [r["site"] for r in sz["faults"]]
        assert "unit.statusz_site" in sites
    finally:
        faults.clear("unit.statusz_site")
        srv.stop()
    assert all(r["site"] != "unit.statusz_site"
               for r in srv._statusz()["faults"])


def test_chaos_smoke_entrypoint(tmp_path):
    """bench.py --chaos-smoke body: injected transient failure + corrupted
    newest checkpoint, full recovery, structured report."""
    from paddle_tpu.resilience.chaos import run_smoke

    rep = run_smoke(total_steps=5, fail_at=2, directory=str(tmp_path))
    assert rep["completed_steps"] == 5
    assert rep["transient_restarts"] == 1
    assert rep["resumed_from_step"] == 1
    assert rep["elapsed_s"] > 0
    json.dumps(rep)  # bench prints it as JSON
