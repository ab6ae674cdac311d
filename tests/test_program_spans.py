"""The program's own spans on the profiler's clock (PR 24).

A toy engine and a toy ``TrainStep`` on the CPU under a real
``jax.profiler`` trace, read back with ``ProfileData``: the scheduler's tree
of spans, the counts taken at the same boundaries, the same names through a
``Tracer``, and the names the program gives to its kernels and step phases.
No timing is asserted."""

import importlib
import threading
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.observability import faults, tracing
from paddle_tpu.profiler import metrics as prof_metrics
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models.gpt import GPTForCausalLM

PS, MAXLEN, CHUNK = 8, 64, 16
LANES, NEW = 3, 6
DISPATCHING = ("serving.prefill", "serving.prefill_cached",
               "serving.prefill_chunk", "serving.decode_step",
               "serving.verify_step")


def _gpt():
    paddle.seed(0)
    return GPTForCausalLM(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                          num_attention_heads=2,
                          max_position_embeddings=MAXLEN)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 96, (n,)).tolist()


def _counts():
    """``serving.*`` histogram sums and counts, summed over labels."""
    out = {}
    for row in prof_metrics.get_registry().collect():
        if row["name"].endswith(("_sum", "_count")):
            out[row["name"]] = out.get(row["name"], 0.0) + float(row["value"])
    return out


def _delta(after, before, name):
    return after.get(name, 0.0) - before.get(name, 0.0)


def _trace_lines(trace_dir):
    """``[[(name, start_ns, end_ns)]]``: one list per host thread."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(path)
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in line.events]
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines]


def _start_trace(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


@pytest.fixture(scope="module")
def engine():
    eng = ServingEngine(_gpt().eval(), num_slots=4, page_size=PS,
                        max_model_len=MAXLEN, prefill_chunk_tokens=CHUNK)
    # every program the traced stretch uses, before it
    for h in [eng.submit(_prompt(5, i), max_new_tokens=NEW)
              for i in range(LANES)] + \
            [eng.submit(_prompt(40, 9), max_new_tokens=4)]:
        h.result(timeout=600)
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def served(engine, tmp_path_factory):
    """A traced stretch in two parts: ``LANES`` equal requests queued while
    the scheduler is held at the top of a turn, so one admission takes them
    all and every decode dispatch carries all of them; then one prompt long
    enough to be ingested in chunks."""
    trace_dir = tmp_path_factory.mktemp("trace")
    held, go = threading.Event(), threading.Event()
    c0 = _counts()
    _start_trace(trace_dir)
    try:
        faults.inject("serving.scheduler_wedge", times=1,
                      fn=lambda: (held.set(), go.wait(60)))
        assert held.wait(60)
        handles = [engine.submit(_prompt(5, 20 + i), max_new_tokens=NEW)
                   for i in range(LANES)]
        go.set()
        for h in handles:
            h.result(timeout=600)
        c1 = _counts()
        long = engine.submit(_prompt(40, 30), max_new_tokens=4)
        long.result(timeout=600)
        time.sleep(0.1)                 # the scheduler idles, still traced
        c2 = _counts()
    finally:
        go.set()
        faults.clear()
        jax.profiler.stop_trace()
    lines = _trace_lines(trace_dir)
    sched = [ln for ln in lines
             if any(n == "serving.iteration" for n, _, _ in ln)]
    return {"lines": lines, "sched": sched, "c0": c0, "c1": c1, "c2": c2,
            "handles": handles, "long": long}


def _named(events, *names):
    return [e for e in events if e[0] in names]


def _within(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


# ------------------------------------------------------ the scheduler's tree
def test_engine_spans_are_on_one_thread(served):
    assert len(served["sched"]) == 1
    engine_names = {"serving.iteration", "serving.admit", "serving.dispatch",
                    "serving.device_wait", "serving.emit",
                    "serving.idle_wait", *DISPATCHING}
    elsewhere = [n for ln in served["lines"] if ln is not served["sched"][0]
                 for n, _, _ in ln if n in engine_names]
    assert elsewhere == []
    seen = {n for n, _, _ in served["sched"][0]}
    assert {"serving.iteration", "serving.admit", "serving.prefill",
            "serving.prefill_chunk", "serving.decode_step",
            "serving.dispatch", "serving.device_wait", "serving.emit",
            "serving.idle_wait"} <= seen
    # the caller's span is the caller's thread's
    assert "serving.submit" not in seen


def test_device_wait_inside_dispatching_span_inside_iteration(served):
    ev = served["sched"][0]
    iterations = _named(ev, "serving.iteration")
    dispatching = _named(ev, *DISPATCHING)
    waits = _named(ev, "serving.device_wait")
    assert waits and len(waits) == len(_named(ev, "serving.dispatch"))
    for w in waits + _named(ev, "serving.dispatch"):
        assert _within(w, dispatching), w
    for d in dispatching + _named(ev, "serving.admit", "serving.emit"):
        assert _within(d, iterations), d
    # one dispatch and one wait in each dispatching span, in that order
    for d in dispatching:
        inner = sorted(e for e in _named(ev, "serving.dispatch",
                                         "serving.device_wait")
                       if _within(e, [d]))
        assert [e[0] for e in sorted(inner, key=lambda e: e[1])] == \
            ["serving.dispatch", "serving.device_wait"]


def test_prefill_stays_inside_admit_and_chunks_outside(served):
    ev = served["sched"][0]
    admits = _named(ev, "serving.admit")
    for p in _named(ev, "serving.prefill"):
        assert _within(p, admits)
    chunks = _named(ev, "serving.prefill_chunk")
    assert len(chunks) == 3             # 40 tokens in chunks of 16
    assert not any(_within(c, admits) for c in chunks)


def test_idle_turn_is_idle_wait_not_iteration(served):
    ev = served["sched"][0]
    idle, iterations = _named(ev, "serving.idle_wait"), \
        _named(ev, "serving.iteration")
    assert idle
    assert not any(_within(i, iterations) for i in idle)
    # every iteration did something: it holds an admission that prefilled,
    # a chunk or a step
    work = _named(ev, *DISPATCHING)
    assert all(any(_within(w, [it]) for w in work) for it in iterations)


# ----------------------------------------- counts at the same boundaries
def test_decode_steps_equal_both_histograms_counts(served):
    steps = len(_named(served["sched"][0], "serving.decode_step"))
    c0, c2 = served["c0"], served["c2"]
    assert steps == _delta(c2, c0, "serving.step_seconds_count")
    assert steps == _delta(c2, c0, "serving.decode_batch_size_count")
    assert steps == _delta(c2, c0, "serving.step_page_utilization_count")
    assert steps == (NEW - 1) + (4 - 1)


def test_mean_batch_is_the_lanes_put_in(served):
    c0, c1, c2 = served["c0"], served["c1"], served["c2"]
    n = _delta(c1, c0, "serving.decode_batch_size_count")
    assert n == NEW - 1
    assert _delta(c1, c0, "serving.decode_batch_size_sum") / n == LANES
    # the long prompt then decodes alone
    n = _delta(c2, c1, "serving.decode_batch_size_count")
    assert _delta(c2, c1, "serving.decode_batch_size_sum") / n == 1


def test_page_utilization_is_a_share_of_the_pool(served, engine):
    c0, c1 = served["c0"], served["c1"]
    mean = _delta(c1, c0, "serving.step_page_utilization_sum") \
        / _delta(c1, c0, "serving.step_page_utilization_count")
    # three requests of 5 + 6 tokens hold two pages each
    assert mean == pytest.approx(
        LANES * 2 / engine.block_manager.num_pages)


# ------------------------------------------------------- nothing armed
def test_unarmed_span_builds_no_span_and_takes_no_lock(monkeypatch):
    assert not tracing.enabled()

    class Untouchable:
        def __getattr__(self, name):
            raise AssertionError(f"the span registry's lock was touched: "
                                 f"{name}")
        __enter__ = __exit__ = acquire = release = None

    def no_span(*a, **k):
        raise AssertionError("a Span was built with nothing armed")

    monkeypatch.setattr(tracing, "_LOCK", Untouchable())
    monkeypatch.setattr(tracing, "Span", no_span)
    with tracing.span("serving.decode_step", lambda: 1 / 0, batch=3) as cm:
        assert tracing.current_span() is None
    assert isinstance(cm, jax.profiler.TraceAnnotation)


# ------------------------------------------------------- a Tracer armed
def test_tracer_gets_the_same_names_with_parents_and_trace_id(engine):
    tr = tracing.Tracer().start()
    try:
        long = engine.submit(_prompt(40, 40), max_new_tokens=4)
        long.result(timeout=600)
        # the handle is done inside serving.emit: let the turn's spans close
        deadline = time.time() + 60
        while time.time() < deadline and any(
                s["name"] == "serving.iteration"
                for s in tracing.open_spans()):
            time.sleep(0.005)
    finally:
        tr.stop()
    by_id = {s.span_id: s for s in tr.spans}
    names = {s.name for s in tr.spans}
    assert {"serving.submit", "serving.iteration", "serving.admit",
            "serving.prefill_chunk", "serving.decode_step",
            "serving.dispatch", "serving.device_wait",
            "serving.emit"} <= names
    for s in tr.find("serving.device_wait") + tr.find("serving.dispatch"):
        assert by_id[s.parent_id].name in DISPATCHING
    for s in tr.find("serving.decode_step") + tr.find("serving.emit") \
            + tr.find("serving.admit"):
        assert by_id[s.parent_id].name == "serving.iteration"
    # a chunk call roots on its request's trace, and so do the dispatch and
    # the wait inside it
    chunks = tr.find("serving.prefill_chunk")
    assert len(chunks) == 3
    for c in chunks:
        assert c.trace_id == long.trace_id
        kids = [s for s in tr.spans if s.parent_id == c.span_id]
        assert sorted(k.name for k in kids) == \
            ["serving.device_wait", "serving.dispatch"]
        assert {k.trace_id for k in kids} == {long.trace_id}
    # a decode step links the requests it serves; the list is only built
    # for a sink
    for s in tr.find("serving.decode_step"):
        assert s.attrs["links"] == [long.trace_id] and s.attrs["batch"] == 1


def test_armed_spans_also_reach_the_profiler_trace(tmp_path):
    tr = tracing.Tracer().start()
    _start_trace(tmp_path)
    try:
        with tracing.span("outer.region", tag=1):
            with tracing.span("inner.region"):
                pass
    finally:
        jax.profiler.stop_trace()
        tr.stop()
    seen = {n for ln in _trace_lines(tmp_path) for n, _, _ in ln}
    assert {"outer.region", "inner.region"} <= seen
    # (an idle engine of this module may add its serving.idle_wait)
    assert [s.name for s in tr.spans if s.name.endswith(".region")] == \
        ["inner.region", "outer.region"]


# ------------------------------------------------------------- TrainStep
@pytest.fixture(scope="module")
def train_step():
    m = _gpt()
    o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, o, loss_fn=None)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(1, 96, (4, 16)).astype("int64"))
    step({"input_ids": ids, "labels": ids})
    return step, ids


def test_train_step_span_in_the_profiler_trace(train_step, tmp_path):
    step, ids = train_step
    _start_trace(tmp_path)
    try:
        for _ in range(3):
            loss = step({"input_ids": ids, "labels": ids})
        float(loss)
    finally:
        jax.profiler.stop_trace()
    spans = [e for ln in _trace_lines(tmp_path) for e in ln
             if e[0] == "jit.train_step"]
    assert len(spans) == 3


def test_lowered_train_step_names_its_phases(train_step):
    step, _ = train_step
    fn = step._last_fn
    args = [step._diff_params, step._opt_state, step._buffers,
            step._frozen_params, step._lr_dev, step._rng_carry]
    text = fn._jitted.lower(*args, *step._last_batch_vals).as_text(
        debug_info=True)
    for scope in ("forward_loss", "lm_head_loss", "optimizer_step"):
        assert scope in text, scope
    # the head and the loss lie under the forward's scope, and their
    # backward under its transpose
    assert "forward_loss)/lm_head_loss" in text
    assert "transpose(jvp(forward_loss))/lm_head_loss" in text


# -------------------------------------------------- names on the kernels
def _pallas_scopes(fn, *args):
    """The name stack of every ``pallas_call`` in ``fn``'s jaxpr."""
    out = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                out.append(str(e.source_info.name_stack))
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _paged_args(quantized=False, chunk=None):
    H, D, P, B, NP = 4, 64, 9, 2, 4
    dt = jnp.int8 if quantized else jnp.float32
    q = jnp.zeros((B, H, D) if chunk is None else (B, chunk, H, D),
                  jnp.float32)
    pool = jnp.zeros((P, PS, H, D), dt)
    scales = (jnp.ones((P, PS, H), jnp.float32),) * 2 if quantized else ()
    return (q, pool, pool, *scales, jnp.zeros((B, NP), jnp.int32),
            jnp.ones((B,), jnp.int32))


#: site -> the name stack its kernel carries.  Three sites carry none on
#: purpose: the benchmark's accepted readers find them by the instruction
#: name they have without one (PERF.md section 7 says what has to be
#: repointed before they are named ``paged_decode``, ``flash_dkdv`` and
#: ``flash_dq``).
PAGED_SITES = {
    "flash_decode": ("_paged_flash_pallas", False, ""),
    "flash_decode_int8": ("_paged_q_flash_pallas", True, "paged_decode_q"),
}


@pytest.mark.parametrize("site", sorted(PAGED_SITES))
def test_paged_kernel_sites_carry_their_names(site):
    pa = importlib.import_module("paddle_tpu.ops.paged_attention")
    fn, quantized, want = PAGED_SITES[site]
    q, *pools, table, lens = _paged_args(quantized)
    tail = ()
    if "flash" in fn:       # the served kernels: stacked pools and a layer
        pools, tail = [jnp.stack([p, p]) for p in pools], (1,)
    scopes = _pallas_scopes(
        lambda *a: getattr(pa, fn)(*a, 0.125, True, *tail), q, *pools,
        table, lens)
    assert scopes == [want]


@pytest.mark.parametrize("quantized,want", [
    (False, "chunk_attention"), (True, "chunk_attention/paged_chunk_q")])
def test_chunk_attention_names_the_kernel_behind_it(quantized, want):
    """One kernel a call (no [B*C]-row expansion through the decode
    kernel), under the scope ``chunk_attn_device_ms`` reads it by; the
    bf16 one carries no name of its own, so its instruction is
    ``%chunk_attention.N``."""
    pa = importlib.import_module("paddle_tpu.ops.paged_attention")
    fn = pa.paged_chunk_attend_quant if quantized else pa.paged_chunk_attend
    assert _pallas_scopes(fn, *_paged_args(quantized, chunk=3)) == [want]


def test_flash_kernel_sites_carry_their_names():
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    x = jnp.zeros((2, 256, 128), jnp.float32)

    def loss(q, k, v):
        with jax.named_scope("forward_loss"):
            return jnp.sum(fa._flash(q, k, v, 0.1, True, 128, 128, 0))

    scopes = _pallas_scopes(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    # forward by its name; both backward kernels keep the bare
    # transpose(jvp(...)) the accepted flash_bwd_roofline looks for
    assert scopes == ["jvp(forward_loss)/flash_fwd",
                      "transpose(jvp(forward_loss))",
                      "transpose(jvp(forward_loss))"]
