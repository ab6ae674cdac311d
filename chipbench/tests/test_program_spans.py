"""The readers of the program's own spans, names and counts (PR 24) on
made-up traces with known answers: an iteration's self time with nested
children, an idle gap under each span, the clock's shift, and a program
without the span, name or counter giving nothing."""

import os

import pytest

from chipbench import program_spans as P, spec as _spec, trace_reduce as T, \
    trace_scopes

MS = 1_000_000
US = 1_000
ENG = "python3"         # the scheduler thread's line, as the profiler names it
MOSAIC = 'custom-call(...), custom_call_target="tpu_custom_call"'
DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data",
                    "small_trace.xplane.pb")


class Obs:
    def __init__(self, ops=(), modules=(), host=(), scoped=None,
                 counters=None, window_ms=100):
        self.spec = _spec.Spec()
        self.trace = T.Trace(
            {0: list(ops)}, {0: list(modules)},
            [("main", "bench.window", 0, window_ms * MS)] + list(host))
        self.trace.scoped = scoped
        self.t0, self.t1 = T.window(self.trace)
        self.host = {"counters": counters or {}}

        class cell:
            name = "no_such_cell"
            chips = 1
        self.cell = cell

    def read(self, name):
        return self.spec.module("layer_metrics", name).read(self)


def _turn(t, decode_wait_ms, chunk_wait_ms=None):
    """One scheduler turn starting at ``t`` ms: admit 0.2 ms, an optional
    chunk call (dispatch 1 ms, wait), a decode step (dispatch 2 ms, wait),
    emit 0.5 ms, and 0.3 ms of self time at the end.  Returns the spans
    and the turn's end."""
    spans, c = [], t * MS

    def add(name, dur, at=None):
        spans.append((ENG, name, c if at is None else at, int(dur)))

    start = c
    add("serving.admit", 0.2 * MS)
    c += int(0.2 * MS)
    for name, wait in (("serving.prefill_chunk", chunk_wait_ms),
                       ("serving.decode_step", decode_wait_ms)):
        if wait is None:
            continue
        disp = (1 if "chunk" in name else 2) * MS
        add(name, disp + wait * MS)
        add("serving.dispatch", disp)
        add("serving.device_wait", wait * MS, at=c + disp)
        c += disp + wait * MS
    add("serving.emit", 0.5 * MS)
    c += int(0.8 * MS)
    spans.append((ENG, "serving.iteration", start, c - start))
    return spans, c / MS


# ------------------------------------------------------------ sched_host_ms
def test_iteration_self_time_with_nested_children():
    a, end = _turn(1, decode_wait_ms=6)
    b, _ = _turn(end, decode_wait_ms=6, chunk_wait_ms=20)
    obs = Obs(host=a + b + [("main", "serving.submit", 2 * MS, MS)])
    # turn a: 0.2 + 2 + 0.5 + 0.3 = 3.0 of host; turn b: one more ms of
    # chunk dispatch
    assert P.iteration_host_ms(obs.trace, obs.t0, obs.t1) == \
        pytest.approx([3.0, 4.0])
    assert obs.read("sched_host_ms") == pytest.approx(3.5)
    assert obs.host["sched_host_ms_median"] == pytest.approx(3.5)
    table = obs.host["engine_span_ms"]
    assert table["serving.iteration"] == [pytest.approx((9.0 + 30.0) / 2), 2]
    assert table["serving.device_wait"] == [pytest.approx(32 / 3), 3]
    assert table["serving.dispatch"] == [pytest.approx(5 / 3), 3]
    assert "serving.submit" not in table


def test_an_iteration_cut_by_the_window_is_left_out():
    a, _ = _turn(95, decode_wait_ms=6)          # ends after 100 ms
    b, _ = _turn(1, decode_wait_ms=6)
    obs = Obs(host=a + b)
    assert P.iteration_host_ms(obs.trace, obs.t0, obs.t1) == \
        pytest.approx([3.0])


def test_a_wait_on_another_thread_is_not_the_iterations():
    a, _ = _turn(1, decode_wait_ms=6)
    other = [("replica-2", "serving.device_wait", 2 * MS, MS)]
    obs = Obs(host=a + other)
    assert obs.read("sched_host_ms") == pytest.approx(3.0)


# -------------------------------------------------- idle under each span
def _busy(*intervals):
    return [(f"%fusion.{i} = f32[1] fusion(...)", int(a * MS),
             int((b - a) * MS)) for i, (a, b) in enumerate(intervals)]


def test_innermost_segments():
    spans = [(ENG, "serving.iteration", 0, 10 * MS),
             (ENG, "serving.decode_step", 2 * MS, 6 * MS),
             (ENG, "serving.dispatch", 2 * MS, 1 * MS),
             (ENG, "serving.device_wait", 3 * MS, 5 * MS),
             (ENG, "serving.idle_wait", 12 * MS, 2 * MS)]
    assert P.innermost(spans) == [
        (0, 2 * MS, "serving.iteration"),
        (2 * MS, 3 * MS, "serving.dispatch"),
        (3 * MS, 8 * MS, "serving.device_wait"),
        (8 * MS, 10 * MS, "serving.iteration"),
        (12 * MS, 14 * MS, "serving.idle_wait")]


def test_idle_gap_under_each_span():
    # the turn: admit 1.0-1.2, dispatch 1.2-3.2, wait 3.2-9.2, emit
    # 9.2-9.7, self 9.7-10.0; the device runs 3.0-9.0 and from 10.5 on
    turn, end = _turn(1, decode_wait_ms=6)
    assert end == pytest.approx(10.0)
    obs = Obs(ops=_busy((0, 1.0), (3.0, 9.0), (10.5, 100)), host=turn)
    by, idle = P.idle_by_span(obs.trace, obs.t0, obs.t1)
    assert idle == int(2.0 * MS) + int(1.5 * MS)
    assert {k: v / MS for k, v in by.items()} == pytest.approx({
        "serving.admit": 0.2, "serving.dispatch": 1.8,
        "serving.device_wait": 0.2, "serving.emit": 0.5,
        "serving.iteration": 0.3, "no program span": 0.5})
    assert obs.read("idle_named_share.serve") == pytest.approx(
        100 * 3.0 / 3.5)
    assert obs.host["idle_by_program_span"]["serving.dispatch"] == \
        pytest.approx(1.8e-3)


def test_the_clock_offset_is_found_and_shifted_by():
    # the host's clock runs 1.0 ms behind the device's; a read-back ends
    # 0.1 ms (0.3 in the second pair) after its program on the device
    turn, _ = _turn(1, decode_wait_ms=6)        # wait ends at 9.2 (host)
    turn2, _ = _turn(20, decode_wait_ms=6)      # wait ends at 28.2 (host)
    modules = [("jit_step(1)", int(4.1 * MS), int(6.0 * MS)),   # ends 10.1
               ("jit_step(1)", int(22.9 * MS), int(6.0 * MS))]  # ends 28.9
    obs = Obs(ops=_busy((4.1, 10.1), (22.9, 28.9)), modules=modules,
              host=turn + turn2)
    offset, pairs = P.clock_offset_ns(obs.trace)
    assert offset == pytest.approx(-0.9 * MS, abs=2) and pairs == 2
    raw, _ = P.idle_by_span(obs.trace, obs.t0, obs.t1)
    shifted, _ = P.idle_by_span(obs.trace, obs.t0, obs.t1, offset)
    # unshifted, the device seems to idle 0.9 and 0.7 ms into the host's
    # waits; on its own clock the waits begin as the programs do (4.1 and
    # 23.1 against 4.1 and 22.9) and only 0.2 ms of idle time is left there
    assert raw["serving.device_wait"] / MS == pytest.approx(1.6, abs=1e-3)
    assert shifted["serving.device_wait"] / MS == pytest.approx(0.2, abs=1e-3)
    assert shifted["serving.dispatch"] / MS == pytest.approx(3.8, abs=1e-3)
    obs.read("idle_named_share.serve")
    assert obs.host["program_clock_offset_ms"] == pytest.approx(-0.9, abs=1e-3)
    assert obs.host["program_clock_pairs"] == 2


def test_a_wait_far_from_every_program_is_no_pair():
    turn, _ = _turn(1, decode_wait_ms=6)
    obs = Obs(ops=_busy((50, 60)), modules=[("jit_step(1)", 50 * MS, 40 * MS)],
              host=turn)
    assert P.clock_offset_ns(obs.trace) == (0, 0)


# ------------------------------------------------------- missing -> None
@pytest.mark.parametrize("metric", [
    "sched_host_ms", "idle_named_share.serve", "decode_batch_mean",
    "page_util_mean", "chunk_attn_device_ms", "train_dispatch_ms",
    "head_loss_device_ms"])
def test_a_program_without_the_span_name_or_counter_reads_nothing(metric):
    """The parent of PR 24 under these readers: ``bench.*`` spans, JAX's own
    events, unnamed kernels, the old counters."""
    obs = Obs(ops=_busy((0, 50)) + [(f"%chunk.3 = f32[1] {MOSAIC}", 60 * MS,
                                     6 * MS)],
              modules=[("jit_step(1)", 0, 50 * MS)],
              host=[("main", "bench.idle_generator", MS, 90 * MS),
                    (ENG, "PjitFunction(step)", 2 * MS, MS),
                    ("main", "serving.submit", 3 * MS, MS)],
              scoped=[("%fusion.1 = f32[1] fusion(...)", 0, 50 * MS,
                       "jit(step)/dot_general:"),
                      (f"%chunk.3 = f32[1] {MOSAIC}", 60 * MS, 6 * MS,
                       "jit(chunk)/pallas_call:")],
              counters={"serving.step_seconds_sum": 1.0,
                        "serving.step_seconds_count": 20.0})
    assert obs.read(metric) is None


def test_no_trace_file_reads_no_scope():
    obs = Obs(ops=_busy((0, 50)))
    assert trace_scopes.of(obs) == []
    assert obs.read("chunk_attn_device_ms") is None


# --------------------------------------------------------------- counters
def test_means_of_the_counts_at_a_dispatch():
    obs = Obs(counters={"serving.decode_batch_size_sum": 290.0,
                        "serving.decode_batch_size_count": 20.0,
                        "serving.step_page_utilization_sum": 9.0,
                        "serving.step_page_utilization_count": 20.0})
    assert obs.read("decode_batch_mean") == pytest.approx(14.5)
    assert obs.read("page_util_mean") == pytest.approx(45.0)


# ------------------------------------------------- names on device work
def test_scope_is_matched_as_a_whole_component():
    ev = [("a", 0, 1, "jit(step)/jvp(forward_loss)/lm_head_loss/dot_general:"),
          ("b", 0, 1, "jit(step)/transpose(jvp(forward_loss))/lm_head_loss/"
                      "transpose:"),
          ("c", 0, 1, "jit(step)/transpose(jvp(lm_head_loss))/mul:"),
          ("d", 0, 1, "jit(step)/lm_head_loss2/dot_general:"),
          ("e", 0, 1, "jit(step)/my.lm_head_loss/dot_general:"),
          ("f", 0, 1, "")]
    assert [e[0] for e in trace_scopes.under(ev, "lm_head_loss")] == \
        ["a", "b", "c"]
    assert [e[0] for e in trace_scopes.under(ev, "forward_loss")] == ["a", "b"]


def test_chunk_attention_kernel_time():
    scoped = [(f"%chunk_attention.{i} = f32[1] {MOSAIC}", (10 + 8 * i) * MS,
               d * MS, "jit(chunk)/chunk_attention/pallas_call:")
              for i, d in enumerate((5, 6, 7))]
    scoped += [
        # an inner name later on does not hide the scope
        (f"%paged_decode.9 = f32[1] {MOSAIC}", 40 * MS, 9 * MS,
         "jit(chunk)/chunk_attention/paged_decode/pallas_call:"),
        # the decode program's kernel and a fusion under the scope are not it
        (f"%step.1 = f32[1] {MOSAIC}", 50 * MS, 1 * MS,
         "jit(step)/pallas_call:"),
        ("%fusion.2 = f32[1] fusion(...)", 52 * MS, 30 * MS,
         "jit(chunk)/chunk_attention/reshape:"),
        # outside the window
        (f"%chunk_attention.7 = f32[1] {MOSAIC}", 200 * MS, 50 * MS,
         "jit(chunk)/chunk_attention/pallas_call:")]
    obs = Obs(ops=_busy((0, 90)), scoped=scoped)
    assert obs.read("chunk_attn_device_ms") == pytest.approx(6.5)
    assert obs.host["chunk_attn_kernels"] == 4


def test_head_and_loss_time_per_step():
    # two steps of 40 ms whole inside the window and one cut by its start;
    # under the scope a step has 3 ms forward and 5 ms backward
    modules = [("jit_step(7)", (-20 + 40 * i) * MS, 40 * MS)
               for i in range(3)]
    scoped = []
    for i in range(3):
        t = (-20 + 40 * i) * MS
        scoped += [
            ("%fusion.1 = f32[8] fusion(...)", t + 22 * MS, 3 * MS,
             "jit(step)/jvp(forward_loss)/lm_head_loss/dot_general:"),
            ("%fusion.2 = f32[8] fusion(...)", t + 26 * MS, 5 * MS,
             "jit(step)/transpose(jvp(forward_loss))/lm_head_loss/"
             "dot_general:"),
            ("%fusion.3 = f32[8] fusion(...)", t + 32 * MS, 4 * MS,
             "jit(step)/optimizer_step/mul:")]
    obs = Obs(ops=_busy((0, 100)), modules=modules, scoped=scoped)
    # the step that began before the window is left out, with its head and
    # loss, though those lie inside the window
    assert obs.read("head_loss_device_ms") == pytest.approx(8.0)
    assert obs.host["head_loss_ops_per_step"] == pytest.approx(2.0)


def test_train_dispatch_time():
    host = [("main", "jit.train_step", (5 + 30 * i) * MS, d * US)
            for i, d in enumerate((900, 1100, 1600))]
    host.append(("main", "jit.train_step", 99 * MS, 5 * MS))   # cut: out
    obs = Obs(host=host)
    assert obs.read("train_dispatch_ms") == pytest.approx(1.2)


# ------------------------------------------- the loader on a chip's trace
def test_scopes_of_the_recorded_trace():
    """The wire-format reader gives what ``ProfileData`` gives, event for
    event, and the name stack besides."""
    plain = T.load(DATA)
    scoped = trace_scopes.load(DATA)
    assert sorted(scoped) == plain.devices() == [0]
    assert [e[:3] for e in scoped[0]] == plain.ops[0]
    fusions = [e for e in scoped[0] if e[0].startswith("%fusion")]
    assert len(fusions) == 5
    assert {e[3] for e in fusions} == {"jit(small_step)/dot_general:"}
    assert len(trace_scopes.under(scoped[0], "small_step")) == 5
    assert trace_scopes.under(scoped[0], "step") == []
