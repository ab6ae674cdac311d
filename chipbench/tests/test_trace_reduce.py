"""The reduction from a trace to numbers, on made-up events with known
answers and on the small trace recorded on the chip (``data/``)."""

import os

import pytest

from chipbench import trace_reduce as T

MS = 1_000_000
DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "data", "small_trace.xplane.pb")


def _trace():
    """A 20 ms window: a 2 ms step at 1, 6 and 14 ms (each a 1.5 ms matmul
    and an overlapping 1 ms kernel), the host idle-sleeping from 8 to 13."""
    ops, modules = [], []
    for t in (1, 6, 14):
        modules.append(("jit_step(77)", t * MS, 2 * MS))
        ops.append(("%fusion.1 = bf16[8,8] fusion(...)", t * MS, 3 * MS // 2))
        ops.append(("%custom-call.2 = custom-call(...) kernel", t * MS + MS, MS))
    host = [("main", "bench.window", 0, 20 * MS),
            ("main", "bench.step_call", 0, 1 * MS),
            ("main", "bench.wait_step", 3 * MS, 2 * MS + MS // 2),
            ("main", "bench.idle_generator", 8 * MS, 5 * MS),
            ("worker", "PjitFunction(step)", 8 * MS + MS // 2, MS),
            ("worker", "SomethingLong", 0, 100 * MS)]
    return T.Trace({0: ops}, {0: modules}, host)


def test_union_clip_and_gaps():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2)]
    assert T.union_ns(ev) == 20
    assert T.union_ns([]) == 0
    assert T.clip(ev, 8, 32) == [("a", 8, 2), ("b", 8, 7), ("c", 30, 2),
                                 ("d", 31, 1)]
    assert T.gaps(ev, 0, 40) == [(15, 15), (35, 5)]
    assert T.gaps([], 0, 7) == [(0, 7)]
    assert T.gaps(ev, 2, 12) == []


def test_window_busy_and_idle_share():
    tr = _trace()
    assert T.window(tr) == (0, 20 * MS)
    busy, window = T.busy_seconds(tr)
    assert busy == pytest.approx(0.006) and window == pytest.approx(0.020)
    # cut to the first 7 ms: the step at 1 ms whole, the one at 6 ms half
    busy, window = T.busy_seconds(tr, 0, 7 * MS)
    assert busy == pytest.approx(0.003) and window == pytest.approx(0.007)
    two = T.Trace({0: tr.ops[0], 1: []}, {}, tr.host)
    assert T.busy_seconds(two)[0] == pytest.approx(0.003)   # mean over chips
    with pytest.raises(ValueError):
        T.busy_seconds(T.Trace({}, {}, tr.host))
    bare = T.Trace({0: tr.ops[0]}, {}, [])
    assert T.window(bare) == (1 * MS, 16 * MS)


def test_time_by_name_and_matching():
    tr = _trace()
    by = T.time_by_name(tr.ops[0])
    assert by["%fusion.1 = bf16[8,8] fusion(...)"] == (9 * MS // 2, 3)
    kernels = T.matching(tr.ops[0], "KERNEL")
    assert len(kernels) == 3 and sum(d for _, _, d in kernels) == 3 * MS
    assert T.matching(tr.ops[0], "nothing") == []
    assert T.top_ops(tr)[0] == ["fusion.1 bf16[8,8] fusion", 0.0045]
    assert T.top_ops(tr, top=1) == [["fusion.1 bf16[8,8] fusion", 0.0045]]


def test_gaps_go_to_what_the_host_was_doing():
    tr = _trace()
    got = dict(T.idle_gaps(tr))
    # 3-6 ms -> wait_step; 8-14 -> idle_generator (the benchmark's own span
    # wins over the TraceMe inside it); 0-1 -> step_call; 16-20 -> nothing
    # of the benchmark's, so the innermost other event
    assert got["bench.wait_step"] == pytest.approx(0.003)
    assert got["bench.idle_generator"] == pytest.approx(0.006)
    assert got["bench.step_call"] == pytest.approx(0.001)
    assert got["SomethingLong [worker]"] == pytest.approx(0.004)
    assert sum(got.values()) == pytest.approx(0.014)
    assert T.host_cover(T.Trace({0: []}, {}, []), 5) == "no host span"
    assert T.idle_gaps(tr, top=2)[0][0] == "bench.idle_generator"


def test_short_names():
    assert T.short_name("%fusion.12 = bf16[4,8]{1,0:T(8,128)} fusion(bf16[4] %x), "
                        "kind=kOutput, calls=%f") == "fusion.12 bf16[4,8] fusion"
    assert T.short_name("jit_step(123)") == "jit_step(123)"
    assert len(T.short_name("%x = " + "f32[1]{0} " * 100 + "add(")) <= 160


def test_the_recorded_trace():
    tr = T.load(DATA)
    assert tr.devices() == [0]
    assert tr.lines[("/device:TPU:0", "XLA Ops")] == 15
    assert tr.lines[("/device:TPU:0", "XLA Modules")] == 5
    mods = T.time_by_name(tr.modules[0])
    (name, (total, n)), = mods.items()
    assert name.startswith("jit_small_step(") and n == 5
    assert 20_000 < total < 30_000                       # five runs of ~5 us
    t0, t1 = T.window(tr)
    assert (t1 - t0) / 1e9 == pytest.approx(0.0164, abs=0.001)
    busy, window = T.busy_seconds(tr)
    # the device's clock runs about 1.1 ms ahead of the host's in this
    # trace, so the first execution falls before the window's first span
    assert busy == pytest.approx(4 * 4.8e-6, rel=0.05)
    assert 1.0 - busy / window > 0.99
    spans = {n for _, n, _, _ in tr.host if n.startswith("bench.")}
    assert spans == {"bench.window", "bench.step_call", "bench.wait_step",
                     "bench.idle_generator"}
    gaps = dict(T.idle_gaps(tr))
    assert set(gaps) <= spans and "bench.idle_generator" in gaps
    assert sum(gaps.values()) == pytest.approx(window - busy, rel=1e-6)
    fusion = T.matching(tr.ops[0], "%fusion = ")
    assert len(fusion) == 5
    summary = T.summary(tr)
    assert summary["modules"][0][2] == 4 and summary["bench_spans"]
