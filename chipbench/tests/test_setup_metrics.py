"""The five ``setup_*`` readers on a made-up record with known answers:
what ended before the window counts and what ended after it does not, a
program without the query reads ``None``, and so does a sum of nought."""

import time

import pytest

from chipbench import setup_record, spec as _spec
from paddle_tpu.observability import programs

NAMES = ("setup_trace_lower_s", "setup_executable_s", "setup_programs_built",
         "setup_engine_init_s", "setup_import_s")


class Obs:
    def __init__(self, t_open, setup_s):
        self.spec = _spec.Spec()
        self.host = {"t_open": t_open}
        self.end_to_end = {"setup_s": setup_s}

    def read(self, name):
        return self.spec.module("layer_metrics", name).read(self)


def _build(rec, name, trace, lower, executable, hit=False):
    """One program's three builds as JAX announces them: a start, the
    pause it "took", a cache hit inside the last, its duration."""
    for kind, seconds in (("trace", trace), ("lower", lower),
                          ("compile", executable)):
        rec.enter(kind)
        time.sleep(seconds)
        if hit and kind == "compile":
            rec.cache_hit()
        rec.built(kind, seconds, name)


@pytest.fixture()
def recorded(monkeypatch):
    """A record of its own behind the readers' query: an import, an engine
    built with a program inside its construction, a windowed and a loose
    program before the window; the reference's program after it."""
    rec = programs.BuildRecord()
    led = programs.ProgramLedger(record=rec)
    monkeypatch.setattr(setup_record, "query", lambda: led.builds)
    t0 = time.time()
    rec.add_phase("startup.import", None, t0, 0.004)
    time.sleep(0.005)
    with rec.phase("serving.engine_init"):
        with rec.phase("serving.engine_init.pools"):
            _build(rec, "zeros", 0.001, 0.001, 0.002)
        time.sleep(0.003)
    with rec.phase("serving.engine_start"):
        time.sleep(0.002)
    win = led.compile_window(("serve_step", 16), family="decode")
    _build(rec, "step", 0.002, 0.003, 0.004, hit=True)
    win.close()
    _build(rec, "loose", 0.001, 0.001, 0.001)
    t_open = time.time()
    _build(rec, "reference_forward", 0.005, 0.005, 0.005)
    return Obs(t_open, t_open - t0), rec


def test_readers_sum_what_ended_before_the_window(recorded):
    obs, rec = recorded
    got = {name: obs.read(name) for name in NAMES}
    assert got["setup_trace_lower_s"] == pytest.approx(
        0.001 + 0.001 + 0.002 + 0.003 + 0.001 + 0.001)
    assert got["setup_executable_s"] == pytest.approx(0.002 + 0.004 + 0.001)
    assert got["setup_programs_built"] == 3.0
    assert got["setup_import_s"] == pytest.approx(0.004)
    phases = rec.builds()["phases"]
    engine = sum(phases[n]["self_s"] for n in (
        "serving.engine_init", "serving.engine_init.pools",
        "serving.engine_start"))
    assert got["setup_engine_init_s"] == pytest.approx(engine)
    # construction without its build: at least the two pauses, and not
    # the 4 ms the program inside it took
    assert 0.005 <= engine < phases["serving.engine_init"]["seconds"] \
        + phases["serving.engine_start"]["seconds"] - 0.004 + 1e-9
    assert all(isinstance(v, float) and v > 0 for v in got.values())


def test_first_reader_fills_the_host_line(recorded):
    obs, _ = recorded
    obs.read("setup_import_s")
    host = obs.host
    assert list(host["setup_builds"]) == [
        repr(("serve_step", 16)), "zeros", "loose"]     # costliest first
    assert host["setup_builds"][repr(("serve_step", 16))] == [
        1, 0.002, 0.003, 0.0, 0.004, 1]
    assert "reference_forward" not in host["setup_builds"]
    assert host["setup_cache"] == {"hits": 1, "misses": 2,
                                   "compile_s": 0.003, "cache_load_s": 0.004}
    assert set(host["setup_phases"]) == {
        "startup.import", "serving.engine_init", "serving.engine_init.pools",
        "serving.engine_start"}
    seconds, self_s = host["setup_phases"]["serving.engine_init.pools"]
    assert self_s == pytest.approx(seconds - 0.004, abs=2e-4)
    # import + engine phases + builds + what is left = setup_s
    whole = sum(obs.read(n) for n in (
        "setup_import_s", "setup_engine_init_s", "setup_trace_lower_s",
        "setup_executable_s")) + host["setup_unaccounted_s"]
    assert whole == pytest.approx(obs.end_to_end["setup_s"], abs=1e-9)
    assert host["setup_unaccounted_s"] > 0


def test_a_program_without_the_query_reads_nothing(monkeypatch):
    monkeypatch.setattr(setup_record, "query", lambda: None)
    obs = Obs(time.time(), 1.0)
    assert [obs.read(name) for name in NAMES] == [None] * 5
    assert set(obs.host) == {"t_open"}


def test_a_sum_of_nought_reads_nothing(monkeypatch):
    """A process that built nothing and went through no phase inside the
    interval: ``None``, never 0.0; and a training cell has no engine."""
    rec = programs.BuildRecord()
    monkeypatch.setattr(setup_record, "query", lambda: rec.builds)
    obs = Obs(time.time(), 0.5)
    assert [obs.read(name) for name in NAMES] == [None] * 5
    assert obs.host["setup_builds"] == {} and obs.host["setup_phases"] == {}
    assert obs.host["setup_unaccounted_s"] == 0.5
    _build(rec, "train_step", 0.001, 0.001, 0.001)
    later = Obs(time.time(), 0.5)
    assert later.read("setup_programs_built") == 1.0
    assert later.read("setup_engine_init_s") is None
    assert later.read("setup_import_s") is None


def test_the_real_query_is_the_ledgers():
    assert setup_record.query() == programs.ledger().builds
