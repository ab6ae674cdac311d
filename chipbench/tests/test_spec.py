"""(a) every name in BENCHMARK.json leads to a file; bad names are refused;
(b) a new cell, configuration, mix and metric are files and entries only;
(c) the result line has the contract's keys; (d) no TPU, no timing."""

import filecmp
import json
import os
import time

import pytest

from chipbench import run, spec as _spec, trace_reduce
from . import toy

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_every_named_thing_loads():
    spec = _spec.Spec()
    assert 1 <= spec.run_seconds <= 51
    for cell in spec.cells():
        wl = cell.workload
        spec.module("entries", wl["entry"])
        spec.module("traffic", wl["kind"])
        for kind in ("models", "reference", "costs"):
            spec.module(kind, cell.config["family"])
        names = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer()
        for m in cell.per_layer():
            assert callable(spec.module("layer_metrics", m["name"]).read)
            assert m["moves"] in names
        assert len(cell.why) <= 200 and wl["who"]
        assert all(k in cell.config for k in ("source", "reduced", "assumed"))
    files = [c["file"] for c in spec.data["configs"]]
    assert len(set(files)) == len(files)
    assert all(f.startswith("chipbench/") for f in files)


@pytest.mark.parametrize("bad", ["a/b", "../x", "a b", "a,b", ".hid", "-x",
                                 "", "x" * 65, "café", "a..b", None])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        _spec.check_name(bad)
    if isinstance(bad, str):
        with pytest.raises((ValueError, FileNotFoundError)):
            _spec.load_module("layer_metrics", bad)


@pytest.mark.parametrize("good", ["gpt2_medium.lm_train", "device_idle_share.serve",
                                  "a-b", "_x", "9lives"])
def test_good_names_pass(good):
    assert _spec.check_name(good) == good


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        _spec.Spec().cell("no_such.cell")


def test_new_things_are_new_files_only(toy_root, toy_spec):
    """The toy root adds a configuration, two mixes and two cells; here a
    metric is added too.  Every harness file is still byte for byte the
    repo's, and the new cell runs."""
    bench = os.path.join(toy_root, "chipbench")
    with open(os.path.join(bench, "layer_metrics", "steps_counted.py"), "w") as f:
        f.write("def read(obs):\n    return float(obs.host['steps'])\n")
    for folder, _, names in os.walk(toy.BENCH):
        if "__pycache__" in folder or folder.startswith(
                (os.path.join(toy.BENCH, "tests"), os.path.join(toy.BENCH, "data"))):
            continue
        rel = os.path.relpath(folder, toy.BENCH)
        for name in names:
            assert filecmp.cmp(os.path.join(folder, name),
                               os.path.join(bench, rel, name), shallow=False)
    cell = toy_spec.cell("toy_gpt.toy_batches")
    assert cell.config["n_embd"] == 64 and cell.workload["batch_size"] == 4
    metric = toy_spec.module("layer_metrics", "steps_counted")

    class Obs:
        host = {"steps": 7}
    assert metric.read(Obs) == 7.0


def _fake_trace():
    ms = 1_000_000
    ops = {0: [("fusion.1", 0, 2 * ms), ("custom-call.7", 3 * ms, 1 * ms)]}
    host = [("main", "bench.window", 0, 10 * ms),
            ("main", "bench.step_call", 2 * ms, 1 * ms)]
    return trace_reduce.Trace(ops, {0: [("jit_step(1)", 0, 4 * ms)]}, host)


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_has_the_contracts_keys(toy_spec, monkeypatch, trace):
    """A whole run of the toy training cell, the look for a chip skipped.
    The CPU has no device plane, so the traced run reads a made-up trace."""
    monkeypatch.setattr(trace_reduce, "load", lambda path: _fake_trace())
    cell = toy_spec.cell("toy_gpt.toy_batches")
    result = run.run_cell(toy_spec, cell, 2 ** 31 + 5, 0.5, trace,
                          t0=time.time())
    keys = list(result)
    assert keys[-1] == "compared"
    assert keys[:-1] == CONTRACT_KEYS + (["breakdown"] if trace else [])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    wanted = cell.per_layer() if trace else cell.end_to_end()
    for m in wanted:
        if m["name"] in result["metrics"]:
            got = result["metrics"][m["name"]]
            assert set(got) == {"value", "unit"} and got["unit"] == m["unit"]
            assert isinstance(got["value"], float) and got["value"] > 0
    if trace:
        assert result["device"]["busy_s"] == pytest.approx(0.003)
        assert result["device"]["window_s"] == pytest.approx(0.010)
        assert result["breakdown"]["device_ops"][0] == ["fusion.1", 0.002]
        assert len(result["breakdown"]["idle_gaps"]) <= 10
        assert "train_mfu" not in result["metrics"]     # no peak for a CPU
        assert "device_idle_share.train" in result["metrics"]
    else:
        assert set(result["metrics"]) == {m["name"] for m in wanted}
    for v in result["compared"].values():
        assert set(v) == {"value", "limit"}
    json.dumps(result)


def test_no_tpu_no_timing(capsys):
    """The timing path raises without a TPU instead of falling back, and the
    command prints no result."""
    with pytest.raises(run.NoChip):
        run.device_facts(1)
    rc = run.main(["--workload", "gpt2_medium.lm_train", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == run.NO_CHIP and rc not in (0, 2, 3)
    assert out.out == "" and "not 'tpu'" in out.err
