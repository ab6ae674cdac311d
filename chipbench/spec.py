"""What ``BENCHMARK.json`` names, and where each named thing lives.

The harness knows no cell, configuration or metric by name.  A name in
``BENCHMARK.json`` leads to a file of its own:

- a cell ``<name>``            -> ``chipbench/workloads/<name>.json`` (its
  deployment: entry, engine or optimizer, limits of ``correct``)
- a traffic mix ``<traffic>``  -> ``chipbench/mixes/<traffic>.json`` (lengths,
  rates, clients, batch: data that one general generator reads)
- a configuration              -> the ``file`` its entry gives
- a per-layer metric ``<name>``-> ``chipbench/layer_metrics/<name>.py``
- a cell's ``entry``           -> ``chipbench/entries/<entry>.py``
- a cell's traffic ``kind``    -> ``chipbench/traffic/<kind>.py``
- a configuration's ``family`` -> ``chipbench/models/<family>.py`` (how the
  program builds it) and ``chipbench/reference/<family>.py`` (the plain
  reference)
- a cell's ``optimizer.name``  -> ``chipbench/optimizers/<name>.py`` (how the
  program builds it and hands back the first gradient) and
  ``chipbench/reference/<name>.py`` (its published rule)

so a later PR adds any of them as new files plus new entries and edits no
file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_name(name):
    """A name is at most 64 letters, digits, ``_``, ``.`` and ``-`` and does
    not start with ``.`` or ``-``: it becomes a file name, so nothing that
    could lead out of the benchmark's directory is let through."""
    if not isinstance(name, str) or not _NAME.match(name) or ".." in name:
        raise ValueError(f"not a valid benchmark name: {name!r}")
    return name


def load_module(kind, name, root=HERE):
    """``chipbench/<kind>/<name>.py`` as a module.  Loaded by path, since a
    metric's name may hold dots (``device_idle_share.serve``)."""
    check_name(name)
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    mod_name = f"chipbench.{kind}.{name.replace('.', '_dot_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads``: its own file, its configuration's file,
    and the metrics that ``BENCHMARK.json`` says it reports."""

    def __init__(self, spec, entry):
        self.spec = spec
        self.name = check_name(entry["name"])
        self.chips = int(entry["chips"])
        self.traffic = check_name(entry["traffic"])
        self.why = entry.get("why", "")
        cfg_entry = spec.config_entry(check_name(entry["config"]))
        self.config_name = cfg_entry["name"]
        self.config = _read_json(os.path.join(spec.root, cfg_entry["file"]))
        # the traffic mix's parameters, then the cell's own (its deployment,
        # its limits), which may override one
        self.workload = dict(
            _read_json(os.path.join(spec.bench_dir, "mixes",
                                    self.traffic + ".json")),
            **_read_json(os.path.join(spec.bench_dir, "workloads",
                                      self.name + ".json")))
        for key in ("entry", "kind"):
            check_name(self.workload[key])

    def _reports(self, metric):
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self):
        return [m for m in self.spec.data["end_to_end"] if self._reports(m)]

    def per_layer(self):
        return [m for m in self.spec.data["per_layer"] if self._reports(m)]


class Spec:
    def __init__(self, root=ROOT, bench_dir=None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "chipbench")
        self.data = _read_json(os.path.join(root, "BENCHMARK.json"))
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for e in self.data[group]:
                check_name(e["name"])
        self.run_seconds = int(self.data["run_seconds"])
        self._modules = {}

    def config_entry(self, name):
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def cell(self, name):
        check_name(name)
        for w in self.data["workloads"]:
            if w["name"] == name:
                return Cell(self, w)
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def cells(self):
        return [Cell(self, w) for w in self.data["workloads"]]

    def module(self, kind, name):
        """The file ``<kind>/<name>.py`` as a module, loaded once."""
        if (kind, name) not in self._modules:
            self._modules[kind, name] = load_module(kind, name,
                                                    root=self.bench_dir)
        return self._modules[kind, name]
