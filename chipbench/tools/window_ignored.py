"""A negative control of a cell whose configuration has sliding-window
layers: the program with every window layer attending the WHOLE context
(``sliding_window_layout`` all full, the rotary positions as they were),
held to the reference with the window by the cell's own comparison.
``correct`` has to read false wherever a compared request outgrew the
window.

    python3 -m chipbench.tools.window_ignored --workload <cell> --seed <n> \\
        --seconds 6 --num-pages 12288

Without the window every layer keeps every page of a sequence in one pool,
so the cell's slots fit beside the weights only with fewer pages
(``--num-pages``: admission waits for them).  Prints the run's compared
numbers as one JSON line, as ``tools/readings.py`` prints a control's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from .. import run as _run, spec as _spec
from .readings import _plain


def window_ignored(spec, cell, seed, seconds, num_pages, device=None):
    family = spec.module("models", cell.config["family"])
    made = family.program_config

    def without_window(cfg, dtype=None):
        conf = made(cfg, dtype)
        conf["sliding_window_layout"] = [0] * len(conf.sliding_window_layout)
        return conf

    family.program_config = without_window
    cell.workload["engine"] = dict(cell.workload["engine"],
                                   num_pages=int(num_pages))
    kept = {}
    try:
        result = _run.run_cell(spec, cell, seed, seconds, False,
                               t0=time.time(), device=device, kept=kept)
    finally:
        family.program_config = made
    served = kept.get("served", [])
    return {"workload": cell.name, "seed": seed, "what": "window_ignored",
            "correct": result["correct"], "compared": result["compared"],
            "numbers": _plain(kept.get("numbers", {})),
            "longest_compared": max((len(p) + len(o) for p, o in served),
                                    default=0)}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.tools.window_ignored")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--num-pages", type=int, required=True)
    args = ap.parse_args(argv)
    spec = _spec.Spec()
    cell = spec.cell(args.workload)
    device = _run.claim_chip(spec, cell.chips)
    print(json.dumps(window_ignored(spec, cell, args.seed, args.seconds,
                                    args.num_pages, device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
