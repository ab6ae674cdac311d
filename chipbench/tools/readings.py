"""The readings a limit of ``correct`` is set from, many seeds in one
process (set-up is long; each seed still builds its own model, engine or
step from its own weights).

    python3 -m chipbench.tools.readings --workload <cell> --seeds 1,2,3 \
        --seconds 6 --stand-ins 3 [--out chiprun_out/readings.jsonl]
    python3 -m chipbench.tools.readings --workload <cell> --judge <that file>

For every seed it makes one whole run of the cell (``run.run_cell``: set-up,
a window of ``--seconds``, the comparison with the plain reference) and
prints the numbers compared: the program's lower readings.  For the first
``--stand-ins`` seeds it then puts the control and each fault the cell can
have in the program's place (the entry driver's ``stand_ins``), prints
their numbers (the upper readings) and holds them to the cell's limits by
the run's own comparison (``compare.judge``): ``correct`` has to read false
for each.  One JSON object per line.  Limits are set after the readings
are taken: ``--judge`` holds the rows of a recorded file to the limits as
the cell's file has them now, and needs no chip.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from .. import compare, run as _run, spec as _spec


def _plain(numbers):
    return {k: v for k, v in numbers.items()
            if isinstance(v, (int, float, str)) or v is None}


def judged(cell, seed, what, numbers):
    """A stand-in's numbers beside the cell's limits, as a run judges the
    program's: ``correct`` false is what a control or a fault has to read."""
    checks = compare.judge(numbers, cell.workload["limits"])
    return {"workload": cell.name, "seed": seed, "what": what,
            "correct": all(c["ok"] for c in checks),
            "numbers": _plain(numbers),
            "compared": {c["name"]: {"value": c["value"], "limit": c["limit"]}
                         for c in checks},
            "failed_numbers": [c["name"] for c in checks if not c["ok"]]}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.tools.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds")
    ap.add_argument("--judge", default=None)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--stand-ins", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    spec = _spec.Spec()
    cell = spec.cell(args.workload)
    if args.judge:
        with open(args.judge) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        for row in rows:
            if row["workload"] == cell.name:
                print(json.dumps(judged(cell, row["seed"], row["what"],
                                        row["numbers"])))
        return 0
    device = _run.claim_chip(spec, cell.chips)
    entry = spec.module("entries", cell.workload["entry"])
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            kept = {}
            t0 = time.time()
            result = _run.run_cell(spec, cell, seed, args.seconds, False,
                                   t0=t0, device=device, kept=kept)
            emit({"workload": cell.name, "seed": seed, "what": "program",
                  "correct": result["correct"],
                  "numbers": _plain(kept["numbers"]),
                  "compared": result["compared"],
                  "metrics": result["metrics"], "seconds": time.time() - t0})
            if i < args.stand_ins:
                ctx = _run.RunContext(spec, cell, seed, args.seconds, False,
                                      t0, device)
                for name, numbers in entry.stand_ins(ctx, kept).items():
                    emit(judged(cell, seed, name, numbers))
            kept.clear()
            gc.collect()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
