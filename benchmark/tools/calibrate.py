#!/usr/bin/env python3
"""Builder's tool, never run by the benchmark's own runs: a cell's numbers
on many seeds, with the control beside them.

    python3 benchmark/tools/calibrate.py --workload W --seeds 1,2,3 \
        --seconds 12 [--control fp8[,half_batch]] [--out chiprun_out/cal.jsonl]

Each seed is one whole run of the cell (window of `--seconds`), followed,
with ``--control``, by the reference in the lower precision (or with the
fault planted) put in the program's place.  One line per seed: the numbers
compared, the control's readings and the end-to-end metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as R  # noqa: E402
from benchmark.harness import common as C  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--bench-file", default=None)
    args = ap.parse_args()
    cell = C.load_cell(args.workload, args.bench_file)
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        R.T_START = time.time()
        try:
            rec = R.run_cell(cell, seed, args.seconds, False,
                             control=args.control)
            row = {"seed": seed,
                   "numbers": {k: v["value"] for k, v in rec["checks"].items()},
                   "limits": {k: v["limit"] for k, v in rec["checks"].items()},
                   "control": rec["notes"].get("control"),
                   "metrics": rec["metrics"], "setup_s": rec["setup_s"],
                   "memory_peak_bytes": rec["device"]["memory_peak_bytes"],
                   "notes": {k: rec["notes"].get(k) for k in (
                       "reference_s", "checked_tokens", "compiles_in_window",
                       "steps_in_window", "judged_requests", "prog_losses",
                       "ref_losses", "change_gap_by_leaf",
                       "grad_gap_by_leaf")}}
        except C.BenchError as e:
            row, rc = {"seed": seed, "error": str(e)[-3000:]}, 1
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
