#!/usr/bin/env python3
"""Builder's tool: one sweep of an open-loop cell over arrival rates, to
find the highest rate the system sustains without a growing backlog.  The
cell then runs at four fifths of it; the number goes into the traffic file.

    python3 benchmark/tools/sweep.py --workload W --rates 1.5,2,2.5,3,3.5 \
        --seconds 40 --seed 77 --out chiprun_out/sweep.jsonl
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=77)
    ap.add_argument("--out", default=None)
    ap.add_argument("--bench-file", default=None)
    args = ap.parse_args()
    cell = C.load_cell(args.workload, args.bench_file)
    for rate in (float(r) for r in args.rates.split(",")):
        cell["traffic"]["rate_per_s"] = rate
        R.T_START = time.time()
        try:
            rec = R.run_cell(cell, args.seed, args.seconds, False)
            w = rec["window"]
            # a backlog that grows: arrivals of the window's second half
            # wait longer for their first token than those of its first
            half = w["t_open"] + args.seconds / 2
            ttft = lambda rs: sorted(
                (r.token_times[0] - r.due) for r in rs if r.token_times)
            first = ttft([r for r in rec["judged"] if r.due < half])
            second = ttft([r for r in rec["judged"] if r.due >= half])
            med = lambda xs: xs[len(xs) // 2] if xs else None
            row = {"rate_per_s": rate, "correct": all(
                       c["value"] <= c["limit"] for c in rec["checks"].values()),
                   "metrics": rec["metrics"],
                   "requests": len(rec["judged"]),
                   "failed": len(rec["failed"]),
                   "ttft_median_s_first_half": med(first),
                   "ttft_median_s_second_half": med(second),
                   "drain_s": rec["notes"]["drain_s"],
                   "queue_depth_at_close":
                       rec["metrics_close"].get("tpujob_serve_queue_depth"),
                   "tokens_per_s": sum(len(r.tokens) for r in rec["judged"])
                       / args.seconds}
        except C.BenchError as e:
            row = {"rate_per_s": rate, "error": str(e)[-2000:]}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
