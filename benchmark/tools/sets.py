#!/usr/bin/env python3
"""Builder's tool: runs of the committed command, as the driver makes them,
each in a new process, their result lines gathered into one file.

    python3 benchmark/tools/sets.py --workload W --seeds 11,12,13 \
        --seconds 51 --trace 0 --tag A --out chiprun_out/w_sets.jsonl

Every run's standard output and error are kept under ``chiprun_out/<tag>/``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", default="A")
    ap.add_argument("--out", required=True)
    ap.add_argument("--bench-file", default=None)
    args = ap.parse_args()
    bench = json.load(open(args.bench_file or os.path.join(ROOT, "BENCHMARK.json")))
    keep = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(keep, exist_ok=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rc = 0
    for seed in args.seeds.split(","):
        cmd = bench["command"] + ["--workload", args.workload, "--seed", seed,
                                  "--seconds", str(args.seconds),
                                  "--trace", str(args.trace)]
        if args.bench_file:
            cmd += ["--bench-file", args.bench_file]
        t0 = time.time()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           env={**os.environ, "BENCH_RUN": "sets"})
        took = time.time() - t0
        stem = os.path.join(keep, f"{args.workload}_{seed}_t{args.trace}")
        open(stem + ".out", "w").write(p.stdout)
        open(stem + ".err", "w").write(p.stderr[-20000:])
        kept = os.path.join(ROOT, ".bench_work", "requests.json")
        if os.path.exists(kept):
            os.replace(kept, stem + ".requests.json")
        last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
        try:
            res = json.loads(last)
        except ValueError:
            res = {"error": p.stderr[-1500:]}
        row = {"tag": args.tag, "workload": args.workload, "seed": int(seed),
               "trace": args.trace, "rc": p.returncode,
               "wall_s": round(took, 1), **res}
        rc = rc or p.returncode or (0 if res.get("correct") else 1)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        brief = {k: v["value"] for k, v in res.get("metrics", {}).items()}
        print(json.dumps({"tag": args.tag, "seed": int(seed),
                          "rc": p.returncode, "correct": res.get("correct"),
                          "wall_s": round(took, 1), **brief}), flush=True)
        if p.returncode:
            print(p.stderr[-1500:], flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
