#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one metric
sits in a file of its own, found by the name in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``.
The configuration's ``kind`` selects the window driver (``serve``,
``train``).  This process never imports jax; it is the load generator and
the parent of the processes that hold the chip, one at a time.  No chip (or
fewer than the cell asks for): non-zero exit and no result line.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import time

T_START = time.time()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import common as C  # noqa: E402


def read_metrics(rec: dict, metrics: list) -> dict:
    """Each metric from its own reader, ``metrics/<name>.py``; a dotted
    suffix (``ttft_p95_ms.closed``) selects a variant of a shared reader.
    A reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for m in metrics:
        base, _, variant = m["name"].partition(".")
        reader = importlib.import_module("benchmark.metrics." + base)
        value = reader.read(rec, variant or None)
        if value is not None:
            out[m["name"]] = value
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             control: str | None = None) -> dict:
    """Runs the cell once and returns the result object's pieces."""
    kind = cell["config"]["kind"]
    driver = importlib.import_module(f"benchmark.harness.{kind}_window")
    rec = driver.run(cell, seed, seconds, trace, T_START, control=control)
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    rec["metrics"] = read_metrics(rec, wanted)
    rec["units"] = {m["name"]: m["unit"] for m in wanted}
    if trace and rec.get("trace"):
        # what the trace holds besides: programs, and kernels by program
        kernels: dict = {}
        for k in rec["trace"].get("kernels", []):
            key = f"{k['module']}:{k['name']}_{k['shape']}"
            n, sec = kernels.get(key, (0, 0.0))
            kernels[key] = (n + 1, sec + k["ns"] / 1e9)
        rec["notes"]["trace_module_s"] = rec["trace"].get("module_s")
        rec["notes"]["trace_kernels"] = kernels
    return rec


def line_of(rec: dict, trace: bool) -> str:
    device, breakdown = dict(rec["device"]), None
    if trace:
        tr = rec.get("trace") or {}
        C.need(tr.get("busy_s", 0) > 0,
               "the traced run saw no operation on the device")
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    return C.result_line(
        checks=rec["checks"], attempted=len(rec["judged"]),
        failed=len(rec["failed"]), metrics=rec["metrics"],
        units=rec["units"], device=device, notes=rec["notes"],
        breakdown=breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench-file", default=None,
                    help="another BENCHMARK.json (the tests' tiny one)")
    args = ap.parse_args(argv)
    try:
        cell = C.load_cell(args.workload, args.bench_file)
        rec = run_cell(cell, args.seed, args.seconds, bool(args.trace))
        line = line_of(rec, bool(args.trace))
    except C.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
