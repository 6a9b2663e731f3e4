"""What every part of the benchmark shares, and nothing that imports jax.

The runner (``benchmark/run.py``) is the load generator's process and the
parent of every process that holds the chip, so this module stays off jax.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
WORK = os.path.join(ROOT, ".bench_work")      # git-ignored scratch
RESULT_TAG = "BENCH_CHILD_RESULT "
PY = sys.executable


class BenchError(Exception):
    """The run cannot give a result: non-zero exit, no result line."""


def need(cond, msg: str) -> None:
    """A check that survives ``python -O``."""
    if not cond:
        raise BenchError(msg)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_file: str | None = None) -> dict:
    """The cell's entry of ``workloads`` with its configuration and traffic
    files loaded: everything a run needs is found by the names in
    ``BENCHMARK.json``, so a new cell is new files and new entries."""
    bench_file = bench_file or os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    need(workload in cells, f"no workload {workload!r} in {bench_file}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    need(cell["config"] in configs, f"no config {cell['config']!r}")
    base = os.path.dirname(os.path.abspath(bench_file))
    config_file = os.path.join(base, configs[cell["config"]]["file"])
    traffic_dir = os.path.join(os.path.dirname(os.path.dirname(config_file)),
                               "traffic")
    traffic_file = os.path.join(traffic_dir, cell["traffic"] + ".json")

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload, "chips": cell["chips"],
        "config_name": cell["config"], "config_file": config_file,
        "config": load_json(config_file),
        "traffic_name": cell["traffic"], "traffic": load_json(traffic_file),
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": [m for m in bench["per_layer"] if reported(m)],
    }


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(**extra) -> dict:
    """The environment of a child that may hold the chip.  The compile cache
    follows ``utils/compile_cache.py``'s one rule (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` in the checkout); every program is kept
    there whatever its compile time, so that a second run compiles nothing."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["TPUJOB_FLIGHTREC_DIR"] = WORK
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.pop("BENCH_RUN", None)
    env.update({k: str(v) for k, v in extra.items()})
    return env


class Children:
    """Every process this run starts, so that none outlives it."""

    def __init__(self) -> None:
        self.procs: list = []

    def start(self, argv, env, log_name: str) -> subprocess.Popen:
        os.makedirs(WORK, exist_ok=True)
        log_path = os.path.join(WORK, log_name)
        with open(log_path, "w") as log:
            proc = subprocess.Popen(argv, env=env, cwd=ROOT, stdout=log,
                                    stderr=subprocess.STDOUT,
                                    start_new_session=True)
        proc.log_path = log_path
        self.procs.append(proc)
        return proc

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except OSError:
                    pass
            proc.wait()


def log_tail(proc, n: int = 40) -> str:
    with open(proc.log_path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def child_result(proc) -> dict | None:
    """The last object a child printed behind ``RESULT_TAG``."""
    result = None
    with open(proc.log_path, errors="replace") as f:
        for line in f:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
    return result


def run_child(children: Children, argv, env, log_name: str,
              timeout: float) -> dict:
    proc = children.start(argv, env, log_name)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise BenchError(f"{log_name}: no result after {timeout:.0f}s\n"
                         + log_tail(proc))
    result = child_result(proc)
    if rc != 0 or result is None:
        raise BenchError(f"{log_name}: exit code {rc}\n" + log_tail(proc))
    return result


def reduce_trace(children: Children, trace_dir: str) -> dict:
    """The trace under `trace_dir` reduced by ``harness/xplane.py`` in a
    process held to the CPU (the chip's process has gone); the raw trace is
    deleted."""
    out_path = os.path.join(WORK, "trace_reduced.json")
    proc = children.start(
        [PY, "-m", "benchmark.harness.xplane", trace_dir, out_path],
        child_env(JAX_PLATFORMS="cpu"), "xplane.log")
    need(proc.wait(timeout=300) == 0,
         "trace reduction failed\n" + log_tail(proc))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return load_json(out_path)


def emit_child_result(obj: dict) -> None:
    print(RESULT_TAG + json.dumps(obj), flush=True)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of all the values given."""
    xs = sorted(values)
    need(xs, "percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def check_device(device: dict, platform: str, chips: int) -> None:
    need(device["platform"] == platform,
         f"JAX found platform {device['platform']!r}, the configuration "
         f"runs on {platform!r}: no result")
    need(device["count"] >= chips,
         f"JAX found {device['count']} devices, the cell needs {chips}")


def decide(checks: dict) -> bool:
    """``correct``: every number compared is within its limit.  Each is
    printed beside its limit as the last lines on standard error."""
    ok = True
    for name, c in checks.items():
        good = c["value"] is not None and c["value"] <= c["limit"]
        ok = ok and good
        print(f"check {name}: value {c['value']} limit {c['limit']}"
              f"{'' if good else '  <-- over the limit'}", file=sys.stderr)
    sys.stderr.flush()
    return ok


def result_line(*, checks: dict, attempted: int, failed: int, metrics: dict,
                units: dict, device: dict, notes: dict,
                breakdown: dict | None = None) -> str:
    correct = decide(checks)
    out = {
        "correct": bool(correct and failed == 0),
        "attempted": int(attempted), "failed": int(failed),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["notes"] = notes
    out["checks"] = checks          # last: each number beside its limit
    return json.dumps(out)

