"""The ``serve_glm_moe_lite`` window driver end to end on the CPU at tiny
widths (``BENCHMARK.glm-tiny.json`` beside this file): the cell's rehearsal.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "BENCHMARK.glm-tiny.json")

from benchmark.harness import common as C  # noqa: E402
from benchmark.harness import serve_glm_moe_lite_window as SW  # noqa: E402


def test_cell_runs_end_to_end():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tiny-glm.closed2-deep", "--seed",
         str(2 ** 31 + 4321), "--seconds", "3", "--trace", "0",
         "--bench-file", TINY],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    notes = res["notes"]
    assert notes["compiles_in_window"] == 0
    assert notes["checked_rungs"] == [64, 96, 128]
    assert sum(c > 70 for c in notes["checked_contexts"]) >= 2
    assert set(res["checks"]) == {"logit_gap_mean", "requests_failed",
                                  "callers_run_dry"}
    assert notes["logit_gap_max"] >= res["checks"]["logit_gap_mean"]["value"]


def test_a_tampered_answer_is_not_correct():
    """A fault planted underneath the comparison: one served token of a
    sampled request replaced; the run is not correct."""
    cell = C.load_cell("tiny-glm.closed2-deep", TINY)

    def tamper(sample):
        sample[0].tokens[1] = (sample[0].tokens[1] + 1) % 256

    os.environ["JAX_PLATFORMS"] = "cpu"
    rec = SW.run(cell, 7, 2.0, False, 0.0, tamper=tamper)
    assert rec["checks"]["logit_gap_mean"]["value"] > \
        cell["config"]["check"]["logit_gap_mean"]
