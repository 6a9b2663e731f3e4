"""Tests of the benchmark's own harness, on the CPU at tiny sizes.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.  The tiny
cells (``BENCHMARK.tiny.json`` beside this file) state ``"platform": "cpu"``
in their configurations: the harness never falls back to a CPU, it runs
where the configuration says it runs.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "BENCHMARK.tiny.json")
REAL = os.path.join(ROOT, "BENCHMARK.json")

from benchmark.harness import common as C  # noqa: E402
from benchmark.harness import loadgen as LG  # noqa: E402
from benchmark.harness import traffic as TR  # noqa: E402
from benchmark.harness import xplane as X  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def cpu_env():
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


# ---------------------------------------------------------------------------
# every cell's window driver, end to end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", ["tiny.closed2", "tiny.train", "tiny.open"])
def test_cell_runs_end_to_end(cell):
    bench = C.load_json(TINY)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 1234), "--seconds", "2",
         "--trace", "0", "--bench-file", TINY],
        capture_output=True, text=True, env=cpu_env(), cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"        # the numbers compared come last
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"]: m["unit"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert all(isinstance(v["value"], float) and v["value"] > 0
               for v in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # each number compared stands beside its limit at the end of stderr
    for name, c in res["checks"].items():
        assert f"check {name}: value {c['value']} limit {c['limit']}" \
            in p.stderr


def test_no_result_where_the_platform_is_missing(tmp_path):
    """A configuration that runs on a TPU gives no result on a CPU."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "train6l.dense-2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=cpu_env(), cwd=ROOT, timeout=600)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


# ---------------------------------------------------------------------------
# traffic: the same lengths and schedule whatever --seed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mix", ["closed16", "open-bursty"])
def test_serve_traffic_is_fixed(mix):
    traffic = C.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                       mix + ".json"))
    a, b = TR.serve_requests(traffic, 51), TR.serve_requests(traffic, 51)
    assert a == b and len(a) > 50
    assert all(32 <= r["prompt_tokens"] <= 3072 for r in a)
    assert all(16 <= r["answer_tokens"] <= 512 for r in a)
    assert max(r["prompt_tokens"] + r["answer_tokens"] for r in a) <= 4096
    # a longer window only extends the same schedule
    longer = TR.serve_requests(traffic, 60)
    if traffic["loop"] == "open":
        assert [r["due_s"] for r in longer[:len(a)]] == \
            [r["due_s"] for r in a]
    # the seed makes the token ids and nothing else
    ids1 = TR.token_ids(1, 0, a[0]["prompt_tokens"], 32000)
    ids2 = TR.token_ids(2 ** 31 + 5, 0, a[0]["prompt_tokens"], 32000)
    assert ids1 != ids2 and len(ids1) == len(ids2)
    assert ids1 == TR.token_ids(1, 0, a[0]["prompt_tokens"], 32000)


def test_train_traffic_is_a_function_of_seed_and_step():
    a = TR.train_batch(7, 3, 8, 64, 32000)
    assert (a == TR.train_batch(7, 3, 8, 64, 32000)).all()
    assert (a != TR.train_batch(8, 3, 8, 64, 32000)).any()
    assert (a != TR.train_batch(7, 4, 8, 64, 32000)).any()
    assert a.shape == (8, 65) and len({tuple(r) for r in a}) == 8


def test_shared_prefix_parameter():
    traffic = {"loop": "closed", "traffic_seed": 3, "callers": 2,
               "requests_per_caller": 20,
               "prompt_tokens": {"dist": "fixed", "value": 40},
               "answer_tokens": {"dist": "uniform", "min": 2, "max": 4},
               "shared_prefix": {"share": 1.0, "groups": 1, "tokens": 16}}
    reqs = TR.serve_requests(traffic, 5)
    ids = [TR.token_ids(9, r["index"], 40, 256, r["prefix"]) for r in reqs]
    assert all(i[:16] == ids[0][:16] for i in ids)
    assert len({tuple(i[16:]) for i in ids}) == len(ids)


# ---------------------------------------------------------------------------
# the closed loop's window and the window's edges
# ---------------------------------------------------------------------------


def test_closed_window_opens_after_each_lane_finished(monkeypatch):
    delays = {0: 0.05, 1: 0.4}

    def fake_generate(port, req, timeout=0):
        req.sent = time.time()
        time.sleep(delays[req.spec["caller"]])
        req.token_times, req.tokens = [time.time()], [1]
        req.end = time.time()

    monkeypatch.setattr(LG, "generate", fake_generate)
    lists = [[LG.Request({"caller": c, "answer_tokens": 1}, [1])
              for _ in range(50)] for c in (0, 1)]
    loop = LG.ClosedLoop(0, lists)
    t0 = time.time()
    loop.start()
    assert loop.wait_each_lane_finished_one(10)
    opened = time.time()
    loop.close(5)
    assert opened - t0 >= 0.4                  # the slow lane's first answer
    assert all(lst[0].end <= opened for lst in lists)
    assert sum(r.end is not None for r in lists[0]) > 1   # others kept going


def test_token_on_the_edge_is_counted_once():
    r = LG.Request({"answer_tokens": 4}, [1])
    r.token_times = [10.0, 20.0, 20.0, 30.0]
    first = LG.tokens_in_window([r], 10.0, 20.0)
    second = LG.tokens_in_window([r], 20.0, 30.0)
    assert (first, second) == (1, 2)
    assert first + second + LG.tokens_in_window([r], 30.0, 40.0) == 4


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", [REAL, TINY])
def test_benchmark_json_names_and_metrics(path):
    b = C.load_json(path)
    base = os.path.dirname(path)
    cells = {w["name"]: w for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for group in (b["configs"], b["workloads"], b["end_to_end"],
                  b["per_layer"]):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        reader = m["name"].partition(".")[0] + ".py"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           reader)), reader
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1
                                    for m in b["end_to_end"])

    def reports(metric, cell):
        return cell in metric.get("workloads", list(cells))

    for m in b["per_layer"]:
        assert m["workloads"], m["name"]
        assert "mfu" not in m["name"] or "mfu" in m["name"].split("_")
        for cell in m["workloads"]:
            assert cell in cells
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
    for name in cells:
        assert sum(reports(m, name) for m in b["end_to_end"]) >= 2
        assert any(reports(m, name) for m in b["per_layer"])
        loaded = C.load_cell(name, path)
        assert loaded["config"]["kind"] in ("serve", "train")
    for c in b["configs"]:
        assert os.path.exists(os.path.join(base, c["file"]))


# ---------------------------------------------------------------------------
# the reduction from a trace to busy time, module times and idle gaps
# ---------------------------------------------------------------------------


def test_xplane_reduction_on_a_hand_made_trace():
    us = 1000.0
    trace = {"planes": {
        "/device:TPU:0": {
            "XLA Modules": [["jit_step(1)", 0, 60 * us],
                            ["jit_insert(2)", 70 * us, 30 * us]],
            "XLA Ops": [
                ["%while.1 = (s32[], f32[4]{0}) while(...)", 0, 50 * us],
                ["%fusion.1 = f32[4]{0} fusion(...)", 0, 20 * us],
                ["%kern.2 = bf16[16,32,128]{2,1,0} custom-call(...)",
                 20 * us, 30 * us],
                ["%fusion.3 = f32[8]{0} fusion(...)", 50 * us, 10 * us],
                ["%fusion.4 = f32[8]{0} fusion(...)", 70 * us, 30 * us]]},
        "/host:CPU": {"main": [["scheduler.admit", 58 * us, 14 * us],
                               ["python", 0, 120 * us]]}}}
    r = X.reduce(trace)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(90e-6)
    # the window is what the device's own events span, not the host's
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["module_s"] == {"jit_step": pytest.approx(60e-6),
                             "jit_insert": pytest.approx(30e-6)}
    ops = dict(r["device_ops"])
    assert "while.1" not in " ".join(ops)           # loops hold no own time
    assert ops["kern.2_bf16[16,32,128]"] == pytest.approx(30e-6)
    assert r["idle_gaps"] == [["scheduler.admit", pytest.approx(10e-6)]]
    assert [k["module"] for k in r["kernels"]] == ["jit_step"]


def test_xplane_reduction_on_the_recorded_fixture():
    """A cut of a trace recorded on the v5e (PR 24's own chip run of the
    serving ring, 600 events a line): busy time by an independent sweep."""
    trace = C.load_json(os.path.join(HERE, "fixtures", "trace_small.json"))
    r = X.reduce(trace)
    ops = trace["planes"]["/device:TPU:0"]["XLA Ops"]
    points = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops])
    depth, busy, last = 0, 0.0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert set(r["module_s"]) == {"jit_step", "jit_convert_element_type"}
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10
    assert all(g[1] > 0 for g in r["idle_gaps"])
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)


def test_parse_op_handles_tuples_and_layouts():
    op = X.parse_op("%while.37 = (s32[]{:T(128)}, bf16[16,1,4096]{2,0,1:T(8,128)"
                    "(2,1)S(1)}) while((s32[], bf16[16,1,4096]) %tuple.1)")
    assert (op["name"], op["kind"], op["shape"]) == ("while.37", "while", "s32[]")
    op = X.parse_op("%closed_call.12 = bf16[16,32,128]{2,1,0:T(8,128)(2,1)S(1)} "
                    "custom-call(s32[16]{0:T(128)S(1)} %broadcast)")
    assert (op["name"], op["kind"], op["shape"]) == \
        ("closed_call.12", "custom-call", "bf16[16,32,128]")


# ---------------------------------------------------------------------------
# the control and the faults have to come out as not correct
# ---------------------------------------------------------------------------


def train_spec(seed=11):
    cell = C.load_cell("tiny.train", TINY)
    return cell, {"seed": seed, "seconds": 0.3, "config": cell["config"],
                  "traffic": cell["traffic"], "chips": 1, "trace_dir": None,
                  "control": None}


def correct_of(numbers, cfg, capsys):
    from benchmark.harness import train_window as TW

    line = C.result_line(checks=TW.checks_of(numbers, cfg), attempted=1,
                         failed=0, metrics={}, units={}, device={}, notes={})
    capsys.readouterr()
    return json.loads(line)["correct"]


@pytest.fixture(scope="module")
def train_reference():
    from benchmark.reference import train_check

    _, spec = train_spec()
    return train_check.reference(spec)


@pytest.mark.parametrize("control", ["fp8", "half_batch"])
def test_train_control_comes_out_not_correct(control, train_reference, capsys):
    """The reference in the precision below, and the reference with half of
    the batch left out, put in the program's place."""
    from benchmark.harness import train_window as TW
    from benchmark.reference import train_check

    cell, spec = train_spec()
    low = train_check.reference({**spec, "control": control})
    assert not correct_of(TW.gaps(low, train_reference), cell["config"], capsys)


def _unchanged(step):
    import jax
    import jax.numpy as jnp

    def f(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return f


def _half_batch(step):
    def f(state, batch):
        t = batch["tokens"]
        half = t.shape[0] // 2
        return step(state, {"tokens": t.at[half:].set(t[:half])})
    return f


@pytest.mark.parametrize("fault", [None, _unchanged, _half_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_train_run_with_the_timed_path_broken(fault, train_reference, capsys):
    """Drives the rest of a run past the look for a chip: the program's own
    compiled step (broken underneath, or sound), the reference, the
    comparison and the result line."""
    from benchmark.harness import train_child
    from benchmark.harness import train_window as TW

    cell, spec = train_spec()
    prog = train_child.program(spec, break_step=fault)
    assert prog["steps"] >= 1 and not prog.get("early")
    ok = correct_of(TW.gaps(prog, train_reference), cell["config"], capsys)
    assert ok is (fault is None)


def _alter_a_token(sample):
    r = sample[-1]
    r.tokens[len(r.tokens) // 2] = (r.tokens[len(r.tokens) // 2] + 97) % 256


@pytest.mark.parametrize("tamper, control", [(None, "fp8"),
                                             (_alter_a_token, None)],
                         ids=["fp8_control", "token_altered"])
def test_serve_run_control_and_fault(tamper, control, capsys):
    """A whole run of the tiny serving cell: sound it is correct; its fp8
    control reads over the limit; with one served token altered where the
    answer is produced, ``correct`` comes out false."""
    from benchmark.harness import serve_window as SW

    cell = C.load_cell("tiny.closed2", TINY)
    os.environ["JAX_PLATFORMS"] = "cpu"
    rec = SW.run(cell, 5, 1.0, False, time.time(), control=control,
                 tamper=tamper)
    line = json.loads(C.result_line(
        checks=rec["checks"], attempted=len(rec["judged"]),
        failed=len(rec["failed"]), metrics={}, units={}, device={}, notes={}))
    capsys.readouterr()
    limit = cell["config"]["check"]["logit_gap_max"]
    if tamper is None:
        assert line["correct"] is True
        assert rec["notes"]["control"]["gap_max"] > 3 * limit
    else:
        assert line["correct"] is False
        assert rec["checks"]["logit_gap_max"]["value"] > limit
