"""The window driver for configurations of ``kind: train``.

The trainer runs as the job's pod runs it: ``python -m
paddle_operator_tpu.launch.launcher -- <train_child>``, with the rendezvous
environment of a one-worker job.  Once it has gone, a second child runs the
plain reference on the freed chip, and this process compares the two.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics

from benchmark.harness import common as C


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared.  Norms are compared by the worst leaf: the gap
    between the program's norm and the reference's, against the reference's
    norm of that leaf or of the median leaf, whichever is larger.  Leaves
    whose reference gradient is under a thousandth of the median leaf's move
    by round-off alone and are left out of the change."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad_norms"]
    g_med = statistics.median(g_ref.values())
    grad_by_leaf = {n: abs(prog["grad_norms"][n] - g) / max(g, g_med)
                    for n, g in g_ref.items()}
    c_ref = ref["change_norms"]
    moving = [n for n in c_ref if g_ref[n] >= 1e-3 * g_med]
    c_med = statistics.median(c_ref[n] for n in moving)
    change_by_leaf = {n: abs(prog["change_norms"][n] - c_ref[n])
                      / max(c_ref[n], c_med) for n in moving}
    return {"loss_gap_max": loss_gap,
            "first_grad_gap": max(grad_by_leaf.values()),
            "param_change_gap": max(change_by_leaf.values()),
            "grad_gap_by_leaf": grad_by_leaf,
            "change_gap_by_leaf": change_by_leaf,
            "nought_leaves": sorted(set(c_ref) - set(moving))}


def checks_of(numbers: dict, cfg: dict) -> dict:
    return {k: {"value": numbers[k], "limit": cfg["check"][k]}
            for k in ("loss_gap_max", "first_grad_gap", "param_change_gap")}


def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        *, control: str | None = None) -> dict:
    children = C.Children()
    try:
        return _run(cell, seed, seconds, trace, t_start, control, children)
    finally:
        children.stop_all()


def _run(cell, seed, seconds, trace, t_start, control, children) -> dict:
    cfg = cell["config"]
    os.makedirs(C.WORK, exist_ok=True)
    trace_dir = os.path.join(C.WORK, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    spec = {"seed": seed, "seconds": seconds, "config": cfg,
            "traffic": cell["traffic"], "chips": cell["chips"],
            "trace_dir": trace_dir if trace else None, "control": None}
    spec_path = os.path.join(C.WORK, "train_spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = C.child_env(TPUJOB_NAME="bench", TPUJOB_NUM_WORKERS=1,
                      TPUJOB_RANK=0,
                      TPUJOB_MESH=json.dumps(cfg["train"].get("mesh") or {}))
    env.pop("TPUJOB_CHECKPOINT_PATH", None)
    argv = [C.PY, "-m", "paddle_operator_tpu.launch.launcher", "--",
            C.PY, "-m", "benchmark.harness.train_child", spec_path]
    proc = children.start(argv, env, "trainer.log")
    rc = proc.wait(timeout=1300)
    prog = C.child_result(proc)
    C.need(prog is not None, f"trainer: exit code {rc}, no result\n"
           + C.log_tail(proc))
    C.check_device(prog["device"], cfg["platform"], cell["chips"])
    C.need(rc == 0, f"trainer: exit code {rc}\n" + C.log_tail(proc))

    trace_out = C.reduce_trace(children, trace_dir) if trace else None

    ref = C.run_child(children,
                      [C.PY, "-m", "benchmark.reference.train_check",
                       spec_path], C.child_env(), "reference.log", 900)
    C.check_device(ref["device"], cfg["platform"], cell["chips"])
    numbers = gaps(prog, ref)
    controls = {}
    for name in (control.split(",") if control else []):
        # calibration only: the reference in a lower precision, or with a
        # fault planted, put in the program's place
        with open(spec_path, "w") as f:
            json.dump({**spec, "control": name}, f)
        low = C.run_child(children,
                          [C.PY, "-m", "benchmark.reference.train_check",
                           spec_path], C.child_env(),
                          f"control-{name}.log", 900)
        g = gaps(low, ref)
        controls[name] = {k: g[k] for k in ("loss_gap_max", "first_grad_gap",
                                            "param_change_gap")}
    window = {"t_open": prog["t_open"], "t_close": prog["t_close"],
              "seconds": seconds,
              "real_seconds": prog["t_close"] - prog["t_open"],
              "steps": prog["steps"],
              "tokens_per_step": prog["tokens_per_step"],
              "data_wait_s": prog["data_wait_s"], "traced": prog["traced"]}
    steps = list(range(prog["steps"]))
    return {
        "cell": cell, "seed": seed, "window": window,
        "setup_s": prog["t_open"] - t_start,
        "judged": steps, "failed": [], "checks": checks_of(numbers, cfg),
        "device": {**prog["device"], "memory_peak_bytes": prog["peak_bytes"]},
        "trace": trace_out,
        "notes": {
            "bytes_in_use_after_window": prog["bytes_in_use"],
            "compile_requests_hits_setup": prog["compiles_setup"],
            "compiles_in_window": prog["compiles_in_window"],
            "steps_in_window": prog["steps"],
            "step_s": window["real_seconds"] / max(1, prog["steps"]),
            "prog_losses": prog["losses"], "ref_losses": ref["losses"],
            "reference_s": ref["seconds"],
            "reference_compile_requests_hits": ref.get("compiles"),
            "reference_peak_bytes": ref["peak_bytes"],
            "control": controls or None,
            "grad_gap_by_leaf": numbers["grad_gap_by_leaf"],
            "change_gap_by_leaf": numbers["change_gap_by_leaf"],
            "nought_leaves": numbers["nought_leaves"],
        },
    }
