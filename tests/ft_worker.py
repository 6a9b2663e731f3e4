"""Subprocess worker for the elastic-resume / preemption-drain tests.

Why a subprocess (the same own-your-environment move as
``__graft_entry__.dryrun_multichip``): this jax/XLA:CPU build
heap-corrupts — malloc aborts or silently wrong losses — when train-step
executables are compiled for device-SUBSET meshes (the dp-resize rigs
below) inside a long-lived process that has already run many other
sharded programs.  Standalone the exact same code is rock solid, so the
tests exec it here with a fresh runtime and assert on the JSON the
worker prints as its last line (``RESULT {...}``).

Run directly:  python -m tests.ft_worker elastic | drain <ckpt_dir>
"""

import json
import os
import sys


def _rig(dp, global_batch):
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.api.types import MeshSpec
    from paddle_operator_tpu.models import llama as L
    from paddle_operator_tpu.parallel.mesh import make_mesh
    from paddle_operator_tpu.train import trainer as T

    model, cfg = L.make_model("tiny")
    mesh = make_mesh(MeshSpec(dp=dp), devices=jax.devices()[:dp])
    opt = T.make_optimizer(1e-3, warmup_steps=1, decay_steps=50)
    pats = L.partition_patterns(cfg)
    ex = (jnp.zeros((global_batch, 8), jnp.int32),)
    sh, _ = T.state_shardings(model, opt, mesh, pats, ex)
    step = T.make_train_step(model, opt, mesh, sh)

    def init():
        return T.create_state(model, opt, mesh, pats, ex)

    return cfg, init, step


def _run(state, step_fn, cfg, *, gb, seq, seed, start_step, steps):
    from paddle_operator_tpu.train.data import deterministic_lm_batches

    losses = []
    it = deterministic_lm_batches(gb, seq, cfg.vocab_size, seed=seed,
                                  start_step=start_step)
    for _ in range(steps):
        state, m = step_fn(state, next(it))
        losses.append(float(m["loss"]))
    return state, losses


def run_elastic() -> dict:
    """Save at dp=4 after 3 steps; resume at dp=2 AND dp=1; report the
    loss trajectories next to the uninterrupted dp=4 run."""
    import tempfile

    from paddle_operator_tpu.ft.elastic import elastic_resume
    from paddle_operator_tpu.train.checkpoint import CheckpointManager

    GB, SEQ, STEPS, SPLIT, SEED = 8, 17, 6, 3, 7
    cfg, init4, step4 = _rig(4, GB)
    _, baseline = _run(init4(), step4, cfg, gb=GB, seq=SEQ, seed=SEED,
                       start_step=0, steps=STEPS)
    state, losses_a = _run(init4(), step4, cfg, gb=GB, seq=SEQ, seed=SEED,
                           start_step=0, steps=SPLIT)
    path = tempfile.mkdtemp(prefix="ft-elastic-")
    ckpt = CheckpointManager(path, save_interval_steps=1)
    ckpt.save(int(state.step), state, force=True)
    ckpt.wait(); ckpt.close()

    out = {"baseline": baseline, "losses_a": losses_a, "resumes": {}}
    for dp in (2, 1):
        cfg2, init_s, step_s = _rig(dp, GB)
        state2, resumed, plan = elastic_resume(
            CheckpointManager(path), init_s,
            saved_global_batch=GB, global_batch=GB)
        wq = state2.params["layers"]["attn"]["wq"]["kernel"]
        _, losses_b = _run(state2, step_s, cfg2, gb=GB, seq=SEQ,
                           seed=SEED, start_step=plan["data_start_step"],
                           steps=STEPS - SPLIT)
        out["resumes"][str(dp)] = {
            "resumed": resumed, "plan": plan, "losses_b": losses_b,
            "mesh_devices": int(wq.sharding.mesh.devices.size),
        }
    return out


def run_drain(ckpt_dir: str) -> dict:
    """The acceptance path: real SIGTERM mid-run at dp=4 → in-flight step
    finishes → forced durable checkpoint → elastic resume at dp=2 →
    trajectory + goodput snapshot reported."""
    import signal

    from paddle_operator_tpu.ft import (
        EXIT_PREEMPTED,
        GoodputTracker,
        PreemptionWatcher,
        elastic_resume,
    )
    from paddle_operator_tpu.ft.preemption import inject_preemption
    from paddle_operator_tpu.train import trainer as T
    from paddle_operator_tpu.train.checkpoint import CheckpointManager
    from paddle_operator_tpu.train.data import deterministic_lm_batches

    GB, SEQ, TOTAL, KILL_AT, SEED = 8, 17, 8, 4, 5
    cfg, init4, step4 = _rig(4, GB)
    _, baseline = _run(init4(), step4, cfg, gb=GB, seq=SEQ, seed=SEED,
                       start_step=0, steps=TOTAL)

    ckpt = CheckpointManager(ckpt_dir, save_interval_steps=2)
    goodput = GoodputTracker()
    watcher = PreemptionWatcher.install(signals=(signal.SIGTERM,))
    with goodput.phase("init"):
        state = init4()

    state, hist = T.fit(
        state, step4,
        inject_preemption(
            deterministic_lm_batches(GB, SEQ, cfg.vocab_size, seed=SEED),
            KILL_AT, watcher, signal_self=True),
        steps=TOTAL, checkpoint=ckpt, preemption=watcher,
        goodput=goodput)
    watcher.uninstall()
    drained_step = int(state.step)
    latest = ckpt.latest_step()
    ckpt.close()

    cfg2, init2, step2 = _rig(2, GB)
    state2, resumed, plan = elastic_resume(
        CheckpointManager(ckpt_dir), init2,
        saved_global_batch=GB * SEQ, global_batch=GB * SEQ,
        goodput=goodput)
    goodput.record_lost_steps(drained_step - plan["step"], 0.1)
    losses2 = []
    it2 = deterministic_lm_batches(GB, SEQ, cfg.vocab_size, seed=SEED,
                                   start_step=plan["data_start_step"])
    for _ in range(TOTAL - plan["data_start_step"]):
        state2, m = step2(state2, next(it2))
        goodput.tick()
        losses2.append(float(m["loss"]))

    return {
        "baseline": baseline,
        "hist": [float(h["loss"]) for h in hist],
        "losses2": losses2,
        "draining": watcher.draining,
        "exit_code": EXIT_PREEMPTED if watcher.draining else 0,
        "drained_step": drained_step,
        "latest_checkpoint_step": latest,
        "resumed": resumed,
        "plan": plan,
        "goodput": goodput.to_status(),
    }


def launch(mode: str, *args: str, timeout: float = 900) -> dict:
    """Run this worker in a fresh interpreter and return its RESULT json
    (the isolation boundary the module docstring explains)."""
    import subprocess

    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    # NOTE: do NOT point the worker at the suite's persistent compile
    # cache (JAX_COMPILATION_CACHE_DIR): enabling it here makes this
    # jax build's subset-mesh compile path heap-corrupt INSIDE the
    # worker (malloc_consolidate abort in the drain rig) — the exact
    # failure mode the subprocess isolation exists to dodge.  The
    # ~30s of from-scratch recompilation per launch is the price.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "tests.ft_worker", mode, *args],
        env=env, cwd=root, capture_output=True, text=True,
        timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"ft_worker {mode} failed rc={proc.returncode}\n"
            f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"ft_worker {mode}: no RESULT line\n"
                       f"stdout: {proc.stdout[-2000:]}")


def main() -> int:
    # a test helper never reaches for an accelerator, whatever the
    # environment of the process that started it says
    import jax

    jax.config.update("jax_platforms", "cpu")
    mode = sys.argv[1]
    if mode == "elastic":
        out = run_elastic()
    elif mode == "drain":
        out = run_drain(sys.argv[2])
    else:
        raise SystemExit(f"unknown mode {mode!r}")
    print("RESULT " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
