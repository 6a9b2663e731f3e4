#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two things the operator schedules, through the entry points a
user would call, once, on the accelerator JAX finds:

- ``train``  a TPUJob reconciled through FakeAPI/FakeFleet, its rendezvous
  ConfigMap turned into the pod's environment, and the pod's own command
  (``python -m paddle_operator_tpu.launch.launcher -- ...``) run as a child:
  ``JobEnv.from_env() -> initialize() -> job_mesh() -> create_state ->
  make_train_step -> fit`` on LLaMA-7B at full width (depth cut to one
  chip's memory), then save, restore into an abstract template, one more
  step, and one step with the optimizer state offloaded to host memory.
- ``serve``  ``python -m paddle_operator_tpu.infer.serve`` as a child, the
  continuous paged ring on the whole ``1b`` preset, answering
  ``/v1/generate`` over HTTP; then a reference child checks the server's
  tokens against the XLA einsum's logits on the same chip.
- ``serve-int8``  the same server with ``SERVE_KV_QUANT=int8``.

``--chips 4`` runs instead, and only, the two paths that exist across
chips and what each is compared with: the trainer on a ``fsdp=2, tp=2``
mesh against one device, and ``SERVE_TP=4`` against ``SERVE_TP=1``.

This process never imports jax: a parent that has touched JAX holds the
chip.  Every phase is one child process at a time.  Any phase that fails,
finds no TPU, or did not run the kernel it was meant to makes the exit
code non-zero, and then no ``"ok": true`` is printed.  The last line of a
good run is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")        # git-ignored scratch
RESULT_TAG = "CHIP_SMOKE_RESULT "
SEED = 0

# Stated tolerances.  Loss: the sharded step reduces in another order (tp
# splits every contraction) in bf16.  Logits: the lm head's output is
# bf16 (spacing 2**-6 at the |logit| of 2..4 a random-init 1b model shows)
# and two programs that compute it round differently through 16 layers;
# 0.15 is ten such steps, and a wrong mask or scale moves logits by O(1).
# Random-init logits tie easily, so tokens are held to "within LOGIT_TOL
# of the reference's best logit", never to identity across programs.
LOSS_TOL = 0.05
LOGIT_TOL = 0.15
# the int8 pool answers from quantized KV: tests/test_kvquant.py pins its
# logits within 0.15 of the bf16 pool's, on top of the kernel's own gap
KVQ_LOGIT_TOL = LOGIT_TOL + 0.15

# LLaMA-7B at its published width (models/llama.py CONFIGS["7b"]: dim 4096,
# 32 heads x 128, ffn 11008, vocab 32000), seq 2048.  Depth is what one
# 16 GB chip holds with bf16 params and int8 Adam moments at batch 8.
TRAIN = dict(preset="7b", n_layers=8, batch=8, seq=2048,
             param_dtype="bfloat16", moments="int8", checkpoint=True,
             offload_layers=2, platform="tpu", flash=True)

# the largest preset one chip holds whole in bf16
SERVE = dict(preset="1b", vocab=32000, slots=8, block=256, max_len=2048,
             chunk=8, prompt_lens=(128, 512, 1024), new_tokens=64,
             platform="tpu", decode_attn="pallas", kernel="pallas")


class PhaseFailed(Exception):
    pass


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def need(cond, msg: str) -> None:
    """A check that survives ``python -O``."""
    if not cond:
        raise PhaseFailed(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_entries() -> int:
    from paddle_operator_tpu.utils.compile_cache import cache_dir

    try:
        return sum(1 for f in os.listdir(cache_dir()) if f.endswith("-cache"))
    except OSError:
        return 0


def child_env(**extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["TPUJOB_FLIGHTREC_DIR"] = WORK     # SIGTERM dumps stay in the checkout
    env.update({k: str(v) for k, v in extra.items()})
    return env


# every child this process starts, so that none outlives it
_CHILDREN: list = []


def start(argv, env, log_name: str) -> subprocess.Popen:
    os.makedirs(WORK, exist_ok=True)
    log = open(os.path.join(WORK, log_name), "w")
    proc = subprocess.Popen(argv, env=env, cwd=ROOT, stdout=log,
                            stderr=subprocess.STDOUT, start_new_session=True)
    proc.log_path = log.name
    log.close()
    _CHILDREN.append(proc)
    return proc


def stop_all() -> None:
    for proc in _CHILDREN:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()


def log_tail(proc, n: int = 30) -> str:
    with open(proc.log_path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def run_child(argv, env, log_name: str, timeout: float) -> dict:
    """Run one of this file's child functions to its end and return the
    result object it printed."""
    proc = start(argv, env, log_name)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise PhaseFailed(f"{log_name}: no result after {timeout:.0f}s\n"
                          + log_tail(proc))
    result = None
    with open(proc.log_path, errors="replace") as f:
        for line in f:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
    if rc != 0 or result is None:
        raise PhaseFailed(f"{log_name}: exit code {rc}\n" + log_tail(proc))
    return result


def child_argv(fn: str, sizes: dict) -> list:
    return [sys.executable, "-c",
            f"import chip_smoke; chip_smoke.{fn}()", json.dumps(sizes)]


def check_device(device: dict, platform: str, count: int) -> None:
    need(device["platform"] == platform,
         f"JAX found platform {device['platform']!r}, not {platform!r}")
    need(device["count"] == count,
         f"JAX found {device['count']} devices, not {count}")


# ---------------------------------------------------------------------------
# train: parent side
# ---------------------------------------------------------------------------


def job_contract(sizes: dict, mesh: dict, ckpt_dir: str):
    """Reconcile a one-worker TPUJob the way the operator would and return
    ``(argv, env)`` of its pod: the container's own command, its literal
    env, and the rendezvous ConfigMap it mounts with ``envFrom``."""
    from paddle_operator_tpu.api import (
        MeshSpec, ResourceSpec, TPUJob, TPUJobSpec, TPUSpec,
    )
    from paddle_operator_tpu.controller.fake_api import FakeAPI, FakeFleet
    from paddle_operator_tpu.controller.reconciler import (
        TPUJobReconciler, run_to_settled,
    )

    chips = math.prod(mesh.values())
    command = [sys.executable, "-m", "paddle_operator_tpu.launch.launcher",
               "--"] + child_argv("train_child", sizes)
    job = TPUJob(name="chip-smoke", spec=TPUJobSpec(
        intranet="PodIP",
        tpu=TPUSpec(topology={1: "1x1", 4: "2x2"}[chips],
                    chips_per_worker=chips),
        mesh=MeshSpec(**mesh), checkpoint_path=ckpt_dir,
        worker=ResourceSpec(replicas=1, template={"spec": {"containers": [
            {"name": "tpujob", "image": "local", "command": command}]}})))
    problems = job.validate()
    need(not problems, f"TPUJob does not validate: {problems}")
    api = FakeAPI()
    rec, fleet = TPUJobReconciler(api), FakeFleet(api)
    api.create("TPUJob", job.to_dict())
    run_to_settled(rec, "default", job.name)            # pods created
    pods = [o for k, o in api.store.items() if k[0] == "Pod"]
    need(len(pods) == 1, f"expected one pod, got {len(pods)}")
    # the kubelet's part: this pod lands on this machine
    pods[0].setdefault("status", {})["podIP"] = "127.0.0.1"
    fleet.run_all()
    run_to_settled(rec, "default", job.name)            # ConfigMap barrier
    container = pods[0]["spec"]["containers"][0]
    env = dict(api.get("ConfigMap", "default", job.name)["data"])
    for e in container.get("env", []):
        env[e["name"]] = e.get("value", "127.0.0.1")    # POD_IP: fieldRef
    return container["command"], env


def phase_train(sizes: dict, mesh: dict, *, name: str = "train",
                devices: int = 1, timeout: float = 900) -> dict:
    """Run the train child as the job's pod; returns its result."""
    ckpt_dir = os.path.join(WORK, f"ckpt-{name}")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    argv, contract = job_contract(sizes, mesh, ckpt_dir)
    before = cache_entries()
    t0 = time.time()
    r = run_child(argv, child_env(**contract), f"{name}.log", timeout)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    check_device(r["device"], sizes["platform"], devices)
    loss = r["loss"]
    need(all(x == x and abs(x) < 1e9 for x in loss), f"loss not finite: {loss}")
    # two batches take turns: each must read lower than its last time
    need(all(b < a for a, b in zip(loss, loss[2:])),
         f"loss did not fall: {loss}")
    if sizes["flash"]:
        need(r["flash_custom_calls"] > 0,
             "the lowered train step holds no flash kernel custom call")
    if sizes["checkpoint"]:
        need(r["restore"]["resumed"] and abs(
            r["restore"]["loss"] - r["restore"]["continued_loss"])
            <= LOSS_TOL, f"loss not continuous across restore: {r['restore']}")
    say(name, seconds=round(time.time() - t0, 1),
        cache_entries=[before, cache_entries()], **r)
    return r


# ---------------------------------------------------------------------------
# train: the pod's program (what follows ``launcher --``)
# ---------------------------------------------------------------------------


def emit_result(obj: dict) -> None:
    print(RESULT_TAG + json.dumps(obj), flush=True)


def count_cache_events() -> dict:
    """Count JAX's persistent-cache requests and hits from here on."""
    import jax

    seen = {"requests": 0, "hits": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            seen["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    return seen


def device_report() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> list:
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def train_child() -> None:
    sizes = json.loads(sys.argv[1])
    import dataclasses
    import itertools

    from paddle_operator_tpu.launch import launcher
    from paddle_operator_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    env = launcher.initialize()         # JobEnv.from_env(); jax.distributed
    import jax                          # only past one worker
    import jax.numpy as jnp
    import numpy as np

    cache = count_cache_events()
    device = device_report()
    if device["platform"] != sizes["platform"]:
        # no chip: say what was found and stop before any work
        emit_result({"device": device, "loss": [], "early": True})
        raise SystemExit(3)

    from paddle_operator_tpu.models import llama as L
    from paddle_operator_tpu.parallel.mesh import make_mesh, mesh_shape
    from paddle_operator_tpu.train import trainer as T
    from paddle_operator_tpu.train.checkpoint import (
        CheckpointManager, resume_or_init,
    )
    from paddle_operator_tpu.train.data import (
        DevicePrefetcher, deterministic_lm_batches,
    )

    want = int(np.prod(mesh_shape(env.mesh)))
    if want == len(jax.devices()):
        mesh = launcher.job_mesh(env)   # ICI-aware assignment on TPU
    else:                               # the one-device run on a 4-chip host
        mesh = make_mesh(env.mesh, devices=jax.devices()[:want])

    def build(n_layers: int, offload: bool):
        cfg = dataclasses.replace(
            L.CONFIGS[sizes["preset"]], n_layers=n_layers,
            max_seq_len=sizes["seq"],
            param_dtype=jnp.dtype(sizes["param_dtype"]))
        model = L.Llama(cfg, mesh)
        # warmup 1: the schedule starts at lr 0, so the first update is a
        # no-op and the second moves at full rate — a few steps show a fall
        opt = T.make_optimizer(3e-4, warmup_steps=1, decay_steps=1000,
                               moments=sizes["moments"])
        spec = (model, opt, mesh, L.partition_patterns(cfg),
                (jnp.zeros((sizes["batch"], 8), jnp.int32),))
        shardings, _ = T.state_shardings(*spec, offload_opt_state=offload)
        state = T.create_state(*spec, rng=jax.random.PRNGKey(SEED),
                               offload_opt_state=offload)
        step = T.make_train_step(model, opt, mesh, shardings)
        like = T.abstract_state(*spec, offload_opt_state=offload)
        return cfg, state, step, like

    def batches():
        # two seeded batches in turn: a loss that falls within a few steps
        two = list(itertools.islice(deterministic_lm_batches(
            sizes["batch"], sizes["seq"] + 1,
            L.CONFIGS[sizes["preset"]].vocab_size, seed=SEED), 2))
        return DevicePrefetcher(itertools.cycle(two), mesh)

    cfg, state, step, like = build(sizes["n_layers"], offload=False)
    per_device: dict = {}
    for leaf in jax.tree.leaves(state):
        for s in leaf.addressable_shards:
            per_device[str(s.device)] = (per_device.get(str(s.device), 0)
                                         + s.data.nbytes)
    data = batches()
    lowered = step.lower(state, next(data)).as_text()
    flash_calls = lowered.count("tpu_custom_call")

    # five steps in three segments of `fit`, each synced at its end: the
    # first compiles; the next two are plain steps; the last two hold the
    # one periodic save (step 4), which runs async while step 5 donates
    # the state it is reading (checkpoint.py: no host snapshot off the CPU)
    ckpt = CheckpointManager(env.checkpoint_path, max_to_keep=1,
                             save_interval_steps=4) \
        if sizes["checkpoint"] else None
    hist, seconds = [], []
    for n in (1, 2, 2):
        t0 = time.perf_counter()
        state, h = T.fit(state, step, data, steps=n, checkpoint=ckpt)
        seconds.append(time.perf_counter() - t0)
        hist += h
    compile_s, step_s, save_steps_s = (seconds[0], seconds[1] / 2,
                                       seconds[2] / 2)
    loss = [h["loss"] for h in hist]

    restore = None
    if ckpt is not None:
        saved_step = int(state.step)
        ckpt.save(saved_step, state, force=True)
        ckpt.wait()
        state, m = step(state, next(data))   # what continuing would read
        continued = float(m["loss"])
        del state, m                         # one copy on the device
        ckpt.close()
        t0 = time.perf_counter()
        ckpt2 = CheckpointManager(env.checkpoint_path)
        state, resumed = resume_or_init(ckpt2, None, like)
        restore_s = time.perf_counter() - t0
        # the continued step took a batch; `data` cycles over two, so skip
        # one more to replay the same batch
        next(data)
        state, m = step(state, next(data))
        restore = {"resumed": bool(resumed), "step": saved_step,
                   "restore_s": round(restore_s, 2), "loss": float(m["loss"]),
                   "continued_loss": continued}
        ckpt2.close()
    peak = peak_bytes()
    del state, step, like

    offload = None
    if sizes["offload_layers"]:
        # trainer.py's pinned_host branch: moments live in host memory and
        # stream through the step from inside jit
        _, ostate, ostep, _ = build(sizes["offload_layers"], offload=True)
        kinds = {s.sharding.memory_kind
                 for s in jax.tree.leaves(ostate.opt_state)}
        olosses = []
        for _ in range(2):
            ostate, m = ostep(ostate, next(data))
            olosses.append(float(m["loss"]))
        kinds_after = {s.sharding.memory_kind
                       for s in jax.tree.leaves(ostate.opt_state)}
        offload = {"layers": sizes["offload_layers"], "loss": olosses,
                   "opt_state_memory_kinds": sorted(kinds | kinds_after)}
        if not all(np.isfinite(olosses)) or kinds_after != {"pinned_host"}:
            emit_result({"device": device, "loss": loss, "offload": offload})
            raise SystemExit("offloaded step: loss not finite or moments "
                             f"left pinned_host: {offload}")

    emit_result({
        "device": device, "mesh": env.mesh.to_dict(),
        "model": {"dim": cfg.dim, "heads": cfg.n_heads,
                  "head_dim": cfg.head_dim, "ffn": cfg.ffn_dim,
                  "vocab": cfg.vocab_size, "layers": cfg.n_layers,
                  "params": cfg.num_params()},
        "batch": sizes["batch"], "seq": sizes["seq"],
        "param_dtype": sizes["param_dtype"], "moments": sizes["moments"],
        "loss": loss, "compile_s": round(compile_s, 2),
        "step_s": round(step_s, 3),
        "step_s_with_async_save": round(save_steps_s, 3),
        "flash_custom_calls": flash_calls,
        "restore": restore, "offload": offload,
        "state_bytes_per_device": per_device, "peak_bytes_in_use": peak,
        "compile_cache": cache,
    })


# ---------------------------------------------------------------------------
# serve: parent side
# ---------------------------------------------------------------------------


def make_prompts(sizes: dict) -> list:
    rng = random.Random(SEED)
    return [[rng.randrange(1, sizes["vocab"]) for _ in range(n)]
            for n in sizes["prompt_lens"]]


def http(url: str, body=None, timeout: float = 600):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data,
                                 method="GET" if body is None else "POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def metric(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith((name + "{", name + " ")):
            return float(line.rsplit(" ", 1)[1])
    raise PhaseFailed(f"/metrics has no {name}")


def wait_ready(proc, base: str, timeout: float) -> None:
    """Until /readyz answers 200; a server that died or never gets there
    fails the phase with the end of its log."""
    deadline = time.time() + timeout
    while True:
        need(proc.poll() is None,
             f"server exited {proc.returncode} before it was ready\n"
             + log_tail(proc))
        need(time.time() < deadline,
             "server not ready in time\n" + log_tail(proc))
        try:
            if http(base + "/readyz", timeout=5)[0] == 200:
                return
        except OSError:
            pass
        time.sleep(0.5)


def phase_serve(sizes: dict, *, name: str = "serve", kv_quant: str = "",
                tp: int = 1, probe: int = 0, traffic: bool = True,
                timeout: float = 600) -> dict:
    """Start the real server, talk to it over HTTP, SIGTERM it.  Asks
    prompt number `probe` first and returns it with the answer; with
    `traffic`, then two prompts at once and one of them again."""
    from paddle_operator_tpu.api.types import EXIT_PREEMPTED

    port = free_port()
    env = child_env(
        MODEL_PRESET=sizes["preset"], SERVE_CONTINUOUS=1, SERVE_PAGED=1,
        SERVE_SLOTS=sizes["slots"], SERVE_BLOCK_SIZE=sizes["block"],
        SERVE_MAX_LEN=sizes["max_len"], SERVE_CHUNK=sizes["chunk"],
        SERVE_TP=tp, TPUJOB_PORT=port, TPUJOB_NAME="chip-smoke")
    env.pop("TPUJOB_CHECKPOINT_PATH", None)     # smoke mode: seeded init
    if kv_quant:
        env["SERVE_KV_QUANT"] = kv_quant
    before = cache_entries()
    t0 = time.time()
    proc = start([sys.executable, "-m", "paddle_operator_tpu.infer.serve"],
                 env, f"{name}.log")
    base = f"http://127.0.0.1:{port}"
    try:
        wait_ready(proc, base, timeout)
        ready_s = time.time() - t0
        with open(proc.log_path, errors="replace") as f:
            log = f.read()
        banner = next((ln for ln in log.splitlines()
                       if ln.startswith("serving ")), "")
        for want in (f"platform={sizes['platform']},",
                     f"decode_attn={sizes['decode_attn']},",
                     f"kv_quant={kv_quant or 'none'},", f"tp={tp},"):
            need(want in banner, f"start-up line lacks {want!r}: {banner!r}")
        in_use = next((ln for ln in log.splitlines()
                       if ln.startswith("serving ready:")), "")
        # weights and pool resident: under SERVE_TP nothing may sit whole
        # on the first device (the CPU backend reports no byte counts)
        held = [b for b in json.loads(
            in_use.partition("=")[2].replace("None", "null") or "[]") if b]
        need(len(held) < 2 or max(held) < 2 * min(held),
             f"one device of the serving mesh holds far more than "
             f"another: {in_use}")

        prompts = make_prompts(sizes)
        new = sizes["new_tokens"]

        def generate(prompt):
            code, body = http(base + "/v1/generate",
                              {"tokens": [prompt], "max_new_tokens": new})
            need(code == 200, f"/v1/generate answered {code}: {body[:300]}")
            row = json.loads(body)["tokens"][0]
            need(len(row) == len(prompt) + new and row[:len(prompt)] == prompt,
                 f"expected prompt + {new} tokens, got {len(row)}")
            return row

        out = {"prompt": prompts[probe],
               "answer": generate(prompts[probe])[len(prompts[probe]):]}
        if traffic:
            # two at once: the ring decodes them side by side
            rows, errs = {}, []

            def client(i):
                try:
                    rows[i] = generate(prompts[i])
                except Exception as e:      # surfaced below, on this thread
                    errs.append(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in (1, 2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout)
            need(not errs and len(rows) == 2, f"concurrent requests: {errs}")
            hit0 = metric(http(base + "/metrics")[1],
                          "tpujob_serve_prefix_hit_rate")
            again = generate(prompts[1])
            hit1 = metric(http(base + "/metrics")[1],
                          "tpujob_serve_prefix_hit_rate")
            need(hit1 > hit0, "resubmission not admitted through the prefix "
                 f"cache: hit rate {hit0} -> {hit1}")
            out["prefix_hit_rate"] = [hit0, hit1]
            # the cold admission prefilled the whole prompt, the hit only
            # its suffix over cached blocks: another program.  In f32 the
            # answers are identical; where they part, the reference child
            # holds BOTH tokens at that position to the same logits.
            n = len(prompts[1])
            out["resubmit"] = {"prompt": prompts[1], "first": rows[1][n:],
                               "again": again[n:]}
        need(http(base + "/readyz")[0] == 200, "/readyz not 200 under load")
        os.killpg(proc.pid, signal.SIGTERM)
        rc = proc.wait(timeout=120)
        need(rc == EXIT_PREEMPTED,
             f"SIGTERM drain exited {rc}, not {EXIT_PREEMPTED}")
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    say(name, preset=sizes["preset"], banner=banner, device_bytes=in_use,
        ready_s=round(ready_s, 1), seconds=round(time.time() - t0, 1),
        answered=4 if traffic else 1, new_tokens=new,
        prefix_hit_rate=out.get("prefix_hit_rate"),
        resubmit_identical=(out["resubmit"]["first"]
                            == out["resubmit"]["again"]
                            if "resubmit" in out else None),
        cache_entries=[before, cache_entries()])
    return out


def phase_reference(sizes: dict, served: dict, *, name: str = "reference",
                    tp: int = 1, devices: int = 1,
                    timeout: float = 600) -> dict:
    """A child that holds the chip alone: the einsum's logits for the
    probe prompt, against the kernel's and against the servers' tokens."""
    before = cache_entries()
    r = run_child(child_argv("reference_child",
                             dict(sizes, served=served, tp=tp)),
                  child_env(), f"{name}.log", timeout)
    check_device(r["device"], sizes["platform"], devices)
    need(r["kernel_vs_xla_max_abs"] <= LOGIT_TOL,
         f"kernel and einsum logits differ by {r['kernel_vs_xla_max_abs']}")
    for who, gaps in r["token_gaps"].items():
        tol = KVQ_LOGIT_TOL if who.endswith("int8") else LOGIT_TOL
        need(max(gaps) <= tol, f"{who}: a served token is {max(gaps)} "
             f"below the reference's best logit (tolerance {tol})")
    for who, d in r["resubmit"].items():
        need(d is None or max(d["gaps"]) <= LOGIT_TOL,
             f"{who}: the resubmission parts from the first answer at new "
             f"token {d and d['index']} by more than a near-tie: {d}")
    if tp > 1:
        need(r["tp_vs_xla_max_abs"] <= LOGIT_TOL,
             f"tp={tp} logits differ by {r['tp_vs_xla_max_abs']}")
        need(r["tp_all_reduces"] > 0 and (
            r["tp_custom_calls"] > 0 or sizes["kernel"] != "pallas"),
            f"sharded decode step lacks the kernel or its psum: {r}")
    say(name, cache_entries=[before, cache_entries()], **r)
    return r


def reference_child() -> None:
    sizes = json.loads(sys.argv[1])
    import dataclasses

    from paddle_operator_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    cache = count_cache_events()
    device = device_report()
    if device["platform"] != sizes["platform"]:
        emit_result({"device": device, "early": True})
        raise SystemExit(3)

    from paddle_operator_tpu.infer import decode as D
    from paddle_operator_tpu.infer.serve import load_serving_params
    from paddle_operator_tpu.models.llama import CONFIGS

    cfg = CONFIGS[sizes["preset"]]
    xla = dataclasses.replace(cfg, decode_attn="xla")
    ker = dataclasses.replace(cfg, decode_attn=sizes["kernel"])
    params, _ = load_serving_params(cfg, None, seed=SEED)  # the server's own
    out = {"device": device, "token_gaps": {}, "resubmit": {},
           "compile_cache": cache}

    def prefill(tokens, mesh=None, p=params):
        """Logits after `tokens` (they pick the next one) and the filled
        cache.  Prefill never runs the single-query kernel, so one
        config serves every comparison."""
        l0, kv = jax.jit(lambda p, t: D.prefill(
            p, xla, t, sizes["max_len"], mesh=mesh))(
                p, jnp.asarray([tokens], jnp.int32))
        return np.asarray(l0[0]), kv

    def decode(c, token, kv, mesh=None, p=params):
        """Logits of one single-query decode step fed `token`."""
        l1, _ = jax.jit(lambda p, t, kv: D.decode_step(
            p, c, t, kv, mesh=mesh))(p, jnp.asarray([token], jnp.int32), kv)
        return np.asarray(l1[0])

    def tp_compare(prompt, t1, l1) -> dict:
        """The kernel's step on the SERVE_TP mesh: its logits against the
        one-device einsum's, what the compiler made of it, and where the
        parameters live."""
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        mesh = make_serving_mesh(sizes["tp"])
        sharded = D.shard_params_for_serving(params, cfg, mesh)
        _, kv = prefill(prompt, mesh, sharded)
        tok = jnp.asarray([t1], jnp.int32)
        step = jax.jit(lambda p, t, kv: D.decode_step(
            p, ker, t, kv, mesh=mesh)).lower(sharded, tok, kv).compile()
        text = step.as_text()
        return {
            "tp_vs_xla_max_abs": float(np.abs(
                np.asarray(step(sharded, tok, kv)[0][0]) - l1).max()),
            "tp_custom_calls": text.count("tpu_custom_call"),
            "tp_all_reduces": text.count("all-reduce"),
            "tp_all_gathers": text.count("all-gather"),
            "param_bytes_per_device": {
                str(d): sum(s.data.nbytes
                            for leaf in jax.tree.leaves(sharded)
                            for s in leaf.addressable_shards
                            if s.device == d)
                for d in mesh.devices.flat}}

    for who, served in sizes["served"].items():
        # the first new token is the prefill's pick, the second the
        # first decode step's: each against the einsum's best logit
        t1, t2 = served["answer"][:2]
        l0, kv = prefill(served["prompt"])
        l1 = decode(xla, t1, kv)
        out["token_gaps"][who] = [float(l0.max() - l0[t1]),
                                  float(l1.max() - l1[t2])]
        if "kernel_vs_xla_max_abs" not in out:
            # the same step through the kernel: same token, same cache
            out["kernel_vs_xla_max_abs"] = float(
                np.abs(decode(ker, t1, kv) - l1).max())
            out["logit_abs_max"] = float(np.abs(l1).max())
            if sizes["tp"] > 1:
                out.update(tp_compare(served["prompt"], t1, l1))
        re = served.get("resubmit")
        if re is not None:
            # where the two answers part (None: they never do), the gap
            # of each one's token to the best logit at that position
            i = next((i for i, (a, b) in enumerate(zip(re["first"],
                                                       re["again"]))
                      if a != b), None)
            out["resubmit"][who] = None
            if i is not None:
                l, _ = prefill(re["prompt"] + re["first"][:i])
                out["resubmit"][who] = {
                    "index": i, "gaps": [float(l.max() - l[re["first"][i]]),
                                         float(l.max() - l[re["again"][i]])]}
    out["peak_bytes_in_use"] = peak_bytes()
    emit_result(out)


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------


def run_one_chip() -> dict:
    r = phase_train(TRAIN, mesh={})
    served = {
        "serve": phase_serve(SERVE),
        # probe 1 is two whole blocks long: its second token reads
        # dequantized pool blocks, not only the lane's bf16 staging tail
        "serve-int8": phase_serve(SERVE, name="serve-int8", kv_quant="int8",
                                  probe=1, traffic=False),
    }
    phase_reference(SERVE, served)
    return r["device"]


def run_four_chips() -> dict:
    sharded = phase_train(dict(TRAIN, offload_layers=0),
                          mesh={"fsdp": 2, "tp": 2}, name="train-fsdp2-tp2",
                          devices=4)
    # what it is compared with sees the same host — four devices, a mesh
    # over the first — and skips what the one-chip run already proves
    one = phase_train(dict(TRAIN, offload_layers=0, checkpoint=False),
                      mesh={}, name="train-one-device", devices=4)
    gap = abs(sharded["loss"][0] - one["loss"][0])
    need(gap <= LOSS_TOL, f"first-step loss differs by {gap}: "
         f"{sharded['loss'][0]} sharded vs {one['loss'][0]} on one device")
    by_dev = sharded["state_bytes_per_device"]
    whole = max(one["state_bytes_per_device"].values())
    need(len(by_dev) == 4 and max(by_dev.values()) < whole,
         f"state not spread over four devices below the one-chip "
         f"{whole}: {by_dev}")
    say("train-compare", first_step_loss_gap=gap, tolerance=LOSS_TOL,
        sharded_bytes_per_device=by_dev, one_device_bytes=whole)

    served = {"serve-tp4": phase_serve(SERVE, name="serve-tp4", tp=4),
              "serve-tp1": phase_serve(SERVE, name="serve-tp1",
                                       traffic=False)}
    phase_reference(SERVE, served, tp=4, devices=4)
    return sharded["device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths and what each "
                         "is compared with")
    args = ap.parse_args(argv)
    shutil.rmtree(WORK, ignore_errors=True)
    try:
        device = run_one_chip() if args.chips == 1 else run_four_chips()
    except (PhaseFailed, ImportError) as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        stop_all()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
