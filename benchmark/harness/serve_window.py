"""The window driver for configurations of ``kind: serve``.

The runner's process is the load generator and never imports jax.  The
server is a child started through ``infer/serve.py main()`` as a pod starts
it (``serve_child.py`` registers the configuration first).  After the window
has closed and the peak memory has been read, the server is stopped and a
second child runs the plain reference on the chip the server has freed.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import signal
import subprocess
import threading
import time
import urllib.error
import urllib.request

from benchmark.harness import common as C
from benchmark.harness import loadgen as LG
from benchmark.harness import traffic as TR

TRACE_S = 4.0            # the traced run traces the window's last seconds,
TRACE_END_S = 1.0        # up to one second before it closes


def http_get(url: str, timeout: float = 30.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def ctl(port: int, path: str, timeout: float = 120.0) -> dict:
    code, body = http_get(f"http://127.0.0.1:{port}{path}", timeout)
    C.need(code == 200, f"control {path} answered {code}: {body[:200]}")
    return json.loads(body)


def scrape(port: int) -> dict:
    """``/metrics`` as name (labels dropped) -> value; histogram buckets
    are left out, their ``_sum`` and ``_count`` kept."""
    code, text = http_get(f"http://127.0.0.1:{port}/metrics")
    C.need(code == 200, f"/metrics answered {code}")
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#") or "_bucket{" in line:
            continue
        name, _, value = line.rpartition(" ")
        out[name.split("{")[0]] = float(value)
    code, text = http_get(f"http://127.0.0.1:{port}/statusz")
    if code == 200:
        out["statusz"] = json.loads(text)
    return out


def wait_ready(proc, port: int, timeout: float) -> None:
    deadline = time.time() + timeout
    while True:
        C.need(proc.poll() is None,
               f"server exited {proc.returncode} before it was ready\n"
               + C.log_tail(proc))
        C.need(time.time() < deadline,
               "server not ready in time\n" + C.log_tail(proc))
        try:
            if http_get(f"http://127.0.0.1:{port}/readyz", 5)[0] == 200:
                return
        except OSError:
            pass
        time.sleep(0.25)


class LaneSampler(threading.Thread):
    """Samples ``/statusz`` (lane positions, free blocks) five times a
    second; only the traced run has one."""

    def __init__(self, port: int) -> None:
        super().__init__(daemon=True)
        self.port, self.stop, self.samples = port, threading.Event(), []

    def run(self) -> None:
        while not self.stop.wait(0.2):
            try:
                code, text = http_get(f"http://127.0.0.1:{self.port}/statusz", 5)
            except OSError:
                continue
            if code == 200:
                st = json.loads(text)
                self.samples.append({"t": time.time(),
                                     "lanePos": st["lanePos"],
                                     "kvBlocksFree": st["kvBlocksFree"]})


def server_env(cell: dict, seed: int, port: int, ctl_port: int) -> dict:
    s = cell["config"]["serve"]
    env = C.child_env(
        MODEL_PRESET=cell["config_name"], BENCH_CONFIG_FILE=cell["config_file"],
        BENCH_SEED=seed, BENCH_CTL_PORT=ctl_port, TPUJOB_PORT=port,
        TPUJOB_NAME="bench", SERVE_CONTINUOUS=1, SERVE_PAGED=1,
        SERVE_SLOTS=s["lanes"], SERVE_BLOCK_SIZE=s["block"],
        SERVE_MAX_LEN=s["max_len"], SERVE_CHUNK=s["chunk"],
        # warm-up is the benchmark's: real requests, one per prefill bucket,
        # and no throwaway second pool beside the real one
        SERVE_PREWARM=0,
        # no host copy of the weights for /v1/swap: 7.5 GB through the host
        SERVE_SWAP_RETAIN=0, SERVE_DRAIN_BUDGET_S=1)
    env.pop("TPUJOB_CHECKPOINT_PATH", None)
    for k, v in (s.get("env") or {}).items():
        env[k] = str(v)
    return env


def warm_up(port: int, cell: dict, seed: int) -> dict:
    """One request in each prefill bucket the cell's traffic reaches, all at
    once (so the decode step runs with several lanes live), then the same
    again: the second round has to find every program compiled."""
    s, vocab = cell["config"]["serve"], cell["config"]["vocab_size"]
    took = {}
    for rnd in range(2):
        reqs = [LG.Request({"answer_tokens": s["chunk"] * 2 + 1},
                           TR.token_ids(seed, 10_000_000 + 100 * rnd + i, n,
                                        vocab))
                for i, n in enumerate(s["warm_prompts"])]
        threads = [threading.Thread(target=LG.generate, args=(port, r, 1100))
                   for r in reqs]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        took[f"round{rnd}"] = round(time.time() - t0, 2)
        bad = [r.error for r in reqs if r.error]
        C.need(not bad, f"warm-up request failed: {bad}")
    return took


def build_requests(cell: dict, seed: int, seconds: float) -> list:
    vocab = cell["config"]["vocab_size"]
    return [LG.Request(spec, TR.token_ids(seed, spec["index"],
                                          spec["prompt_tokens"], vocab,
                                          spec["prefix"]))
            for spec in TR.serve_requests(cell["traffic"], seconds)]


def sample_for_check(finished: list, seed: int, check: dict) -> list:
    """A sample of the finished requests, drawn from the seed, with the
    longest in it.  The others are drawn from those of at most
    ``short_tokens`` tokens (prompt and answer), so that the reference runs
    at two lengths whatever the seed: the longest at ``max_len``, the rest
    at ``short_tokens``.  Prompts of every prefill bucket fit under it."""
    if not finished:
        return []
    size = lambda r: len(r.prompt) + len(r.tokens)
    longest = max(finished, key=size)
    rest = [r for r in finished
            if r is not longest and size(r) <= check["short_tokens"]]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:max(0, check["requests"] - 1)]


def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        *, control: str | None = None, tamper=None) -> dict:
    """One run of a serving cell; returns the pieces of the result line.
    `tamper` (tests only) is given the sampled requests before they are
    checked, to plant a fault underneath the comparison."""
    children = C.Children()
    try:
        return _run(cell, seed, seconds, trace, t_start, control, children,
                    tamper)
    finally:
        children.stop_all()


def _run(cell, seed, seconds, trace, t_start, control, children,
         tamper) -> dict:
    cfg, traffic = cell["config"], cell["traffic"]
    port, ctl_port = C.free_port(), C.free_port()
    requests = build_requests(cell, seed, seconds)
    proc = children.start([C.PY, "-m", "benchmark.harness.serve_child"],
                          server_env(cell, seed, port, ctl_port),
                          "server.log")
    wait_ready(proc, port, 1100)
    ready_s = time.time() - t_start
    kv_blocks_total = scrape(port).get("statusz", {}).get("kvBlocksFree")
    device = ctl(ctl_port, "/device")
    C.check_device(device, cfg["platform"], cell["chips"])
    warm = warm_up(port, cell, seed)
    compiles0 = ctl(ctl_port, "/compiles")

    # ---- the window -------------------------------------------------------
    if traffic["loop"] == "closed":
        lists = [[] for _ in range(traffic["callers"])]
        for r in sorted(requests, key=lambda r: r.spec["order"]):
            lists[r.spec["caller"]].append(r)
        loop = LG.ClosedLoop(port, lists,
                             think_s=traffic.get("think_ms", 0) / 1e3,
                             stagger_s=traffic.get("stagger_ms", 0) / 1e3)
        loop.start()
        # the pre-roll: the window opens on a ring in which every lane has
        # finished a request, and the pre-roll is set-up
        C.need(loop.wait_each_lane_finished_one(300),
               "pre-roll: not every caller got an answer in 300 s\n"
               + C.log_tail(proc))
        t_open = time.time()
    else:
        # schedule times are relative to the opening; the pre-roll's
        # requests are due before it
        t_open = time.time() + float(traffic.get("preroll_s", 0.0))
        loop = LG.OpenLoop(port, requests, t_open)
        loop.start()
        time.sleep(max(0.0, t_open - time.time()))
    m_open = scrape(port)
    t_close = t_open + seconds
    trace_dir = os.path.join(C.WORK, "trace")
    traced = None
    sampler = LaneSampler(port) if trace else None
    if trace:
        sampler.start()
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = min(TRACE_S, max(0.5, seconds / 4))
        time.sleep(max(0.0, t_close - TRACE_END_S - span - time.time()))
        t0 = time.time()
        ctl(ctl_port, "/trace/start?dir=" + trace_dir)
        time.sleep(span)
        ctl(ctl_port, "/trace/stop")
        traced = (t0, time.time())
    time.sleep(max(0.0, t_close - time.time()))
    m_close = scrape(port)
    if sampler is not None:
        sampler.stop.set()
    if traffic["loop"] == "closed":
        sent = list(loop.sent)
        loop.close(0.0)
    else:
        loop.close(seconds + 90.0)       # wait for every answer that is due
        sent = requests
    t_end = time.time()
    compiles1 = ctl(ctl_port, "/compiles")
    mem = ctl(ctl_port, "/mem")
    m_end = scrape(port)

    if traffic["loop"] == "closed":
        # judged: every request that ended inside the window
        judged = [r for r in sent if r.end is not None and r.end < t_close]
    else:
        # judged: every request due inside the window, however late it ended
        judged = [r for r in sent if t_open <= r.due < t_close]
    failed = [r for r in judged if not r.done]
    finished = [r for r in judged if r.done]

    # ---- stop the server, free the chip ------------------------------------
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()

    trace_out = C.reduce_trace(children, trace_dir) if trace else None

    # ---- the plain reference, on the freed chip -----------------------------
    sample = sample_for_check(finished, seed, cfg["check"])
    if tamper is not None:
        tamper(sample)
    check_in = os.path.join(C.WORK, "check_in.json")
    with open(check_in, "w") as f:
        json.dump({"seed": seed, "config_file": cell["config_file"],
                   "control": control,
                   "requests": [{"prompt": r.prompt, "served": r.tokens}
                                for r in sample]}, f)
    ref = C.run_child(children,
                      [C.PY, "-m", "benchmark.reference.serve_check",
                       check_in], C.child_env(), "reference.log", 900)
    C.check_device(ref["device"], cfg["platform"], cell["chips"])

    if os.environ.get("BENCH_KEEP_REQUESTS"):
        # builder's look at a run: what the client saw of every request
        with open(os.path.join(C.WORK, "requests.json"), "w") as f:
            json.dump({"t_open": t_open, "t_close": t_close, "requests": [
                {"i": r.spec["index"], "caller": r.spec.get("caller"),
                 "due": r.due, "sent": r.sent, "end": r.end,
                 "p": len(r.prompt), "a": r.spec["answer_tokens"],
                 "first": r.token_times[0] if r.token_times else None,
                 "n": len(r.tokens), "error": r.error} for r in sent]}, f)

    window = {"t_open": t_open, "t_close": t_close, "t_end": t_end,
              "seconds": seconds, "traced": traced}
    peak = max((d.get("peak_bytes_in_use") or 0) for d in mem) if mem else 0
    checks = {
        "logit_gap_max": {"value": ref["gap_max"],
                          "limit": cfg["check"]["logit_gap_max"]},
        "requests_failed": {"value": len(failed), "limit": 0},
    }
    return {
        "cell": cell, "seed": seed, "window": window, "setup_s": t_open - t_start,
        "requests": sent, "judged": judged, "finished": finished,
        "failed": failed, "checks": checks,
        "metrics_open": m_open, "metrics_close": m_close, "metrics_end": m_end,
        "device": {**device, "memory_peak_bytes": peak},
        "trace": trace_out, "kv_blocks_total": kv_blocks_total,
        "lane_samples": sampler.samples if sampler is not None else [],
        "notes": {
            "ready_s": ready_s, "warm_up_s": warm, "compile_requests_hits_setup":
                [compiles0["requests"], compiles0["hits"]],
            "compiles_in_window": compiles1["requests"] - compiles0["requests"],
            "reference_s": ref["seconds"],
            "reference_compile_requests_hits": ref.get("compiles"), "checked_tokens": ref["tokens"],
            "checked_requests": len(sample), "control": ref.get("control"),
            "failed_errors": [r.error for r in failed][:5],
            # the same run read over shorter windows from the same opening:
            # what a smaller run_seconds would have measured
            "tokens_per_s_by_seconds": {
                str(s): LG.tokens_in_window(sent, t_open, t_open + s) / s
                for s in (10, 20, 30, 40) if s < seconds},
            "judged_requests": len(judged), "drain_s": t_end - t_close,
        },
    }
