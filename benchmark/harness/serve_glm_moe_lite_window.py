"""The window driver for configurations of ``kind: serve_glm_moe_lite`` — a
``glm4_moe_lite`` model (GLM-4.7-Flash: latent attention over sigmoid-routed
experts) on the paged continuous ring.

The kind: the server is ``infer/serve.py main()`` started by
``serve_glm_moe_lite_child.py`` (the cell's ``config.json`` turned into
``models/glm_moe_lite.py GlmMoeLiteConfig`` by ``harness/glm_moe_lite.py``,
the seeded weights laid out as the program's tree, ``kv_b_proj`` cut into
the two halves the program holds), with ``SERVE_CONTINUOUS=1 SERVE_PAGED=1``
and the configuration's ``serve`` block (lanes, block, ``max_len``, chunk,
``env``); every rung of ``serve.rungs`` is warmed by one real request of
``serve.warm_prompts`` before the window; ``correct`` is decided by
``reference/glm_moe_lite_serve_check.py`` against
``reference/glm_moe_lite_ref.py`` (``check.logit_gap_mean`` over the served
tokens of the sample, with ``requests_failed`` and ``callers_run_dry`` at
0).  The configuration file needs the published ``config.json``'s keys,
``torch_dtype``, ``platform``, ``serve`` and ``check`` (``long_requests``,
``long_context``, ``short_tokens``, ``logit_gap_mean``).

The window itself is ``serve_afmoe_window.py``'s, piece by piece (the same
load generator, warm-up, pre-roll, scrapes, trace and judging, imported
from ``serve_window.py``; the sample the reference judges and the two more
``/statusz`` scrapes as the trace starts and stops, imported from
``serve_afmoe_window.py``): what differs is the child and the comparison.
Closed loops only.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import time

from benchmark.harness import common as C
from benchmark.harness import loadgen as LG
from benchmark.harness import serve_afmoe_window as AW
from benchmark.harness import serve_window as SW

CHILD = "benchmark.harness.serve_glm_moe_lite_child"
CHECK = "benchmark.reference.glm_moe_lite_serve_check"


def phase_seconds(m_open: dict, m_close: dict) -> dict:
    """The ring thread's self seconds by phase over the window (``/statusz``
    ``phaseSeconds`` at its edges), largest first: where a run that lost a
    second of the ring lost it (PERF.md section 7's stall)."""
    a = (m_open.get("statusz") or {}).get("phaseSeconds") or {}
    b = (m_close.get("statusz") or {}).get("phaseSeconds") or {}
    grown = {k: round(v - a.get(k, 0.0), 3) for k, v in b.items()}
    return dict(sorted(grown.items(), key=lambda kv: -kv[1])[:8])


def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        *, control: str | None = None, tamper=None) -> dict:
    children = C.Children()
    try:
        return _run(cell, seed, seconds, trace, t_start, control, children,
                    tamper)
    finally:
        children.stop_all()


def _run(cell, seed, seconds, trace, t_start, control, children,
         tamper) -> dict:
    cfg, traffic = cell["config"], cell["traffic"]
    C.need(traffic["loop"] == "closed",
           "serve_glm_moe_lite_window drives closed loops only")
    port, ctl_port = C.free_port(), C.free_port()
    requests = SW.build_requests(cell, seed, seconds)
    proc = children.start([C.PY, "-m", CHILD],
                          SW.server_env(cell, seed, port, ctl_port),
                          "server.log")
    SW.wait_ready(proc, port, 1100)
    ready_s = time.time() - t_start
    kv_blocks_total = SW.scrape(port).get("statusz", {}).get("kvBlocksFree")
    device = SW.ctl(ctl_port, "/device")
    C.check_device(device, cfg["platform"], cell["chips"])
    warm = SW.warm_up(port, cell, seed)
    compiles0 = SW.ctl(ctl_port, "/compiles")

    # ---- the window -------------------------------------------------------
    lists = [[] for _ in range(traffic["callers"])]
    for r in sorted(requests, key=lambda r: r.spec["order"]):
        lists[r.spec["caller"]].append(r)
    loop = LG.ClosedLoop(port, lists,
                         think_s=traffic.get("think_ms", 0) / 1e3,
                         stagger_s=traffic.get("stagger_ms", 0) / 1e3)
    loop.start()
    C.need(loop.wait_each_lane_finished_one(300),
           "pre-roll: not every caller got an answer in 300 s\n"
           + C.log_tail(proc))
    t_open = time.time()
    m_open = SW.scrape(port)
    t_close = t_open + seconds
    trace_dir = os.path.join(C.WORK, "trace")
    traced, statusz_traced = None, None
    sampler = SW.LaneSampler(port) if trace else None
    if trace:
        sampler.start()
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = min(SW.TRACE_S, max(0.5, seconds / 4))
        time.sleep(max(0.0, t_close - SW.TRACE_END_S - span - time.time()))
        t0 = time.time()
        SW.ctl(ctl_port, "/trace/start?dir=" + trace_dir)
        s0 = SW.scrape(port).get("statusz")
        time.sleep(span)
        s1 = SW.scrape(port).get("statusz")
        SW.ctl(ctl_port, "/trace/stop")
        traced, statusz_traced = (t0, time.time()), (s0, s1)
    time.sleep(max(0.0, t_close - time.time()))
    m_close = SW.scrape(port)
    if sampler is not None:
        sampler.stop.set()
    sent = list(loop.sent)
    # a caller whose list ran dry would idle its lane: the mix is too short
    dry = [i for i, rs in enumerate(lists)
           if rs and rs[-1].end is not None and rs[-1].end < t_close]
    loop.close(0.0)
    t_end = time.time()
    compiles1 = SW.ctl(ctl_port, "/compiles")
    mem = SW.ctl(ctl_port, "/mem")
    m_end = SW.scrape(port)

    judged = [r for r in sent if r.end is not None and r.end < t_close]
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
    sample = AW.sample_for_check(finished, seed, cfg)
    if tamper is not None:
        tamper(sample)
    check_in = os.path.join(C.WORK, "check_in.json")
    with open(check_in, "w") as f:
        json.dump({"seed": seed, "config_file": cell["config_file"],
                   "control": control,
                   "requests": [{"prompt": r.prompt, "served": r.tokens}
                                for r in sample]}, f)
    ref = C.run_child(children,
                      [C.PY, "-m", CHECK, check_in], C.child_env(), "reference.log", 900)
    C.check_device(ref["device"], cfg["platform"], cell["chips"])

    if os.environ.get("BENCH_KEEP_REQUESTS"):
        with open(os.path.join(C.WORK, "requests.json"), "w") as f:
            json.dump({"t_open": t_open, "t_close": t_close, "requests": [
                {"i": r.spec["index"], "caller": r.spec.get("caller"),
                 "order": r.spec.get("order"), "sent": r.sent, "end": r.end,
                 "p": len(r.prompt), "a": r.spec["answer_tokens"],
                 "first": r.token_times[0] if r.token_times else None,
                 "n": len(r.tokens), "error": r.error} for r in sent]}, f)

    window = {"t_open": t_open, "t_close": t_close, "t_end": t_end,
              "seconds": seconds, "traced": traced}
    peak = max((d.get("peak_bytes_in_use") or 0) for d in mem) if mem else 0
    checks = {
        "logit_gap_mean": {"value": ref["gap_mean"],
                           "limit": cfg["check"]["logit_gap_mean"]},
        "requests_failed": {"value": len(failed), "limit": 0},
        "callers_run_dry": {"value": len(dry), "limit": 0},
    }
    size = lambda r: len(r.prompt) + len(r.tokens)
    return {
        "cell": cell, "seed": seed, "window": window,
        "setup_s": t_open - t_start,
        "requests": sent, "judged": judged, "finished": finished,
        "failed": failed, "checks": checks,
        "metrics_open": m_open, "metrics_close": m_close, "metrics_end": m_end,
        "statusz_traced": statusz_traced,
        "device": {**device, "memory_peak_bytes": peak},
        "trace": trace_out, "kv_blocks_total": kv_blocks_total,
        "lane_samples": sampler.samples if sampler is not None else [],
        "notes": {
            "ready_s": ready_s, "warm_up_s": warm,
            "compile_requests_hits_setup":
                [compiles0["requests"], compiles0["hits"]],
            "compiles_in_window": compiles1["requests"] - compiles0["requests"],
            "reference_s": ref["seconds"],
            "reference_compile_requests_hits": ref.get("compiles"),
            "checked_tokens": ref["tokens"], "checked_requests": len(sample),
            "checked_contexts": [size(r) for r in sample],
            "checked_rungs": sorted({AW.rung_of(len(r.prompt),
                                             cfg["serve"]["rungs"])
                                     for r in sample}),
            # reported, not compared (reference/afmoe_serve_check.py compare)
            "logit_gap_max": ref["gap_max"],
            "mismatch_share": ref.get("mismatch_share"),
            "gap_max_by_request": ref.get("gap_max_by_request"),
            "gap_mean_by_request": ref.get("gap_mean_by_request"),
            "control": ref.get("control"),
            "failed_errors": [r.error for r in failed][:5],
            "highest_order_sent": max(
                (r.spec["order"] for r in sent), default=None),
            "requests_per_caller": traffic["requests_per_caller"],
            "tokens_per_s_by_seconds": {
                str(s): LG.tokens_in_window(sent, t_open, t_open + s) / s
                for s in (10, 20, 30, 40) if s < seconds},
            "judged_requests": len(judged), "drain_s": t_end - t_close,
            "phase_seconds_in_window": phase_seconds(m_open, m_close),
        },
    }
