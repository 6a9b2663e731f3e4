"""The window driver for configurations of ``kind: serve_afmoe``: the
serving window of ``serve_window.py`` (the same load generator, warm-up,
pre-roll, scrapes, trace and judging — imported from it) around another
child (``serve_afmoe_child.py``: the ``afmoe`` architecture's configuration
and weights) and another comparison (``reference/afmoe_serve_check.py``
against ``reference/afmoe_ref.py``).

What differs from ``serve_window._run``: the sample the reference judges
(:func:`sample_for_check`: contexts past the sliding window and a request
on every rung of the prefill ladder the window used), and two more scrapes
of ``/statusz`` as the trace starts and stops (``statusz_traced``: the
routing counters over the traced seconds, for ``moe_gmm_roofline``).
"""

from __future__ import annotations

import json
import os
import random
import shutil
import signal
import subprocess
import time

from benchmark.harness import common as C
from benchmark.harness import loadgen as LG
from benchmark.harness import serve_window as SW


def rung_of(n_prompt: int, rungs: list) -> int:
    return min(r for r in rungs if r >= n_prompt)


def sample_for_check(finished: list, seed: int, cfg: dict) -> list:
    """The finished requests the reference judges, drawn from the seed:
    ``long_requests`` whose context (prompt and answer) passes
    ``long_context`` tokens — so that the comparison sees the window cut
    and the kernel's skipped first block — taking the wide rungs of the
    ladder first, then one request of at most ``short_tokens`` on every
    other rung the finished requests used.  The long ones are checked at
    ``max_len``, the others at ``short_tokens``: two compiled lengths
    whatever the seed."""
    check, rungs = cfg["check"], cfg["serve"]["rungs"]
    size = lambda r: len(r.prompt) + len(r.tokens)
    pool = list(finished)
    random.Random(seed).shuffle(pool)
    by_rung: dict = {}
    for r in pool:
        by_rung.setdefault(rung_of(len(r.prompt), rungs), []).append(r)
    long_all = [r for r in pool if size(r) > check["long_context"]]
    C.need(len(long_all) >= check["long_requests"],
           f"only {len(long_all)} finished requests pass "
           f"{check['long_context']} tokens of context: the comparison "
           f"needs {check['long_requests']}")
    # the widest rungs' first, one each, then whatever is left
    firsts, seen = [], set()
    for r in sorted(long_all, key=lambda r: -rung_of(len(r.prompt), rungs)):
        rung = rung_of(len(r.prompt), rungs)
        if rung not in seen:
            seen.add(rung)
            firsts.append(r)
    rest = [r for r in long_all if r not in firsts]
    long = (firsts + rest)[:check["long_requests"]]
    covered = {rung_of(len(r.prompt), rungs) for r in long}
    short = []
    for rung in sorted(by_rung):
        fit = [r for r in by_rung[rung] if size(r) <= check["short_tokens"]]
        if rung not in covered and fit:
            short.append(fit[0])
    return long + short


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
           "serve_afmoe_window drives closed loops only")
    port, ctl_port = C.free_port(), C.free_port()
    requests = SW.build_requests(cell, seed, seconds)
    proc = children.start([C.PY, "-m", "benchmark.harness.serve_afmoe_child"],
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
    sample = sample_for_check(finished, seed, cfg)
    if tamper is not None:
        tamper(sample)
    check_in = os.path.join(C.WORK, "check_in.json")
    with open(check_in, "w") as f:
        json.dump({"seed": seed, "config_file": cell["config_file"],
                   "control": control,
                   "requests": [{"prompt": r.prompt, "served": r.tokens}
                                for r in sample]}, f)
    ref = C.run_child(children,
                      [C.PY, "-m", "benchmark.reference.afmoe_serve_check",
                       check_in], C.child_env(), "reference.log", 900)
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
            "checked_rungs": sorted({rung_of(len(r.prompt),
                                             cfg["serve"]["rungs"])
                                     for r in sample}),
            # reported, not compared (reference/afmoe_serve_check.py)
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
        },
    }
