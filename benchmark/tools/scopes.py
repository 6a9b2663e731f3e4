#!/usr/bin/env python3
"""Builder's tool: device self-time by named scope and by program, from one
traced run of a cell made with a compile cache of its own.

    python3 benchmark/tools/scopes.py --workload serve16l.closed16 --seed 7 \
        --seconds 24 --out chiprun_out/scopes_closed16.json

The program names its layers with ``jax.named_scope`` (embed, norm, attn.qkv,
attn.rope, cache_write, attn.kernel, attn.out, ffn, lm_head, sample, loss,
opt_update), which puts them into every operation's ``op_name``.  The
persistent compile cache strips debug information from its key, so an
executable cached before the scopes existed keeps running without them: this
tool points ``JAX_COMPILATION_CACHE_DIR`` at a new, empty directory, so that
every program of the run is compiled from the source as it stands.  The run
itself is the harness's own traced run (``run.run_cell``); the tool only keeps
the raw trace, which ``common.reduce_trace`` deletes, and reads it again for
the names.  ``op_name`` is read from whichever stat of a device operation's
event carries it; where none does, from the ``metadata`` of the programs' own
HLO text, which the run is asked to dump.  No run of the benchmark calls this.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import common as C  # noqa: E402
from benchmark.harness import xplane as XP  # noqa: E402

SCOPES = ("attn.qkv", "attn.rope", "attn.kernel", "attn.out", "cache_write",
          "embed", "norm", "attn", "ffn", "lm_head", "sample", "loss",
          "opt_update")
SCOPE_RE = re.compile(r"(?<![\w.])(" + "|".join(
    re.escape(s) for s in SCOPES) + r")(?![\w.])")
KEPT = os.path.join(C.WORK, "scopes_trace")
HLO = os.path.join(C.WORK, "scopes_hlo")


def scope_of(op_name: str) -> str:
    """The innermost scope of the vocabulary in an ``op_name`` such as
    ``jit(step)/while/body/attn.qkv/dot_general`` or
    ``jit(step_fn)/transpose(jvp(Llama))/layers/ffn/mlp/w1/dot_general``."""
    found = SCOPE_RE.findall(op_name or "")
    return found[-1] if found else "_unscoped_"


def pass_of(op_name: str) -> str:
    """Which pass of a train step an operation belongs to."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward"


def hlo_op_names(hlo_dir: str) -> dict:
    """``{module: {(instruction, shape): op_name}}`` from the optimized HLO
    text the run dumped (the fallback where no event stat carries
    ``op_name``).  Programs of one name (``jit_insert`` once per bucket)
    number their instructions alike: the output shape tells them apart."""
    out: dict = {}
    pat = re.compile(r'^\s*(?:ROOT\s+)?(%?[\w.\-]+ = .*?)op_name="([^"]*)"')
    for path in sorted(glob.glob(os.path.join(hlo_dir, "*.txt"))):
        base = os.path.basename(path)
        if "after_optimizations" not in base or "buffer" in base:
            continue
        m = re.search(r"(jit_[A-Za-z0-9_]+)", base)
        if not m:
            continue
        names = out.setdefault(m.group(1), {})
        with open(path, errors="replace") as f:
            for line in f:
                hit = pat.match(line)
                if hit:
                    op = XP.parse_op(hit.group(1))
                    names.setdefault((op["name"], op["shape"]), hit.group(2))
    return out


def load_ops(trace_dir: str):
    """The device operations ``(text, start_ns, dur_ns, stats)`` and the
    programs ``(name, start_ns, end_ns)`` of the newest trace under
    `trace_dir`.  Needs jax's ``ProfileData``: runs in a process held to the
    CPU, after the chip's has gone."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    C.need(paths, f"no .xplane.pb under {trace_dir}")
    events, modules = [], []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not XP.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == XP.MODULES_LINE:
                modules += [(ev.name, float(ev.start_ns),
                             float(ev.start_ns + ev.duration_ns))
                            for ev in line.events]
            elif line.name == XP.OPS_LINE:
                events += [(ev.name, float(ev.start_ns),
                            float(ev.duration_ns),
                            {str(k): v for k, v in ev.stats})
                           for ev in line.events]
    C.need(events, "the trace holds no device operation")
    return events, modules


def by_scope(events: list, modules: list, from_hlo: dict, top: int) -> dict:
    """Self-time of the device operations by program, and within a program
    by scope, by pass and by operation."""
    stat_keys: dict = {}
    for _, _, _, stats in events:
        for k, v in stats.items():
            if isinstance(v, str) and "/" in v and "jit(" in v:
                stat_keys[k] = stat_keys.get(k, 0) + 1
    stat = max(stat_keys, key=stat_keys.get) if stat_keys else None
    stats_at = {(t, s): st for t, s, _, st in events}

    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]

    def module_at(start: float) -> str:
        """The program running at `start` (one chip: programs never
        overlap)."""
        i = bisect.bisect_right(starts, start) - 1
        return modules[i][0] if i >= 0 and start < modules[i][2] else ""

    def program(name: str) -> dict:
        return programs.setdefault(name, {"calls": 0, "seconds": 0.0,
                                          "scopes": {}, "passes": {},
                                          "ops": {}})

    programs: dict = {}
    for name, a, b in modules:
        p = program(name)
        p["calls"] += 1
        p["seconds"] += (b - a) / 1e9
    unnamed = 0
    for text, start, own in XP.self_times([e[:3] for e in events]):
        op = XP.parse_op(text)
        if op["kind"] in ("while", "call", "conditional") or own <= 0:
            continue
        mod = module_at(start)
        if stat is not None:
            op_name = stats_at[(text, start)].get(stat, "")
        else:
            op_name = from_hlo.get(XP.module_name(mod), {}).get(
                (op["name"], op["shape"]), "")
        unnamed += not op_name
        p, scope, sec = program(mod), scope_of(op_name), own / 1e9
        p["scopes"][scope] = p["scopes"].get(scope, 0.0) + sec
        key = pass_of(op_name) + ":" + scope
        p["passes"][key] = p["passes"].get(key, 0.0) + sec
        row = p["ops"].setdefault(XP.label(text), [0.0, 0, scope, op_name])
        row[0] += sec
        row[1] += 1
    for p in programs.values():
        p["ops"] = sorted(([k] + v for k, v in p["ops"].items()),
                          key=lambda r: -r[1])[:top]
    return {"op_name_stat": stat or "none: read from the dumped HLO text",
            "stats_seen": sorted({k for e in events[:2000] for k in e[3]}),
            "operations": len(events), "without_op_name": unnamed,
            "programs": programs}


def show(out: dict) -> None:
    print(f"op_name read from: {out['op_name_stat']}; event stats seen: "
          f"{out['stats_seen']}; {out['without_op_name']} of "
          f"{out['operations']} operations without one")
    for name, p in sorted(out["programs"].items(),
                          key=lambda kv: -kv[1]["seconds"]):
        if not p["calls"]:
            continue
        per = 1e3 * p["seconds"] / p["calls"]
        print(f"\n== {name}: {p['calls']} calls, {p['seconds']:.3f} s, "
              f"{per:.3f} ms a call")
        total = sum(p["scopes"].values()) or 1.0
        for scope, sec in sorted(p["scopes"].items(), key=lambda kv: -kv[1]):
            print(f"   {scope:<14} {sec:8.4f} s  {100 * sec / total:5.1f} %  "
                  f"{1e3 * sec / p['calls']:8.3f} ms a call")
        if any(not k.startswith("forward:") for k in p["passes"]):
            for key, sec in sorted(p["passes"].items(), key=lambda kv: -kv[1]):
                print(f"   pass {key:<24} {sec:8.4f} s  "
                      f"{100 * sec / total:5.1f} %")
        for label, sec, n, scope, op_name in p["ops"]:
            print(f"   op {label[:60]:<60} {sec:8.4f} s x{n:<6} {scope:<12} "
                  f"{op_name[-70:]}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=24.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--reduce", nargs=2, metavar=("TRACE_DIR", "OUT"),
                    help="(internal) read a kept trace in this process")
    args = ap.parse_args()
    if args.reduce:
        events, modules = load_ops(args.reduce[0])
        with open(args.reduce[1], "w") as f:
            json.dump(by_scope(events, modules, hlo_op_names(HLO), args.top),
                      f)
        return 0

    for d in (KEPT, HLO, os.path.join(C.WORK, "scopes_cache")):
        shutil.rmtree(d, ignore_errors=True)
    # every child of the run inherits these: a compile cache of its own, so
    # that no executable without the names is found, and the HLO text
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(C.WORK,
                                                           "scopes_cache")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_dump_to={HLO}"
                               " --xla_dump_hlo_as_text").strip()
    reduce_trace = C.reduce_trace

    def keeping(children, trace_dir):
        shutil.copytree(trace_dir, KEPT)
        return reduce_trace(children, trace_dir)

    C.reduce_trace = keeping      # the drivers call it through the module
    from benchmark import run as R

    try:
        rec = R.run_cell(C.load_cell(args.workload), args.seed, args.seconds,
                         True)
        line = json.loads(R.line_of(rec, True))
    except C.BenchError as e:
        print(f"scopes: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"correct": line["correct"], "metrics": {
        k: v["value"] for k, v in line["metrics"].items()},
        "idle_gaps": line["breakdown"]["idle_gaps"],
        "setup": line["notes"].get("compile_requests_hits_setup")
        or line["notes"].get("compiles_setup")}))
    reduced = os.path.join(C.WORK, "scopes_reduced.json")
    env = C.child_env(JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([C.PY, os.path.abspath(__file__), "--reduce", KEPT,
                        reduced], env=env, cwd=ROOT, capture_output=True,
                       text=True)
    if p.returncode:
        print(p.stderr[-3000:], file=sys.stderr)
        return 1
    out = C.load_json(reduced)
    out["workload"], out["seed"] = args.workload, args.seed
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
    show(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
