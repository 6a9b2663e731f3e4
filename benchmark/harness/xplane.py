"""From a profiler trace to numbers: the one reduction every PR shares.

The reduction works on a plain form of the trace,
``{"planes": {plane: {line: [[name, start_ns, dur_ns], ...]}}}``, so that it
can be checked on a small recorded trace (``tests/fixtures``) without jax.
:func:`load` turns an ``.xplane.pb`` into that form (needs jax's
``ProfileData``; run as ``python -m benchmark.harness.xplane <dir> <out>``
in a process held to the CPU, after the chip's process has gone).

On a TPU the device planes are ``/device:TPU:<n>``; their ``XLA Modules``
line holds one event per executed program (``jit_step(<hash>)``) and their
``XLA Ops`` line one per HLO operation, loops (``%while``) and calls
enclosing the operations inside them.  Busy time is the union of the
operations' intervals; an operation's own time is its duration less that
of the operations it encloses.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def parse_op(text: str) -> dict:
    """``%fusion.1 = bf16[16,14336]{1,0:T(8,128)} fusion(...)`` ->
    name, output shape (first of a tuple) and HLO kind."""
    name, sep, rest = text.partition(" = ")
    name = name.strip().lstrip("%")
    if not sep:
        return {"name": name, "shape": "", "kind": ""}
    rest = rest.strip()
    if rest.startswith("("):
        depth, end = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                end = i
                break
        type_text, after = rest[1:end], rest[end + 1:].strip()
    else:
        type_text, _, after = rest.partition(" ")
    m = re.match(r"([a-z0-9]+\[[0-9,]*\])", type_text.strip())
    shape = m.group(1) if m else ""
    kind = after.split("(", 1)[0].strip()
    return {"name": name, "shape": shape, "kind": kind}


def label(text: str) -> str:
    op = parse_op(text)
    return (op["name"] + " " + op["shape"]).strip()


def union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def self_times(events) -> list:
    """``[(text, start, own_ns)]``: each event's duration less that of the
    events it encloses (events of one line nest, they never cross)."""
    out, stack = [], []
    for text, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            stack[-1][2][2] -= dur
        rec = [text, start, dur]
        out.append(rec)
        stack.append((text, end, rec))
    return [(t, s, max(0.0, o)) for t, s, o in out]


def module_name(text: str) -> str:
    return text.split("(", 1)[0]


def reduce(trace: dict, top: int = 10) -> dict:
    planes = trace["planes"]
    devs = {p: ls for p, ls in planes.items()
            if DEVICE_PLANE.match(p) and ls.get(OPS_LINE)}
    # the traced window is what the device planes span: the host's tracer
    # starts before the device's and stops after it
    spans = [(s, s + d) for ls in devs.values() for evs in ls.values()
             for _, s, d, *_ in evs]
    if not spans:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0}
    w0, w1 = min(a for a, _ in spans), max(b for _, b in spans)
    host = [(n, s, s + d) for p, ls in planes.items() if p.startswith("/host:")
            for evs in ls.values() for n, s, d, *_ in evs]
    busy, modules, ops, gaps, kernels = 0.0, {}, {}, [], []
    for ls in devs.values():
        evs = [(e[0], e[1], e[2]) for e in ls[OPS_LINE]]
        covered = union((s, s + d) for _, s, d in evs)
        busy += sum(b - a for a, b in covered)
        edges = [w0] + [x for ab in covered for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        mods = [(module_name(e[0]), e[1], e[1] + e[2])
                for e in ls.get(MODULES_LINE, [])]
        for name, a, b in mods:
            modules[name] = modules.get(name, 0.0) + (b - a)
        for text, start, own in self_times(evs):
            op = parse_op(text)
            if op["kind"] in ("while", "call", "conditional"):
                continue
            key = label(text)
            ops[key] = ops.get(key, 0.0) + own
            if op["kind"] == "custom-call":
                inside = next((n for n, a, b in mods if a <= start < b), "")
                kernels.append({"name": op["name"], "shape": op["shape"],
                                "module": inside, "start_ns": start,
                                "ns": own})
    n = len(devs)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        best, best_key = "_no_host_event_", None
        for name, s, e in host:
            over = min(b, e) - max(a, s)
            if over > 0:
                key = (over, -(e - s))
                if best_key is None or key > best_key:
                    best, best_key = name, key
        named.append([best, (b - a) / 1e9])
    return {
        "devices": n, "busy_s": busy / n / 1e9, "window_s": (w1 - w0) / 1e9,
        "module_s": {k: v / n / 1e9 for k, v in modules.items()},
        "device_ops": [[k.replace(" ", "_"), v / n / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
        "kernels": kernels,
    }


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under `trace_dir` in the plain form."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            keep = (DEVICE_PLANE.match(plane.name)
                    and line.name in (OPS_LINE, MODULES_LINE)) or \
                plane.name.startswith("/host:")
            if not keep:
                continue
            lines.setdefault(line.name, []).extend(
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events)
    return {"planes": planes}


def main(argv) -> int:
    trace = load(argv[1])
    out = reduce(trace)
    if len(argv) > 3:       # a cut of the raw trace, for a fixture
        n = int(argv[3])
        cut = {"planes": {p: {l: evs[:n] for l, evs in ls.items()}
                          for p, ls in trace["planes"].items()}}
        with open(argv[2] + ".raw.json", "w") as f:
            json.dump(cut, f)
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
