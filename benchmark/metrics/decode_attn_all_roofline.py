"""Decode kernel (``ops/decode_attention.py``), EVERY call, against the
contexts the lanes held WHILE the device was traced: the least time the
chip could take for the kernel's calls in the traced seconds over the
device time of those calls, whichever program holds them.

Two things ``decode_attn_roofline`` does not do (PERF.md section 6, PR 33).
Since ISSUE 33 a whole-prompt insert carries one decode step of the ring's
live lanes, so a share of the kernel's calls runs inside ``jit_insert*``,
where that reader (``jit_step`` only) does not see them: here a call is
told by its output, ``[lanes, heads, head_dim]`` (the flash kernel's inside
an insert is ``[1, heads, W, head_dim]``), in either program.  And
``window.traced`` ends when the profiler has stopped and WRITTEN its file,
17-47 s after the device's last traced operation, so the lane samples
inside it are mostly of seconds the trace does not hold, and a quiet or a
crowded four seconds reads as a faster or a slower kernel: here the samples
are those of the device planes' own span, ``trace.window_s`` from the
start of the trace.

Bytes and operations of a step's call: from those samples' contexts
(``opsbytes.decode_attention_call``).  A carried call attends only the
lanes that rode (never the inserted one), so it counts that share of a
step's call: lanes an insert's step advanced (``insertStepLanesTotal /
insertStepsTotal``) over lanes a decode step advanced
(``decodeLaneStepsTotal / decodeStepsTotal``), between the window's
edges."""
from benchmark.harness import opsbytes
from benchmark.harness.peaks import peak


def _delta(rec, key):
    a = rec.get("metrics_open", {}).get("statusz", {})
    b = rec.get("metrics_close", {}).get("statusz", {})
    return b.get(key, 0) - a.get(key, 0)


def ride_share(rec) -> float:
    """Lanes a carried step advanced over lanes a decode step advanced."""
    steps, inserts = _delta(rec, "decodeStepsTotal"), _delta(rec, "insertStepsTotal")
    lanes = _delta(rec, "decodeLaneStepsTotal")
    if steps <= 0 or inserts <= 0 or lanes <= 0:
        return 0.0
    return min(1.0, _delta(rec, "insertStepLanesTotal") / inserts / (lanes / steps))


def read(rec, variant=None):
    cfg, trace, w = rec["cell"]["config"], rec.get("trace") or {}, rec["window"]
    shape = f"[{cfg['serve']['lanes']},{cfg['num_attention_heads']},{opsbytes.head_dim(cfg)}]"
    calls = [k for k in trace.get("kernels", [])
             if k["ns"] >= 1000 and k["shape"].endswith(shape)
             and ("step" in k["module"] or "insert" in k["module"])]
    seconds = sum(k["ns"] for k in calls) / 1e9
    if not calls or seconds <= 0 or not w.get("traced"):
        return None
    t0 = w["traced"][0]
    samples = [s for s in rec.get("lane_samples") or []
               if t0 <= s["t"] <= t0 + trace.get("window_s", 0.0)]
    if not samples:
        return None
    context = sum(sum(s["lanePos"]) for s in samples) / len(samples)
    need = opsbytes.decode_attention_call(cfg, context)
    carried = sum("step" not in k["module"] for k in calls)
    n = len(calls) - carried + (carried * ride_share(rec) if carried else 0.0)
    return opsbytes.roofline_share_pct(
        need["flops"] * n, need["bytes"] * n, seconds,
        peak(rec["device"]["kind"]))
