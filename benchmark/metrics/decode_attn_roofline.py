"""Decode kernel (``ops/decode_attention.py``): the least time the chip
could take for the kernel's calls in the traced seconds over the device
time of those calls.  Calls: the custom calls inside ``jit_step`` programs.
Bytes and operations: from the lanes' contexts as ``/statusz`` gave them
while the trace ran (``opsbytes.decode_attention_call``)."""
from benchmark.harness import opsbytes
from benchmark.harness.peaks import peak


def read(rec, variant=None):
    trace, w = rec.get("trace") or {}, rec["window"]
    calls = [k for k in trace.get("kernels", [])
             if "step" in k["module"] and k["ns"] >= 1000]
    samples = [s for s in rec.get("lane_samples") or []
               if w["traced"] and w["traced"][0] <= s["t"] <= w["traced"][1]]
    seconds = sum(k["ns"] for k in calls) / 1e9
    if not calls or not samples or seconds <= 0:
        return None
    context = sum(sum(s["lanePos"]) for s in samples) / len(samples)
    need = opsbytes.decode_attention_call(rec["cell"]["config"], context)
    return opsbytes.roofline_share_pct(
        need["flops"] * len(calls), need["bytes"] * len(calls), seconds,
        peak(rec["device"]["kind"]))
