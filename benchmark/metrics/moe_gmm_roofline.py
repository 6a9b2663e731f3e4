"""Grouped products of the expert layers (``models/afmoe.py
grouped_matmul``): the least time the chip could take for their calls
inside ``jit_step`` in the traced seconds over the device time of those
calls.  Calls: the custom calls inside ``jit_step`` that are not the decode
kernel's (``opsbytes_afmoe.is_attention_call``), three a layer-step.  Bytes
and operations: each touched expert's three matrices once and 2 x 3 x
hidden x expert width an assignment, from the routing counters'
per-layer-step means over the traced seconds (``/statusz`` scraped as the
trace starts and stops)."""
from benchmark.harness import opsbytes_afmoe
from benchmark.harness.opsbytes import roofline_share_pct
from benchmark.harness.peaks import peak


def read(rec, variant=None):
    cfg, trace = rec["cell"]["config"], rec.get("trace") or {}
    a, b = rec.get("statusz_traced") or (None, None)
    if not a or not b or "moeLayerStepsTotal" not in a or "moeLayerStepsTotal" not in b:
        return None
    calls = [k for k in trace.get("kernels", [])
             if "step" in k["module"] and k["ns"] >= 1000
             and not opsbytes_afmoe.is_attention_call(cfg, k)]
    steps = b["moeLayerStepsTotal"] - a["moeLayerStepsTotal"]
    seconds = sum(k["ns"] for k in calls) / 1e9
    if not calls or steps <= 0 or seconds <= 0:
        return None
    need = opsbytes_afmoe.grouped_products_layer_step(
        cfg, (b["moeAssignmentsTotal"] - a["moeAssignmentsTotal"]) / steps,
        (b["moeExpertsTouchedTotal"] - a["moeExpertsTouchedTotal"]) / steps)
    layer_steps = len(calls) / 3.0
    return roofline_share_pct(
        need["flops"] * layer_steps, need["bytes"] * layer_steps, seconds,
        peak(rec["device"]["kind"]))
