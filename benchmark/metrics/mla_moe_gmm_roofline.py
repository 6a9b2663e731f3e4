"""Grouped products of the expert layers under a latent-attention block
(``models/afmoe.py grouped_matmul`` at GLM-4.7-Flash's widths):
``moe_gmm_roofline``'s reading — the least time the chip could take for
their calls inside ``jit_step`` in the traced seconds over the device time
of those calls, three calls a layer-step, bytes and operations from the
routing counters' per-layer-step means over the traced seconds — with the
calls told from the latent kernel's by
``opsbytes_glm_moe_lite.is_attention_call``."""
from benchmark.harness import opsbytes_glm_moe_lite as O
from benchmark.harness.opsbytes import roofline_share_pct
from benchmark.harness.peaks import peak


def read(rec, variant=None):
    cfg, trace = rec["cell"]["config"], rec.get("trace") or {}
    a, b = rec.get("statusz_traced") or (None, None)
    if ("n_routed_experts" not in cfg or not a or not b
            or "moeLayerStepsTotal" not in a or "moeLayerStepsTotal" not in b):
        return None
    calls = [k for k in trace.get("kernels", [])
             if "step" in k["module"] and k["ns"] >= 1000
             and not O.is_attention_call(k)]
    steps = b["moeLayerStepsTotal"] - a["moeLayerStepsTotal"]
    seconds = sum(k["ns"] for k in calls) / 1e9
    if not calls or steps <= 0 or seconds <= 0:
        return None
    need = O.grouped_products_layer_step(
        cfg, (b["moeAssignmentsTotal"] - a["moeAssignmentsTotal"]) / steps,
        (b["moeExpertsTouchedTotal"] - a["moeExpertsTouchedTotal"]) / steps)
    layer_steps = len(calls) / 3.0
    return roofline_share_pct(
        need["flops"] * layer_steps, need["bytes"] * layer_steps, seconds,
        peak(rec["device"]["kind"]))
