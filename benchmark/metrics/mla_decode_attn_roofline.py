"""Latent decode kernel (``ops/decode_attention.py
latent_paged_decode_attention``): the least time the chip could take for
the kernel's calls inside ``jit_step`` in the traced seconds over the
device time of those calls.  Bytes and operations: from the lanes' contexts
as ``/statusz`` gave them while the trace ran — 1,152 bytes a cached token
a layer-step, read once for all heads, and 2 x heads x (576 + 512)
operations (``opsbytes_glm_moe_lite.latent_decode_step``)."""
from benchmark.harness import opsbytes_glm_moe_lite as O
from benchmark.harness.opsbytes import roofline_share_pct
from benchmark.harness.peaks import peak


def read(rec, variant=None):
    cfg, trace, w = rec["cell"]["config"], rec.get("trace") or {}, rec["window"]
    if "kv_lora_rank" not in cfg:
        return None
    calls = [k for k in trace.get("kernels", [])
             if "step" in k["module"] and k["ns"] >= 1000
             and O.is_attention_call(k)]
    samples = [s for s in rec.get("lane_samples") or []
               if w["traced"] and w["traced"][0] <= s["t"] <= w["traced"][1]]
    seconds = sum(k["ns"] for k in calls) / 1e9
    if not calls or not samples or seconds <= 0:
        return None
    steps = [O.latent_decode_step(cfg, s["lanePos"]) for s in samples]
    per_call = {k: sum(s[k] for s in steps) / len(steps) / steps[0]["calls"]
                for k in ("bytes", "flops")}
    return roofline_share_pct(
        per_call["flops"] * len(calls), per_call["bytes"] * len(calls),
        seconds, peak(rec["device"]["kind"]))
