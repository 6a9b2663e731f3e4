"""Whole-prompt insert of a latent-attention model attending its EXPANDED
heads through the flash kernel (``models/glm_moe_lite.py attention``,
``ops/pallas_attention.py``): the least time the chip could take for the
kernel's calls inside ``jit_insert*`` in the traced seconds over the device
time of those calls.  Operations: heads x (256 + 256) x n^2 a layer for a
prompt of n REAL tokens, the causal half (padding to the rung and the
tiles above the diagonal are not work the algorithm needs).  A traced call
says its rung (its output is ``[1, heads, rung, head]``) and not its
prompt, so a call on a rung counts the mean n and n^2 of the window's
prompts on that rung (``opsbytes_glm_moe_lite.insert_attention_layer``)."""
import re

from benchmark.harness import opsbytes_glm_moe_lite as O
from benchmark.harness.opsbytes import roofline_share_pct
from benchmark.harness.peaks import peak


def read(rec, variant=None):
    cfg, trace, w = rec["cell"]["config"], rec.get("trace") or {}, rec["window"]
    if "kv_lora_rank" not in cfg:
        return None
    rungs = cfg["serve"]["rungs"]
    by_rung: dict = {}
    for r in rec["requests"]:
        if r.token_times and w["t_open"] <= r.token_times[0] < w["t_close"]:
            rung = min(x for x in rungs if x >= len(r.prompt))
            by_rung.setdefault(rung, []).append(len(r.prompt))
    flops = nbytes = seconds = 0.0
    for k in trace.get("kernels", []):
        if "insert" not in k["module"] or k["ns"] < 1000 \
                or not O.is_attention_call(k):
            continue
        dims = [int(x) for x in re.findall(r"\d+", k["shape"].split("[")[-1])]
        prompts = by_rung.get(dims[2] if len(dims) == 4 else None)
        if not prompts:
            continue
        need = O.insert_attention_layer(
            cfg, sum(prompts) / len(prompts),
            sum(n * n for n in prompts) / len(prompts))
        flops, nbytes = flops + need["flops"], nbytes + need["bytes"]
        seconds += k["ns"] / 1e9
    if seconds <= 0:
        return None
    return roofline_share_pct(flops, nbytes, seconds,
                              peak(rec["device"]["kind"]))
