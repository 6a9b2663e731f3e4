"""Train kernel (``ops/pallas_attention.py``): the least time the chip
could take for the flash kernel's calls of the traced steps (forward, its
recomputation, dK/dV and dQ together) over their device time.  Calls: the
custom calls inside whole ``jit_step_fn`` programs of the trace."""
from benchmark.harness import opsbytes
from benchmark.harness.peaks import peak


def read(rec, variant=None):
    trace, cfg, t = rec.get("trace") or {}, rec["cell"]["config"], rec["cell"]["traffic"]
    # the compiler's own custom calls (layout, placement) last nanoseconds
    calls = [k for k in trace.get("kernels") or [] if k["ns"] >= 1000]
    need = opsbytes.flash_attention_layer(cfg, t["batch"], t["seq"])
    groups = len(calls) // need["calls"]
    seconds = sum(k["ns"] for k in calls) / 1e9
    if not groups or len(calls) % need["calls"] or seconds <= 0:
        return None
    return opsbytes.roofline_share_pct(need["flops"] * groups, need["bytes"] * groups,
                                       seconds, peak(rec["device"]["kind"]))
