"""Executor: share of the device's program time spent in prefill, from the
trace's "XLA Modules" line: time in ``jit_insert*`` over time in all."""


def read(rec, variant=None):
    mods = (rec.get("trace") or {}).get("module_s") or {}
    total = sum(mods.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in mods.items() if "insert" in k) / total
