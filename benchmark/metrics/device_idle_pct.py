"""Device: 1 - union of the device operations' intervals over the traced
window (the same busy and window seconds the result line's ``device`` has)."""


def read(rec, variant=None):
    trace = rec.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
