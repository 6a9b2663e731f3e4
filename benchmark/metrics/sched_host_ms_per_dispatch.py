"""Scheduler: host time of the ring's thread per decode dispatch, from
``/statusz`` at the window's edges: the self seconds of all its phases but
the waits (``sched.idle.*``: for work; ``sched.consume_wait``: for the
device), over the dispatches."""


def busy(phase_seconds: dict) -> float:
    return sum(v for k, v in phase_seconds.items()
               if not k.startswith("sched.idle.") and k != "sched.consume_wait")


def read(rec, variant=None):
    a, b = rec["metrics_open"].get("statusz"), rec["metrics_close"].get("statusz")
    if not a or not b or "phaseSeconds" not in a or "phaseSeconds" not in b:
        return None
    dispatches = b["dispatchesTotal"] - a["dispatchesTotal"]
    if dispatches <= 0:
        return None
    return 1e3 * (busy(b["phaseSeconds"]) - busy(a["phaseSeconds"])) / dispatches
