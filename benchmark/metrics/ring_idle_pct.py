"""Scheduler (``infer/scheduler.py``): share of its time the ring's thread
spent waiting for an arrival with nothing resident and nothing queued, from
the self seconds of its phases on ``/statusz`` at the window's edges:
``sched.idle.no_work`` over all of them.  The phases tile the thread's time,
so their sum is the time between the two scrapes on the server's own clock,
however late a scrape ran.  Nothing to read where the phase table is absent
or did not grow; a ring that never waited reads 0."""

IDLE = "sched.idle.no_work"


def read(rec, variant=None):
    a, b = rec["metrics_open"].get("statusz"), rec["metrics_close"].get("statusz")
    if not a or not b or "phaseSeconds" not in a or "phaseSeconds" not in b:
        return None
    a, b = a["phaseSeconds"], b["phaseSeconds"]
    seconds = sum(b.values()) - sum(a.values())
    if seconds <= 0:
        return None
    return 100.0 * (b.get(IDLE, 0.0) - a.get(IDLE, 0.0)) / seconds
