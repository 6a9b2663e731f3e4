"""Scheduler (``infer/scheduler.py``): mean time a request waited for a
lane, from the server's own ``tpujob_serve_queue_wait_ms`` histogram on
``/metrics``: its sum's growth over its count's between the window's edges."""


def read(rec, variant=None):
    a, b = rec["metrics_open"], rec["metrics_end"]
    n = b.get("tpujob_serve_queue_wait_ms_count", 0) - a.get("tpujob_serve_queue_wait_ms_count", 0)
    if n <= 0:
        return None
    return (b["tpujob_serve_queue_wait_ms_sum"] - a["tpujob_serve_queue_wait_ms_sum"]) / n
