"""Executor (``infer/executor.py``): share of the prefilled positions that
were padding, from the server's cumulative counters on ``/statusz`` at the
window's edges: 1 - real prompt (or suffix) tokens prefilled over the widths
of the insert programs dispatched for them."""


def read(rec, variant=None):
    a, b = rec["metrics_open"].get("statusz"), rec["metrics_close"].get("statusz")
    if not a or not b or "prefillBucketTokensTotal" not in a or "prefillBucketTokensTotal" not in b:
        return None
    computed = b["prefillBucketTokensTotal"] - a["prefillBucketTokensTotal"]
    if computed <= 0:
        return None
    return 100.0 * (1.0 - (b["prefillTokensTotal"] - a["prefillTokensTotal"]) / computed)
