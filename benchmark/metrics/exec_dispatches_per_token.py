"""Executor (``infer/executor.py``): compiled dispatches per emitted token
over the window, from the server's cumulative ``dispatchesPerToken`` and
``tokensTotal`` at the window's edges."""


def read(rec, variant=None):
    a, b = rec["metrics_open"].get("statusz"), rec["metrics_close"].get("statusz")
    if not a or not b:
        return None
    tokens = b["tokensTotal"] - a["tokensTotal"]
    if tokens <= 0:
        return None
    chunks = b["dispatchesPerToken"] * b["tokensTotal"] - a["dispatchesPerToken"] * a["tokensTotal"]
    return chunks / tokens
