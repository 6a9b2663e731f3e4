"""Executor: lanes live in the decode program's dispatches, from the server's
cumulative counters on ``/statusz`` at the window's edges: device decode
iterations times the lanes live in each plan, over iterations times the
configuration's lanes."""


def read(rec, variant=None):
    a, b = rec["metrics_open"].get("statusz"), rec["metrics_close"].get("statusz")
    if not a or not b or "decodeStepsTotal" not in a or "decodeStepsTotal" not in b:
        return None
    steps = b["decodeStepsTotal"] - a["decodeStepsTotal"]
    if steps <= 0:
        return None
    lanes = rec["cell"]["config"]["serve"]["lanes"]
    return 100.0 * (b["decodeLaneStepsTotal"] - a["decodeLaneStepsTotal"]) / (steps * lanes)
