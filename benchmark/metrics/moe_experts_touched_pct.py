"""Expert layer: experts with at least one token in a decode step's layer,
over the experts there are — ``moeExpertsTouchedTotal`` over
``moeLayerStepsTotal`` x ``num_experts``, between the ``/statusz`` scrapes
at the window's edges.  It is the share of the expert weights a decode step
reads."""


def moved(rec, key):
    """The growth of a cumulative counter over the window, or None where
    the program has no such counter (a parent without it)."""
    a, b = rec["metrics_open"].get("statusz"), rec["metrics_close"].get("statusz")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]


def read(rec, variant=None):
    touched, steps = moved(rec, "moeExpertsTouchedTotal"), moved(rec, "moeLayerStepsTotal")
    if not touched or not steps:
        return None
    return 100.0 * touched / (steps * rec["cell"]["config"]["num_experts"])
