"""Expert layer: the busiest expert's decode assignments over the mean
expert's, from ``moeExpertLoadTotal`` (assignments by expert, summed over
the expert layers) between the ``/statusz`` scrapes at the window's edges.
1 is an even load; the grouped product's longest group is this much longer
than the mean."""


def read(rec, variant=None):
    a, b = rec["metrics_open"].get("statusz"), rec["metrics_close"].get("statusz")
    if not a or not b or "moeExpertLoadTotal" not in a or "moeExpertLoadTotal" not in b:
        return None
    load = [y - x for x, y in zip(a["moeExpertLoadTotal"], b["moeExpertLoadTotal"])]
    if not load or sum(load) <= 0:
        return None
    return max(load) * len(load) / sum(load)
