"""Paged pool (``infer/paged.py``): blocks that live lanes' contexts fill
over blocks held, mean of the samples of ``/statusz`` taken in the window."""


def read(rec, variant=None):
    samples, total = rec.get("lane_samples") or [], rec.get("kv_blocks_total")
    block = rec["cell"]["config"]["serve"]["block"]
    if not samples or not total:
        return None
    live = [sum(-(-p // block) for p in s["lanePos"] if p > 0) for s in samples]
    return 100.0 * sum(live) / len(live) / total
