"""95th percentile over all judged requests of the mean gap between a
request's tokens (first to last, over the count less one)."""
from benchmark.harness.common import percentile


def read(rec, variant=None):
    xs = [(r.token_times[-1] - r.token_times[0]) * 1e3 / (len(r.token_times) - 1)
          for r in rec["judged"] if r.done and len(r.token_times) > 1]
    bad = sum(1 for r in rec["judged"] if not r.done)
    xs += [max(xs, default=0.0)] * bad
    return percentile(xs, 95) if xs else None
