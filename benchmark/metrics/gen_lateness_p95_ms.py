"""Load generator: how late it sent, due time against send time.  A starved
generator must not read as a fast server."""
from benchmark.harness.common import percentile


def read(rec, variant=None):
    xs = [(r.sent - r.due) * 1e3 for r in rec["judged"]
          if r.due is not None and r.sent is not None]
    return percentile(xs, 95) if xs else None
