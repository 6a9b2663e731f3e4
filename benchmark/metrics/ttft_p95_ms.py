"""95th percentile over all judged requests of the time from when the
request was due (open loop) or sent (closed loop) to its first token.  A
request that failed counts as the worst seen."""
from benchmark.harness.common import percentile


def ttfts_ms(rec):
    good = [(r.token_times[0] - (r.due if r.due is not None else r.sent)) * 1e3
            for r in rec["judged"] if r.done and r.token_times]
    bad = len(rec["judged"]) - len(good)
    return good + [max(good, default=0.0)] * bad


def read(rec, variant=None):
    xs = ttfts_ms(rec)
    return percentile(xs, 95) if xs else None
