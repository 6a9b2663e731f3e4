"""Answer tokens whose time of emission lies in the window, over its
seconds: tokens of requests that began before it or end after it count for
the part inside."""
from benchmark.harness.loadgen import tokens_in_window


def read(rec, variant=None):
    w = rec["window"]
    return tokens_in_window(rec["requests"], w["t_open"], w["t_close"]) / w["seconds"]
