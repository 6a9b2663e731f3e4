"""Process start to window open: load, warm-up, compilation, pre-roll."""


def read(rec, variant=None):
    return rec["setup_s"]
