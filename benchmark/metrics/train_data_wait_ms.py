"""Input pipeline (``train/data.py``): host clock around the fetch of a
batch from the ``DevicePrefetcher``, mean per step of the window."""


def read(rec, variant=None):
    waits = rec["window"].get("data_wait_s") or []
    return 1e3 * sum(waits) / len(waits) if waits else None
