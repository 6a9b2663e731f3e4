"""Tokens of all the window's steps over its real length and its chips.
The window closes with the step in flight when ``--seconds`` have passed,
the last step ended by ``block_until_ready``."""


def read(rec, variant=None):
    w = rec["window"]
    return w["steps"] * w["tokens_per_step"] / w["real_seconds"] / rec["cell"]["chips"]
