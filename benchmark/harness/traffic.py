"""The one general traffic generator.  A mix is a data file of parameters
(``benchmark/traffic/<mix>.json``); this module turns it into requests.

Lengths, order and arrival times come from the file and its own fixed
``traffic_seed``: they are the same in every run, whatever ``--seed``, so
that two runs differ by the system and not by the work (a 4096-wide prefill
costs 279 ms: three more of them in a window moved PR 24's rate by more
than its bound).  ``--seed`` makes the token ids (and the weights).

No jax here: the load generator's process imports this.
"""

from __future__ import annotations

import math

import numpy as np


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, spec["value"], dtype=np.int64)
    elif dist == "uniform":
        out = rng.integers(spec["min"], spec["max"] + 1, n)
    elif dist == "lognormal":
        out = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        out = np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return out


def token_ids(seed: int, index: int, n: int, vocab: int,
              prefix: tuple | None = None) -> list:
    """Token ids of request `index` under `--seed`; ``prefix=(group, k)``
    makes the first k ids those of the group's shared prefix."""
    ids = np.random.default_rng([seed, 1, index]).integers(1, vocab, n)
    if prefix is not None:
        group, k = prefix
        k = min(k, n)
        ids[:k] = np.random.default_rng([seed, 2, group]).integers(
            1, vocab, k)
    return ids.tolist()


def serve_requests(traffic: dict, seconds: float) -> list:
    """Every request of the mix for a window of `seconds`, as dicts with
    ``index``, ``prompt_tokens``, ``answer_tokens``, ``prefix`` and either
    ``caller`` and ``order`` (closed loop) or ``due_s`` relative to the
    window's opening (open loop; negative in the pre-roll)."""
    rng = np.random.default_rng(traffic["traffic_seed"])
    share = traffic.get("shared_prefix") or {}
    if traffic["loop"] == "closed":
        callers, per = traffic["callers"], traffic["requests_per_caller"]
        n = callers * per
        where = [{"caller": i % callers, "order": i // callers}
                 for i in range(n)]
    elif traffic["loop"] == "open":
        rate, cv = traffic["rate_per_s"], traffic["gap_cv"]
        shape = 1.0 / (cv * cv)
        t, where = -float(traffic.get("preroll_s", 0.0)), []
        # gamma-distributed gaps of mean 1/rate; drawn one by one, so a
        # longer window only extends the same schedule
        while True:
            t += rng.gamma(shape, 1.0 / (rate * shape))
            if t >= seconds:
                break
            where.append({"due_s": t})
        n = len(where)
        rng = np.random.default_rng([traffic["traffic_seed"], 7])
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    prompts = _lengths(rng, traffic["prompt_tokens"], n)
    answers = _lengths(rng, traffic["answer_tokens"], n)
    groups = (rng.integers(0, share["groups"], n) if share else None)
    out = []
    for i in range(n):
        r = {"index": i, "prompt_tokens": int(prompts[i]),
             "answer_tokens": int(answers[i]), "prefix": None, **where[i]}
        if share and rng.random() < share["share"]:
            r["prefix"] = (int(groups[i]), int(share["tokens"]))
        out.append(r)
    return out


def train_batch(seed: int, step: int, batch: int, seq: int,
                vocab: int) -> np.ndarray:
    """The token ids of global step `step`: int32 [batch, seq + 1], rows
    that all differ, a pure function of ``(seed, step)``."""
    rng = np.random.default_rng([seed, 3, step])
    return rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)


def train_batches(seed: int, traffic: dict, vocab: int, start: int = 0):
    step = start
    while True:
        yield {"tokens": train_batch(seed, step, traffic["batch"],
                                     traffic["seq"], vocab)}
        step += 1
