"""Load generation over HTTP against ``POST /v1/generate`` (streaming).

Runs in the runner's process, which never imports jax.  One thread per
request in flight; each reads its newline-delimited stream and stamps every
token with the host clock as it arrives.
"""

from __future__ import annotations

import http.client
import json
import threading
import time


class Request:
    """One request and everything the client saw of it."""

    def __init__(self, spec: dict, prompt: list) -> None:
        self.spec = spec
        self.prompt = prompt
        self.due = None          # open loop: when it was due (host clock)
        self.sent = None
        self.token_times: list = []
        self.tokens: list = []
        self.end = None
        self.error = None

    @property
    def done(self) -> bool:
        return self.end is not None and self.error is None


def generate(port: int, req: Request, timeout: float = 120.0) -> None:
    """Send `req`, read its stream to the end; never raises: what went
    wrong is in ``req.error`` and the request counts as failed."""
    body = json.dumps({"tokens": [req.prompt], "stream": True,
                       "max_new_tokens": req.spec["answer_tokens"],
                       "temperature": 0.0}).encode()
    req.sent = time.time()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            req.error = f"http {resp.status}: {resp.read()[:200]!r}"
            return
        while True:
            line = resp.readline()
            if not line:
                req.error = req.error or "stream ended without a done event"
                return
            ev = json.loads(line)
            if "token" in ev:
                req.token_times.append(time.time())
                req.tokens.append(int(ev["token"]))
            elif ev.get("done"):
                served = [int(t) for t in ev["tokens"]]
                if served[:len(req.prompt)] != req.prompt or \
                        served[len(req.prompt):] != req.tokens:
                    req.error = "done event disagrees with the stream"
                elif len(req.tokens) != req.spec["answer_tokens"]:
                    req.error = (f"{len(req.tokens)} tokens for "
                                 f"{req.spec['answer_tokens']} asked")
                return
            elif "error" in ev:
                req.error = f"server: {ev['error']}"
                return
    except (OSError, http.client.HTTPException, ValueError) as e:
        req.error = f"{type(e).__name__}: {e}"
    finally:
        req.end = time.time()
        conn.close()


class ClosedLoop:
    """`callers` callers, each with its own fixed list, each sending its
    next request when the last one ends.  They start before the window:
    :meth:`wait_each_lane_finished_one` returns once every caller has had
    an answer, and only then does the window open."""

    def __init__(self, port: int, lists: list, *, think_s: float = 0.0,
                 stagger_s: float = 0.0) -> None:
        self.port = port
        self.lists = lists
        # a caller takes `think_s` between an answer's end and its next
        # request, and caller i starts i * `stagger_s` after caller 0
        self.think_s, self.stagger_s = think_s, stagger_s
        self.stop = threading.Event()
        self.first_done = [threading.Event() for _ in lists]
        self.sent: list = []
        self._lock = threading.Lock()
        self.threads = [threading.Thread(target=self._caller, args=(i,),
                                         daemon=True)
                        for i in range(len(lists))]

    def _caller(self, i: int) -> None:
        time.sleep(i * self.stagger_s)
        for k, req in enumerate(self.lists[i]):
            if k:
                time.sleep(self.think_s)
            if self.stop.is_set():
                return
            with self._lock:
                self.sent.append(req)
            generate(self.port, req)
            self.first_done[i].set()
            if req.error is not None:
                return              # a failed caller stops: it is counted

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def wait_each_lane_finished_one(self, timeout: float) -> bool:
        deadline = time.time() + timeout
        return all(e.wait(max(0.0, deadline - time.time()))
                   for e in self.first_done)

    def close(self, timeout: float) -> None:
        """No new requests; those in flight are left to end (or `timeout`)."""
        self.stop.set()
        deadline = time.time() + timeout
        for t in self.threads:
            t.join(max(0.0, deadline - time.time()))


class OpenLoop:
    """Requests sent on a fixed schedule, whatever the server does; each is
    timed from when it was due."""

    def __init__(self, port: int, requests: list, t_open: float) -> None:
        self.port = port
        self.requests = sorted(requests, key=lambda r: r.spec["due_s"])
        for r in self.requests:
            r.due = t_open + r.spec["due_s"]
        self.threads: list = []
        self.dispatcher = threading.Thread(target=self._dispatch, daemon=True)

    def _dispatch(self) -> None:
        for req in self.requests:
            wait = req.due - time.time()
            if wait > 0:
                time.sleep(wait)
            t = threading.Thread(target=generate, args=(self.port, req),
                                 daemon=True)
            t.start()
            self.threads.append(t)

    def start(self) -> None:
        self.dispatcher.start()

    def close(self, timeout: float) -> None:
        """Every request of the schedule has been sent; wait for each."""
        deadline = time.time() + timeout
        self.dispatcher.join(max(0.0, deadline - time.time()))
        for t in self.threads:
            t.join(max(0.0, deadline - time.time()))


def tokens_in_window(requests, t_open: float, t_close: float) -> int:
    """Answer tokens whose time of emission lies in ``[t_open, t_close)``:
    half-open, so a token on an edge is counted once."""
    return sum(1 for r in requests for t in r.token_times
               if t_open <= t < t_close)
