"""Observability layer (ISSUE 15, utils/tracing.py): trace contexts +
span sets, fixed-bucket latency histograms + fleet folding, the flight
recorder, the doc-drift guard, and trace-context propagation under
adversity (retry-after-pod-death at the router, lane
migration/adoption, chunked/streamed prefill) — the heavier traced
parity matrix rides the dryrun ``serve-trace`` line."""

import json
import logging
import os
import re
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from types import SimpleNamespace

import pytest

from paddle_operator_tpu.utils import tracing as TR

ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Trace kit units
# ---------------------------------------------------------------------------


class TestTraceKit:
    def test_header_roundtrip(self):
        assert TR.parse_trace_header(None) is None
        assert TR.parse_trace_header("") is None
        assert TR.parse_trace_header("abc") == ("abc", None)
        assert TR.parse_trace_header("abc-def") == ("abc", "def")
        assert TR.format_trace_header("abc") == "abc"
        assert TR.format_trace_header("abc", "def") == "abc-def"
        tid, parent = TR.parse_trace_header(
            TR.format_trace_header("t1", "s1"))
        assert (tid, parent) == ("t1", "s1")

    def test_request_trace_spans_and_root(self):
        tr = TR.RequestTrace(trace_id="tid1", parent="up1", pod="p0",
                             request_id="r1")
        t0 = time.monotonic()
        tr.add("queue_wait", t0 - 0.01, t0, prio=1)
        tr.finish()
        wire = tr.to_wire()
        assert wire["traceId"] == "tid1"
        root, span = wire["spans"]
        assert root["name"] == "request" and root["parent"] == "up1"
        assert root["attrs"]["requestId"] == "r1"
        assert span["parent"] == root["id"]
        assert span["attrs"]["prio"] == 1
        assert span["pod"] == "p0"
        assert 5 <= span["dur"] <= 500
        # wall anchoring: t0 is epoch ms, roughly now
        assert abs(span["t0"] - time.time() * 1e3) < 60_000
        # within this pod the root is the single unresolved-parent span
        assert TR.span_roots(wire["spans"]) == [root]

    def test_span_cap_bounds_long_generations(self):
        tr = TR.RequestTrace()
        for i in range(TR.RequestTrace.MAX_SPANS + 50):
            tr.add("decode_dispatch", time.monotonic())
        tr.finish()
        wire = tr.to_wire()
        assert len(wire["spans"]) == TR.RequestTrace.MAX_SPANS
        assert wire["spans"][0]["attrs"]["droppedSpans"] == 51

    def test_seed_grafts_prior_pod_spans(self):
        origin = TR.RequestTrace(trace_id="t", pod="origin")
        origin.add("ttft", time.monotonic())
        ow = origin.to_wire()
        adopter = TR.RequestTrace(trace_id="t", parent=ow["rootId"],
                                  pod="adopter")
        adopter.seed(ow["spans"])
        adopter.add("adopt", time.monotonic())
        spans = adopter.to_wire()["spans"]
        # ONE tree: the only unresolved parent is the origin's root
        roots = TR.span_roots(spans)
        assert len(roots) == 1 and roots[0]["id"] == ow["rootId"]
        assert sum(s["name"] == "ttft" for s in spans) == 1

    def test_finish_idempotent_and_error(self):
        tr = TR.RequestTrace()
        tr.finish(error="Boom")
        d1 = tr.to_wire()["spans"][0]["dur"]
        time.sleep(0.01)
        tr.finish()
        assert tr.to_wire()["spans"][0]["dur"] == d1
        assert tr.to_wire()["spans"][0]["attrs"]["error"] == "Boom"


# ---------------------------------------------------------------------------
# Histograms
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class TestHistogram:
    def test_buckets_sum_count(self):
        h = TR.Histogram("x_ms")
        for v in (0.5, 3.0, 100.0, 1e9):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["counts"][0] == 1          # 0.5 <= 1
        assert snap["counts"][2] == 1          # 3.0 <= 4
        assert snap["counts"][-1] == 1         # +Inf
        assert snap["sum"] == pytest.approx(1e9 + 103.5)

    def test_quantile_interpolates(self):
        # 100 samples uniform in one bucket (64, 128]: p95 lands ~95%
        # of the way through it
        counts = [0] * 18
        counts[7] = 100                        # bucket (64, 128]
        q = TR.hist_quantile(TR.BUCKETS_MS, counts, 0.95)
        assert 64 < q <= 128
        assert q == pytest.approx(64 + 0.95 * 64, rel=0.01)
        assert TR.hist_quantile(TR.BUCKETS_MS, [0] * 18, 0.95) is None

    def test_window_rotates_stale_samples_out(self):
        clk = FakeClock()
        h = TR.Histogram("x_ms", window_s=60.0, clock=clk)
        h.observe(50_000.0)                    # slow boot sample
        clk.t += 70
        h.observe(10.0)
        clk.t += 70                            # second rotation:
        h.observe(10.0)                        # boot sample fully aged
        assert h.count == 3                    # cumulative keeps all
        win = h.window_counts()
        assert sum(win) < 3
        assert h.p95() < 1000                  # p95 reads NOW, not boot

    def test_long_quiet_gap_clears_both_epochs(self):
        """Review regression: rotation is driven by observe/snapshot
        calls, so a quiet gap > 2 windows must clear BOTH epochs — the
        first poll after a controller outage must not report a
        long-resolved burst as the current window (and spuriously
        re-trigger the autoscaler's p95 floor)."""
        clk = FakeClock()
        h = TR.Histogram("x_ms", window_s=60.0, clock=clk)
        for _ in range(10):
            h.observe(50_000.0)                # the breach burst
        clk.t += 200                           # > 2 windows of silence
        assert sum(h.window_counts()) == 0
        assert h.p95() is None                 # nothing current
        assert h.count == 10                   # cumulative intact

    def test_fold_and_p95(self):
        h1, h2 = TR.ServeHistograms(), TR.ServeHistograms()
        for _ in range(50):
            h1.ttft.observe(20.0)
        for _ in range(50):
            h2.ttft.observe(900.0)
        folded = TR.fold_latency_hists([h1.snapshot(), h2.snapshot()])
        assert folded["ttft"]["count"] == 100
        p95 = TR.hist_p95(folded["ttft"])
        assert 512 < p95 <= 1024               # tail replica dominates
        # mixed bucket bounds are dropped, not mis-added
        alien = {"ttft": {"buckets": [1.0, 2.0], "counts": [1, 1, 1],
                          "window": [1, 1, 1], "sum": 3.0, "count": 3}}
        refolded = TR.fold_latency_hists(
            [h1.snapshot(), h2.snapshot(), alien])
        assert refolded["ttft"]["count"] == 100

    def test_exposition_scrape_roundtrip(self):
        """Replica render (observability.histogram_exposition) ->
        router parse (parse_serve_histograms) recovers the snapshot."""
        from paddle_operator_tpu.router.router import (
            parse_serve_histograms,
        )
        from paddle_operator_tpu.utils.observability import (
            histogram_exposition,
        )

        hs = TR.ServeHistograms()
        for v in (5.0, 70.0, 70.0, 1e9):
            hs.ttft.observe(v)
        hs.queue_wait.observe(2.0)
        text = histogram_exposition(hs.snapshot(), "ns/j", "0")
        # bucket lines render cumulative and in bound order
        lines = [ln for ln in text.splitlines()
                 if ln.startswith("tpujob_serve_ttft_ms_bucket")]
        assert 'le="1"' in lines[0] and 'le="+Inf"' in lines[-1]
        parsed = parse_serve_histograms(text)
        assert parsed["ttft"]["count"] == 4
        assert sum(parsed["ttft"]["counts"]) == 4
        assert parsed["ttft"]["counts"][-1] == 1       # the +Inf one
        assert parsed["queueWait"]["count"] == 1
        folded = TR.fold_latency_hists([parsed])
        assert TR.hist_p95(folded["ttft"]) is not None

    def test_replica_state_windows_scraped_counters(self):
        """Router-side rate(): the window is the delta against the
        oldest retained scrape; a counter reset (replica restart)
        falls back to the fresh counts instead of a negative lie."""
        from paddle_operator_tpu.router.router import ReplicaState

        def snap(n):
            counts = [0] * 18
            counts[3] = n
            return {"ttft": {"buckets": list(TR.BUCKETS_MS),
                             "counts": counts, "sum": 10.0 * n,
                             "count": n}}

        st = ReplicaState("e:1")
        st.record_hists(snap(5), 1000.0)
        assert sum(st.latency_hist_block()["ttft"]["window"]) == 5
        st.record_hists(snap(25), 1001.0)
        assert sum(st.latency_hist_block()["ttft"]["window"]) == 20
        st.record_hists(snap(2), 1002.0)       # restart: counter fell
        assert sum(st.latency_hist_block()["ttft"]["window"]) == 2


# ---------------------------------------------------------------------------
# Flight recorder (+ chaos names the fault, jax-free)
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_bounded_ring_and_dump(self, tmp_path, monkeypatch):
        monkeypatch.setenv(TR.FLIGHTREC_DIR_ENV, str(tmp_path))
        fr = TR.FlightRecorder(capacity=4, pod="rep-0")
        for i in range(6):
            fr.record("admit", rid=f"r{i}")
        evs = fr.events()
        assert len(evs) == 4 and evs[0]["rid"] == "r2"
        path = fr.dump_file("test_reason")
        assert path == str(tmp_path / "tpujob_flightrec_rep-0.json")
        dump = json.loads(Path(path).read_text())
        assert dump["reason"] == "test_reason"
        assert dump["pod"] == "rep-0"
        assert [e["rid"] for e in dump["events"]] == \
            ["r2", "r3", "r4", "r5"]

    def test_chaos_injection_dump_names_the_fault(self, tmp_path,
                                                  monkeypatch):
        """The chaos satellite's core claim, jax-free: an injected
        fault lands in the pod's ring AND the forced dump names it —
        what a real incident's post-mortem reads."""
        from paddle_operator_tpu.infer.chaos import ChaosInjector

        monkeypatch.setenv(TR.FLIGHTREC_DIR_ENV, str(tmp_path))
        fr = TR.FlightRecorder(pod="chaos-pod")
        batcher = SimpleNamespace(
            executor=SimpleNamespace(replay=lambda plan: "ok"),
            lane=[None, None], pool=None, flightrec=fr)
        inj = ChaosInjector("dispatch_fail@1", seed=0).install(batcher)
        assert batcher.executor.replay("p0") == "ok"     # dispatch 0
        with pytest.raises(RuntimeError, match="chaos"):
            batcher.executor.replay("p1")                # dispatch 1
        assert inj.fired == [("dispatch_fail", 1)]
        dump = json.loads(Path(fr.default_path()).read_text())
        assert dump["reason"] == "chaos:dispatch_fail"
        ev = [e for e in dump["events"]
              if e["kind"] == "chaos_injected"]
        assert ev and ev[0]["fault"] == "dispatch_fail" \
            and ev[0]["dispatch"] == 1


# ---------------------------------------------------------------------------
# get_logger env re-derivation (ISSUE 15 satellite)
# ---------------------------------------------------------------------------


class TestLoggerEnv:
    def test_rank_rederived_and_idempotent(self, monkeypatch):
        from paddle_operator_tpu.utils.observability import get_logger

        name = "tpujob-test-rederive"
        logging.getLogger(name).handlers.clear()
        monkeypatch.setenv("TPUJOB_RANK", "0")
        monkeypatch.setenv("TPUJOB_LOG_LEVEL", "INFO")
        lg = get_logger(name)
        assert len(lg.handlers) == 1
        assert "[rank 0]" in lg.handlers[0].formatter._fmt
        # idempotent: repeated calls never stack handlers
        for _ in range(3):
            get_logger(name)
        assert len(lg.handlers) == 1
        # a subprocess-style env change reaches an EXISTING logger —
        # the regression: the old handlers-present check froze rank 0
        monkeypatch.setenv("TPUJOB_RANK", "3")
        monkeypatch.setenv("TPUJOB_LOG_LEVEL", "DEBUG")
        lg2 = get_logger(name)
        assert lg2 is lg and len(lg.handlers) == 1
        assert "[rank 3]" in lg.handlers[0].formatter._fmt
        assert lg.level == logging.DEBUG
        logging.getLogger(name).handlers.clear()

    def test_app_configured_logger_left_alone(self, monkeypatch):
        """Review regression: an application that pre-configured the
        logger (its own handler + level) keeps it — get_logger must
        not stack a second StreamHandler or override the level."""
        from paddle_operator_tpu.utils.observability import get_logger

        name = "tpujob-test-appconf"
        lg = logging.getLogger(name)
        lg.handlers.clear()
        app_handler = logging.NullHandler()
        lg.addHandler(app_handler)
        lg.setLevel(logging.WARNING)
        monkeypatch.setenv("TPUJOB_LOG_LEVEL", "DEBUG")
        out = get_logger(name)
        assert out.handlers == [app_handler]
        assert out.level == logging.WARNING
        lg.handlers.clear()

    def test_safe_header_value(self):
        """Review regression: client request_ids echo into response
        headers — CR/LF (response splitting) and non-latin-1 chars
        (UnicodeEncodeError mid-response) must be neutralized."""
        assert TR.safe_header_value("ok-id_1") == "ok-id_1"
        assert TR.safe_header_value("x\r\nSet-Cookie: evil=1") == \
            "x__Set-Cookie: evil=1"
        assert TR.safe_header_value("идент-1") == "_____-1"
        assert len(TR.safe_header_value("a" * 500)) == 128
        TR.safe_header_value("any").encode("latin-1")   # always legal


# ---------------------------------------------------------------------------
# Doc-drift guard (ISSUE 15 satellite)
# ---------------------------------------------------------------------------


def _rendered_metric_names():
    """Every tpujob_serve_* base name the export surface renders: the
    gauges (all optional sub-blocks populated), the prefill-pod-only
    gauges (infer/prefill_serve.py metrics_text), and the histogram
    families."""
    from paddle_operator_tpu.utils.observability import serving_gauges

    sample = {
        "prefillMode": "chunked", "kvQuantMode": "int8",
        "priorityQueueDepth": [1], "adapterNames": ["a"],
        "prefillCallsByBucket": {"256": 1},
        "phaseSeconds": {"sched.admit": 1.0},
        # an expert architecture's routing counters (ISSUE 28)
        "moeLayerStepsTotal": 4, "moeAssignmentsTotal": 8,
        "moeExpertsTouchedTotal": 6, "moeExpertLoadTotal": [5, 3],
        "moePrefillAssignmentsTotal": 2,
        "moePrefillExpertLoadTotal": [1, 1],
        "fleet": {"replicasDesired": 1, "prefillReplicasDesired": 1,
                  "generationMin": 0},
    }
    names = {k.split("{", 1)[0] for k in serving_gauges(sample, "j")}
    # prefill pods export two gauges of their own (metrics_text) — the
    # router's scrape map carries both, which pins them rendered
    from paddle_operator_tpu.router.router import _GAUGE_KEYS

    for extra in ("tpujob_serve_prefill_ms_avg",
                  "tpujob_serve_prefill_jobs_total"):
        assert extra in _GAUGE_KEYS
        names.add(extra)
    names |= set(TR.HIST_FAMILIES.values())
    return names


class TestDocDrift:
    def test_every_metric_documented_and_vice_versa(self):
        """docs/observability.md is the catalog of record: every
        rendered tpujob_serve_* name appears there, and every
        tpujob_serve_* name there is rendered — the export and the
        docs can never diverge again."""
        doc = (ROOT / "docs" / "observability.md").read_text()
        doc_names = {re.sub(r"_(bucket|sum|count)$", "", n)
                     for n in re.findall(r"tpujob_serve_[a-z0-9_]+",
                                         doc)}
        rendered = _rendered_metric_names()
        assert rendered - doc_names == set(), \
            f"rendered but undocumented: {sorted(rendered - doc_names)}"
        assert doc_names - rendered == set(), \
            f"documented but never rendered: {sorted(doc_names - rendered)}"


# ---------------------------------------------------------------------------
# Autoscaler reads the histogram-derived p95 (ISSUE 15)
# ---------------------------------------------------------------------------


class TestAutoscalerP95:
    def test_p95_burn_floors_the_ratio(self):
        from paddle_operator_tpu.controller.autoscaler import (
            prefill_load_ratio,
        )

        # queue model reads idle...
        base = prefill_load_ratio(0, 2, 50.0, 1000.0)
        assert base < 0.5
        # ...but the measured p95 breaches the target: burn rate wins
        breached = prefill_load_ratio(0, 2, 50.0, 1000.0,
                                      ttft_p95_ms=2500.0)
        assert breached == pytest.approx(2.5)
        # p95 inside the target never INFLATES a loaded queue reading
        loaded = prefill_load_ratio(40, 1, 400.0, 1000.0)
        assert prefill_load_ratio(40, 1, 400.0, 1000.0,
                                  ttft_p95_ms=100.0) == loaded

    def test_observe_scales_up_on_breached_p95(self):
        from paddle_operator_tpu.api.types import AutoscaleSpec
        from paddle_operator_tpu.controller.autoscaler import (
            FleetAutoscaler,
        )

        spec = AutoscaleSpec(ttft_target_ms=1000.0,
                             tok_s_per_replica=100.0,
                             prefill_min=1, prefill_max=8,
                             min_replicas=1, max_replicas=8,
                             up_cooldown_s=0.0)
        law = FleetAutoscaler(spec)
        serving = {"prefillQueueDepth": 0, "prefillMsAvg": 50.0,
                   "tokensPerSec": 10.0, "ttftP95Ms": 3000.0}
        st = law.observe(None, serving, decode_spec=1, prefill_spec=2,
                         decode_ready=1, prefill_ready=2,
                         decode_draining=False,
                         prefill_draining=False, now=100.0)
        # the folded histogram p95 breaches 3x: the pool scales up
        # even though the queue-depth model reads idle
        assert st["prefillDesired"] > 2
        assert st["prefillReason"] == "up"
        assert st["prefillLoadRatio"] >= 3.0


# ---------------------------------------------------------------------------
# Router stitching under adversity (jax-free stub replicas)
# ---------------------------------------------------------------------------


class _TracedStub(BaseHTTPRequestHandler):
    """Enough of serve.py for the router's tracing path: /readyz,
    /metrics with histogram exposition, /v1/generate honoring
    X-Tpujob-Trace by riding a span set back on the response."""

    protocol_version = "HTTP/1.1"
    ready = True
    dead = False           # accept then slam the connection (pod died)
    ttft_ms = 20.0

    def log_message(self, *a):
        pass

    def do_GET(self):
        cls = type(self)
        if self.path == "/readyz":
            code = 200 if cls.ready else 503
            body = b"{}"
            self.send_response(code)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/metrics":
            from paddle_operator_tpu.utils.observability import (
                histogram_exposition,
            )

            hs = TR.ServeHistograms()
            for _ in range(20):
                hs.ttft.observe(cls.ttft_ms)
            text = ('tpujob_serve_queue_depth{job="j"} 0.0\n'
                    'tpujob_serve_tokens_per_sec{job="j"} 1.0\n'
                    + histogram_exposition(hs.snapshot(), "j", "0"))
            body = text.encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self.send_response(404)
            self.send_header("Content-Length", "0")
            self.end_headers()

    def do_POST(self):
        import socket as _socket

        cls = type(self)
        if cls.dead:
            # mid-proxy pod death: shutdown() (not close()) actually
            # sends the FIN — rfile/wfile still hold the socket, so a
            # bare close() would leave the router blocked on its read
            self.close_connection = True
            try:
                self.connection.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            return
        n = int(self.headers.get("Content-Length", 0))
        req = json.loads(self.rfile.read(n))
        resp = {"tokens": [r + [cls.port] for r in req["tokens"]]}
        ctx = TR.parse_trace_header(
            self.headers.get(TR.TRACE_HEADER))
        if ctx is not None:
            tr = TR.RequestTrace(trace_id=ctx[0], parent=ctx[1],
                                 pod=f"stub-{cls.port}",
                                 request_id=req.get("request_id"))
            t0 = time.monotonic()
            tr.add("queue_wait", t0, t0)
            tr.add("ttft", t0, t0)
            tr.finish()
            resp["trace"] = [tr.to_wire()]
        body = json.dumps(resp).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _traced_stub(**over):
    h = type("TStub", (_TracedStub,), dict({"port": 0}, **over))
    srv = ThreadingHTTPServer(("127.0.0.1", 0), h)
    h.port = srv.server_address[1]
    threading.Thread(
        target=lambda: srv.serve_forever(poll_interval=0.02),
        daemon=True).start()
    return srv, h


def _wait(cond, timeout=8.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise TimeoutError


def _post(url, payload, headers=None, timeout=10):
    req = urllib.request.Request(
        f"{url}/v1/generate", data=json.dumps(payload).encode(),
        method="POST", headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


@pytest.fixture()
def traced_fleet():
    from paddle_operator_tpu.router.router import (
        FleetRouter,
        make_router_server,
    )

    servers = [_traced_stub(), _traced_stub()]
    eps = [f"127.0.0.1:{s.server_address[1]}" for s, _ in servers]
    router = FleetRouter(eps, block_size=4, scrape_interval=0.05,
                         trace=True, upstream_timeout=5.0)
    rsrv = make_router_server("127.0.0.1", 0, router)
    threading.Thread(
        target=lambda: rsrv.serve_forever(poll_interval=0.02),
        daemon=True).start()
    url = f"http://127.0.0.1:{rsrv.server_address[1]}"
    _wait(lambda: sum(st.ready
                      for st in router.replicas.values()) == 2)
    yield url, router, servers
    rsrv.shutdown()
    rsrv.server_close()
    router.close()
    for s, _ in servers:
        s.shutdown()
        s.server_close()


class TestRouterTracing:
    def test_stitched_timeline_single_root(self, traced_fleet):
        url, router, servers = traced_fleet
        tid = TR.new_id()
        code, body, hdrs = _post(
            url, {"tokens": [[1, 2, 3, 4]], "request_id": "rq1"},
            headers={TR.TRACE_HEADER: tid})
        assert code == 200
        # identity satellite: request id + serving replica named
        assert hdrs["X-Request-Id"] == "rq1"
        assert hdrs["X-Router-Replica"] in \
            [f"127.0.0.1:{s.server_address[1]}" for s, _ in servers]
        with urllib.request.urlopen(
                f"{url}/debug/tracez?trace_id={tid}", timeout=5) as r:
            tl = json.loads(r.read())
        spans = tl["spans"]
        names = [s["name"] for s in spans]
        assert names.count("proxy") == 1
        assert "queue_wait" in names and "ttft" in names
        roots = TR.span_roots(spans)
        assert len(roots) == 1 and roots[0]["name"] == "request"

    def test_retry_after_pod_death_one_tree_no_orphans(
            self, traced_fleet):
        """The adversity satellite at the router: attempt 1 dies at
        the socket, the CLIENT retries with the same trace id, attempt
        2 serves — ONE timeline, one parentless root, the dead attempt
        visible, no orphan spans, exactly one ttft."""
        url, router, servers = traced_fleet
        (srv_a, stub_a), (srv_b, stub_b) = servers
        stub_a.dead = True
        stub_b.dead = True
        tid = TR.new_id()
        code, body, _ = _post(url, {"tokens": [[9, 9, 9, 9]],
                                    "request_id": "rq2"},
                              headers={TR.TRACE_HEADER: tid})
        assert code == 503                     # first attempt died
        stub_a.dead = stub_b.dead = False
        _wait(lambda: sum(st.ready
                          for st in router.replicas.values()) == 2)
        code, body, hdrs = _post(url, {"tokens": [[9, 9, 9, 9]],
                                       "request_id": "rq2"},
                                 headers={TR.TRACE_HEADER: tid})
        assert code == 200
        with urllib.request.urlopen(
                f"{url}/debug/tracez?trace_id={tid}", timeout=5) as r:
            spans = json.loads(r.read())["spans"]
        proxies = [s for s in spans if s["name"] == "proxy"]
        assert len(proxies) == 2               # the death IS visible
        assert sorted(p["attrs"]["status"] for p in proxies) \
            == [200, 503]
        roots = TR.span_roots(spans)
        assert len(roots) == 1 and roots[0]["name"] == "request"
        assert sum(s["name"] == "ttft" for s in spans) == 1

    def test_dedupe_replay_names_serving_replica(self, traced_fleet):
        url, router, servers = traced_fleet
        code, _, h1 = _post(url, {"tokens": [[5, 5, 5, 5]],
                                  "request_id": "rq3"})
        assert code == 200 and "X-Router-Replica" in h1
        code, _, h2 = _post(url, {"tokens": [[5, 5, 5, 5]],
                                  "request_id": "rq3"})
        assert code == 200
        assert h2["X-Router-Dedupe"] == "replay"
        assert h2["X-Request-Id"] == "rq3"
        # the replay names the pod that SERVED the recorded result
        assert h2["X-Router-Replica"] == h1["X-Router-Replica"]

    def test_fleet_fold_derives_ttft_p95(self, traced_fleet):
        """The scraped per-replica histograms fold into the fleet
        ttftP95Ms the autoscaler consumes, and the router re-exports
        the fold under tpujob_fleet_*."""
        url, router, servers = traced_fleet
        _wait(lambda: all(st.hists
                          for st in router.replicas.values()))
        fleet = router.statusz()["fleet"]
        assert fleet["latencyHist"]["ttft"]["count"] == 40   # 20 + 20
        assert 16 < fleet["ttftP95Ms"] <= 32   # both stubs observe 20ms
        with urllib.request.urlopen(f"{url}/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "tpujob_fleet_ttft_ms_count 40" in text
        assert 'tpujob_fleet_ttft_ms_bucket{le="+Inf"} 40' in text


# ---------------------------------------------------------------------------
# Traced real ring: bit-neutrality + spans + migration stitching (jax)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models.llama import make_model

    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _ring(cfg, params, **kw):
    from paddle_operator_tpu.infer.scheduler import ContinuousBatcher

    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, 32))
    return ContinuousBatcher(params, cfg, **kw)


class TestTracedRing:
    def test_chunked_prefill_traced_bit_identical(self, tiny):
        """Bit-neutrality fast leg (the full modes x spec x quant
        matrix rides the dryrun serve-trace line): a traced chunked-
        prefill ring's greedy stream equals the untraced ring's, and
        its span set covers every phase with a single-root tree."""
        cfg, params = tiny
        prompt = list(range(1, 13))
        b0 = _ring(cfg, params, prefill_mode="chunked",
                   prefill_chunk=4)
        try:
            want = b0.submit(prompt, max_new_tokens=8) \
                .result(timeout=300)
        finally:
            b0.close()
        b1 = _ring(cfg, params, prefill_mode="chunked",
                   prefill_chunk=4, trace=True)
        try:
            h = b1.submit(prompt, max_new_tokens=8, request_id="t/0",
                          trace_ctx=(TR.new_id(), None))
            assert h.result(timeout=300) == want
            wire = h.trace.to_wire()
            names = [s["name"] for s in wire["spans"]]
            assert names.count("prefill_slice") == 3   # 12 tokens / 4
            for phase in ("queue_wait", "admit", "ttft",
                          "decode_dispatch"):
                assert phase in names, names
            assert len(TR.span_roots(wire["spans"])) == 1
            st = b1.serving_status()
            assert st["latencyHist"]["ttft"]["count"] == 1
            assert st["ttftP95Ms"] > 0
        finally:
            b1.close()

    @pytest.mark.slow
    def test_streamed_handoff_spans_survive(self, tiny):
        """The adversity satellite's streamed-prefill leg: an N-lane
        streamed-handoff disagg admission traces its frames AND stays
        bit-identical — handoff_frame uploads, the disagg_prefill
        phase and the attach all land in one single-root span set.
        ``-m slow`` (the N-lane engine's compiles cost ~25s of tier-1
        budget); the dryrun serve-trace gate's cross-pod leg runs the
        STREAMED remote client every run and pins the same spans."""
        cfg, params = tiny
        prompt = list(range(1, 28))            # multi-block (bs=8)
        kw = dict(paged=True, block_size=8, num_blocks=24,
                  prefill_mode="disagg", prefill_lanes=2,
                  prefill_stream=True, prefill_chunk=8)
        b0 = _ring(cfg, params, **kw)
        try:
            want = b0.submit(prompt, max_new_tokens=6) \
                .result(timeout=300)
        finally:
            b0.close()
        b1 = _ring(cfg, params, trace=True, **kw)
        try:
            h = b1.submit(prompt, max_new_tokens=6,
                          request_id="s/0",
                          trace_ctx=(TR.new_id(), None))
            assert h.result(timeout=300) == want
            spans = h.trace.to_wire()["spans"]
            names = [s["name"] for s in spans]
            assert "handoff_frame" in names, names
            assert "disagg_prefill" in names
            assert "handoff_attach" in names
            assert len(TR.span_roots(spans)) == 1
            assert b1.stats["handoff_frames"] >= 1
        finally:
            b1.close()

    def test_migration_stitches_one_tree_no_double_ttft(self, tiny):
        """The adversity satellite's migration leg: a traced lane
        migrated mid-generation carries its spans in the envelope, the
        adopter seeds them, and the merged set is ONE parentless-root
        tree with exactly one ttft — TTFT observed at the ORIGIN only
        (no double count in either histogram)."""
        from paddle_operator_tpu.infer.resilience import LaneMigrated
        from paddle_operator_tpu.utils import fleetkv as FK

        cfg, params = tiny
        A = _ring(cfg, params, paged=True, block_size=8,
                  num_blocks=16, trace=True)
        B = _ring(cfg, params, paged=True, block_size=8,
                  num_blocks=16, trace=True)
        adopted = {}

        def migrate_out(meta, spill):
            m2, s2 = FK.decode_lane(FK.encode_lane(meta, spill))
            adopted[m2["requestId"]] = B.adopt(m2, s2)
            return True

        A.migrate_out = migrate_out
        A._migrate_on_drain = True
        real = A._step

        def slow(*a, **k):
            time.sleep(0.02)
            return real(*a, **k)

        A._step = slow
        try:
            h = A.submit(list(range(1, 13)), max_new_tokens=24,
                         seed=0, request_id="mig/row0",
                         trace_ctx=(TR.new_id(), "router-span"))
            deadline = time.monotonic() + 30
            while A.stats["chunks"] < 2:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            A.drain(budget_s=30)
            with pytest.raises(LaneMigrated):
                h.result(timeout=5)
            got = adopted["mig/row0"]
            got.result(timeout=120)
            spans = got.trace.to_wire()["spans"]
            names = [s["name"] for s in spans]
            assert "spill" in names            # origin phase survived
            assert "adopt" in names and "restore" in names
            assert sum(n == "ttft" for n in names) == 1
            roots = TR.span_roots(spans)
            # the one unresolved parent is the ORIGIN's root (whose
            # own parent is the router-span context)
            assert len(roots) == 1 \
                and roots[0]["parent"] == "router-span"
            # histograms agree: one TTFT fleet-wide, at the origin
            assert A.hist.ttft.count == 1
            assert B.hist.ttft.count == 0
            # flight recorders carry the outcome on both pods
            assert any(e["kind"] == "migrate_out" and e["ok"]
                       for e in A.flightrec.events())
            assert any(e["kind"] == "adopt"
                       for e in B.flightrec.events())
        finally:
            B.close()
            if A._thread.is_alive():
                A.close()
