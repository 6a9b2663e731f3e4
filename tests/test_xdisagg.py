"""Cross-host disaggregation (ISSUE 13): the handoff wire codec, the
decode-side RemotePrefillClient's failover discipline, the router's
prefill-pool forwarding, and the role-aware fleet aggregate — all
jax-free and fast (tier-1).  The heavyweight remote-vs-in-process
parity matrix rides ``-m slow``; its invariant is pinned EVERY run by
the dryrun ``serve-xdisagg`` line."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from paddle_operator_tpu.utils import fleetkv as FK


def _mk_handoff(n_blocks=2, quant=False, fp=None):
    L, H, bs, D = 2, 2, 4, 8
    rng = np.random.default_rng(0)
    arrays = {
        "k": rng.standard_normal((L, n_blocks, H, bs, D)).astype(
            np.float32),
        "v": rng.standard_normal((L, n_blocks, H, bs, D)).astype(
            np.float32),
    }
    if quant:
        arrays["k"] = (arrays["k"] * 10).astype(np.int8)
        arrays["v"] = (arrays["v"] * 10).astype(np.int8)
        arrays["ks"] = np.ones((L, n_blocks, H), np.float32)
        arrays["vs"] = np.ones((L, n_blocks, H), np.float32)
        arrays["kt"] = rng.standard_normal((L, 1, H, bs, D)).astype(
            np.float32)
        arrays["vt"] = np.zeros((L, 1, H, bs, D), np.float32)
    meta = {"first": 7, "promptLen": 6, "nBlocks": n_blocks,
            "fingerprint": fp or {"layers": L, "blockSize": bs}}
    return meta, arrays


class TestHandoffCodec:
    def test_roundtrip(self):
        meta, arrays = _mk_handoff(quant=True)
        buf = FK.encode_handoff(meta, arrays)
        m2, a2 = FK.decode_handoff(buf)
        assert m2["first"] == 7 and m2["nBlocks"] == 2
        for name, a in arrays.items():
            np.testing.assert_array_equal(a2[name], a)
            assert a2[name].dtype == a.dtype

    def test_kind_and_meta_refusals(self):
        meta, arrays = _mk_handoff()
        lane = FK.encode_envelope("lane", meta, arrays)
        with pytest.raises(FK.EnvelopeError, match="handoff"):
            FK.decode_handoff(lane)
        for missing in ("first", "promptLen", "nBlocks"):
            m = dict(meta)
            del m[missing]
            with pytest.raises(FK.EnvelopeError, match=missing):
                FK.decode_handoff(FK.encode_handoff(m, arrays))

    def test_block_count_must_match_payload(self):
        meta, arrays = _mk_handoff(n_blocks=3)
        meta["nBlocks"] = 2     # lies about the payload
        with pytest.raises(FK.EnvelopeError, match="blocks"):
            FK.decode_handoff(FK.encode_handoff(meta, arrays))

    def test_truncation_refused_at_every_cut(self):
        meta, arrays = _mk_handoff()
        buf = FK.encode_handoff(meta, arrays)
        for cut in (3, 7, len(buf) // 2, len(buf) - 1):
            with pytest.raises(FK.EnvelopeError):
                FK.decode_handoff(buf[:cut])

    def test_fingerprint_mismatch_refused(self):
        mine = {"layers": 2, "blockSize": 4, "quant": "none"}
        FK.check_fingerprint({"fingerprint": dict(mine)}, mine)
        theirs = dict(mine, quant="int8")
        with pytest.raises(FK.EnvelopeError, match="fingerprint"):
            FK.check_fingerprint({"fingerprint": theirs}, mine)


class _StubPrefillHandler(BaseHTTPRequestHandler):
    """A canned prefill pod: mode 'ok' answers a valid envelope,
    'draining' 503s, 'reject' 400s, 'garbage' returns bytes that fail
    the envelope checks."""

    mode = "ok"
    hits = None         # injected list
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n) if n else b""
        self.hits.append(json.loads(body))
        if self.mode == "draining":
            raw = json.dumps({"error": "draining"}).encode()
            self.send_response(503)
        elif self.mode == "reject":
            raw = json.dumps({"error": "bucket overflow"}).encode()
            self.send_response(500)
        elif self.mode == "garbage":
            raw = b"TPKVgarbage-not-an-envelope"
            self.send_response(200)
        else:
            meta, arrays = _mk_handoff(
                fp=json.loads(body).get("fingerprint"))
            raw = FK.encode_handoff(meta, arrays)
            self.send_response(200)
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)


def _stub_pod(mode):
    hits = []
    handler = type("H", (_StubPrefillHandler,),
                   {"mode": mode, "hits": hits})
    srv = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=lambda: srv.serve_forever(
        poll_interval=0.05), daemon=True).start()
    return srv, f"127.0.0.1:{srv.server_address[1]}", hits


class _Req:
    def __init__(self, prompt=(1, 2, 3), rid="r0"):
        self.prompt = list(prompt)
        self.temperature = 0.0
        self.seed = 0
        self.request_id = rid
        self.done = threading.Event()
        self._cancel = False


def _drain_result(client, timeout=10.0):
    import queue

    return client.results.get(timeout=timeout)


class TestRemotePrefillClient:
    def test_failover_past_draining_pod(self):
        """A 503 (draining pod) walks to the next peer — prefill is
        side-effect-free, so retrying elsewhere is always safe."""
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
        )

        d_srv, d_ep, d_hits = _stub_pod("draining")
        o_srv, o_ep, o_hits = _stub_pod("ok")
        client = RemotePrefillClient(peers=[d_ep, o_ep],
                                     backoff_s=0.01)
        client.fingerprint = {"layers": 2, "blockSize": 4}
        try:
            req = _Req()
            client.submit(req, 0)
            item = _drain_result(client)
            assert len(item) == 5, item
            _, slot, arrays, n_blocks, first = item
            assert (slot, n_blocks, first) == (0, 2, 7)
            assert arrays["k"].shape[1] == 2
            assert len(d_hits) == 1 and len(o_hits) == 1
            # the POST carried the job + the ring's fingerprint
            assert o_hits[0]["tokens"] == [1, 2, 3]
            assert o_hits[0]["fingerprint"] == client.fingerprint
        finally:
            client.close()
            for s in (d_srv, o_srv):
                s.shutdown()
                s.server_close()

    def test_exhausted_attempts_post_retriable(self):
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
        )
        from paddle_operator_tpu.infer.resilience import RetriableError

        d_srv, d_ep, _ = _stub_pod("draining")
        client = RemotePrefillClient(peers=[d_ep], max_attempts=2,
                                     backoff_s=0.01)
        try:
            client.submit(_Req(), 1)
            item = _drain_result(client)
            assert len(item) == 3
            assert isinstance(item[2], RetriableError)
        finally:
            client.close()
            d_srv.shutdown()
            d_srv.server_close()

    def test_deterministic_rejection_fails_request(self):
        """A 4xx/5xx (bucket overflow, fingerprint skew) must NOT
        hammer every pod — it fails the one request."""
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
        )

        r_srv, r_ep, r_hits = _stub_pod("reject")
        client = RemotePrefillClient(peers=[r_ep], max_attempts=4,
                                     backoff_s=0.01)
        try:
            client.submit(_Req(), 0)
            item = _drain_result(client)
            assert len(item) == 3
            assert "bucket overflow" in str(item[2])
            assert len(r_hits) == 1     # no retry storm
        finally:
            client.close()
            r_srv.shutdown()
            r_srv.server_close()

    def test_corrupt_envelope_refused(self):
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
        )

        g_srv, g_ep, _ = _stub_pod("garbage")
        client = RemotePrefillClient(peers=[g_ep], max_attempts=1)
        try:
            client.submit(_Req(), 0)
            item = _drain_result(client)
            assert len(item) == 3
            assert isinstance(item[2], FK.EnvelopeError)
        finally:
            client.close()
            g_srv.shutdown()
            g_srv.server_close()

    def test_resolved_request_never_posts(self):
        """A request cancelled/resolved while queued is dropped — the
        POST (and the pod's work) never happens."""
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
        )

        o_srv, o_ep, o_hits = _stub_pod("ok")
        client = RemotePrefillClient(peers=[o_ep])
        try:
            req = _Req()
            req.done.set()
            client.submit(req, 0)
            time.sleep(0.3)
            assert o_hits == []
            assert client.results.empty()
        finally:
            client.close()
            o_srv.shutdown()
            o_srv.server_close()


class TestRouterPrefillForward:
    def test_forward_walks_candidates(self):
        """The router's /v1/prefill relay: least-loaded ready pod
        first, 503/connection failures walk to the next, none ready
        -> 503."""
        from paddle_operator_tpu.router.router import FleetRouter

        d_srv, d_ep, d_hits = _stub_pod("draining")
        o_srv, o_ep, o_hits = _stub_pod("ok")
        r = FleetRouter([], prefill_endpoints=[d_ep, o_ep])
        for ep in (d_ep, o_ep):
            r.prefill[ep].ready = True
        # the draining pod scrapes a SHORTER queue, so it is tried
        # first and the walk must pass it
        r.prefill[d_ep].gauges = {"prefillQueueDepth": 0.0}
        r.prefill[o_ep].gauges = {"prefillQueueDepth": 5.0}
        try:
            body = json.dumps({"tokens": [1, 2]}).encode()
            code, raw, ep = r.forward_prefill(body)
            assert code == 200 and ep == o_ep
            FK.decode_handoff(raw)      # a real envelope came back
            assert r.counters["prefill_jobs_forwarded"] == 1
            # no ready pod at all -> 503, counted
            r.prefill[d_ep].ready = r.prefill[o_ep].ready = False
            code, raw, ep = r.forward_prefill(body)
            assert code == 503 and ep is None
            assert r.counters["no_ready_prefill"] == 1
        finally:
            for s in (d_srv, o_srv):
                s.shutdown()
                s.server_close()

    def test_prefill_endpoints_file_reload_drops_empty(self):
        """Unlike the decode list, an EMPTY prefill file must drop
        stale entries — the autoscaler scales the pool down and back."""
        import os
        import tempfile

        from paddle_operator_tpu.router.router import FleetRouter

        fd, path = tempfile.mkstemp()
        os.write(fd, b"10.0.0.1:8701,10.0.0.2:8701")
        os.close(fd)
        try:
            r = FleetRouter([], prefill_endpoints_file=path)
            r._reload_endpoints_file()
            assert set(r.prefill) == {"10.0.0.1:8701",
                                      "10.0.0.2:8701"}
            with open(path, "w") as f:
                f.write("")
            r._reload_endpoints_file()
            assert r.prefill == {}
        finally:
            os.unlink(path)


class TestRoleAwareAggregate:
    def test_prefill_blocks_fold_into_their_own_keys(self):
        """Satellite: a prefill pod's block must not skew decode
        tok/s or the token-weighted hit rate — its prompt tok/s and
        huge tokensTotal weight would otherwise poison both."""
        from paddle_operator_tpu.router.router import (
            aggregate_fleet_serving,
        )

        agg = aggregate_fleet_serving({
            "0": {"tokensPerSec": 10.0, "prefixHitRate": 0.8,
                  "tokensTotal": 100, "queueDepth": 1,
                  "prefillQueueDepth": 1},
            "1": {"tokensPerSec": 30.0, "prefixHitRate": 0.4,
                  "tokensTotal": 300, "queueDepth": 3,
                  "prefillQueueDepth": 0},
            "pf0": {"role": "prefill", "tokensPerSec": 500.0,
                    "tokensTotal": 50000, "prefillQueueDepth": 4,
                    "prefillMsAvg": 120.0, "prefillJobs": 10,
                    "draining": False},
        })
        # decode sums untouched by the prefill block
        assert agg["tokensPerSec"] == 40
        assert agg["queueDepth"] == 4
        assert agg["prefixHitRate"] == 0.5      # token-weighted, 100:300
        # the prefill pool folds into its own keys
        assert agg["prefillTokensPerSec"] == 500.0
        assert agg["prefillReplicasReporting"] == 1
        assert agg["prefillMsAvg"] == 120.0
        # the POOL's depth REPLACES the decode sum — a remote handoff
        # in flight is counted by its decode ring (_disagg_waiting)
        # AND by the pod serving it, and folding both would feed the
        # SLO autoscaler ~2x the real load
        assert agg["prefillQueueDepth"] == 4
        assert agg["replicasReporting"] == 3

    def test_liveness_folds_across_both_pools(self):
        from paddle_operator_tpu.router.router import (
            aggregate_fleet_serving,
        )

        agg = aggregate_fleet_serving({
            "0": {"tokensPerSec": 1.0, "draining": False},
            "pf0": {"role": "prefill", "draining": True},
        })
        assert agg["draining"] is True


class TestOverloadMapping:
    def test_prefill_timeout_maps_to_retriable_503(self):
        """A backlogged pod's TimeoutError is overload, not a
        per-prompt defect: it must 503 (like draining) so the client
        and router walk to the next candidate, never 500."""
        import threading as _t
        import urllib.error
        import urllib.request
        from http.server import ThreadingHTTPServer

        from paddle_operator_tpu.infer.prefill_serve import (
            _PrefillHandler,
        )

        class _Backlogged:
            draining = False
            stats = {"refused": 0}
            _lock = _t.Lock()

            def fingerprint(self):
                return {"layers": 2}

            def prefill(self, tokens, temperature, seed):
                raise TimeoutError("prefill did not finish within 0s")

        handler = type("H", (_PrefillHandler,),
                       {"frontend": _Backlogged()})
        srv = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        _t.Thread(target=lambda: srv.serve_forever(poll_interval=0.05),
                  daemon=True).start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.server_address[1]}/v1/prefill",
                data=json.dumps({"tokens": [1, 2, 3]}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 503
            assert ei.value.headers.get("Retry-After")
        finally:
            srv.shutdown()
            srv.server_close()


# ---------------------------------------------------------------------------
# Heavyweight: real prefill server + real rings (dryrun serve-xdisagg
# carries the invariant every run; the matrix lives behind -m slow)
# ---------------------------------------------------------------------------


def _tiny():
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models.llama import make_model

    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return params, cfg


@pytest.mark.slow
class TestRemoteParity:
    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_remote_equals_in_process(self, kv_quant):
        import jax

        from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
            make_prefill_server,
        )

        params, cfg = _tiny()
        prompts = [np.asarray(jax.random.randint(
            jax.random.PRNGKey(50 + i), (n,), 0, cfg.vocab_size))
            for i, n in enumerate((13, 33))]

        def ring(client=None):
            return ContinuousBatcher(
                params, cfg, slots=2, max_len=64, chunk_tokens=4,
                prefill_buckets=(16, 64), paged=True, block_size=16,
                prefill_mode="disagg", kv_quant=kv_quant,
                prefill_client=client)

        oracle = ring()
        try:
            refs = [oracle.submit(p, max_new_tokens=8)
                    .result(timeout=600) for p in prompts]
        finally:
            oracle.close()
        psrv = make_prefill_server("127.0.0.1", 0, params, cfg,
                                   block_size=16, max_len=64,
                                   buckets=(16, 64),
                                   kv_quant=kv_quant)
        threading.Thread(target=lambda: psrv.serve_forever(
            poll_interval=0.05), daemon=True).start()
        client = RemotePrefillClient(
            peers=[f"127.0.0.1:{psrv.server_address[1]}"])
        r = ring(client)
        try:
            for p, want in zip(prompts, refs):
                got = r.submit(p, max_new_tokens=8).result(timeout=600)
                assert got == want
            assert r.stats["remote_prefills"] == len(prompts)
            r.pool.check_invariant()
        finally:
            r.close()
            psrv.shutdown()
            psrv.server_close()
            psrv.frontend.close()

    def test_prefill_server_drain_refuses_new_finishes_inflight(self):
        """The prefill pod's drain contract: draining flips /readyz
        false and 503s NEW jobs, while an in-flight job finishes and
        its response flushes."""
        import urllib.request

        from paddle_operator_tpu.infer.prefill_serve import (
            make_prefill_server,
        )

        params, cfg = _tiny()
        psrv = make_prefill_server("127.0.0.1", 0, params, cfg,
                                   block_size=16, max_len=64,
                                   buckets=(16, 64))
        threading.Thread(target=lambda: psrv.serve_forever(
            poll_interval=0.05), daemon=True).start()
        ep = f"http://127.0.0.1:{psrv.server_address[1]}"
        try:
            fp = psrv.frontend.fingerprint()
            body = json.dumps({"tokens": list(range(1, 14)),
                               "fingerprint": fp}).encode()
            results = {}

            def post(tag):
                req = urllib.request.Request(
                    f"{ep}/v1/prefill", data=body,
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=120) as r:
                        results[tag] = (r.status, r.read())
                except urllib.error.HTTPError as e:
                    results[tag] = (e.code, e.read())

            t = threading.Thread(target=post, args=("inflight",))
            t.start()
            # drain the moment the job is in flight
            deadline = time.monotonic() + 30
            while psrv.frontend.depth() == 0:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            psrv.frontend.draining = True
            post("late")
            t.join(timeout=120)
            assert results["late"][0] == 503
            st, raw = results["inflight"]
            assert st == 200
            FK.decode_handoff(raw)      # finished AND flushed intact
            with urllib.request.urlopen(
                    f"{ep}/statusz", timeout=10) as r:
                stz = json.loads(r.read())
            assert stz["draining"] is True
            assert stz["refusedHandoffs"] == 1
        finally:
            psrv.shutdown()
            psrv.server_close()
            psrv.frontend.close()

    def test_queued_timeout_settles_depth_exactly_once(self):
        """A job that times out while QUEUED is dropped by the executor
        without ever posting a result — the timeout path itself must
        settle the depth gauge (the autoscaler scales off it, and the
        drain loop spins on it), and a job that still finishes
        mid-flight must not decrement twice."""
        from paddle_operator_tpu.infer.prefill_serve import (
            PrefillFrontend,
        )

        params, cfg = _tiny()
        fe = PrefillFrontend(params, cfg, block_size=16, max_len=64,
                             buckets=(16, 64))
        try:
            with pytest.raises(TimeoutError):
                fe.prefill(list(range(1, 14)), 0.0, 0, timeout=0.0)
            assert fe.depth() == 0
            # a real job still accounts exactly once afterwards
            buf = fe.prefill(list(range(1, 14)), 0.0, 0)
            FK.decode_handoff(buf)
            deadline = time.monotonic() + 30
            while fe.depth() != 0:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            # the cancelled job never un-settles it (no double
            # decrement from a late executor result)
            time.sleep(0.2)
            assert fe.depth() == 0
        finally:
            fe.close()
