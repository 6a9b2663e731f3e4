"""Serving-path fault tolerance (infer/resilience.py + infer/chaos.py
through the continuous-batching ring): request deadlines resolve as
partials with their blocks freed, SIGTERM drain sheds-then-finishes and
exits EXIT_PREEMPTED, the dispatch watchdog fails clients fast and
self-heals the ring under a restart budget, NaN lanes quarantine one
request without touching the others, and the seeded chaos harness makes
every one of these paths deterministic.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer.chaos import (
    ChaosEvent,
    ChaosInjector,
    parse_schedule,
)
from paddle_operator_tpu.infer.resilience import (
    EXIT_PREEMPTED,
    DispatchWatchdog,
    LaneQuarantined,
    RetriableError,
    RingResilience,
    ServerState,
    ServingDrain,
    ShuttingDown,
)
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import make_model

MAX_LEN = 64


@pytest.fixture(scope="module")
def setup():
    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _prompt(cfg, s, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (s,), 0, cfg.vocab_size,
        dtype=jnp.int32))


def _ref(cfg, params, p, new):
    return np.asarray(D.generate(
        params, cfg, jnp.asarray([p], jnp.int32), max_new_tokens=new,
        max_len=MAX_LEN)[0]).tolist()


def _batcher(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, MAX_LEN))
    return ContinuousBatcher(params, cfg, **kw)


def _pace(b, delay):
    """Slow the resident step down (the established test idiom for
    keeping requests in flight long enough to fault them)."""
    orig = b._step

    def paced(*a):
        time.sleep(delay)
        return orig(*a)

    b._step = paced
    return orig


class TestDeadlines:
    @pytest.mark.slow   # pinned by dryrun serve-chaos (tier-1 budget, ISSUE 10)
    def test_resident_deadline_partial_and_blocks_freed(self, setup):
        """An expired lane retires mid-generation: the request RESOLVES
        with a prefix of the fault-free stream, the flag set, and (paged)
        its pool blocks back on the free list."""
        cfg, params = setup
        b = _batcher(cfg, params, slots=1, paged=True, block_size=8)
        try:
            p = _prompt(cfg, 6, seed=1)
            ref = _ref(cfg, params, p, 24)
            b.submit(p, max_new_tokens=4).result(timeout=120)  # warm
            total0 = b.pool.blocks_free() + b.pool.blocks_cached()
            _pace(b, 0.08)
            h = b.submit(p, max_new_tokens=24, deadline_s=0.35)
            out = h.result(timeout=60)
            assert h.deadline_exceeded
            assert out == ref[:len(out)]          # partial, exact prefix
            assert len(out) < len(ref)            # actually cut short
            assert b.stats["deadline_exceeded"] == 1
            deadline = time.monotonic() + 30
            while b.pool.blocks_free() + b.pool.blocks_cached() < total0:
                assert time.monotonic() < deadline, "blocks not freed"
                time.sleep(0.02)
            b.pool.check_invariant()
        finally:
            b.close()

    def test_queued_deadline_resolves_prompt_only(self, setup):
        """A request whose deadline passes while still QUEUED resolves
        prompt-only with the flag — never silently dropped.  (Also the
        deadline-validation check: <= 0 rejects up front.)"""
        cfg, params = setup
        b = _batcher(cfg, params, slots=1)
        try:
            with pytest.raises(ValueError, match="deadline_s"):
                b.submit(_prompt(cfg, 4), max_new_tokens=2,
                         deadline_s=0.0)
            p = _prompt(cfg, 5, seed=2)
            _pace(b, 0.08)
            blocker = b.submit(p, max_new_tokens=16)
            h = b.submit(p, max_new_tokens=8, deadline_s=0.2)
            out = h.result(timeout=60)
            assert h.deadline_exceeded
            assert out == list(map(int, p))
            blocker.cancel()
        finally:
            b.close()

    def test_http_deadline_header_yields_504_partial(self, setup):
        """X-Request-Deadline over real HTTP: 504 with the partial
        tokens delivered in the body."""
        from paddle_operator_tpu.infer.serve import make_server

        cfg, params = setup
        srv = make_server("127.0.0.1", 0, params, cfg, continuous=True,
                          slots=1, max_len=MAX_LEN, chunk_tokens=4,
                          prefill_buckets=(16, MAX_LEN))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        b = srv.generator.batcher
        try:
            p = _prompt(cfg, 5, seed=3).tolist()
            ref = _ref(cfg, params, p, 24)
            _pace(b, 0.08)
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.server_address[1]}/v1/generate",
                data=json.dumps({"tokens": [p],
                                 "max_new_tokens": 24}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Request-Deadline": "0.35"}, method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=60)
            assert ei.value.code == 504
            out = json.loads(ei.value.read())
            assert out["deadline_exceeded"] == [True]
            row = out["tokens"][0]
            assert row == ref[:len(row)] and len(row) < len(ref)
        finally:
            srv.shutdown()
            srv.generator.close()


class TestShutdown:
    def test_close_fails_queued_with_shutting_down(self, setup):
        cfg, params = setup
        b = _batcher(cfg, params, slots=1)
        p = _prompt(cfg, 5, seed=4)
        _pace(b, 0.08)
        resident = b.submit(p, max_new_tokens=20)
        queued = b.submit(p, max_new_tokens=8)
        b.close()
        with pytest.raises(ShuttingDown):
            queued.result(timeout=10)
        with pytest.raises(ShuttingDown):
            resident.result(timeout=10)
        with pytest.raises(ShuttingDown):      # and new submits refuse
            b.submit(p, max_new_tokens=2)

    def test_blocked_submitter_unblocks_with_shutting_down(self, setup):
        """The satellite regression: a submitter blocked in the bounded
        queue's put loop must get ShuttingDown promptly at close(), not
        hang out the queue-timeout deadline against a dead ring."""
        cfg, params = setup
        b = _batcher(cfg, params, slots=1, max_queue=1,
                     queue_timeout=30.0)
        p = _prompt(cfg, 5, seed=5)
        _pace(b, 0.08)
        b.submit(p, max_new_tokens=20)          # resident
        b.submit(p, max_new_tokens=8)           # fills the queue
        errs = []

        def blocked():
            try:
                b.submit(p, max_new_tokens=4)
            except Exception as e:
                errs.append(e)

        t = threading.Thread(target=blocked)
        t.start()
        time.sleep(0.3)                         # let it block in put
        t0 = time.monotonic()
        b.close()
        t.join(timeout=10)
        assert not t.is_alive(), "submitter still blocked after close"
        assert errs and isinstance(errs[0], ShuttingDown), errs
        assert time.monotonic() - t0 < 25       # not the 30s timeout


class TestWatchdogSelfHeal:
    def test_dispatch_fail_rebuilds_and_serves_identically(self, setup):
        """A raising dispatch fails the RESIDENT requests retriably and
        rebuilds the ring; post-rebuild output is bit-identical to a
        fault-free run (fresh prefill, same math)."""
        cfg, params = setup
        b = _batcher(cfg, params, resilience=RingResilience(
            watchdog=False, max_restarts=3, backoff_base_s=0.05))
        try:
            p = _prompt(cfg, 6, seed=6)
            ref = _ref(cfg, params, p, 8)
            assert b.submit(p, max_new_tokens=8).result(
                timeout=120) == ref
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            inj.events[nxt] = [ChaosEvent("dispatch_fail", nxt)]
            with pytest.raises(RetriableError):
                b.submit(p, max_new_tokens=8).result(timeout=60)
            assert b.stats["watchdog_restarts"] == 1
            assert b.healthy
            assert b.submit(p, max_new_tokens=8).result(
                timeout=120) == ref
        finally:
            b.close()

    def test_stall_fails_clients_before_the_hang_resolves(self, setup):
        """The watchdog monitor fires while the ring thread is still
        stuck: clients get their retriable 503 in ~threshold seconds,
        not after the wedge clears."""
        cfg, params = setup
        res = RingResilience(stall_factor=0, stall_floor_s=60,
                             poll_s=0.02, max_restarts=2,
                             backoff_base_s=0.05)
        b = _batcher(cfg, params, resilience=res)
        try:
            p = _prompt(cfg, 6, seed=7)
            ref = _ref(cfg, params, p, 8)
            b.submit(p, max_new_tokens=8).result(timeout=120)  # warm
            res.stall_floor_s = 0.3    # factor 0 -> pure-floor threshold
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            inj.events[nxt] = [ChaosEvent("dispatch_hang", nxt, 1.2)]
            h = b.submit(p, max_new_tokens=8)
            t0 = time.monotonic()
            with pytest.raises(RetriableError, match="stalled"):
                h.result(timeout=60)
            assert time.monotonic() - t0 < 1.0   # hang was 1.2s
            assert b.submit(p, max_new_tokens=8).result(
                timeout=120) == ref
            assert b.stats["watchdog_restarts"] == 1
        finally:
            b.close()

    def test_restart_budget_exhaustion_flips_healthz(self, setup):
        """Faults past the budget stop self-healing: the ring dies, the
        batcher reports unhealthy (the /healthz flip), and later
        submits are refused instead of queueing into a void."""
        cfg, params = setup
        b = _batcher(cfg, params, resilience=RingResilience(
            watchdog=False, max_restarts=1, backoff_base_s=0.02))
        p = _prompt(cfg, 6, seed=8)
        try:
            b.submit(p, max_new_tokens=4).result(timeout=120)
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            for k in range(8):
                inj.events[nxt + k] = [ChaosEvent("dispatch_fail",
                                                  nxt + k)]
            for _ in range(3):
                try:
                    b.submit(p, max_new_tokens=8).result(timeout=60)
                except Exception:
                    pass
                if not b.healthy:
                    break
            deadline = time.monotonic() + 20
            while b.healthy and time.monotonic() < deadline:
                time.sleep(0.02)
            assert not b.healthy
            assert not b.accepting
            assert b.stats["watchdog_restarts"] == 1    # budget = 1
            with pytest.raises((ShuttingDown, RuntimeError)):
                b.submit(p, max_new_tokens=2).result(timeout=10)
        finally:
            b.close()

    def test_legacy_no_resilience_still_dies_loudly(self, setup):
        """Without a RingResilience the old contract holds: the first
        ring-level fault kills the batcher and fails everything."""
        cfg, params = setup
        b = _batcher(cfg, params)           # resilience=None
        p = _prompt(cfg, 6, seed=9)
        b.submit(p, max_new_tokens=4).result(timeout=120)
        inj = ChaosInjector("").install(b)
        nxt = inj.dispatches
        inj.events[nxt] = [ChaosEvent("dispatch_fail", nxt)]
        with pytest.raises(RuntimeError, match="chaos"):
            b.submit(p, max_new_tokens=8).result(timeout=60)
        assert not b.healthy
        # the fatal fault kills the loop thread, but submit's
        # is_alive() check races its last instants under load — wait
        # for the death the legacy contract promises, then assert it
        b._thread.join(timeout=30)
        with pytest.raises(ShuttingDown):
            b.submit(p, max_new_tokens=2)
        b.close()


class TestNanQuarantine:
    @pytest.mark.slow   # pinned by dryrun serve-chaos (tier-1 budget, ISSUE 10)
    def test_nan_lane_fails_one_request_not_the_ring(self, setup):
        """Poisoned lane -> LaneQuarantined for ITS request only; the
        other resident lane's stream is bit-identical to fault-free
        (attention independence), and the ring keeps serving."""
        cfg, params = setup
        b = _batcher(cfg, params, resilience=RingResilience(
            watchdog=False, nan_check=True))
        try:
            ps = [_prompt(cfg, 6, seed=10 + i) for i in range(2)]
            refs = [_ref(cfg, params, p, 8) for p in ps]
            b.submit(ps[0], max_new_tokens=4).result(timeout=120)
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            inj.events[nxt] = [ChaosEvent("nan_lane", nxt, 0)]
            hs = [b.submit(p, max_new_tokens=8) for p in ps]
            outcomes = []
            for h, ref in zip(hs, refs):
                try:
                    outcomes.append(("ok", h.result(timeout=60) == ref))
                except LaneQuarantined:
                    outcomes.append(("quarantined", True))
            assert sorted(k for k, _ in outcomes) == \
                ["ok", "quarantined"], outcomes
            assert all(good for _, good in outcomes)
            assert b.stats["quarantined_lanes"] == 1
            assert b.healthy
            # the quarantined lane serves the next request exactly
            assert b.submit(ps[0], max_new_tokens=8).result(
                timeout=120) == refs[0]
        finally:
            b.close()

    @pytest.mark.slow   # pinned by dryrun serve-chaos (tier-1 budget, ISSUE 10)
    def test_paged_nan_blocks_scrubbed_before_reuse(self, setup):
        """Paged quarantine must SCRUB the lane's private blocks: a NaN
        row re-mapped under a later lane would poison it through the
        masked-tail 0*NaN contraction.  After quarantine the pool
        invariant holds and later requests are bit-identical."""
        cfg, params = setup
        b = _batcher(cfg, params, slots=1, paged=True, block_size=8,
                     resilience=RingResilience(watchdog=False,
                                               nan_check=True))
        try:
            p = _prompt(cfg, 13, seed=12)   # unaligned: private tail blk
            ref = _ref(cfg, params, p, 10)
            assert b.submit(p, max_new_tokens=10).result(
                timeout=120) == ref
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            inj.events[nxt] = [ChaosEvent("nan_lane", nxt, 0)]
            with pytest.raises(LaneQuarantined):
                b.submit(p, max_new_tokens=10).result(timeout=60)
            b.pool.check_invariant()
            # re-mapped blocks must be clean: repeat several times so a
            # leaked NaN block would certainly be re-used
            for _ in range(2):
                assert b.submit(p, max_new_tokens=10).result(
                    timeout=120) == ref
            b.pool.check_invariant()
        finally:
            b.close()

    def test_nan_check_rejected_with_speculation(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="nan_check"):
            _batcher(cfg, params, spec_k=2, draft_params=params,
                     draft_cfg=cfg,
                     resilience=RingResilience(nan_check=True))


class TestChaosHarness:
    def test_parse_schedule(self):
        evs = parse_schedule(
            "dispatch_fail@5,dispatch_hang@9:2.5,nan_lane@12:1,"
            "client_drop@7,pool_oom@3:2")
        assert [(e.kind, e.at, e.arg) for e in evs] == [
            ("dispatch_fail", 5, None), ("dispatch_hang", 9, 2.5),
            ("nan_lane", 12, 1.0), ("client_drop", 7, None),
            ("pool_oom", 3, 2.0)]
        with pytest.raises(ValueError, match="kind"):
            parse_schedule("explode@3")
        with pytest.raises(ValueError, match="kind@index"):
            parse_schedule("dispatch_fail")

    def test_schedule_fires_deterministically(self, setup):
        """Same schedule + same request pattern -> the same (kind,
        dispatch) firing log, run over run — the property every chaos
        gate leans on."""
        cfg, params = setup

        def run():
            b = _batcher(cfg, params, slots=1,
                         resilience=RingResilience(
                             watchdog=False, backoff_base_s=0.02))
            try:
                p = _prompt(cfg, 6, seed=13)
                b.submit(p, max_new_tokens=4).result(timeout=120)
                inj = ChaosInjector("dispatch_fail@2", seed=3).install(b)
                try:
                    b.submit(p, max_new_tokens=8).result(timeout=60)
                except RetriableError:
                    pass
                b.submit(p, max_new_tokens=4).result(timeout=120)
                return list(inj.fired)
            finally:
                b.close()

        assert run() == run() == [("dispatch_fail", 2)]

    def test_pool_oom_fails_one_request_ring_survives(self, setup):
        """Injected allocator OOM: the growing lane's request fails,
        its blocks free, and the ring keeps serving (the PR4 starvation
        path, now deterministically reachable)."""
        from paddle_operator_tpu.infer.paged import NoFreeBlocks

        cfg, params = setup
        b = _batcher(cfg, params, slots=2, paged=True, block_size=8)
        try:
            p = _prompt(cfg, 6, seed=14)
            ref = _ref(cfg, params, p, 8)
            assert b.submit(p, max_new_tokens=8).result(
                timeout=120) == ref
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            inj.events[nxt] = [ChaosEvent("pool_oom", nxt, 99)]
            h = b.submit(p, max_new_tokens=16)
            with pytest.raises(NoFreeBlocks):
                h.result(timeout=60)
            b.pool.chaos_fail_allocs = 0
            b.pool.check_invariant()
            assert b.submit(p, max_new_tokens=8).result(
                timeout=120) == ref
        finally:
            b.close()


class TestDrain:
    def test_drain_finishes_residents_sheds_queue_exits_83(self, setup):
        """The full first-SIGTERM sequence against a real server:
        admissions 503 with Retry-After, queued work shed retriably,
        residents finish, exit_fn receives EXIT_PREEMPTED."""
        from paddle_operator_tpu.infer.serve import make_server

        cfg, params = setup
        srv = make_server("127.0.0.1", 0, params, cfg, continuous=True,
                          slots=1, max_len=MAX_LEN, chunk_tokens=4,
                          prefill_buckets=(16, MAX_LEN))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        b = srv.generator.batcher
        exits = []
        drain = ServingDrain(srv, srv.state, batcher=b, budget_s=30.0,
                             exit_fn=exits.append)
        try:
            p = _prompt(cfg, 5, seed=15)
            ref = _ref(cfg, params, p, 12)
            b.submit(p, max_new_tokens=4).result(timeout=120)  # warm
            _pace(b, 0.05)
            resident = b.submit(p, max_new_tokens=12)
            # the drain must catch `resident` RESIDENT (not still in
            # the admission queue, where it would be shed): wait for
            # the lane to hold it before flipping the drain
            deadline = time.monotonic() + 10
            while resident not in b.lane:
                assert time.monotonic() < deadline, "never admitted"
                time.sleep(0.01)
            queued = b.submit(p, max_new_tokens=12)     # slots=1
            t = threading.Thread(target=drain.run, args=("test",))
            t.start()
            # while draining: new admissions get 503 + Retry-After
            deadline = time.monotonic() + 10
            while not srv.state.draining:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            req = urllib.request.Request(
                f"{base}/v1/generate",
                data=json.dumps({"tokens": [p.tolist()],
                                 "max_new_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 503
            assert ei.value.headers.get("Retry-After") is not None
            t.join(timeout=60)
            assert exits == [EXIT_PREEMPTED]
            assert resident.result(timeout=10) == ref   # finished whole
            with pytest.raises(ShuttingDown):
                queued.result(timeout=10)
        finally:
            srv.shutdown()
            srv.generator.close()

    def test_drain_budget_expiry_cancels_with_blocks_returned(self,
                                                              setup):
        """Budget expiry: stragglers cancel with their partial tokens
        and the paged pool gets EVERY block back (free+cached == the
        pre-request level)."""
        cfg, params = setup
        b = _batcher(cfg, params, slots=1, paged=True, block_size=8)
        p = _prompt(cfg, 6, seed=16)
        ref = _ref(cfg, params, p, 24)
        b.submit(p, max_new_tokens=4).result(timeout=120)   # warm
        total0 = b.pool.blocks_free() + b.pool.blocks_cached()
        _pace(b, 0.12)      # 6 chunks x 0.12s: cannot finish in-budget
        h = b.submit(p, max_new_tokens=24)
        time.sleep(0.1)                         # let it admit
        t0 = time.monotonic()
        b.drain(budget_s=0.3)
        out = h.result(timeout=10)              # partial, flushed
        assert out == ref[:len(out)] and len(out) < len(ref)
        assert b.pool.blocks_free() + b.pool.blocks_cached() == total0
        b.pool.check_invariant()
        assert time.monotonic() - t0 < 20

    def test_double_sigterm_immediate_exit_with_partials(self, setup):
        """Second signal = immediate exit: exit_fn fires without
        waiting for the drain budget, and resident requests RESOLVE
        with their best-effort partials."""
        from paddle_operator_tpu.infer.serve import make_server

        cfg, params = setup
        srv = make_server("127.0.0.1", 0, params, cfg, continuous=True,
                          slots=1, max_len=MAX_LEN, chunk_tokens=4,
                          prefill_buckets=(16, MAX_LEN))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        b = srv.generator.batcher
        exits = []
        drain = ServingDrain(srv, srv.state, batcher=b, budget_s=300.0,
                             exit_fn=exits.append)
        drain._prev = None          # signal-handler chain, test-wired
        try:
            p = _prompt(cfg, 6, seed=17)
            ref = _ref(cfg, params, p, 24)
            b.submit(p, max_new_tokens=4).result(timeout=120)
            _pace(b, 0.08)
            h = b.submit(p, max_new_tokens=24)
            time.sleep(0.25)                    # some tokens flowed
            drain._handler(15, None)            # SIGTERM #1: drain start
            t0 = time.monotonic()
            drain._handler(15, None)            # SIGTERM #2: immediate
            assert exits and exits[-1] == EXIT_PREEMPTED
            assert time.monotonic() - t0 < 5    # not the 300s budget
            out = h.result(timeout=10)          # partial flushed
            assert out == ref[:len(out)]
        finally:
            srv.shutdown()
            srv.generator.close()


class TestHealthEndpoints:
    def test_readyz_vs_healthz_split(self, setup):
        """/healthz = liveness (flips only when the ring is dead);
        /readyz = readiness (also false while draining)."""
        from paddle_operator_tpu.infer.serve import make_server

        cfg, params = setup
        srv = make_server("127.0.0.1", 0, params, cfg, continuous=True,
                          slots=1, max_len=MAX_LEN, chunk_tokens=4,
                          prefill_buckets=(16, MAX_LEN))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"

        def get(path):
            try:
                with urllib.request.urlopen(f"{base}{path}",
                                            timeout=10) as r:
                    return r.status, json.loads(r.read()), r.headers
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read()), e.headers

        try:
            assert get("/healthz")[0] == 200
            assert get("/readyz")[0] == 200
            # draining: NOT live-dead, but NOT ready
            srv.state.draining = True
            assert get("/healthz")[0] == 200
            code, body, headers = get("/readyz")
            assert code == 503 and body["reason"] == "draining"
            assert headers.get("Retry-After") is not None
            srv.state.draining = False
            # dead ring: both flip
            srv.generator.batcher.healthy = False
            assert get("/healthz")[0] == 503
            assert get("/readyz")[0] == 503
        finally:
            srv.shutdown()
            srv.generator.close()


class TestClientRetry:
    """client/client.py post_generate against a flapping fake server."""

    def _flapping(self, fails, retry_after=None, code=503):
        """HTTP server answering `code` for the first `fails` POSTs,
        then 200 with a token payload."""
        from http.server import BaseHTTPRequestHandler, HTTPServer

        state = {"calls": 0}

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                state["calls"] += 1
                if state["calls"] <= fails:
                    body = b'{"error": "flap"}'
                    self.send_response(code)
                    if retry_after is not None:
                        self.send_header("Retry-After", str(retry_after))
                else:
                    body = json.dumps({"tokens": [[1, 2, 3]]}).encode()
                    self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        srv = HTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, state

    def _client(self):
        import importlib
        import os
        import sys

        sys.path.insert(0, "client")
        mod = importlib.import_module("client")
        # the kube CLI module shadows stdlib-free import paths; only
        # post_generate is under test here
        assert os.path.exists("client/client.py")
        return mod

    def test_retries_503_until_success_with_jitter(self, setup):
        import random

        cli = self._client()
        srv, state = self._flapping(fails=2)
        sleeps = []
        try:
            code, out = cli.post_generate(
                f"http://127.0.0.1:{srv.server_address[1]}",
                {"tokens": [[1]]}, rng=random.Random(0),
                backoff_base_s=0.2, sleep=sleeps.append)
            assert code == 200 and out["tokens"] == [[1, 2, 3]]
            assert state["calls"] == 3
            # exponential base with jitter in [0.5, 1.5)
            assert 0.1 <= sleeps[0] < 0.3
            assert 0.2 <= sleeps[1] < 0.6
        finally:
            srv.shutdown()

    def test_honors_retry_after_header(self):
        import random

        cli = self._client()
        srv, _ = self._flapping(fails=1, retry_after=1.25)
        sleeps = []
        try:
            code, _ = cli.post_generate(
                f"http://127.0.0.1:{srv.server_address[1]}",
                {"tokens": [[1]]}, rng=random.Random(0),
                sleep=sleeps.append)
            assert code == 200
            assert 1.25 * 0.5 <= sleeps[0] < 1.25 * 1.5
        finally:
            srv.shutdown()

    def test_retry_cap_and_non_503_passthrough(self):
        import random

        cli = self._client()
        srv, state = self._flapping(fails=99)
        try:
            with pytest.raises(urllib.error.HTTPError):
                cli.post_generate(
                    f"http://127.0.0.1:{srv.server_address[1]}",
                    {"tokens": [[1]]}, max_retries=2,
                    rng=random.Random(0), sleep=lambda s: None)
            assert state["calls"] == 3          # initial + 2 retries
        finally:
            srv.shutdown()
        srv, state = self._flapping(fails=1, code=400)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                cli.post_generate(
                    f"http://127.0.0.1:{srv.server_address[1]}",
                    {"tokens": [[1]]}, rng=random.Random(0),
                    sleep=lambda s: None)
            assert ei.value.code == 400         # caller bug: no retry
            assert state["calls"] == 1
        finally:
            srv.shutdown()

    def test_deadline_caps_retries(self):
        import random

        cli = self._client()
        srv, _ = self._flapping(fails=99, retry_after=10)
        try:
            t0 = time.monotonic()
            with pytest.raises(TimeoutError, match="deadline"):
                cli.post_generate(
                    f"http://127.0.0.1:{srv.server_address[1]}",
                    {"tokens": [[1]]}, deadline_s=1.0,
                    rng=random.Random(0))
            # refused to sleep past the deadline instead of sleeping 10s
            assert time.monotonic() - t0 < 5
        finally:
            srv.shutdown()


class TestWatchdogUnit:
    def test_stall_fires_once_and_p95_excludes_stalls(self):
        fired = []
        cfg = RingResilience(stall_factor=0, stall_floor_s=0.1,
                             poll_s=0.01)
        wd = DispatchWatchdog(cfg, fired.append)
        try:
            wd.begin()
            time.sleep(0.3)
            wd.end()
            assert len(fired) == 1
            # the stalled region must NOT poison the p95 -> threshold
            # stays at the floor, not factor*0.3
            assert wd._p95.value() is None
            wd.begin()
            wd.end()
            assert wd._p95.value() is not None
        finally:
            wd.close()

    def test_restart_budget_refills_after_quiet_window(self):
        """The budget caps restart DENSITY: a quiet restart_window_s
        refills it (and resets the backoff ladder), so transient faults
        weeks apart never kill a healthy long-lived pod."""
        from paddle_operator_tpu.infer.resilience import RestartBudget

        now = [0.0]
        cfg = RingResilience(max_restarts=2, restart_window_s=100,
                             backoff_base_s=0.25)
        b = RestartBudget(cfg, clock=lambda: now[0])
        assert b.spend() == 0.25 and b.spend() == 0.5
        assert b.exhausted                       # 2 restarts, no gap
        now[0] += 101                            # quiet window passes
        assert not b.exhausted                   # refilled
        assert b.spend() == 0.25                 # ladder reset too

    def test_hard_stall_escalates(self):
        hard = []
        cfg = RingResilience(stall_factor=0, stall_floor_s=0.05,
                             hard_stall_factor=2.0, poll_s=0.01)
        wd = DispatchWatchdog(cfg, lambda e: None, hard.append)
        try:
            wd.begin()
            time.sleep(0.25)
            wd.end()
            assert len(hard) == 1
        finally:
            wd.close()

    def test_threshold_scales_with_megastep(self):
        """Regression (ISSUE 11 satellite): a LEGAL N-step dispatch is
        ~N x a 1-step one — without the scale-aware threshold, a p95
        learned on 1-step dispatches would flag the first SERVE_MEGASTEP
        dispatch as a stall and trigger a spurious rebuild."""
        cfg = RingResilience(stall_factor=2.0, stall_floor_s=0.001,
                             poll_s=10.0)
        wd = DispatchWatchdog(cfg, lambda e: None)
        try:
            for _ in range(8):          # learned 1-step p95 ~ 0.1s
                wd._p95.add(0.1)
            wd.begin()                  # 1-step region: old behavior
            assert wd.threshold() == pytest.approx(0.2)
            wd.end()
            wd.begin(scale=8)           # 8-step region
            # a legal 8-step dispatch (~0.8s) sits well under the
            # scaled threshold (8 x factor x p95 = 1.6s); the UNscaled
            # threshold (0.2s) would have called it a stall
            assert wd.threshold() == pytest.approx(1.6)
            wd.end()
        finally:
            wd.close()

    def test_scaled_regions_feed_per_iteration_p95(self):
        """An N-step region's duration is normalized to per-iteration
        time before entering the p95 — so the threshold stays correct
        when SERVE_MEGASTEP changes (or drops back to 1) at runtime."""
        cfg = RingResilience(poll_s=10.0)   # floor 60s: nothing stalls
        wd = DispatchWatchdog(cfg, lambda e: None)
        try:
            wd.begin(scale=4)
            wd._start = time.monotonic() - 0.4   # legal 4-step region
            wd.end()
            assert 0.05 < wd._p95.value() < 0.2  # ~0.1 per iteration
        finally:
            wd.close()


class TestServingStatus:
    def test_status_and_gauges_carry_ft_fields(self, setup):
        from paddle_operator_tpu.utils.observability import serving_gauges

        cfg, params = setup
        b = _batcher(cfg, params)
        try:
            st = b.serving_status()
            assert st["draining"] is False and st["healthy"] is True
            for k in ("deadlineExceeded", "watchdogRestarts",
                      "quarantinedLanes"):
                assert st[k] == 0
            g = serving_gauges(st, "ns/job")
            assert g['tpujob_serve_watchdog_restarts{job="ns/job"}'] == 0
            assert g['tpujob_serve_draining{job="ns/job"}'] == 0.0
            st["draining"] = True
            st["deadlineExceeded"] = 3
            g = serving_gauges(st, "ns/job")
            assert g['tpujob_serve_draining{job="ns/job"}'] == 1.0
            assert g['tpujob_serve_deadline_exceeded{job="ns/job"}'] == 3
        finally:
            b.close()
