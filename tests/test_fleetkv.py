"""Fleet-level KV (ISSUE 12): the wire envelope, cross-process chain
key agreement, router migration brokering, host-tier peer
export/import, and lane migration bit-identity.

Fast tier: envelope codec + refusal paths, the chain-key JSON wire
pin, jax-free router broker units with stub adopters, pool
import/export units, and ONE tiny-ring in-process migration parity
test.  The HTTP/tp2/quant matrices ride ``-m slow`` with their
invariants carried every run by the dryrun ``serve-fleetkv`` line.
"""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from paddle_operator_tpu.utils import fleetkv as FK
from paddle_operator_tpu.utils.radixkey import chain_key, prefix_chain_key


def _lane_parts(n_blocks=2, layers=2, heads=1, bs=8, d=4, rid="r/row0"):
    rng = np.random.default_rng(0)
    meta = {"requestId": rid, "prompt": [1, 2, 3], "out": [9],
            "left": 5, "maxNew": 6, "temperature": 0.0, "seed": 1,
            "eos": None, "priority": 1, "adapter": None,
            "fingerprint": {"layers": layers, "kvHeads": heads,
                            "headDim": d, "blockSize": bs,
                            "quant": "none", "specK": 0}}
    spill = {"pos": 4, "tok": 7, "temp": 0.0,
             "key": np.array([3, 4], np.uint32), "n_blocks": n_blocks,
             "k": rng.standard_normal(
                 (layers, n_blocks, heads, bs, d)).astype(np.float32),
             "v": rng.standard_normal(
                 (layers, n_blocks, heads, bs, d)).astype(np.float32)}
    return meta, spill


class TestEnvelope:
    def test_lane_roundtrip_bit_exact(self):
        meta, spill = _lane_parts()
        buf = FK.encode_lane(meta, spill)
        m2, s2 = FK.decode_lane(buf)
        assert m2["prompt"] == meta["prompt"]
        assert m2["requestId"] == meta["requestId"]
        assert s2["pos"] == spill["pos"]
        assert s2["n_blocks"] == spill["n_blocks"]
        assert np.array_equal(s2["key"], spill["key"])
        assert np.array_equal(s2["k"], spill["k"])
        assert np.array_equal(s2["v"], spill["v"])
        assert s2["k"].dtype == spill["k"].dtype

    def test_bfloat16_payload_roundtrips_bit_exact(self):
        """Regression (caught driving the REAL server): a production
        pool holds bfloat16 — an ml_dtypes extension dtype whose numpy
        ``.str`` is an opaque '|V2'.  It must travel by NAME and come
        back as bfloat16 with the exact bytes, never as raw void rows
        that poison the promote upload."""
        import ml_dtypes

        meta, spill = _lane_parts()
        spill["k"] = spill["k"].astype(ml_dtypes.bfloat16)
        spill["v"] = spill["v"].astype(ml_dtypes.bfloat16)
        buf = FK.encode_lane(meta, spill)
        _, s2 = FK.decode_lane(buf)
        assert s2["k"].dtype == ml_dtypes.bfloat16
        assert s2["k"].tobytes() == spill["k"].tobytes()
        # an unresolvable manifest dtype refuses, never decodes void
        with pytest.raises(FK.EnvelopeError, match="dtype"):
            FK._resolve_dtype("|V2")

    def test_truncated_envelope_refuses_cleanly(self):
        """Satellite pin: a cut-short envelope must refuse, never
        partially apply — at any truncation point."""
        meta, spill = _lane_parts()
        buf = FK.encode_lane(meta, spill)
        for cut in (3, 10, len(buf) // 2, len(buf) - 1):
            with pytest.raises(FK.EnvelopeError):
                FK.decode_lane(buf[:cut])

    def test_version_skew_refuses_cleanly(self):
        meta, spill = _lane_parts()
        buf = bytearray(FK.encode_lane(meta, spill))
        buf[4] = FK.VERSION + 1        # the frame's version byte
        with pytest.raises(FK.EnvelopeError, match="version"):
            FK.decode_lane(bytes(buf))

    def test_payload_corruption_refuses(self):
        meta, spill = _lane_parts()
        buf = bytearray(FK.encode_lane(meta, spill))
        buf[-3] ^= 0xFF                # flip a payload byte
        with pytest.raises(FK.EnvelopeError, match="checksum"):
            FK.decode_lane(bytes(buf))

    def test_missing_meta_refuses(self):
        meta, spill = _lane_parts()
        del meta["prompt"]
        buf = FK.encode_lane(meta, spill)
        with pytest.raises(FK.EnvelopeError, match="prompt"):
            FK.decode_lane(buf)

    def test_fingerprint_mismatch_refuses(self):
        meta, _ = _lane_parts()
        mine = dict(meta["fingerprint"], quant="int8")
        with pytest.raises(FK.EnvelopeError, match="fingerprint"):
            FK.check_fingerprint(meta, mine)

    def test_prefix_roundtrip_and_int8_wire_halving(self):
        # arrays big enough that payload dominates the JSON header
        bs, d, layers = 32, 16, 4
        bf16 = {"k": np.ones((layers, 1, 1, bs, d), np.float32),
                "v": np.zeros((layers, 1, 1, bs, d), np.float32)}
        i8 = {"k": np.ones((layers, 1, 1, bs, d), np.int8),
              "v": np.zeros((layers, 1, 1, bs, d), np.int8),
              "ks": np.ones((layers, 1, 1), np.float32),
              "vs": np.ones((layers, 1, 1), np.float32)}
        chunks = [[1] * bs, [2] * bs]
        b16 = FK.encode_prefix({"fingerprint": {}}, chunks, [0, 1],
                               [bf16, bf16])
        b8 = FK.encode_prefix({"fingerprint": {}}, chunks, [0, 1],
                              [i8, i8])
        meta, ch, idx, pl = FK.decode_prefix(b16)
        assert idx == [0, 1] and ch == chunks
        assert np.array_equal(pl[0]["k"], bf16["k"])
        m8, _, _, p8 = FK.decode_prefix(b8)
        assert "ks" in p8[0]
        # the capacity argument on the wire: int8 codes + scale rows
        # are well under 2/3 of the f32 rows (bf16 ships as 2-byte
        # rows in production; this f32 test pool bounds looser)
        assert len(b8) < 0.6 * len(b16)

    def test_lane_envelope_wire_bytes_int8_vs_f32(self):
        """Per-row wire accounting exists for the bench: int8 lanes
        ship codes + tiny scale planes."""
        meta, spill = _lane_parts(n_blocks=4, layers=4, bs=32, d=16)
        f32 = len(FK.encode_lane(meta, spill))
        q = dict(spill)
        q["k"] = np.ones(spill["k"].shape, np.int8)
        q["v"] = np.ones(spill["v"].shape, np.int8)
        q["ks"] = np.ones(spill["k"].shape[:3], np.float32)
        q["vs"] = np.ones(spill["k"].shape[:3], np.float32)
        assert len(FK.encode_lane(meta, q)) < 0.6 * f32


class TestChainKeyWire:
    """Satellite pin (alongside the radixkey ASLR regression in
    test_fleet.py): chain keys must survive the replica -> router ->
    replica JSON hop EXACTLY — as ints, never coerced through float
    (Python hash values exceed 2**53, where float round-trips lose
    low bits)."""

    def test_chain_keys_json_roundtrip_int_stable(self):
        rng = np.random.default_rng(7)
        toks = [int(t) for t in rng.integers(0, 50000, (64,))]
        keys = []
        key = None
        for j in range(8):
            key = chain_key(key, tuple(toks[j * 8:(j + 1) * 8]))
            keys.append(key)
        wire = json.dumps({"keys": keys, "tokens": toks})
        back = json.loads(wire)
        assert back["keys"] == keys
        assert all(isinstance(k, int) for k in back["keys"])
        # float coercion WOULD have lost bits for wide keys — prove
        # the pin bites: at least one key needs > 53 bits
        assert any(abs(k) > (1 << 53) for k in keys), \
            "test keys too narrow to detect float coercion"
        assert any(int(float(k)) != k for k in keys if abs(k) > (1 << 53))

    def test_affinity_key_recomputed_after_wire_hop(self):
        """The router computes the affinity key from JSON-decoded
        tokens; a replica computes it from its own copy — they must
        agree (the whole affinity contract)."""
        toks = list(range(100, 150))
        wire_toks = json.loads(json.dumps(toks))
        assert prefix_chain_key(toks, 8, 2) \
            == prefix_chain_key(wire_toks, 8, 2)


# ---------------------------------------------------------------------------
# Pool export/import units (host tier only, demote hook stubbed)
# ---------------------------------------------------------------------------


def _mgr(**kw):
    from paddle_operator_tpu.infer.paged import PagedCacheManager

    kw.setdefault("slots", 1)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 8)
    kw.setdefault("host_cache_blocks", 8)
    m = PagedCacheManager(**kw)
    m.demote_fetch = lambda blk: {"k": np.full((1,), blk),
                                  "v": np.full((1,), blk)}
    return m


class TestPoolExportImport:
    def test_export_only_host_resident_walk_continues(self):
        m = _mgr()
        P = list(range(100, 124))       # 3 full blocks
        m.admit(0, P)
        m.publish(0, P)
        m.retire(0)
        # demote the whole chain via pressure
        m.admit(0, list(range(900, 964)))   # needs all 8 blocks
        m.retire(0)
        assert m.stats["host_demotions"] >= 3
        chunks, idx, payloads = m.export_host_chain(P)
        assert len(chunks) == 3
        assert idx and all(0 <= j < 3 for j in idx)
        assert len(payloads) == len(idx)
        m.check_invariant()

    def test_import_then_admit_host_hits(self):
        src = _mgr()
        P = list(range(100, 124))
        src.admit(0, P)
        src.publish(0, P)
        src.retire(0)
        src.admit(0, list(range(900, 964)))
        src.retire(0)
        chunks, idx, payloads = src.export_host_chain(P)
        assert len(idx) == 3
        dst = _mgr()
        n = dst.import_host_blocks(chunks, idx, payloads)
        assert n == 3
        assert dst.stats["peer_blocks_imported"] == 3
        dst.check_invariant()           # demoted == tier keys holds
        hit_len, _ = dst.admit(0, P)
        assert hit_len == len(P) - 1    # full hit (last pos re-sampled)
        assert len(dst.take_promotions()) == 3
        assert dst.stats["host_promotions"] == 3
        dst.check_invariant()

    def test_import_skips_existing_and_malformed(self):
        dst = _mgr()
        P = list(range(100, 116))
        dst.admit(0, P)
        dst.publish(0, P)
        chunks = [P[:8], P[8:16]]
        pay = [{"k": np.zeros(1), "v": np.zeros(1)}] * 2
        assert dst.import_host_blocks(chunks, [0, 1], pay) == 0
        dst.retire(0)
        dst.check_invariant()
        # ragged (non-block) chunks refuse wholesale
        assert dst.import_host_blocks([[1, 2]], [0],
                                      [pay[0]]) == 0

    def test_import_skips_unreachable_parent_gap(self):
        """A block whose parent chain entry exists NEITHER locally nor
        in the import is unreachable by _lookup — importing it would
        spend tier space on bytes no admission can hit."""
        dst = _mgr()
        P = list(range(100, 124))           # 3 full blocks
        chunks = [P[:8], P[8:16], P[16:24]]
        pay = {"k": np.zeros(1), "v": np.zeros(1)}
        # block 2 alone, with blocks 0-1 absent everywhere: skipped
        assert dst.import_host_blocks(chunks, [2], [pay]) == 0
        dst.check_invariant()
        # blocks 1+2 with block 0 absent: both skipped (1's parent is
        # missing, and without 1 block 2's parent is missing too)
        assert dst.import_host_blocks(chunks, [1, 2],
                                      [pay, dict(pay)]) == 0
        # contiguous from the root: all land and chain through
        assert dst.import_host_blocks(
            chunks, [0, 1, 2], [dict(pay), dict(pay), dict(pay)]) == 3
        dst.check_invariant()
        hit_len, _ = dst.admit(0, P)
        assert hit_len == len(P) - 1        # reachable: full hit
        dst.take_promotions()
        dst.retire(0)

    def test_host_evictions_counter_visible(self):
        """Satellite pin: dropped-oldest tier overflows were invisible
        — now they count."""
        m = _mgr(host_cache_blocks=2)
        assert m.host_evictions() == 0
        m.admit(0, list(range(100, 124)))
        m.publish(0, list(range(100, 124)))
        m.retire(0)
        m.admit(0, list(range(900, 964)))   # demotes 3 into a 2-tier
        m.retire(0)
        assert m.host_evictions() >= 1
        assert m.host_evictions() == m.host.stats["overflow_drops"]


# ---------------------------------------------------------------------------
# Router brokering (jax-free, stub adopters)
# ---------------------------------------------------------------------------


class _StubAdopter(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    accept = True
    ready = True
    parked = 0

    def log_message(self, *a):
        pass

    def _send(self, code, obj):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        cls = type(self)
        if self.path == "/readyz":
            self._send(200 if cls.ready else 503, {})
        elif self.path == "/metrics":
            body = (
                'tpujob_serve_queue_depth{job="j"} 0.0\n'
                'tpujob_serve_kv_blocks_free{job="j"} 10.0\n'
                f'tpujob_serve_parked_lanes{{job="j"}} {cls.parked}\n'
                'tpujob_serve_host_cache_blocks{job="j"} 5.0\n'
            ).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._send(404, {})

    def do_POST(self):
        cls = type(self)
        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n) if n else b""
        if self.path == "/v1/kv/restore":
            cls.restores.append(body)
            if cls.accept:
                self._send(200, {"adopted": "x"})
            else:
                self._send(409, {"error": "fingerprint mismatch"})
        else:
            self._send(404, {})


def _adopter(accept=True, parked=0):
    h = type("Adopter", (_StubAdopter,),
             {"accept": accept, "parked": parked, "restores": [],
              "ready": True})
    srv = ThreadingHTTPServer(("127.0.0.1", 0), h)
    threading.Thread(
        target=lambda: srv.serve_forever(poll_interval=0.02),
        daemon=True).start()
    return srv, h


def _wait(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise TimeoutError


class TestRouterBroker:
    @pytest.fixture()
    def fleet(self):
        from paddle_operator_tpu.router.router import FleetRouter

        servers = [_adopter(parked=2), _adopter(parked=0)]
        eps = [f"127.0.0.1:{s.server_address[1]}" for s, _ in servers]
        router = FleetRouter(eps, block_size=8, scrape_interval=0.05)
        router.start()
        _wait(lambda: sum(st.ready
                          for st in router.replicas.values()) == 2)
        _wait(lambda: all("parkedLanes" in st.gauges
                          for st in router.replicas.values()))
        yield router, eps, servers
        router.close()
        for s, _ in servers:
            s.shutdown()
            s.server_close()

    def test_scrape_surfaces_parked_and_host_gauges(self, fleet):
        """Satellite pin: /statusz shows per-replica parked_lanes and
        host_cache_blocks from the existing scrape loop."""
        router, eps, _ = fleet
        status = router.statusz()
        assert status["replicas"][eps[0]]["parkedLanes"] == 2.0
        assert status["replicas"][eps[0]]["hostCacheBlocks"] == 5.0
        assert status["replicas"][eps[1]]["parkedLanes"] == 0.0

    def test_parse_serve_gauges_picks_up_new_keys(self):
        from paddle_operator_tpu.router.router import parse_serve_gauges

        parsed = parse_serve_gauges(
            'tpujob_serve_parked_lanes{job="j"} 3.0\n'
            'tpujob_serve_host_cache_blocks{job="j"} 7.0\n')
        assert parsed == {"parkedLanes": 3.0, "hostCacheBlocks": 7.0}

    def test_broker_prefers_fewest_parked_and_excludes_origin(self,
                                                              fleet):
        router, eps, servers = fleet
        # least-parked first; origin excluded entirely
        assert router.migration_candidates("")[0] == eps[1]
        assert router.migration_candidates(eps[1]) == [eps[0]]
        meta, spill = _lane_parts(rid="cid/row0")
        buf = FK.encode_lane(meta, spill)
        code, resp = router.broker_migration(buf, "cid/row0", eps[0])
        assert code == 200 and resp["target"] == eps[1]
        assert len(servers[1][1].restores) == 1
        # the adopter got the EXACT envelope bytes
        assert servers[1][1].restores[0] == buf
        # retrieval routing: row id AND client-level id both resolve
        assert router.migrate_target("cid/row0") == eps[1]
        assert router.migrate_target("cid") == eps[1]

    def test_replayed_migration_dedupes(self, fleet):
        router, eps, servers = fleet
        meta, spill = _lane_parts(rid="rep/row0")
        buf = FK.encode_lane(meta, spill)
        code, first = router.broker_migration(buf, "rep/row0", eps[0])
        assert code == 200
        code2, again = router.broker_migration(buf, "rep/row0", eps[0])
        assert code2 == 200 and again.get("deduped")
        assert again["target"] == first["target"]
        # the replay was answered from the table, never re-forwarded
        assert len(servers[1][1].restores) == 1
        assert router.counters["migration_replays"] == 1

    def test_refusing_adopter_falls_through_then_503(self, fleet):
        router, eps, servers = fleet
        for _, h in servers:
            h.accept = False
        meta, spill = _lane_parts(rid="no/row0")
        buf = FK.encode_lane(meta, spill)
        code, resp = router.broker_migration(buf, "no/row0", "")
        assert code == 503
        # both candidates were tried, neither recorded
        assert len(servers[0][1].restores) == 1
        assert len(servers[1][1].restores) == 1
        assert router.migrate_target("no/row0") is None

    def test_base_request_id_strips_row_suffix_only(self):
        from paddle_operator_tpu.router.router import FleetRouter

        f = FleetRouter._base_request_id
        assert f("cid/row0") == "cid"
        assert f("cid/row12") == "cid"
        assert f("cid") == "cid"
        assert f("cid/rowX") == "cid/rowX"
        assert f("a/rowing") == "a/rowing"

    def test_multi_row_base_mapping_first_adopter_wins(self):
        """Rows of one request adopted by DIFFERENT replicas: each row
        id routes to its own adopter, and the client-level id keeps
        the FIRST adopter (a later row must not overwrite it and
        orphan the earlier adopter's lane)."""
        from paddle_operator_tpu.router.router import FleetRouter

        r = FleetRouter()
        r.record_migration("c/row0", "hostB:1")
        r.record_migration("c/row1", "hostC:1")
        assert r.migrate_target("c/row0") == "hostB:1"
        assert r.migrate_target("c/row1") == "hostC:1"
        assert r.migrate_target("c") == "hostB:1"
        r.close()


# ---------------------------------------------------------------------------
# Lane migration parity (tiny real rings, in-process wire hop)
# ---------------------------------------------------------------------------


MAX_LEN = 64
BS = 8


@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models.llama import make_model

    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _ring(cfg, params, **kw):
    from paddle_operator_tpu.infer.scheduler import ContinuousBatcher

    kw.setdefault("slots", 1)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    kw.setdefault("num_blocks", 16)
    return ContinuousBatcher(params, cfg, **kw)


def _throttle(b, delay=0.02):
    """test_qos's pause-free throttle: slow each resident dispatch so
    a drain deterministically lands mid-generation."""
    real = b._step

    def slow(*a, **k):
        time.sleep(delay)
        return real(*a, **k)

    b._step = slow


def _ref(params, cfg, prompt, new):
    import jax.numpy as jnp

    from paddle_operator_tpu.infer import decode as D

    return np.asarray(D.generate(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=new, max_len=MAX_LEN)[0]).tolist()


class TestLaneMigration:
    def test_drain_by_migration_bit_identical(self, setup):
        """The tentpole pin, fast leg (bf16 tp=1): a lane migrated
        mid-generation through the WIRE CODEC resumes on the adopter
        bit-identically to the uninterrupted oracle; the origin's
        client gets the retriable LaneMigrated signal; both pools keep
        their invariants.  tp=2 x quant legs ride the dryrun
        serve-fleetkv gate + ``-m slow``."""
        from paddle_operator_tpu.infer.resilience import LaneMigrated

        cfg, params = setup
        A = _ring(cfg, params)
        B = _ring(cfg, params)
        adopted = {}

        def migrate_out(meta, spill):
            m2, s2 = FK.decode_lane(FK.encode_lane(meta, spill))
            adopted[m2["requestId"]] = B.adopt(m2, s2)
            return True

        A.migrate_out = migrate_out
        A._migrate_on_drain = True
        try:
            prompt = list(range(1, 13))
            new = 24
            oracle = _ref(params, cfg, prompt, new)
            _throttle(A)
            h = A.submit(prompt, max_new_tokens=new, seed=0,
                         request_id="mig/row0")
            # deterministic mid-generation point: wait for the first
            # consumed chunk, then drain (the throttle guarantees
            # completion is still far away)
            deadline = time.monotonic() + 30
            while A.stats["chunks"] < 2:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            A.drain(budget_s=30)
            with pytest.raises(LaneMigrated):
                h.result(timeout=5)
            assert A.stats["lane_migrations"] == 1
            got = adopted["mig/row0"].result(timeout=120)
            assert got == oracle, "migrated stream diverged"
            assert B.stats["adopted_lanes"] == 1
            assert B.stats["restored_lanes"] == 1
            B.pool.check_invariant()
        finally:
            B.close()
            if A._thread.is_alive():
                A.close()

    def test_adopt_refuses_mismatches_loudly(self, setup):
        """Satellite pin: truncated and skewed envelopes refuse
        CLEANLY — no lane state is touched."""
        cfg, params = setup
        B = _ring(cfg, params)
        try:
            meta, spill = _lane_parts(rid="bad/row0")
            # geometry fingerprint from another ring entirely
            with pytest.raises(FK.EnvelopeError, match="fingerprint"):
                B.adopt(meta, spill)
            # right fingerprint, wrong payload shape
            meta2 = dict(meta, fingerprint=B._fingerprint())
            with pytest.raises(FK.EnvelopeError, match="shape"):
                B.adopt(meta2, spill)
            # no remaining budget
            m3, s3 = _lane_parts(rid="done/row0")
            m3["fingerprint"] = B._fingerprint()
            m3["left"] = 0
            with pytest.raises(FK.EnvelopeError, match="budget"):
                B.adopt(m3, s3)
            assert B.stats["adopted_lanes"] == 0
            assert all(r is None for r in B.lane)
            # a VALID envelope's remaining deadline re-anchors on the
            # adopter (regression: migrated lanes must keep the PR 10
            # 504-partial-at-deadline contract)
            m4, s4 = _lane_parts(n_blocks=1, layers=cfg.n_layers,
                                 heads=cfg.n_kv_heads, bs=BS,
                                 d=cfg.head_dim, rid="dl/row0")
            m4["fingerprint"] = B._fingerprint()
            m4["left"] = 1
            m4["deadlineS"] = 5.0
            t0 = time.monotonic()
            req = B.adopt(m4, s4)
            assert req.deadline is not None
            assert 0 < req.deadline - t0 <= 5.5
            req.cancel()        # resolve the junk lane, never decode
            B.pool.check_invariant()
        finally:
            B.close()

    @pytest.mark.slow
    @pytest.mark.parametrize("leg", ["int8", "adapter", "spec"])
    def test_migration_parity_matrix(self, setup, leg):
        """Slow matrix (dryrun serve-fleetkv carries the tp2/quant
        invariant every run): migrated lanes resume bit-identically
        for int8 pools, adapter lanes (re-resolved by NAME on the
        adopter), and speculative lanes (draft ring travels)."""
        import jax
        import jax.numpy as jnp

        cfg, params = setup
        kw = {}
        oracle_new = 16
        submit_kw = {}
        if leg == "int8":
            kw["kv_quant"] = "int8"
        elif leg == "adapter":
            from paddle_operator_tpu.infer.qos import AdapterRegistry

            def reg():
                r = AdapterRegistry(cfg, capacity=2, rank=4)
                r.load("t1", seed=5)
                return r

            submit_kw["adapter"] = "t1"
        elif leg == "spec":
            from paddle_operator_tpu.models.llama import Llama

            dcfg = cfg.draft()
            dparams = Llama(dcfg).init(
                jax.random.PRNGKey(1),
                jnp.zeros((1, 8), jnp.int32))["params"]
            kw.update(draft_params=dparams, draft_cfg=dcfg, spec_k=2)
        rings = []
        try:
            A = _ring(cfg, params,
                      **dict(kw, adapters=reg())
                      if leg == "adapter" else kw)
            B = _ring(cfg, params,
                      **dict(kw, adapters=reg())
                      if leg == "adapter" else kw)
            rings = [A, B]
            prompt = list(range(1, 13))
            # oracle: the SAME request run uninterrupted on the
            # adopter ring BEFORE the migration (restore maps fresh
            # private blocks, so the warm radix cannot influence it)
            oracle = B.submit(prompt, max_new_tokens=oracle_new,
                              seed=0, **submit_kw).result(timeout=300)
            adopted = {}

            def migrate_out(meta, spill):
                m2, s2 = FK.decode_lane(FK.encode_lane(meta, spill))
                adopted[m2["requestId"]] = B.adopt(m2, s2)
                return True

            A.migrate_out = migrate_out
            A._migrate_on_drain = True
            if leg == "spec":
                real = A._spec_step

                def slow(*a, **k):
                    time.sleep(0.02)
                    return real(*a, **k)

                A._spec_step = slow
            else:
                _throttle(A)
            h = A.submit(prompt, max_new_tokens=oracle_new, seed=0,
                         request_id=f"{leg}/row0", **submit_kw)
            deadline = time.monotonic() + 60
            while A.stats["chunks"] < 2:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            A.drain(budget_s=60)
            assert A.stats["lane_migrations"] == 1, h.error
            got = adopted[f"{leg}/row0"].result(timeout=300)
            assert got == oracle, f"{leg}: migrated stream diverged"
            B.pool.check_invariant()
        finally:
            for r in rings:
                if r._thread.is_alive():
                    r.close()

    @pytest.mark.slow
    def test_http_fleet_drain_migration_e2e(self, setup):
        """The whole wire: a request through the REAL router to a
        REAL replica, the replica drained mid-generation, the lane
        brokered to the peer, the client's production retry
        discipline collecting the bit-identical result."""
        from paddle_operator_tpu.router.simfleet import SimFleet

        fleet = SimFleet(2, fleet_kv=True, slots=2, num_blocks=16,
                         ring_extra={"host_cache_blocks": 16})
        try:
            prompt = list(range(1, 13))
            base = {"tokens": [prompt], "max_new_tokens": 24,
                    "seed": 3}
            st, oracle = fleet.post(dict(base, request_id="orc-1"))
            assert st == 200
            result = {}

            def client():
                st2, body = fleet.post(dict(base, request_id="mig-1"),
                                       max_retries=20)
                result["st"], result["body"] = st2, body

            t = threading.Thread(target=client)
            t.start()
            _wait(lambda: any(
                r.batcher is not None
                and any(x is not None for x in r.batcher.lane)
                for r in fleet.replicas), timeout=30)
            idx = next(i for i, r in enumerate(fleet.replicas)
                       if any(x is not None for x in r.batcher.lane))
            fleet.drain_replica(idx)
            t.join(timeout=120)
            assert result.get("st") == 200, result
            assert result["body"]["tokens"] == oracle["tokens"]
            assert fleet.router.counters["migrations_brokered"] >= 1
            assert fleet.router.counters["routed_migrated"] >= 1
            assert fleet.replicas[1 - idx].batcher.stats[
                "adopted_lanes"] >= 1
            fleet.check_invariants()
        finally:
            fleet.close()

    @pytest.mark.slow
    def test_peer_prefix_fetch_identical_to_cold(self, setup):
        """Peer fetch ring leg (slow — the dryrun serve-fleetkv line
        carries this invariant every run; the fast tier keeps the
        jax-free export/import units): a prompt warm (demoted) on A
        and cold on B admits on B through the host-hit path with the
        SAME stream as a cold admit, and the counters move."""
        cfg, params = setup
        A = _ring(cfg, params, num_blocks=8, host_cache_blocks=16)
        B = _ring(cfg, params, num_blocks=8, host_cache_blocks=16)
        try:
            rng = np.random.default_rng(1)
            P = [int(t) for t in rng.integers(1, cfg.vocab_size,
                                              (24,))]
            new = 6
            cold = A.submit(P, max_new_tokens=new).result(timeout=300)
            assert cold == _ref(params, cfg, P, new)
            # pressure demotes P's chain on A
            Q = [int(t) for t in rng.integers(1, cfg.vocab_size,
                                              (56,))]
            A.submit(Q, max_new_tokens=4).result(timeout=300)
            assert A.pool.stats["host_demotions"] >= 3

            def peer_fetch(tokens, ns):
                chunks, idx, payloads = A.pool.export_host_chain(
                    tokens, ns=0)
                if not idx:
                    return None
                payloads = [{k: np.asarray(v) for k, v in p.items()}
                            for p in payloads]
                return FK.encode_prefix(
                    {"fingerprint": B._fingerprint()}, chunks, idx,
                    payloads)

            B.peer_fetch = peer_fetch
            got = B.submit(P, max_new_tokens=new,
                           request_id="pf/row0").result(timeout=300)
            assert got == cold, "peer-fetched stream diverged"
            assert B.stats["peer_prefix_fetches"] == 1
            assert B.pool.stats["peer_blocks_imported"] >= 3
            assert B.pool.stats["host_promotions"] >= 3
            A.pool.check_invariant()
            B.pool.check_invariant()
        finally:
            A.close()
            B.close()
