"""Prefill-pool throughput (ISSUE 14): the streamed-handoff frame
codec, the N-lane batched chunk-interleaved engine's head-of-line
bound and parity, the mid-stream chaos discipline, the autoscaler's
occupancy-aware denominator, and the CRD/fold plumbing.  Fast legs are
jax-free or tiny-model tp=1 bf16; the heavyweight matrix (int8, spec,
tp=2, remote) rides ``-m slow`` with its invariants pinned EVERY run
by the dryrun ``serve-prefillpool`` line."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from paddle_operator_tpu.utils import fleetkv as FK


def _mk_frames(fp=None, n_frames=2, blocks_per=1, quant=False,
               bs=4, n_blocks_total=4):
    """A valid streamed handoff: ``n_frames`` intermediate frames of
    ``blocks_per`` blocks each + the terminal frame carrying the
    rest."""
    L, H, D = 2, 2, 8
    rng = np.random.default_rng(3)

    def blk_arrays(n):
        a = {"k": rng.standard_normal((L, n, H, bs, D)).astype(
                np.float32),
             "v": rng.standard_normal((L, n, H, bs, D)).astype(
                np.float32)}
        if quant:
            a["k"] = (a["k"] * 10).astype(np.int8)
            a["v"] = (a["v"] * 10).astype(np.int8)
            a["ks"] = np.ones((L, n, H), np.float32)
            a["vs"] = np.ones((L, n, H), np.float32)
        return a

    wires = []
    j0 = 0
    for seq in range(n_frames):
        wires.append(FK.encode_handoff_frame(seq, j0,
                                             blk_arrays(blocks_per)))
        j0 += blocks_per
    final_arrays = blk_arrays(n_blocks_total - j0)
    if quant:
        final_arrays["kt"] = np.zeros((L, 1, H, bs, D), np.float32)
        final_arrays["vt"] = np.zeros((L, 1, H, bs, D), np.float32)
    wires.append(FK.encode_handoff_final(
        {"seq": n_frames, "nFrames": n_frames + 1, "j0": j0,
         "first": 11, "promptLen": 13, "nBlocks": n_blocks_total,
         "fingerprint": fp or {"layers": L, "blockSize": bs},
         "tDone": 123.0}, final_arrays))
    return wires


class TestFrameCodec:
    def test_roundtrip_through_wire_reader(self):
        wires = _mk_frames(quant=True)
        stream = b"".join(wires)
        pos = [0]

        def read(n):
            b = stream[pos[0]:pos[0] + n]
            pos[0] += len(b)
            return b

        for seq in range(len(wires)):
            buf = FK.read_wire_frame(read)
            kind, meta, arrays = FK.decode_handoff_frame(buf, seq)
            if seq < len(wires) - 1:
                assert kind == FK.FRAME_KIND
                assert arrays["k"].dtype == np.int8
            else:
                assert kind == FK.FINAL_KIND
                assert meta["first"] == 11 and meta["nBlocks"] == 4
                assert "kt" in arrays
        assert FK.read_wire_frame(read) is None     # clean EOF

    def test_out_of_order_refused(self):
        wires = _mk_frames()
        buf = wires[1][4:]      # strip the length prefix
        with pytest.raises(FK.EnvelopeError, match="out of order"):
            FK.decode_handoff_frame(buf, 0)

    def test_mid_frame_death_refused(self):
        """A stream cut mid-frame (pod SIGKILL) raises instead of
        yielding a short frame — the wholesale-refusal entry point."""
        wires = _mk_frames()
        stream = b"".join(wires)[:len(wires[0]) + 7]
        pos = [0]

        def read(n):
            b = stream[pos[0]:pos[0] + n]
            pos[0] += len(b)
            return b

        assert FK.read_wire_frame(read) is not None     # frame 0 OK
        with pytest.raises(FK.EnvelopeError, match="mid-frame"):
            FK.read_wire_frame(read)

    def test_corrupt_frame_payload_refused(self):
        wires = _mk_frames()
        env = bytearray(wires[0][4:])
        env[-3] ^= 0xFF                     # flip a payload byte
        with pytest.raises(FK.EnvelopeError, match="checksum"):
            FK.decode_handoff_frame(bytes(env), 0)

    def test_terminal_meta_refusals(self):
        with pytest.raises(FK.EnvelopeError, match="nFrames"):
            FK.decode_handoff_frame(FK.encode_envelope(
                FK.FINAL_KIND,
                {"seq": 0, "j0": 0, "first": 1, "promptLen": 2,
                 "nBlocks": 1}, {}), 0)
        # frame count disagreeing with its own seq
        with pytest.raises(FK.EnvelopeError, match="disagrees"):
            FK.decode_handoff_frame(FK.encode_envelope(
                FK.FINAL_KIND,
                {"seq": 2, "nFrames": 2, "j0": 0, "first": 1,
                 "promptLen": 2, "nBlocks": 1}, {}), 2)


# ---------------------------------------------------------------------------
# Mid-stream chaos: pod death + corrupt frame, through the real client
# ---------------------------------------------------------------------------


class _StreamStub(BaseHTTPRequestHandler):
    """A canned STREAMING prefill pod: 'ok' plays a full valid stream,
    'die_mid' sends one frame then kills the connection mid-frame
    (the SIGKILL signature), 'corrupt' flips a byte in frame 1."""

    mode = "ok"
    hits = None
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        body = json.loads(self.rfile.read(n) if n else b"{}")
        self.hits.append(body)
        wires = _mk_frames(fp=body.get("fingerprint"))
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def emit(raw):
            self.wfile.write(f"{len(raw):x}\r\n".encode() + raw
                             + b"\r\n")
            self.wfile.flush()

        if self.mode == "die_mid":
            emit(wires[0])
            emit(wires[1][:9])          # half a frame, then die
            self.connection.close()
            return
        if self.mode == "corrupt":
            bad = bytearray(wires[1])
            bad[-3] ^= 0xFF
            wires[1] = bytes(bad)
        for w in wires:
            emit(w)
        self.wfile.write(b"0\r\n\r\n")


def _stream_stub(mode):
    hits = []
    handler = type("H", (_StreamStub,), {"mode": mode, "hits": hits})
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


class TestStreamChaos:
    def test_mid_stream_death_retries_exactly_once(self):
        """A pod dying mid-frame: the partial stream is discarded
        WHOLESALE, the retry lands the full stream on a healthy pod,
        and exactly one terminal item posts (frames from the dead
        attempt are idempotently overwritten by the retry's)."""
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
        )

        d_srv, d_ep, d_hits = _stream_stub("die_mid")
        o_srv, o_ep, o_hits = _stream_stub("ok")
        client = RemotePrefillClient(peers=[d_ep, o_ep],
                                     backoff_s=0.01, stream=True)
        client.fingerprint = {"layers": 2, "blockSize": 4}
        try:
            client.submit(_Req(), 0)
            items, finals = [], []
            deadline = time.monotonic() + 20
            while not finals and time.monotonic() < deadline:
                try:
                    it = client.results.get(timeout=0.2)
                except Exception:
                    continue
                items.append(it)
                if it[0] == "final":
                    finals.append(it)
            assert len(finals) == 1
            _, req, slot, arrays, lane, j0, n_blocks, first, _ = \
                finals[0]
            assert (slot, n_blocks, first) == (0, 4, 11)
            assert client.stats["refused_streams"] == 1
            assert len(d_hits) == 1 and len(o_hits) == 1
            # no second final ever arrives
            time.sleep(0.3)
            assert all(i[0] != "final"
                       for i in _drain_all(client.results))
        finally:
            client.close()
            for s in (d_srv, o_srv):
                s.shutdown()
                s.server_close()

    def test_corrupt_frame_refused_wholesale_then_retriable(self):
        """A CRC-bad mid-stream frame refuses the WHOLE stream; with
        no healthy candidate the request fails RETRIABLY (503 — the
        fleet-level client retry re-routes it) rather than activating
        a lane on corrupt bytes."""
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
        )
        from paddle_operator_tpu.infer.resilience import RetriableError

        c_srv, c_ep, c_hits = _stream_stub("corrupt")
        client = RemotePrefillClient(peers=[c_ep], max_attempts=2,
                                     backoff_s=0.01, stream=True)
        client.fingerprint = {"layers": 2, "blockSize": 4}
        try:
            client.submit(_Req(), 1)
            err = None
            deadline = time.monotonic() + 20
            while err is None and time.monotonic() < deadline:
                try:
                    it = client.results.get(timeout=0.2)
                except Exception:
                    continue
                if it[0] == "frame":
                    continue        # pre-corruption frames: harmless
                assert len(it) == 3
                err = it[2]
            assert isinstance(err, RetriableError)
            assert client.stats["refused_streams"] == 2
            assert len(c_hits) == 2
        finally:
            client.close()
            c_srv.shutdown()
            c_srv.server_close()


def _drain_all(q):
    out = []
    while True:
        try:
            out.append(q.get_nowait())
        except Exception:
            return out


# ---------------------------------------------------------------------------
# Autoscaler occupancy denominator + CRD/fold plumbing (jax-free)
# ---------------------------------------------------------------------------


class TestOccupancyDenominator:
    def test_lanes_scale_the_allowed_depth(self):
        from paddle_operator_tpu.controller.autoscaler import (
            prefill_load_ratio,
        )

        r1 = prefill_load_ratio(8, 1, 100.0, 1000.0, lanes=1)
        r4 = prefill_load_ratio(8, 1, 100.0, 1000.0, lanes=4)
        assert r4 == pytest.approx(r1 / 4)

    def test_half_empty_batch_never_reads_saturated(self):
        """The satellite's exact clause: depth counts RUNNING jobs, so
        2 jobs on a 4-lane pod (occupancy 0.5) must read ~0 load, not
        'queue of 2'."""
        from paddle_operator_tpu.controller.autoscaler import (
            prefill_load_ratio,
        )

        loaded = prefill_load_ratio(2, 1, 100.0, 1000.0, lanes=4)
        eased = prefill_load_ratio(2, 1, 100.0, 1000.0, lanes=4,
                                   batch_occupancy=0.5)
        assert eased == 0.0 < loaded
        # a SATURATED batch (occupancy 1.0) keeps the full reading
        assert prefill_load_ratio(
            2, 1, 100.0, 1000.0, lanes=4,
            batch_occupancy=1.0) == loaded

    def test_observe_threads_occupancy_and_lanes(self):
        from paddle_operator_tpu.api.types import AutoscaleSpec
        from paddle_operator_tpu.controller.autoscaler import (
            FleetAutoscaler,
        )

        auto = FleetAutoscaler(AutoscaleSpec(
            ttft_target_ms=1000.0, tok_s_per_replica=100.0,
            max_replicas=4, prefill_max=4))
        # depth 3 on one 4-lane pod at occupancy 0.75 = all in-flight,
        # one lane still free: no up-scale pressure
        st = auto.observe(
            None, {"prefillQueueDepth": 3, "prefillMsAvg": 400.0,
                   "prefillLanes": 4, "prefillBatchOccupancy": 0.75,
                   "tokensPerSec": 0.0},
            decode_spec=1, prefill_spec=1, decode_ready=1,
            prefill_ready=1, decode_draining=False,
            prefill_draining=False, now=1000.0)
        assert st["prefillLoadRatio"] <= 1.0
        assert st["prefillReason"] != "up"
        # the same depth WITHOUT occupancy (a 1-lane pool) overloads
        st1 = auto.observe(
            None, {"prefillQueueDepth": 3, "prefillMsAvg": 400.0,
                   "tokensPerSec": 0.0},
            decode_spec=1, prefill_spec=1, decode_ready=1,
            prefill_ready=1, decode_draining=False,
            prefill_draining=False, now=2000.0)
        assert st1["prefillLoadRatio"] > 1.0


class TestPoolSpecPlumbing:
    def test_crd_roundtrip_lanes_stream_prefix(self):
        from paddle_operator_tpu.api.types import PrefillPoolSpec

        pp = PrefillPoolSpec.from_dict(
            {"replicas": 2, "lanes": 4, "stream": True,
             "prefixBlocks": 128})
        assert (pp.lanes, pp.stream, pp.prefix_blocks) == (4, True,
                                                           128)
        assert PrefillPoolSpec.from_dict(pp.to_dict()) == pp
        # defaults stay invisible (no spurious CRD churn)
        assert PrefillPoolSpec(replicas=1).to_dict() == {"replicas": 1}

    def test_fold_weights_occupancy_by_jobs(self):
        from paddle_operator_tpu.router.router import (
            aggregate_fleet_serving,
        )

        agg = aggregate_fleet_serving({
            "pf0": {"role": "prefill", "prefillLanes": 4,
                    "prefillBatchOccupancy": 1.0, "prefillJobs": 90,
                    "prefillHolWaitMs": 12.0},
            "pf1": {"role": "prefill", "prefillLanes": 4,
                    "prefillBatchOccupancy": 0.0, "prefillJobs": 10,
                    "prefillHolWaitMs": 40.0},
        })
        assert agg["prefillLanes"] == 4
        assert agg["prefillBatchOccupancy"] == pytest.approx(0.9)
        assert agg["prefillHolWaitMs"] == 40.0      # fleet max


# ---------------------------------------------------------------------------
# The N-lane engine: deterministic head-of-line bound + parity (tiny)
# ---------------------------------------------------------------------------


def _tiny():
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models.llama import make_model

    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return params, cfg


def _engine(params, cfg, lanes, **kw):
    from paddle_operator_tpu.infer.executor import PrefillExecutor

    return PrefillExecutor(params, cfg, max_len=96, block_size=16,
                           buckets=(96,), lanes=lanes,
                           prefill_chunk=16, **kw)


def _job(prompt):
    from paddle_operator_tpu.infer.prefill_serve import _Job

    return _Job(prompt, 0.0, 0)


def _collect_finals(pe, n, timeout=120.0):
    """(req, iteration-count-at-post) in posting order."""
    out = []
    deadline = time.monotonic() + timeout
    while len(out) < n and time.monotonic() < deadline:
        try:
            item = pe.results.get(timeout=0.2)
        except Exception:
            continue
        if isinstance(item[0], str):
            if item[0] == "final":
                out.append(item[1])
        elif len(item) == 3:
            raise item[2]
        else:
            out.append(item[0])
    assert len(out) == n, f"only {len(out)}/{n} prefills finished"
    return out


class TestHeadOfLine:
    """The ISSUE 14 HOL satellite, deterministic via the pause-gate
    pattern (PR 10): freeze the engine, stage a saturating set of
    long jobs plus one short prompt, release — at lanes=4 the short
    prompt's prefill completes FIRST (one chunk-slice quantum + its
    own work: it takes a free lane and finishes in its first
    iteration while the longs still have slices left); at lanes=1 the
    FIFO engine pins it behind every long job (the control the ≥3x
    acceptance bar is measured against)."""

    def test_short_prompt_first_at_lanes4_last_at_lanes1(self):
        params, cfg = _tiny()
        rng = np.random.default_rng(0)
        longs = [[int(x) for x in rng.integers(1, cfg.vocab_size, 80)]
                 for _ in range(3)]
        short = [int(x) for x in rng.integers(1, cfg.vocab_size, 8)]

        for lanes, want_first in ((4, True), (1, False)):
            pe = _engine(params, cfg, lanes)
            gate = threading.Event()
            pe.pause_gate = lambda g=gate: g.wait(timeout=60)
            try:
                jobs = [_job(p) for p in longs]
                sj = _job(short)
                for i, j in enumerate(jobs):
                    pe.submit(j, i)
                pe.submit(sj, 3)
                gate.set()
                order = _collect_finals(pe, 4)
                if want_first:
                    # short completes in its FIRST engine iteration,
                    # strictly ahead of every 5-slice long job
                    assert order[0] is sj, "short prompt was blocked"
                else:
                    assert order[-1] is sj, \
                        "1-lane FIFO control unexpectedly reordered"
            finally:
                pe.close()


class TestEnginePearity:
    # ~8s; the lanes=4 chunk-interleave bit-parity invariant is pinned
    # by the dryrun serve-prefillpool gate, so this twin rides -m slow
    @pytest.mark.slow
    def test_lanes4_stream_interleave_bit_identical(self):
        """The tier-1 parity leg: lanes=4 × chunk-interleave ×
        streamed handoff, greedy-bit-identical to ``decode.generate``
        (the matrix — int8, spec, tp=2, remote — rides ``-m slow``
        and the serve-prefillpool dryrun line)."""
        import jax
        import jax.numpy as jnp

        from paddle_operator_tpu.infer import decode as ID
        from paddle_operator_tpu.infer.scheduler import ContinuousBatcher

        params, cfg = _tiny()
        new = 6
        prompts = [np.asarray(jax.random.randint(
            jax.random.PRNGKey(50 + i), (n,), 0, cfg.vocab_size,
            dtype=jnp.int32)) for i, n in enumerate((57, 9, 40))]
        refs = [np.asarray(ID.generate(
            params, cfg, jnp.asarray([p], jnp.int32),
            max_new_tokens=new, max_len=96)[0]).tolist()
            for p in prompts]
        r = ContinuousBatcher(
            params, cfg, slots=3, max_len=96, chunk_tokens=4,
            prefill_buckets=(16, 96), paged=True, block_size=16,
            prefill_mode="disagg", prefill_lanes=4,
            prefill_stream=True, prefill_chunk=16)
        try:
            hs = [r.submit(p, max_new_tokens=new) for p in prompts]
            for h, want in zip(hs, refs):
                assert h.result(timeout=600) == want
            # streamed frames actually flowed (57- and 40-token
            # prompts complete blocks before their final slice)
            assert r.stats["handoff_frames"] >= 1
            assert r.executor.prefill_exec.batch_occupancy() > 0
            r.pool.check_invariant()
        finally:
            r.close()


# ---------------------------------------------------------------------------
# Heavyweight matrix behind -m slow (invariants on serve-prefillpool)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestPrefillPoolMatrix:
    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_remote_stream_parity(self, kv_quant):
        import jax
        import jax.numpy as jnp

        from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
        from paddle_operator_tpu.infer.prefill_serve import (
            RemotePrefillClient,
            make_prefill_server,
        )

        params, cfg = _tiny()
        new = 6
        prompts = [np.asarray(jax.random.randint(
            jax.random.PRNGKey(50 + i), (n,), 0, cfg.vocab_size,
            dtype=jnp.int32)) for i, n in enumerate((57, 9, 40))]

        def ring(client=None):
            return ContinuousBatcher(
                params, cfg, slots=3, max_len=96, chunk_tokens=4,
                prefill_buckets=(16, 96), paged=True, block_size=16,
                prefill_mode="disagg", kv_quant=kv_quant,
                prefill_client=client)

        oracle = ring()
        try:
            want = [oracle.submit(p, max_new_tokens=new)
                    .result(timeout=600) for p in prompts]
        finally:
            oracle.close()
        psrv = make_prefill_server(
            "127.0.0.1", 0, params, cfg, block_size=16, max_len=96,
            buckets=(16, 96), kv_quant=kv_quant, lanes=4,
            prefill_chunk=16, prefix_blocks=32)
        threading.Thread(target=lambda: psrv.serve_forever(
            poll_interval=0.05), daemon=True).start()
        client = RemotePrefillClient(
            peers=[f"127.0.0.1:{psrv.server_address[1]}"],
            stream=True)
        r = ring(client)
        try:
            for p, w in zip(prompts, want):
                assert r.submit(p, max_new_tokens=new) \
                    .result(timeout=600) == w
            assert r.stats["handoff_frames"] >= 1
            assert r.stats["remote_prefills"] == len(prompts)
            r.pool.check_invariant()
        finally:
            r.close()
            psrv.shutdown()
            psrv.server_close()
            psrv.frontend.close()

    def test_prefill_side_prefix_hit_bit_identical_to_cold(self):
        """Decode radix OFF, so a resubmit's only reuse is the
        ENGINE's own prefix cache — streams must stay bit-identical
        and the engine must actually hit."""
        import jax
        import jax.numpy as jnp

        from paddle_operator_tpu.infer import decode as ID
        from paddle_operator_tpu.infer.scheduler import ContinuousBatcher

        params, cfg = _tiny()
        new = 6
        prompts = [np.asarray(jax.random.randint(
            jax.random.PRNGKey(60 + i), (n,), 0, cfg.vocab_size,
            dtype=jnp.int32)) for i, n in enumerate((57, 40))]
        refs = [np.asarray(ID.generate(
            params, cfg, jnp.asarray([p], jnp.int32),
            max_new_tokens=new, max_len=96)[0]).tolist()
            for p in prompts]
        r = ContinuousBatcher(
            params, cfg, slots=2, max_len=96, chunk_tokens=4,
            prefill_buckets=(16, 96), paged=True, block_size=16,
            prefill_mode="disagg", prefill_lanes=4,
            prefill_stream=True, prefill_chunk=16,
            prefill_prefix_blocks=64, prefix_cache=False)
        try:
            for h, w in zip([r.submit(p, max_new_tokens=new)
                             for p in prompts], refs):
                assert h.result(timeout=600) == w
            pe = r.executor.prefill_exec
            assert pe.prefix_hits == 0
            for h, w in zip([r.submit(p, max_new_tokens=new)
                             for p in prompts], refs):
                assert h.result(timeout=600) == w, \
                    "prefill-side prefix hit diverged from cold"
            assert pe.prefix_hits == len(prompts)
            r.pool.check_invariant()
        finally:
            r.close()
