"""Minimal generation server — the deployable face of the infer layer.

Runs in a worker pod (or anywhere with the params): loads a checkpoint
through the same ``TPUJOB_CHECKPOINT_PATH`` contract training uses, jits
:func:`infer.decode.generate`, and serves JSON over stdlib HTTP (the same
transport discipline as the ps/ and heter/ tiers — no web framework).

    POST /v1/generate
      {"tokens": [[...], ...], "max_new_tokens": N,
       "temperature": 0.7, "top_k": 40, "top_p": 0.9, "eos_token": 2}
    -> {"tokens": [[...], ...]}   (prompt + continuation per row)

Two modes:

- **batch mode** (:class:`Generator`): each distinct (batch,
  prompt-length, options) combination jits once (bounded LRU) and whole
  batches run synchronously — exact, simple, but staggered requests
  serialize behind each other.
- **continuous mode** (``make_server(..., continuous=True)``): requests
  are admitted into a fixed ring of decode lanes sharing ONE resident
  compiled step (infer/scheduler.py) — staggered concurrent requests
  decode side by side, lanes recycle on eos/budget, and the compile set
  is fixed regardless of arrival pattern.  Per-request knobs:
  max_new_tokens, temperature, seed, eos_token; top-k/top-p are
  server-global statics of the resident program.  With
  ``SERVE_SPEC_K > 0`` the ring decodes SPECULATIVELY (docs/serving.md):
  a draft model proposes K tokens per round, the target verifies them
  in one chunked forward, and every response carries its measured
  ``accept_rate``.  With ``SERVE_PAGED=1`` the ring's KV lives in a
  block pool with radix prefix reuse (infer/paged.py): requests
  sharing a cached prompt prefix skip its prefill entirely, and the
  ``status.serving`` block gains ``prefixHitRate``/``kvBlocksFree``
  for the manager's /metrics gauges.
"""

from __future__ import annotations

import json
import threading
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.models.llama import LlamaConfig


class Generator:
    """Jit-per-(shape, options) wrapper around decode.generate.

    The compile cache is a bounded LRU: a long-lived server facing
    clients with varied shapes must not grow jitted programs (and XLA
    compile state) without limit.  Evicted entries simply recompile on
    next use."""

    MAX_CACHED = 32

    def __init__(self, params: Any, cfg: LlamaConfig,
                 max_cached: int = MAX_CACHED, mesh=None) -> None:
        # mesh (make_serving_mesh): TP-sharded batch serving — params
        # laid out once, every jitted generate compiles sharded
        self.mesh = mesh
        if mesh is not None and D.mesh_tp(mesh) > 1:
            params = D.shard_params_for_serving(params, cfg, mesh)
        self.params = params
        self.cfg = cfg
        self._fns: "OrderedDict[tuple, Any]" = OrderedDict()
        self._max_cached = max_cached
        self._lock = threading.Lock()

    def __call__(self, tokens: np.ndarray, *, max_new_tokens: int,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_token: Optional[int] = None,
                 seed: int = 0) -> np.ndarray:
        key = (tokens.shape, max_new_tokens, temperature, top_k, top_p,
               eos_token)
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                fn = jax.jit(lambda p, t, k: D.generate(
                    p, self.cfg, t, max_new_tokens=max_new_tokens,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    eos_token=eos_token, key=k, mesh=self.mesh))
                self._fns[key] = fn
                while len(self._fns) > self._max_cached:
                    self._fns.popitem(last=False)
            else:
                self._fns.move_to_end(key)
        out = fn(self.params, jnp.asarray(tokens, jnp.int32),
                 jax.random.PRNGKey(seed))
        return np.asarray(out)


class ContinuousGenerator:
    """Adapter giving the decode ring the Generator call surface: rows
    of one HTTP request become independent ring requests (they may land
    in different decode waves), and the call blocks until all rows
    finish.  Concurrent HTTP threads interleave in the ring — that is
    the point."""

    def __init__(self, params: Any, cfg: LlamaConfig, **ring_kw) -> None:
        from paddle_operator_tpu.infer.scheduler import ContinuousBatcher

        self.batcher = ContinuousBatcher(params, cfg, **ring_kw)
        self.cfg = cfg
        # fleet-level KV (ISSUE 12): lanes adopted from peers, keyed by
        # the migrated request's idempotent row id — the client's retry
        # (routed here by the router's migration table) collects the
        # result instead of re-generating.  Bounded: an unclaimed
        # handle is dropped oldest-first (its client gave up).
        self.adopted: "OrderedDict[str, Any]" = OrderedDict()
        self._adopted_lock = threading.Lock()

    ADOPTED_CAP = 512

    def adopt_envelope(self, buf: bytes) -> str:
        """Decode + adopt one migrated-lane envelope; returns the
        adopted request id.  Raises fleetkv.EnvelopeError on any
        validation failure (the handler maps it to 409)."""
        from paddle_operator_tpu.utils import fleetkv as FK

        meta, spill = FK.decode_lane(buf)
        rid = meta.get("requestId")
        if not rid:
            raise FK.EnvelopeError(
                "lane envelope carries no requestId — the result "
                "would be unretrievable")
        handle = self.batcher.adopt(meta, spill)
        with self._adopted_lock:
            old = self.adopted.pop(rid, None)
            if old is not None:
                old.cancel()    # replayed migration: one runner only
            self.adopted[rid] = handle
            while len(self.adopted) > self.ADOPTED_CAP:
                _, stale = self.adopted.popitem(last=False)
                stale.cancel()
        return rid

    def take_adopted(self, rid: Optional[str]):
        if rid is None:
            return None
        with self._adopted_lock:
            return self.adopted.pop(rid, None)

    def __call__(self, tokens: np.ndarray, *, max_new_tokens: int,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_token: Optional[int] = None,
                 seed: int = 0) -> list:
        rows, _, _, _ = self.generate_rows(
            tokens, max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, eos_token=eos_token, seed=seed)
        return rows

    def generate_rows(self, tokens, *, max_new_tokens: int,
                      temperature: float = 0.0,
                      top_k: Optional[int] = None,
                      top_p: Optional[float] = None,
                      eos_token: Optional[int] = None, seed: int = 0,
                      request_id: Optional[str] = None,
                      deadline_s: Optional[float] = None,
                      priority: Optional[int] = None,
                      adapter: Optional[str] = None,
                      trace_ctx=None):
        """Rows + per-row speculative accept rates (None entries when
        the ring is not speculative) + per-row deadline-exceeded flags
        (a flagged row carries the PARTIAL tokens produced before its
        ``deadline_s`` budget ran out — the handler's 504-style
        response) + per-row span sets (ISSUE 15 — None entries when
        tracing is off; the router stitches them into one cross-pod
        timeline).  ``request_id`` (the client's, or the handler's
        fallback) is threaded into ``submit`` per row so capacity
        rejections name the offender; ``trace_ctx`` is the parsed
        ``X-Tpujob-Trace`` context every row traces under."""
        if (top_k, top_p) != (self.batcher._top_k, self.batcher._top_p) \
                and (top_k is not None or top_p is not None):
            raise ValueError(
                "top_k/top_p are fixed per continuous server "
                f"(configured: top_k={self.batcher._top_k} "
                f"top_p={self.batcher._top_p})")
        reqs = []
        try:
            for i, row in enumerate(tokens):
                rid_row = (f"{request_id}/row{i}"
                           if request_id is not None else None)
                # fleet-level KV (ISSUE 12): a row whose lane migrated
                # HERE is already decoding (or done) — collect it
                # instead of re-generating; rows without an adopted
                # lane submit as always
                handle = self.take_adopted(rid_row)
                if handle is None:
                    handle = self.batcher.submit(
                        row, max_new_tokens=max_new_tokens,
                        temperature=temperature, seed=seed + i,
                        eos_token=eos_token, deadline_s=deadline_s,
                        priority=priority, adapter=adapter,
                        request_id=rid_row, trace_ctx=trace_ctx)
                reqs.append(handle)
            # ragged rows: sequences stop at eos, no rectangular array
            rows = [r.result(timeout=600) for r in reqs]
        except Exception:
            # a later row's submit rejected (QueueFull) or a result
            # timed out: the already-submitted rows have no consumer —
            # without the cancel they would decode to their full budgets
            # and amplify exactly the overload that shed them
            for r in reqs:
                r.cancel()
            raise
        return (rows, [r.accept_rate for r in reqs],
                [r.deadline_exceeded for r in reqs],
                [getattr(r, "trace", None) for r in reqs])

    def close(self) -> None:
        self.batcher.close()


def load_serving_params(cfg: LlamaConfig, ckpt, *, seed: int = 0,
                        mesh=None):
    """``(params, resumed)`` for a server: what is served, in the served
    dtype and layout, and nothing else on the device.

    With a checkpoint (`ckpt` a CheckpointManager; None means there is
    none to look for), only the ``params`` subtree of the saved
    TrainState is read (``CheckpointManager.restore_params``), cast to
    ``cfg.dtype`` on the way in and placed straight onto the serving
    layout — so the optimizer the job trained with (f32 or int8 moments)
    is irrelevant, and a tp>1 mesh never sees the whole tree on its
    first device.  Without one (smoke mode) the same tree is initialised
    from `seed` under one jit with the cast inside.  No TrainState, no
    optimizer state, no second copy."""
    from paddle_operator_tpu.infer import afmoe_serve as AF
    from paddle_operator_tpu.infer.quant import serving_params
    from paddle_operator_tpu.models.llama import Llama, partition_patterns
    from paddle_operator_tpu.train.checkpoint import restore_newest

    if AF.is_expert_stack(cfg):     # the preset's type selects the tree
        AF.refuse_modes(cfg, {"SERVE_TP>1": D.mesh_tp(mesh) > 1})
        return AF.load_params(cfg, ckpt, seed)
    model = Llama(cfg)

    def init(rng):
        return serving_params(
            model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"],
            cfg.dtype)

    shapes = jax.eval_shape(init, jax.random.PRNGKey(seed))
    if mesh is not None and D.mesh_tp(mesh) > 1:
        from paddle_operator_tpu.parallel.sharding import tree_shardings

        shardings = tree_shardings(shapes, mesh, partition_patterns(cfg),
                                   replicate_indivisible=True)
    else:
        one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        shardings = jax.tree.map(lambda _: one, shapes)
    if (ckpt is not None and ckpt.enabled
            and ckpt.latest_step() is not None):
        like = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            shapes, shardings)
        return restore_newest(
            ckpt, lambda step: ckpt.restore_params(like, step=step)), True
    return jax.jit(init, out_shardings=shardings)(
        jax.random.PRNGKey(seed)), False


def _load_swap_checkpoint(path: str, cfg) -> Any:
    """Restore a TRAINING checkpoint's params for serving — the same
    restore the entrypoint runs at boot — from the ``/v1/swap`` handler
    thread (ISSUE 19): the expensive half of a live swap happens HERE,
    off the ring loop, while the old generation keeps serving.  Raises
    when nothing restores (a swap must never silently flip to
    fresh-init weights)."""
    from paddle_operator_tpu.train.checkpoint import CheckpointManager

    ckpt = CheckpointManager(path)
    if ckpt.latest_step() is None:
        raise ValueError(f"no checkpoint restorable at {path}")
    return load_serving_params(cfg, ckpt)[0]


class _Handler(BaseHTTPRequestHandler):
    generator: Generator  # injected
    state = None          # injected resilience.ServerState
    # fleet identity (make_server job=/replica=): labels the per-pod
    # /metrics gauges the fleet router scrapes for load scoring
    job_key = "local"
    replica_id = ""
    # chunked transfer (the streaming path) requires HTTP/1.1; plain
    # responses carry Content-Length so keep-alive stays correct, and
    # the socket timeout reaps idle/half-dead keep-alive connections
    # that would otherwise pin a server thread forever
    protocol_version = "HTTP/1.1"
    timeout = 120

    def log_message(self, *a):
        pass

    def _send(self, code: int, obj, headers=None) -> None:
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(body)

    def _batcher(self):
        return getattr(self.generator, "batcher", None)

    def do_GET(self):
        # liveness vs readiness split (docs/serving.md resilience):
        # /healthz answers "should this pod be REPLACED" — 200 while
        # the process is up and the ring has not permanently died
        # (watchdog restart budget exhausted / wedged dispatch);
        # /readyz answers "should this pod take TRAFFIC" — also false
        # while merely draining or mid-self-heal, states /healthz must
        # NOT report (a restart would turn a 30s drain into lost work).
        if self.path == "/healthz":
            b = self._batcher()
            if b is not None and not b.healthy:
                self._send(503, {"ok": False, "reason": "ring dead"})
            else:
                self._send(200, {"ok": True})
        elif self.path == "/readyz":
            b = self._batcher()
            draining = bool(self.state and self.state.draining)
            ready = not draining and (b is None or b.accepting)
            if ready:
                self._send(200, {"ready": True})
            else:
                self._send(503, {
                    "ready": False,
                    "reason": ("draining" if draining else "ring"),
                }, headers={"Retry-After":
                            self.state.retry_after_s if self.state else 5})
        elif self.path == "/v1/adapters":
            # adapter registry surface (ISSUE 10): the loaded set, the
            # pool's capacity/rank contract, and which are serving
            b = self._batcher()
            reg = getattr(b, "adapters", None) if b is not None else None
            if reg is None:
                self._send(200, {"adapters": [], "capacity": 0})
            else:
                self._send(200, {"adapters": reg.names(),
                                 "capacity": reg.capacity,
                                 "rank": reg.rank})
        elif self.path == "/statusz":
            # the serving_status block as JSON — what a fleet replica
            # publishes toward status.serving, self-served for
            # debugging and for harnesses that want the raw block
            b = self._batcher()
            st = b.serving_status() if b is not None else {}
            if self.replica_id:
                st["replica"] = self.replica_id
            self._send(200, st)
        elif self.path == "/metrics":
            # per-pod prometheus gauges (the SAME names the manager
            # exports fleet-wide): the router scrapes
            # tpujob_serve_queue_depth / kv_blocks_free /
            # tokens_per_sec from here to score replica load — plus
            # the latency histograms (ISSUE 15) it folds fleet-wide
            from paddle_operator_tpu.utils.observability import (
                histogram_exposition,
                serving_gauges,
            )

            b = self._batcher()
            st = b.serving_status() if b is not None else {}
            gauges = serving_gauges(st, self.job_key,
                                    replica=self.replica_id or None)
            text = "".join(f"{k} {v}\n"
                           for k, v in sorted(gauges.items()))
            text += histogram_exposition(st.get("latencyHist"),
                                         self.job_key,
                                         self.replica_id or None)
            body = text.encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/debug/flightrec":
            # the pod's bounded event ring (ISSUE 15) — the same JSON
            # a watchdog-restart/chaos/SIGTERM dump writes to disk
            b = self._batcher()
            fr = getattr(b, "flightrec", None) if b is not None else None
            self._send(200, fr.dump("debug_endpoint") if fr is not None
                       else {"events": []})
        else:
            self._send(404, {})

    def _stream_generate(self, req, trace_ctx=None,
                         id_hdrs=None) -> None:
        """``"stream": true`` (continuous mode, single row): emit
        newline-delimited JSON events as the ring produces tokens —
        {"token": t} per generated token, then {"done": true, "tokens":
        [full sequence]}.  Chunked transfer; tokens arrive in
        chunk-sized bursts (the ring's decode granularity).  On a
        tracing ring the done event carries the span set (the router's
        streaming relay does not parse the stream, so streamed
        timelines stitch client-side; docs/observability.md)."""
        gen = self.generator
        if not isinstance(gen, ContinuousGenerator):
            raise ValueError("streaming requires the continuous server "
                             "(SERVE_CONTINUOUS=1)")
        if ((req.get("top_k"), req.get("top_p"))
                != (gen.batcher._top_k, gen.batcher._top_p)
                and (req.get("top_k") is not None
                     or req.get("top_p") is not None)):
            raise ValueError(
                "top_k/top_p are fixed per continuous server "
                f"(configured: top_k={gen.batcher._top_k} "
                f"top_p={gen.batcher._top_p})")
        tokens = np.asarray(req["tokens"], np.int32)
        if tokens.ndim != 2 or tokens.shape[0] != 1:
            raise ValueError("streaming takes tokens [1, seq]")
        prio = req.get("priority")
        handle = gen.batcher.submit(
            tokens[0], max_new_tokens=int(req.get("max_new_tokens", 32)),
            temperature=float(req.get("temperature", 0.0)),
            seed=int(req.get("seed", 0)), eos_token=req.get("eos_token"),
            stream=True, request_id=req.get("request_id"),
            deadline_s=req.get("deadline_s"),
            priority=int(prio) if prio is not None else None,
            adapter=req.get("adapter"), trace_ctx=trace_ctx)

        def emit(obj) -> None:
            body = json.dumps(obj).encode() + b"\n"
            self.wfile.write(f"{len(body):x}\r\n".encode() + body
                             + b"\r\n")
            self.wfile.flush()

        # everything from the first socket write onward sits inside the
        # try: a disconnect raising in send_response/end_headers must
        # still reach the finally's cancel, or the abandoned request
        # holds its decode lane to the full token budget
        try:
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            for k, v in (id_hdrs or {}).items():
                self.send_header(k, str(v))
            self.end_headers()
            for tok in handle.stream(timeout=600):
                emit({"token": tok})
            done_ev = {"done": True, "tokens": handle.result(timeout=5)}
            if handle.accept_rate is not None:   # speculative ring
                done_ev["accept_rate"] = handle.accept_rate
            if handle.deadline_exceeded:         # 504-style partial
                done_ev["deadline_exceeded"] = True
            if getattr(handle, "trace", None) is not None:
                done_ev["trace"] = handle.trace.to_wire()
            emit(done_ev)
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            return   # client disconnected mid-stream: nothing to say
        except Exception as e:
            try:
                emit({"error": str(e)})
                self.wfile.write(b"0\r\n\r\n")
            except OSError:
                pass
        finally:
            # on ANY abandoning exit (disconnect, stream timeout, …) the
            # ring must stop decoding for this request — without the
            # cancel a few abandoned long streams would occupy all
            # decode lanes to their full max_new_tokens budget.  A no-op
            # when the generation already finished.
            handle.cancel()

    def _adapters_admin(self, body: bytes) -> None:
        """POST /v1/adapters — runtime load/evict on the serve surface
        (ISSUE 10): ``{"load": {"name": ..., "path"?: ..., "seed"?: ...}}``
        installs (path: .npz deltas; seed/neither: deterministic random
        smoke adapter), ``{"evict": "name"}`` removes — refused with 409
        while a resident or parked lane is still serving it."""
        b = self._batcher()
        reg = getattr(b, "adapters", None) if b is not None else None
        if reg is None:
            self._send(400, {"error": "no adapter registry (set "
                                      "SERVE_ADAPTERS to enable)"})
            return
        from paddle_operator_tpu.infer.qos import AdapterInUse

        def lanes_in_use():
            # resident + parked + QUEUED: a queued request already
            # resolved its adapter slot at submit — evicting/replacing
            # (and a later load reusing the slot) would serve it
            # another tenant's deltas
            in_use = {r.adapter_idx for r in b.lane if r is not None}
            in_use |= {pk.req.adapter_idx for pk in b._parked}
            in_use |= {r.adapter_idx for r in b._pending.items()}
            return in_use

        try:
            req = json.loads(body)
            if "load" in req:
                spec = req["load"]
                name = spec["name"]
                if spec.get("path"):
                    from paddle_operator_tpu.infer.qos import (
                        load_adapter_file,
                    )

                    deltas = load_adapter_file(b.cfg, spec["path"],
                                               reg.rank)
                    idx = reg.load(name, deltas,
                                   in_use=lanes_in_use())
                else:
                    idx = reg.load(name, seed=spec.get("seed"),
                                   in_use=lanes_in_use())
                self._send(200, {"loaded": name, "slot": idx})
            elif "evict" in req:
                reg.evict(req["evict"], in_use=lanes_in_use())
                self._send(200, {"evicted": req["evict"]})
            else:
                raise ValueError("body must carry 'load' or 'evict'")
        except AdapterInUse as e:
            self._send(409, {"error": str(e)})
        except (ValueError, KeyError, TypeError,
                json.JSONDecodeError) as e:
            self._send(400, {"error": str(e)})
        except OSError as e:
            self._send(400, {"error": f"adapter file: {e}"})

    def _kv_restore(self, body: bytes) -> None:
        """POST /v1/kv/restore — adopt a migrated lane (ISSUE 12).
        The body is a fleetkv LANE envelope; a valid one parks the
        lane for restore at the next loop boundary and the client's
        request_id-keyed retry collects the result.  Any validation
        failure refuses the WHOLE envelope: 409 tells the origin to
        keep the lane (completion-wait fallback)."""
        from paddle_operator_tpu.infer.resilience import ShuttingDown
        from paddle_operator_tpu.utils.fleetkv import EnvelopeError

        gen = self.generator
        if not isinstance(gen, ContinuousGenerator):
            self._send(400, {"error": "lane adoption requires the "
                                      "continuous server"})
            return
        if self.state is not None and self.state.draining:
            self._send(503, {"error": "draining"},
                       headers={"Retry-After":
                                self.state.retry_after_s})
            return
        try:
            rid = gen.adopt_envelope(body)
            self._send(200, {"adopted": rid})
        except ShuttingDown as e:
            self._send(503, {"error": str(e)})
        except EnvelopeError as e:
            # flight recorder (ISSUE 15): a refused envelope (CRC,
            # fingerprint skew, truncation) is exactly the event fleet
            # debugging needs a durable record of
            fr = getattr(self._batcher(), "flightrec", None)
            if fr is not None:
                fr.record("envelope_refused", error=str(e)[:200])
            self._send(409, {"error": str(e)})
        except Exception as e:      # noqa: BLE001 — refuse, never crash
            self._send(400, {"error": str(e)})

    def _kv_prefix(self, body: bytes) -> None:
        """POST /v1/kv/prefix — export demoted blocks of a prompt's
        radix chain (ISSUE 12 peer prefix fetch).  200 + a PREFIX
        envelope when the host tier holds any of the chain; 204
        otherwise.  The radix is ring-thread state and this runs on a
        handler thread: any racy surprise degrades to 204 (the
        requester re-prefills cold, exactly as without the fetch)."""
        b = self._batcher()
        try:
            req = json.loads(body)
            tokens = [int(t) for t in req["tokens"]]
            ns = int(req.get("ns", 0))
            if (b is None or b.pool is None or ns != 0
                    or b.pool.host is None):
                raise LookupError
            chunks, idx, payloads = b.pool.export_host_chain(tokens,
                                                             ns=0)
            if not idx:
                raise LookupError
            from paddle_operator_tpu.utils import fleetkv as FK

            # materialize lazily-demoted device slices to numpy HERE
            # (jax arrays are immutable — a concurrent read is safe)
            payloads = [{k: np.asarray(v) for k, v in p.items()}
                        for p in payloads]
            buf = FK.encode_prefix({"fingerprint": b._fingerprint()},
                                   chunks, idx, payloads)
        except Exception:       # noqa: BLE001 — nothing to export
            self.send_response(204)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(buf)))
        self.end_headers()
        self.wfile.write(buf)

    def _swap(self, body: bytes) -> None:
        """POST /v1/swap — live weight swap / elastic TP resize
        (ISSUE 19, docs/serving.md "Live model lifecycle").  Body keys
        (all optional): ``checkpoint`` (path; omitted = rebuild from
        the retained boot base — the TP-resize / quant-flip shape),
        ``draft_checkpoint`` (spec rings), ``tp`` (target degree;
        omitted = keep the mesh), ``generation`` (explicit; omitted =
        bump by one), ``weight_quant`` / ``draft_quant``
        (none|int8|int4; omitted = keep the serving mode),
        ``timeout_s``.  The checkpoint load + quantize runs on THIS
        handler thread while the old generation keeps serving; only
        the quiesce-flip-restore runs on the ring loop.  Responses:
        200 + post-swap summary, 409 a swap is already in flight,
        503 + Retry-After the ring cannot swap right now (draining /
        rebuilding / never reached a boundary — retry)."""
        from paddle_operator_tpu.infer.resilience import (
            RetriableError,
            ShuttingDown,
        )

        b = self._batcher()
        if b is None:
            self._send(400, {"error": "live swap requires the "
                             "continuous ring (SERVE_CONTINUOUS=1)"})
            return
        retry_hdr = {"Retry-After":
                     self.state.retry_after_s if self.state else 5}
        try:
            req = json.loads(body) if body else {}
            base = getattr(self.server, "swap_base", None)
            cfg = getattr(self.generator, "cfg", None)
            ckpt = req.get("checkpoint")
            if ckpt:
                params = _load_swap_checkpoint(ckpt, cfg)
            elif base is not None:
                params = base["params"]
            else:
                raise ValueError(
                    "no 'checkpoint' given and no retained base "
                    "(SERVE_SWAP_RETAIN=0) — nothing to swap to")
            wq = req.get("weight_quant")
            if wq is None:
                wq = (base or {}).get("weight_quant", "none")
            wq = wq or "none"
            if wq != "none":
                from paddle_operator_tpu.infer.quant import (
                    SERVING_SKIP,
                    quantize_params,
                )

                params = quantize_params(params, cfg, mode=wq,
                                         skip=SERVING_SKIP)
            dparams = None
            if getattr(b, "spec_k", 0) > 0:
                dck = req.get("draft_checkpoint")
                if dck:
                    dparams = _load_swap_checkpoint(dck, b.draft_cfg)
                elif base is not None \
                        and base.get("draft_params") is not None:
                    dparams = base["draft_params"]
                else:
                    raise ValueError(
                        "speculative ring: a swap needs "
                        "'draft_checkpoint' or a retained draft base")
                dwq = req.get("draft_quant")
                if dwq is None:
                    dwq = (base or {}).get("draft_quant", "none")
                if (dwq or "none") != "none":
                    from paddle_operator_tpu.infer.quant import (
                        SERVING_SKIP,
                        quantize_params,
                    )

                    dparams = quantize_params(dparams, b.draft_cfg,
                                              mode=dwq,
                                              skip=SERVING_SKIP)
            kw = {}
            tp = req.get("tp")
            if tp is not None and int(tp) != b.serving_tp():
                if int(tp) > 1:
                    from paddle_operator_tpu.parallel.mesh import (
                        make_serving_mesh,
                    )

                    kw["mesh"] = make_serving_mesh(int(tp))
                else:
                    kw["mesh"] = None
            if req.get("generation") is not None:
                kw["generation"] = int(req["generation"])
            res = b.swap_weights(
                params, draft_params=dparams,
                timeout=float(req.get("timeout_s", 120.0)), **kw)
            self._send(200, res)
        except (ShuttingDown, RetriableError) as e:
            self._send(503, {"error": str(e)}, headers=retry_hdr)
        except ValueError as e:
            already = "already in flight" in str(e)
            self._send(409 if already else 400, {"error": str(e)})
        except (KeyError, TypeError, json.JSONDecodeError) as e:
            self._send(400, {"error": str(e)})
        except Exception as e:     # noqa: BLE001 — refuse, never crash
            self._send(503, {"error": str(e)}, headers=retry_hdr)

    def do_POST(self):
        from paddle_operator_tpu.infer.resilience import (
            RetriableError,
            ShuttingDown,
        )

        # drain the body before ANY response: under HTTP/1.1 keep-alive
        # an unread body would be parsed as the next request's start line
        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n) if n else b""
        if self.path == "/v1/kv/restore":
            return self._kv_restore(body)
        if self.path == "/v1/kv/prefix":
            return self._kv_prefix(body)
        if self.path == "/v1/adapters":
            return self._adapters_admin(body)
        if self.path == "/v1/swap":
            return self._swap(body)
        if self.path != "/v1/generate":
            self._send(404, {})
            return
        retry_hdr = {"Retry-After":
                     self.state.retry_after_s if self.state else 5}
        if self.state is not None and self.state.draining:
            # SIGTERM drain: admissions stop FIRST — clients get an
            # explicit retry signal while resident lanes finish
            self._send(503, {"error": "server draining"},
                       headers=retry_hdr)
            return
        try:
            req = json.loads(body)
            # per-request deadline: the X-Request-Deadline header
            # (seconds, the load-balancer convention) or the body's
            # deadline_s — whichever is set; an expired request resolves
            # with the tokens produced so far and a 504-style marker
            # instead of pinning its lane
            deadline_s = req.get("deadline_s")
            hdr = self.headers.get("X-Request-Deadline")
            if deadline_s is None and hdr is not None:
                deadline_s = float(hdr)
            # QoS class (ISSUE 10): the X-Request-Priority header (the
            # router forwards it verbatim) or the body's ``priority``
            # — body wins when both are set, like deadline_s.  0 is
            # the most urgent class; unannotated requests get the
            # server's default (least urgent) class.
            priority = req.get("priority")
            phdr = self.headers.get("X-Request-Priority")
            if priority is None and phdr is not None:
                priority = int(phdr)
            # trace context (ISSUE 15): the router (or a client)
            # propagates X-Tpujob-Trace; on a SERVE_TRACE=1 ring every
            # row traces under it and the span sets ride the response
            # so the router can stitch one cross-pod timeline
            from paddle_operator_tpu.utils import tracing as _TR

            trace_ctx = _TR.parse_trace_header(
                self.headers.get(_TR.TRACE_HEADER))
            # fleet-debugging identity (ISSUE 15 satellite): every
            # generate reply names its request and serving replica.
            # The id is CLIENT input — sanitize before echoing it into
            # a header (CR/LF would split the response; non-latin-1
            # raises inside send_header after the status line)
            id_hdrs = {}
            if req.get("request_id") is not None:
                id_hdrs["X-Request-Id"] = _TR.safe_header_value(
                    req.get("request_id"))
            if self.replica_id:
                id_hdrs["X-Tpujob-Replica"] = self.replica_id
            if req.get("stream"):
                if deadline_s is not None:
                    req["deadline_s"] = float(deadline_s)
                if priority is not None:
                    req["priority"] = int(priority)
                return self._stream_generate(req, trace_ctx=trace_ctx,
                                             id_hdrs=id_hdrs)
            tokens = np.asarray(req["tokens"], np.int32)
            if tokens.ndim != 2:
                raise ValueError("tokens must be [batch, seq]")
            opts = dict(
                max_new_tokens=int(req.get("max_new_tokens", 32)),
                temperature=float(req.get("temperature", 0.0)),
                top_k=req.get("top_k"),
                top_p=req.get("top_p"),
                eos_token=req.get("eos_token"),
                seed=int(req.get("seed", 0)))
            gen = self.generator
            if isinstance(gen, ContinuousGenerator):
                # request_id (client-supplied) flows into submit so
                # validation errors in multi-request logs name their row
                rows, rates, expired, traces = gen.generate_rows(
                    tokens, request_id=req.get("request_id"),
                    deadline_s=(float(deadline_s)
                                if deadline_s is not None else None),
                    priority=(int(priority)
                              if priority is not None else None),
                    adapter=req.get("adapter"),
                    trace_ctx=trace_ctx,
                    **opts)
                resp = {"tokens": rows}
                if getattr(gen.batcher, "spec_k", 0) > 0:
                    # speculative ring: acceptance rides every response
                    resp["accept_rate"] = rates
                if any(t is not None for t in traces):
                    # per-row span sets (ISSUE 15): response metadata
                    # only — the token payload is untouched, so traced
                    # streams stay byte-identical to untraced ones
                    resp["trace"] = [t.to_wire() if t is not None
                                     else None for t in traces]
                if any(expired):
                    # deadline partials: 504 when EVERY row ran out
                    # (the whole request missed its budget), 200 with
                    # per-row flags on a mixed batch — either way the
                    # partial tokens are delivered, never dropped
                    resp["deadline_exceeded"] = expired
                    self._send(504 if all(expired) else 200, resp,
                               headers=id_hdrs)
                    return
                self._send(200, resp, headers=id_hdrs)
                return
            out = gen(tokens, **opts)
            out = out if isinstance(out, list) else out.tolist()
            self._send(200, {"tokens": out}, headers=id_hdrs)
        except (ShuttingDown, RetriableError) as e:
            # the request was fine, the server was not: an explicit
            # retry signal (drain shed, watchdog rebuild in progress)
            self._send(503, {"error": str(e)}, headers=retry_hdr)
        except (ValueError, KeyError, TypeError,
                json.JSONDecodeError) as e:
            self._send(400, {"error": str(e)})
        except Exception as e:
            # server-side failure (dead decode ring, generation timeout):
            # 503 tells clients to retry/fail over, not to blame their
            # request
            self._send(503, {"error": str(e)})


def make_server(host: str, port: int, params: Any, cfg: LlamaConfig,
                *, continuous: bool = False, mesh=None,
                job: str = "local", replica: str = "",
                **ring_kw) -> ThreadingHTTPServer:
    """``continuous=True`` serves through the decode ring
    (infer/scheduler.py; ``ring_kw``: slots, max_len, chunk_tokens,
    prefill_buckets, top_k, top_p).  ``mesh`` (make_serving_mesh)
    makes either mode tensor-parallel — the ring's resident programs
    and the batch generator's jits compile sharded, token streams
    unchanged.  The returned server carries ``.generator`` — call its
    ``close()`` when tearing a continuous server down to stop the ring
    thread."""
    from paddle_operator_tpu.infer.resilience import ServerState

    gen = (ContinuousGenerator(params, cfg, mesh=mesh, **ring_kw)
           if continuous else Generator(params, cfg, mesh=mesh))
    state = ServerState()
    handler = type("Handler", (_Handler,),
                   {"generator": gen, "state": state,
                    "job_key": job, "replica_id": replica})
    srv = ThreadingHTTPServer((host, port), handler)
    srv.generator = gen
    # readiness/drain flags shared with the handler threads; a
    # resilience.ServingDrain flips state.draining on SIGTERM
    srv.state = state
    return srv


def ready_ring(batcher, *, prewarm: bool) -> None:
    """What the entry point does to a built ring before it listens, so
    before ``/readyz`` can answer: every rung of the prefill ladder
    gets its insert compiled ahead (``RingExecutor.compile_inserts``:
    no first prompt on a rung traces or compiles), then the off-thread
    prewarm of the other programs starts unless opted out."""
    batcher.executor.compile_inserts()
    if prewarm:
        batcher.start_prewarm()


def wire_fleet_kv_from_env(batcher, port: int) -> None:
    """Fleet-level KV client wiring (ISSUE 12, docs/serving.md
    "Fleet-level KV"): ``SERVE_KV_MIGRATE=1`` drains by MIGRATION
    (residents spill + POST to a peer instead of waiting out
    completions; completion-wait stays the fallback for lanes no peer
    takes), ``SERVE_KV_PEER_FETCH=1`` asks the fleet for demoted
    prefix blocks on a local radix miss.  ``SERVE_KV_BROKER`` names
    the router (it picks adopters + dedupes replayed migrations);
    ``SERVE_KV_PEERS`` is the router-less static peer list.
    ``SERVE_MIGRATE_PARKED_S`` additionally sheds preemption-parked
    lanes to idle peers OUTSIDE a drain.  Everything here requires
    the paged ring (spills are block-granular); peer fetch further
    needs the host tier (imports land there and promote through the
    host-hit path).  Shared by the real entrypoint and the simfleet
    subprocess replicas."""
    import os

    kv_migrate = os.environ.get("SERVE_KV_MIGRATE", "0") == "1"
    kv_fetch = os.environ.get("SERVE_KV_PEER_FETCH", "0") == "1"
    if not (kv_migrate or kv_fetch):
        return
    if batcher.pool is None:
        print("SERVE_KV_MIGRATE/SERVE_KV_PEER_FETCH ignored: "
              "fleet-level KV requires the paged ring (SERVE_PAGED=1)",
              flush=True)
        return
    from paddle_operator_tpu.utils import fleetkv as FK

    # wire chaos (ISSUE 20): TPUJOB_WIRE_CHAOS scheduling faults on
    # the replica->broker edge swaps the broker endpoint for an
    # injured in-process proxy — migrations/prefix fetches then cross
    # a deterministically faulty wire without touching the router
    from paddle_operator_tpu.utils import wirechaos as WC

    origin = f"{os.environ.get('POD_IP', '127.0.0.1')}:{port}"
    kv_client = FK.FleetKVClient(
        broker=WC.wire_endpoint_from_env(
            "replica-broker", os.environ.get("SERVE_KV_BROKER", "")),
        peers=os.environ.get("SERVE_KV_PEERS", "").split(","),
        origin=origin)
    if kv_migrate:
        batcher.migrate_out = lambda meta, spill: \
            kv_client.migrate_out(FK.encode_lane(meta, spill))
        batcher._migrate_on_drain = True
        parked_s = float(os.environ.get("SERVE_MIGRATE_PARKED_S",
                                        "0") or 0)
        if parked_s > 0:
            batcher.migrate_parked_s = parked_s
    if kv_fetch:
        if batcher.pool.host is None:
            print("SERVE_KV_PEER_FETCH ignored: peer payloads import "
                  "through the host tier — set "
                  "SERVE_HOST_CACHE_BLOCKS/_MB", flush=True)
        else:
            batcher.peer_fetch = kv_client.fetch_prefix


def wire_kv_store_from_env(batcher) -> None:
    """Durable prefix store wiring (ISSUE 17, docs/serving.md "Durable
    prefix store"): ``SERVE_KV_STORE=dir:/path`` attaches the
    persistent tier below host/peer — host-tier overflow drops persist
    through a background writer instead of silently discarding, and the
    submit-thread probe order becomes peer -> store.  Lifecycle knobs:
    ``SERVE_KV_STORE_TTL_S`` (expire idle entries),
    ``SERVE_KV_STORE_BUDGET_MB`` (LRU size budget),
    ``SERVE_KV_STORE_JANITOR_S`` (in-process janitor period; 0 leaves
    lifecycle to the offline ``python -m
    paddle_operator_tpu.infer.kvstore`` pass — the shared-volume
    deployment shape), ``SERVE_KV_STORE_QUEUE`` (writer queue bound,
    drop-oldest).  Requires the paged ring + host tier (spills come
    from the tier; hits land through it); unset is byte-identical to
    the store-less ring."""
    import os
    import threading

    url = os.environ.get("SERVE_KV_STORE", "").strip()
    if not url:
        return
    if batcher.pool is None or batcher.pool.host is None:
        print("SERVE_KV_STORE ignored: the durable store spills from "
              "and promotes through the host tier — set SERVE_PAGED=1 "
              "and SERVE_HOST_CACHE_BLOCKS/_MB", flush=True)
        return
    from paddle_operator_tpu.infer import kvstore as KVS

    try:
        backend = KVS.parse_store_url(url)
    except (ValueError, OSError) as e:
        print(f"SERVE_KV_STORE ignored: {e}", flush=True)
        return
    store = KVS.KVBlockStore(
        backend, fingerprint=batcher._fingerprint(),
        ttl_s=float(os.environ.get("SERVE_KV_STORE_TTL_S", "0") or 0),
        budget_mb=int(os.environ.get("SERVE_KV_STORE_BUDGET_MB", "0")
                      or 0),
        queue_len=int(os.environ.get("SERVE_KV_STORE_QUEUE", "256")
                      or 256))
    batcher.attach_kv_store(store)
    janitor_s = float(os.environ.get("SERVE_KV_STORE_JANITOR_S", "0")
                      or 0)
    if janitor_s > 0:
        def _janitor_loop():
            while not batcher._stop.wait(janitor_s):
                try:
                    store.janitor()
                except OSError:
                    pass

        threading.Thread(target=_janitor_loop, daemon=True,
                         name="kvstore-janitor").start()
    print(f"durable KV store attached: {url} "
          f"(ttl_s={store.ttl_s}, budget_mb={store.budget_mb}, "
          f"janitor_s={janitor_s})", flush=True)


def main() -> int:
    """Serving entrypoint: restore params from TPUJOB_CHECKPOINT_PATH
    (fresh init if none — smoke mode) and serve on TPUJOB_PORT."""
    import os

    from paddle_operator_tpu.launch.launcher import JobEnv
    from paddle_operator_tpu.models.llama import CONFIGS
    from paddle_operator_tpu.train.checkpoint import CheckpointManager
    from paddle_operator_tpu.utils import tracing as TR
    from paddle_operator_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every phase of this process is a TraceMe on the profiler's host
    # plane from here on (utils/tracing.py): whoever starts the profiler
    # finds the loop's phases on the device's clock
    TR.set_annotator(jax.profiler.TraceAnnotation)
    env = JobEnv.from_env()
    cfg = CONFIGS[os.environ.get("MODEL_PRESET", "7b")]
    # SERVE_TP=n: tensor-parallel serving over the pod's first n chips
    # (weights a single chip cannot hold — the 7B-on-v5e case).  The
    # mesh carries only the tp axis; DP is separate server replicas.
    mesh = None
    tp = int(os.environ.get("SERVE_TP", "1"))
    if tp > 1:
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        mesh = make_serving_mesh(tp)
    # TPUJOB_CHECKPOINT_PATH; restored (or smoke-initialised) in the
    # served dtype, straight onto the serving layout
    from paddle_operator_tpu.infer import afmoe_serve as AF

    # an architecture the quantizers and the draft model are not written
    # for refuses them here, before anything is loaded
    AF.refuse_modes(cfg, {
        "QUANTIZE=int8": os.environ.get("QUANTIZE", "") == "int8",
        "SERVE_WEIGHT_QUANT": (os.environ.get("SERVE_WEIGHT_QUANT", "none")
                               or "none") != "none",
        "SERVE_TP>1": tp > 1,
        "SERVE_SPEC_K>0": int(os.environ.get("SERVE_SPEC_K", "0")) > 0})
    params, resumed = load_serving_params(cfg, CheckpointManager(),
                                          mesh=mesh)
    if os.environ.get("QUANTIZE", "") == "int8":
        from paddle_operator_tpu.infer.quant import quantize_params

        params = quantize_params(params)   # ~1.4-1.5x decode at batch 8
    # SERVE_WEIGHT_QUANT=int8|int4 (docs/serving.md "Quantized
    # weights"): quantize the TARGET model's matmul kernels at load —
    # per-output-channel absmax codes + f32 scale planes replacing the
    # kernel leaves, dequant fused at the matmul sites, with the serving
    # skip list (embeddings / lm_head / norms stay bf16).  The codes
    # ride the params dispatch operand, so bf16-default processes trace
    # byte-identical programs.  SERVE_DRAFT_QUANT (below, spec rings
    # only) is the safe proving ground: quantize the draft first.
    wq = os.environ.get("SERVE_WEIGHT_QUANT", "none") or "none"
    # live swap (ISSUE 19): retain a HOST copy of the pre-quant serving
    # base so a checkpoint-less /v1/swap — a TP resize or a quant-mode
    # flip — can rebuild from it without a checkpoint round-trip.
    # Host RAM, not HBM; SERVE_SWAP_RETAIN=0 opts out (swaps then
    # require a 'checkpoint' in the body).
    swap_base = None
    if os.environ.get("SERVE_SWAP_RETAIN", "1") == "1":
        swap_base = {"params": jax.device_get(params),
                     "weight_quant": wq}
    if wq != "none":
        from paddle_operator_tpu.infer.quant import (
            SERVING_SKIP,
            quantize_params,
        )

        params = quantize_params(params, cfg, mode=wq, skip=SERVING_SKIP)
    # opt-in: continuous mode fixes top_k/top_p server-side, so flipping
    # it on by default would 400 existing clients that pass them
    continuous = os.environ.get("SERVE_CONTINUOUS", "0") == "1"
    ring_kw = {}
    spec_k = int(os.environ.get("SERVE_SPEC_K", "0"))
    if continuous:
        from paddle_operator_tpu.infer.resilience import RingResilience

        ring_kw = {"slots": int(os.environ.get("SERVE_SLOTS", "8")),
                   "chunk_tokens": int(os.environ.get("SERVE_CHUNK", "8")),
                   "max_queue": int(os.environ.get("SERVE_MAX_QUEUE",
                                                   "0")),
                   # self-healing on by default for deployed rings:
                   # dispatch faults shed the resident requests (503)
                   # and rebuild instead of wedging every lane forever
                   "resilience": RingResilience.from_env()}
        if os.environ.get("SERVE_MAX_LEN"):
            ring_kw["max_len"] = int(os.environ["SERVE_MAX_LEN"])
        # SERVE_GENERATION (ISSUE 19): the weight generation this
        # replica boots serving (operator-injected from
        # spec.serving.generation) — the fleet roll's convergence
        # signal; /v1/swap bumps it live
        ring_kw["generation"] = int(
            os.environ.get("SERVE_GENERATION", "0") or 0)
        # SERVE_PAGED=1: block-pool KV cache + radix prefix reuse
        # (infer/paged.py; docs/serving.md has the layout/eviction/CoW
        # story).  SERVE_BLOCK_SIZE sets pool-block granularity (keep
        # at the decode kernel's key block, 256, on TPU);
        # SERVE_PREFIX_CACHE=0 disables radix reuse while keeping
        # paging; SERVE_NUM_BLOCKS oversizes/undersizes the pool from
        # its contiguous-HBM-parity default.  SERVE_PAGED=0 (default)
        # keeps the contiguous ring — the parity oracle.
        # SERVE_KV_QUANT=int8 (docs/serving.md): store paged pool
        # blocks as int8 codes + per-(block, kv-head) f32 scales with
        # the dequant fused into the decode kernels — ~2x resident
        # lanes per HBM byte at a bounded (~17% v5e) per-step cost;
        # enable when the deployment is CAPACITY-bound (kv_blocks_free
        # pinned at 0), keep the default bf16 pool when latency-bound.
        # Requires the paged ring (the pool block is the quantization
        # unit), so it implies SERVE_PAGED=1 — with the OTHER paged
        # knobs (SERVE_BLOCK_SIZE / SERVE_PREFIX_CACHE /
        # SERVE_NUM_BLOCKS) honored exactly as under an explicit
        # SERVE_PAGED=1.
        kvq = os.environ.get("SERVE_KV_QUANT", "none")
        if kvq != "none":
            ring_kw["kv_quant"] = kvq
            if os.environ.get("SERVE_PAGED", "0") != "1":
                print("SERVE_KV_QUANT implies SERVE_PAGED=1 (the pool "
                      "block is the quantization unit)", flush=True)
        if os.environ.get("SERVE_PAGED", "0") == "1" or kvq != "none":
            ring_kw["paged"] = True
            ring_kw["block_size"] = int(
                os.environ.get("SERVE_BLOCK_SIZE", "256"))
            ring_kw["prefix_cache"] = os.environ.get(
                "SERVE_PREFIX_CACHE", "1") == "1"
            if os.environ.get("SERVE_NUM_BLOCKS"):
                ring_kw["num_blocks"] = int(os.environ["SERVE_NUM_BLOCKS"])
            # Hierarchical cache (docs/serving.md): a host-RAM spill
            # tier behind the radix cache — eviction DEMOTES refcount-0
            # cached blocks to pinned host memory instead of discarding
            # them, and a later hit promotes them back byte-exactly
            # (host RAM holds 10-100x more prefix blocks than the pool
            # at a transfer cost far below re-prefill).  Size it with
            # SERVE_HOST_CACHE_BLOCKS (blocks) or SERVE_HOST_CACHE_MB
            # (megabytes, converted at the pool's per-block host cost);
            # 0/unset (default) keeps behavior byte-identical to the
            # tier-less ring.  Pays when the tenant working set exceeds
            # the HBM pool; skip it for latency-bound single-tenant
            # rings whose working set already fits.
            host_blocks = int(os.environ.get("SERVE_HOST_CACHE_BLOCKS",
                                             "0"))
            host_mb = float(os.environ.get("SERVE_HOST_CACHE_MB", "0"))
            if not host_blocks and host_mb > 0:
                from paddle_operator_tpu.infer.paged import (
                    host_block_bytes,
                )

                host_blocks = int(host_mb * 1e6 // host_block_bytes(
                    cfg, ring_kw["block_size"], kvq))
            if host_blocks > 0:
                ring_kw["host_cache_blocks"] = host_blocks
        # SERVE_PREFILL=inline|chunked|disagg (docs/serving.md): how
        # admission prefill reaches the device.  ``chunked`` interleaves
        # SERVE_PREFILL_CHUNK-token slices into ring iterations so a
        # cold long prompt never stalls resident decode lanes for a
        # whole prefill; ``disagg`` moves cold prefills to a separate
        # executor thread + block pool entirely (implies SERVE_PAGED —
        # the handoff is block-granular).  Both are greedy-bit-identical
        # to inline (the dryrun serve-disagg gate pins it).
        prefill_mode = os.environ.get("SERVE_PREFILL", "inline")
        if prefill_mode != "inline":
            ring_kw["prefill_mode"] = prefill_mode
            if prefill_mode == "disagg" and not ring_kw.get("paged"):
                print("SERVE_PREFILL=disagg implies SERVE_PAGED=1 "
                      "(block-granular handoff)", flush=True)
        if prefill_mode == "disagg":
            # cross-host disaggregation (ISSUE 13, docs/serving.md
            # "Cross-host disaggregation"): SERVE_PREFILL_REMOTE=1
            # moves cold prefills to the PREFILL POOL's pods —
            # SERVE_PREFILL_BROKER (the fleet router, operator-
            # injected) forwards each job to the least-loaded ready
            # prefill pod; SERVE_PREFILL_PEERS is the router-less
            # static list.  Unset keeps the in-process executor.
            from paddle_operator_tpu.infer.prefill_serve import (
                remote_prefill_client_from_env,
            )

            rp = remote_prefill_client_from_env()
            if rp is not None:
                ring_kw["prefill_client"] = rp
            # Prefill-pool throughput (ISSUE 14): SERVE_PREFILL_LANES
            # widens the IN-PROCESS engine into an N-lane batched,
            # chunk-interleaved pool (1, the default, keeps the PR 6
            # monolithic engine — the parity oracle);
            # SERVE_PREFILL_STREAM=1 streams completed block groups to
            # the decode side while the rest of the prompt prefills;
            # SERVE_PREFILL_PREFIX_BLOCKS caps the engine's own radix
            # prefix cache (0 disables).  All three are engine-side
            # and greedy-bit-identical to the 1-lane monolithic path
            # (dryrun serve-prefillpool pins it).
            ring_kw["prefill_lanes"] = int(
                os.environ.get("SERVE_PREFILL_LANES", "1") or 1)
            ring_kw["prefill_stream"] = os.environ.get(
                "SERVE_PREFILL_STREAM", "0") == "1"
            ring_kw["prefill_prefix_blocks"] = int(
                os.environ.get("SERVE_PREFILL_PREFIX_BLOCKS", "0")
                or 0)
        if os.environ.get("SERVE_PREFILL_CHUNK"):
            ring_kw["prefill_chunk"] = int(
                os.environ["SERVE_PREFILL_CHUNK"])
        # SERVE_MEGASTEP=N (ISSUE 11, docs/serving.md "Megastep
        # execution"): fuse N ring iterations into ONE compiled
        # dispatch, with eos / token-budget / deadline-tick
        # continuation carried on device — amortizes the Python
        # dispatch tax ~N x on host-bound rings.  Admission,
        # preemption, promotions and handoff attaches move to megastep
        # boundaries, so a queued request can wait up to N iterations
        # for a lane (the TTFT-granularity tradeoff; keep N=1, the
        # byte-identical default, for latency-critical single-tenant
        # rings).
        # 0/unset = the server's single-step default (the CRD contract:
        # spec.serving.megastep 0 means "server default", and an
        # explicit SERVE_MEGASTEP=0 must disable fusion, not crash-loop
        # the pod on the >=1 constructor validation)
        megastep = int(os.environ.get("SERVE_MEGASTEP", "0") or 0)
        if megastep > 1:
            ring_kw["megastep"] = megastep
        # SERVE_TRACE=1 (ISSUE 15, docs/observability.md): per-request
        # span capture — requests carry X-Tpujob-Trace contexts, phase
        # spans ride response metadata, and the router stitches
        # cross-pod timelines at /debug/tracez.  Off (default) every
        # capture site is one attribute check; on, token streams are
        # still byte-identical (host timestamps only — the serve-trace
        # dryrun line pins both).  The latency histograms and the
        # flight recorder are always on.
        ring_kw["trace"] = os.environ.get("SERVE_TRACE", "0") == "1"
        # Multi-tenant QoS (ISSUE 10, docs/serving.md):
        # SERVE_PRIORITIES classes (0 most urgent; default 2, requests
        # default to the least urgent — opt-in boosts only), and the
        # preemption knobs: SERVE_PREEMPT=0 disables lane spill,
        # SERVE_PREEMPT_MAX_PER_REQ / SERVE_PREEMPT_BUDGET /
        # SERVE_PREEMPT_WINDOW_S bound thrash.  Defaults are
        # byte-identical to the single-FIFO ring for unannotated
        # traffic.
        from paddle_operator_tpu.infer.qos import (
            AdapterRegistry,
            QoSConfig,
        )

        ring_kw["qos"] = QoSConfig.from_env()
        # SERVE_ADAPTERS: comma list of LoRA adapters served off this
        # ONE base param set (S-LoRA style) — ``name`` (deterministic
        # random smoke adapter), ``name:seed:<int>``, or
        # ``name:/path/to/deltas.npz``.  SERVE_ADAPTER_RANK /
        # SERVE_MAX_ADAPTERS size the fixed-shape pool; per-request
        # ``adapter`` (body key) selects one.  More load/evict at
        # runtime via POST /v1/adapters.
        if spec_k == 0:
            adapters = AdapterRegistry.from_env(cfg)
            if adapters is not None:
                ring_kw["adapters"] = adapters
        elif os.environ.get("SERVE_ADAPTERS", "").strip():
            print("SERVE_ADAPTERS ignored: adapters are not supported "
                  "on speculative rings (the draft proposes base-only)",
                  flush=True)
        if spec_k > 0:
            # SERVE_SPEC_K=K: speculative decoding through the ring.
            # SERVE_DRAFT names the draft config — "auto" derives the
            # shallow/narrow companion (LlamaConfig.draft), any preset
            # name uses that config.  Draft weights restore from
            # TPUJOB_DRAFT_CHECKPOINT_PATH when set (fresh init
            # otherwise — smoke mode, acceptance ~1/vocab).
            draft_name = os.environ.get("SERVE_DRAFT", "auto")
            if draft_name == "auto":
                dcfg = cfg.draft()
            else:
                from paddle_operator_tpu.models.llama import CONFIGS

                dcfg = CONFIGS[draft_name]
            from paddle_operator_tpu.infer.speculative import (
                check_draft_compat,
            )

            check_draft_compat(cfg, dcfg)
            dpath = os.environ.get("TPUJOB_DRAFT_CHECKPOINT_PATH")
            dparams, _ = load_serving_params(
                dcfg, CheckpointManager(dpath) if dpath else None,
                seed=1, mesh=mesh)
            if swap_base is not None:
                swap_base["draft_params"] = jax.device_get(dparams)
                swap_base["draft_quant"] = (
                    os.environ.get("SERVE_DRAFT_QUANT", "none")
                    or "none")
            # SERVE_DRAFT_QUANT=int8|int4: quantize the DRAFT only.
            # Spec verify tolerates draft drift by construction — a
            # coarser draft can only lower accept rate, never change
            # emitted tokens — so this is a pure accept-rate/latency
            # trade and the proving ground before SERVE_WEIGHT_QUANT.
            dwq = os.environ.get("SERVE_DRAFT_QUANT", "none") or "none"
            if dwq != "none":
                from paddle_operator_tpu.infer.quant import (
                    SERVING_SKIP,
                    quantize_params,
                )

                dparams = quantize_params(dparams, dcfg, mode=dwq,
                                          skip=SERVING_SKIP)
            ring_kw.update(
                draft_params=dparams, draft_cfg=dcfg, spec_k=spec_k)
    # the decode path this process will trace and the device it runs on:
    # a deployment that landed on the wrong one says so in its first line
    decode_attn, _ = D.resolve_decode_attn(cfg, mesh)
    dev = jax.devices()[0]
    print(f"serving {os.environ.get('MODEL_PRESET', '7b')} "
          f"(platform={dev.platform}, device_kind={dev.device_kind!r}, "
          f"devices={len(jax.devices())}, decode_attn={decode_attn}, "
          f"resumed={resumed}, "
          f"quantize={os.environ.get('QUANTIZE', 'off')}, "
          f"weight_quant={wq}, "
          f"draft_quant={os.environ.get('SERVE_DRAFT_QUANT', 'none') or 'none'}, "
          f"tp={tp}, spec_k={spec_k if continuous else 0}, "
          f"prefill={ring_kw.get('prefill_mode', 'inline') if continuous else '-'}, "
          f"kv_quant={ring_kw.get('kv_quant', 'none') if continuous else '-'}, "
          f"megastep={ring_kw.get('megastep', 1) if continuous else '-'}, "
          f"mode={'continuous' if continuous else 'batch'}) on :{env.port}",
          flush=True)
    srv = make_server("0.0.0.0", env.port, params, cfg,
                      continuous=continuous, mesh=mesh,
                      # fleet identity (operator-injected): labels this
                      # replica's /metrics gauges so the router and the
                      # fleet status block can tell replicas apart
                      job=os.environ.get("TPUJOB_NAME", "local"),
                      replica=os.environ.get("TPUJOB_REPLICA_ID", ""),
                      **ring_kw)
    # the /v1/swap handler reaches the retained base via self.server
    srv.swap_base = swap_base if continuous else None
    # weights + KV pool are resident now; under SERVE_TP no device may
    # hold more than its shard (memory_stats is None on the CPU backend)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in (mesh.devices.flat if mesh is not None else [dev])]
    print(f"serving ready: device_bytes_in_use={in_use}", flush=True)
    # SIGTERM drain (docs/fault-tolerance.md, serving pods): the SAME
    # PreemptionWatcher contract the trainer uses — stop admissions
    # (503 + Retry-After), finish in-flight lanes within the drain
    # budget, flush partials, exit EXIT_PREEMPTED so the reconciler
    # counts the restart as preempted, not failed.  A second SIGTERM
    # exits immediately (partials flushed best-effort).
    from paddle_operator_tpu.ft.preemption import PreemptionWatcher
    from paddle_operator_tpu.infer.chaos import maybe_install_from_env
    from paddle_operator_tpu.infer.resilience import ServingDrain

    batcher = srv.generator.batcher if continuous else None
    if batcher is not None:
        # SERVE_PREWARM=0 opts out of the off-thread warm RUN of the
        # step, suffix-ladder, megastep and chunked/disagg programs
        # (and of its throwaway second pool); the prefill inserts are
        # executables before this process listens either way
        ready_ring(batcher,
                   prewarm=os.environ.get("SERVE_PREWARM", "1") == "1")
        # the other kernel choice this process made, rung by rung (the
        # first line has the decode kernel's): /statusz carries the same
        print("prefill inserts ready: prefill_attn="
              f"{json.dumps(batcher.executor.prefill_attn)}", flush=True)
        # TPUJOB_CHAOS: deterministic fault injection on the live ring
        # (smoke-testing a deployment's resilience end-to-end)
        maybe_install_from_env(batcher)
        wire_fleet_kv_from_env(batcher, env.port)
        wire_kv_store_from_env(batcher)
    watcher = PreemptionWatcher.install()
    drain = ServingDrain(
        srv, srv.state, batcher=batcher,
        budget_s=float(os.environ.get("SERVE_DRAIN_BUDGET_S", "30")))
    drain.install(watcher)
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
