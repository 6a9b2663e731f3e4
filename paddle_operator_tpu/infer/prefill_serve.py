"""Standalone prefill server — the cross-host half of disaggregation.

ISSUE 6 shipped DistServe-style disaggregated prefill IN-PROCESS: a
:class:`~paddle_operator_tpu.infer.executor.PrefillExecutor` thread with
its own block pool, handing completed prompts to the decode ring by
device-to-device block copy (``paged.make_pool_transfer`` — whose
docstring explicitly reserved "a DCN-crossing variant would replace
only this op").  This module is that variant (ISSUE 13): the SAME
``PrefillExecutor`` wrapped in its own HTTP process, so prefill
capacity scales in its OWN pods, independently of decode — the
DistServe argument realized at the pod level.

Protocol (one round-trip, prefill is side-effect-free so retries are
always safe):

    POST /v1/prefill   {"tokens": [...], "temperature": t, "seed": s,
                        "fingerprint": {...}, "requestId": "..."}
    -> 200  application/octet-stream: a fleetkv HANDOFF envelope
            (utils/fleetkv.encode_handoff — dtype/shape manifest +
            CRC + fingerprint; the decode side refuses WHOLESALE on
            any mismatch)
    -> 409  fingerprint mismatch (mixed fleet config — never serve
            bytes the decode pool would misinterpret)
    -> 503  draining / overloaded: the decode side retries another
            pod (a draining prefill pod REFUSES handoffs; in-flight
            jobs finish and their responses complete)

The decode replica's :class:`RemotePrefillClient` plugs into the ring
scheduler exactly where the in-process executor sits (same
``submit(req, slot)`` / ``results`` queue contract), POSTs on worker
threads (never the ring thread), and posts host payloads the scheduler
lands through the PR 8 promote scatter — so remote-disagg output is
greedy-bit-identical to in-process disagg (dryrun ``serve-xdisagg``).

Drain (docs/fault-tolerance.md): SIGTERM flips /readyz false and new
prefills 503; in-flight jobs finish and flush their responses inside
the budget; exit EXIT_PREEMPTED=83 — the reconciler counts the pod
preempted, not failed.  "Prefill pods drain by finishing/refusing
handoffs."
"""

from __future__ import annotations

import json
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from paddle_operator_tpu.utils import tracing as TRC

# One whole-prompt forward per job, bounded by model size — generous
# enough for a cold 7B 2k-token prefill on real chips, small enough
# that a wedged pod sheds its waiters onto healthy peers.
PREFILL_TIMEOUT_S = 120.0


def handoff_fingerprint(cfg, *, block_size: int, kv_quant: str,
                        top_k: Optional[int],
                        top_p: Optional[float],
                        wquant: str = "none",
                        generation: int = 0) -> Dict[str, Any]:
    """The geometry + sampling rule a handoff envelope must match.
    Narrower than the lane-migration fingerprint on purpose: spec
    depth is absent (the DRAFT lane prefills decode-side at attach —
    the snapshot is target KV only) and tp is absent (host bytes
    re-shard through the promote scatter).  top-k/top-p ARE included:
    the prefill pod samples the FIRST token, so a sampling-rule skew
    would silently break bit-identity with the in-process path.
    ``wquant`` (ISSUE 16) is the WEIGHT quant mode: handed-off KV is a
    function of the weights that produced it, so a bf16 prefill pod
    feeding an int8 decode ring would silently break token-identity
    with the in-process cold path — refuse the mixed fleet instead.
    ``generation`` (ISSUE 19) is the WEIGHT generation for the same
    reason: during a fleet rolling swap a prefill pod still on
    checkpoint r must not feed KV into a decode ring already on r+1 —
    the mismatch 409s and the decode side falls back/retries until
    the pool rolls."""
    return {"layers": int(cfg.n_layers),
            "kvHeads": int(cfg.n_kv_heads),
            "headDim": int(cfg.head_dim),
            "blockSize": int(block_size),
            "quant": kv_quant,
            "wquant": wquant,
            "gen": int(generation),
            "topK": top_k, "topP": top_p}


class _Job:
    """The request shim the PrefillExecutor thread reads (it only
    touches prompt/dev_prompt/temperature/seed/adapter_idx and the
    done/_cancel lifecycle flags).  ``wants_frames`` (ISSUE 14
    streamed handoff): the matcher routes the engine's block-group
    frame items into ``frames`` for the chunked HTTP response;
    without it frames are dropped and only the terminal result
    lands."""

    __slots__ = ("prompt", "temperature", "seed", "adapter_idx",
                 "done", "_cancel", "dev_prompt", "result", "error",
                 "t0", "accounted", "wants_frames", "frames")

    def __init__(self, prompt: Sequence[int], temperature: float,
                 seed: int, wants_frames: bool = False) -> None:
        import jax.numpy as jnp

        self.prompt = [int(t) for t in prompt]
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.adapter_idx = 0
        self.done = threading.Event()
        self._cancel = False
        self.dev_prompt = jnp.asarray(
            np.asarray(self.prompt, np.int32)[None, :])
        self.result: Optional[Tuple[Any, ...]] = None
        self.error: Optional[Exception] = None
        self.t0 = time.monotonic()
        # exactly-once depth accounting (under the frontend lock): a
        # timed-out job may be dropped by the executor while QUEUED
        # (no result ever posted) or may still finish and post one —
        # whichever side settles first decrements, the other skips
        self.accounted = False
        self.wants_frames = bool(wants_frames)
        self.frames: Optional["queue.Queue[tuple]"] = (
            queue.Queue() if wants_frames else None)


class PrefillFrontend:
    """The jax half of the prefill server: one PrefillExecutor plus a
    matcher thread that resolves per-job events from its results
    queue, and the snapshot -> host-bytes conversion the wire needs.
    Kept separate from the HTTP shell so tests (and the dryrun gate)
    can drive it in-process."""

    def __init__(self, params: Any, cfg, *, block_size: int,
                 max_len: int, buckets: Tuple[int, ...] = (),
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None, mesh=None,
                 kv_quant: str = "none", lanes: int = 1,
                 prefill_chunk: int = 64,
                 prefix_blocks: int = 0,
                 generation: int = 0) -> None:
        from paddle_operator_tpu.infer import decode as D
        from paddle_operator_tpu.infer import executor as X

        from paddle_operator_tpu.infer import quant as Q

        if mesh is not None and D.mesh_tp(mesh) > 1:
            params = D.shard_params_for_serving(params, cfg, mesh)
        self.cfg = cfg
        self.block_size = int(block_size)
        self.kv_quant = kv_quant
        # detected, not configured: the leaf types of the tree actually
        # dispatched decide the fingerprint (matches the decode side)
        self.wquant = Q.weight_quant_mode(params)
        # weight generation (ISSUE 19): rides the handoff fingerprint
        # so a rolling fleet swap 409s cross-generation handoffs
        self.generation = int(generation)
        self.quant = kv_quant == "int8"
        self.top_k, self.top_p = top_k, top_p
        self.lanes = max(1, int(lanes))
        # the N-lane engine always produces frame items (streaming
        # clients consume them; the matcher drops them for jobs that
        # did not ask) — the 1-lane oracle engine never does
        self.exec = X.PrefillExecutor(
            params, cfg, max_len=max_len, block_size=self.block_size,
            buckets=tuple(buckets) or (max_len,), top_k=top_k,
            top_p=top_p, mesh=mesh, kv_quant=kv_quant,
            lanes=self.lanes, prefill_chunk=prefill_chunk,
            stream=self.lanes > 1, prefix_blocks=prefix_blocks)
        self.draining = False
        self._lock = threading.Lock()
        self._depth = 0
        self.stats = {"jobs": 0, "prompt_tokens": 0, "errors": 0,
                      "refused": 0}
        # rolling per-job wall EMA — the gauge the SLO autoscaler
        # converts a TTFT target into a queue-depth bound with
        self.prefill_ms_avg = 0.0
        # flight recorder (ISSUE 15): the prefill pod's own bounded
        # event ring — refusals, per-job errors, drain transitions —
        # served at /debug/flightrec and dumped on SIGTERM
        import os as _os

        self.flightrec = TRC.FlightRecorder(
            pod=_os.environ.get("TPUJOB_REPLICA_ID", ""))
        self._t_start = time.monotonic()
        self._stop = threading.Event()
        self._matcher = threading.Thread(target=self._match_loop,
                                         daemon=True,
                                         name="prefill-match")
        self._matcher.start()

    def fingerprint(self) -> Dict[str, Any]:
        return handoff_fingerprint(
            self.cfg, block_size=self.block_size,
            kv_quant=self.kv_quant, top_k=self.top_k, top_p=self.top_p,
            wquant=self.wquant, generation=self.generation)

    def depth(self) -> int:
        with self._lock:
            return self._depth

    def _match_loop(self) -> None:
        results = self.exec.results
        while not self._stop.is_set():
            try:
                item = results.get(timeout=0.05)
            except queue.Empty:
                continue
            if isinstance(item[0], str):
                # N-lane engine protocol (ISSUE 14): frames route to
                # streaming jobs; the terminal item completes the job
                kind = item[0]
                job = item[1]
                if kind == "frame":
                    if job.wants_frames and not job.done.is_set():
                        job.frames.put(item)
                    continue
                # ("final", job, slot, snap, lane, j0, n_blocks,
                #  first, t_done)
                job.result = (item[3], item[4], item[5], item[6],
                              int(np.asarray(item[7])), item[8])
                if job.wants_frames:
                    job.frames.put(item)
                self._settle(job)
                continue
            job = item[0]
            if len(item) == 3:
                job.error = item[2]
                if job.wants_frames:
                    job.frames.put(("error", job, item[2]))
            else:
                _, _, snap, n_blocks, first = item
                job.result = (snap, None, 0, n_blocks,
                              int(np.asarray(first)), time.monotonic())
            self._settle(job)

    def _settle(self, job: "_Job") -> None:
        ms = (time.monotonic() - job.t0) * 1e3
        with self._lock:
            if not job.accounted:
                job.accounted = True
                self._depth -= 1
                self.prefill_ms_avg = (
                    ms if not self.prefill_ms_avg
                    else 0.8 * self.prefill_ms_avg + 0.2 * ms)
        job.done.set()

    def _block_ids(self, lane: Optional[int], j0: int,
                   j1: int) -> np.ndarray:
        """Pool block ids backing a job's blocks [j0, j1): the 1-lane
        engine's fixed identity rows 1..M, or lane ``lane``'s identity
        rows on the N-lane engine."""
        if lane is None:
            return np.arange(1 + j0, 1 + j1)
        return self.exec.tables[lane][j0:j1]

    def _host_blocks(self, snap, lane: Optional[int], j0: int,
                     j1: int) -> Dict[str, np.ndarray]:
        """Snapshot -> host bytes for blocks [j0, j1).  jax arrays are
        immutable, so this read races nothing even while the engine
        writes fresh pool versions."""
        ids = self._block_ids(lane, j0, j1)
        arrays: Dict[str, np.ndarray] = {
            "k": np.asarray(snap["k"])[:, ids],
            "v": np.asarray(snap["v"])[:, ids],
        }
        if self.quant:
            arrays["ks"] = np.asarray(snap["ks"])[:, ids]
            arrays["vs"] = np.asarray(snap["vs"])[:, ids]
        return arrays

    def _submit(self, tokens: Sequence[int], temperature: float,
                seed: int, wants_frames: bool = False) -> "_Job":
        job = _Job(tokens, temperature, seed,
                   wants_frames=wants_frames)
        with self._lock:
            self._depth += 1
        self.exec.submit(job, 0)
        return job

    def _timeout(self, job: "_Job", timeout: float) -> None:
        job._cancel = True      # dropped at the executor if queued
        # a QUEUED cancelled job never posts a result, so the
        # matcher never sees it — settle the depth here (the
        # ``accounted`` flag keeps a mid-flight job that still
        # finishes from decrementing twice)
        with self._lock:
            if not job.accounted:
                job.accounted = True
                self._depth -= 1
        raise TimeoutError(
            f"prefill did not finish within {timeout}s")

    def prefill(self, tokens: Sequence[int], temperature: float,
                seed: int,
                timeout: float = PREFILL_TIMEOUT_S) -> bytes:
        """Run one whole-prompt prefill and return its HANDOFF
        envelope.  Raises on executor failure or timeout — the HTTP
        shell maps those to error responses, and the decode side
        fails (or retries) that one request."""
        from paddle_operator_tpu.utils import fleetkv as FK

        job = self._submit(tokens, temperature, seed)
        if not job.done.wait(timeout):
            self.flightrec.record("prefill_timeout",
                                  tokens=len(job.prompt))
            self._timeout(job, timeout)
        if job.error is not None:
            with self._lock:
                self.stats["errors"] += 1
            self.flightrec.record("prefill_error",
                                  error=str(job.error)[:200])
            raise job.error
        snap, lane, _, n_blocks, first, _ = job.result
        arrays = self._host_blocks(snap, lane, 0, n_blocks)
        if self.quant:
            # the prompt's partial last block lives EXACT in the
            # engine lane's staging-tail row — it lands in the decode
            # tail row ``slot`` at attach
            trow = 0 if lane is None else lane
            arrays["kt"] = np.asarray(snap["kt"])[:, trow:trow + 1]
            arrays["vt"] = np.asarray(snap["vt"])[:, trow:trow + 1]
        with self._lock:
            self.stats["jobs"] += 1
            self.stats["prompt_tokens"] += len(job.prompt)
        meta = {"first": first, "promptLen": len(job.prompt),
                "nBlocks": int(n_blocks),
                "fingerprint": self.fingerprint()}
        return FK.encode_handoff(meta, arrays)

    def prefill_stream(self, tokens: Sequence[int], temperature: float,
                       seed: int, timeout: float = PREFILL_TIMEOUT_S):
        """STREAMED prefill (ISSUE 14): yield length-prefixed wire
        frames — completed block groups as they finish, then the
        terminal frame (remaining blocks + staging tail + first token
        + fingerprint) — so the decode side's upload and the wire
        transfer overlap the remaining prefill compute.  Raises
        TimeoutError/executor errors BEFORE the first yield (mapped to
        HTTP statuses); after the first frame the handler can only
        drop the connection, which the client refuses wholesale."""
        from paddle_operator_tpu.utils import fleetkv as FK

        job = self._submit(tokens, temperature, seed,
                           wants_frames=self.lanes > 1)
        if job.frames is None:
            # 1-lane oracle engine: no frames exist — one terminal
            # frame carries the whole handoff (a valid 1-frame stream)
            buf = None
            if not job.done.wait(timeout):
                self._timeout(job, timeout)
            if job.error is not None:
                with self._lock:
                    self.stats["errors"] += 1
                raise job.error
            snap, lane, _, n_blocks, first, t_done = job.result
            arrays = self._host_blocks(snap, lane, 0, n_blocks)
            if self.quant:
                arrays["kt"] = np.asarray(snap["kt"])[:, 0:1]
                arrays["vt"] = np.asarray(snap["vt"])[:, 0:1]
            with self._lock:
                self.stats["jobs"] += 1
                self.stats["prompt_tokens"] += len(job.prompt)
            yield FK.encode_handoff_final(
                {"seq": 0, "nFrames": 1, "j0": 0, "first": first,
                 "promptLen": len(job.prompt), "nBlocks": int(n_blocks),
                 "fingerprint": self.fingerprint(),
                 "tDone": t_done}, arrays)
            return
        deadline = time.monotonic() + timeout
        seq = 0
        while True:
            try:
                item = job.frames.get(
                    timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                self._timeout(job, timeout)
            if item[0] == "error":
                with self._lock:
                    self.stats["errors"] += 1
                raise item[2]
            if item[0] == "frame":
                _, _, _, snap, lane, j0, j1 = item
                yield FK.encode_handoff_frame(
                    seq, j0, self._host_blocks(snap, lane, j0, j1))
                seq += 1
                continue
            # terminal
            snap, lane, j0, n_blocks, first, t_done = job.result
            arrays = self._host_blocks(snap, lane, j0, n_blocks)
            if self.quant:
                arrays["kt"] = np.asarray(snap["kt"])[:, lane:lane + 1]
                arrays["vt"] = np.asarray(snap["vt"])[:, lane:lane + 1]
            with self._lock:
                self.stats["jobs"] += 1
                self.stats["prompt_tokens"] += len(job.prompt)
            yield FK.encode_handoff_final(
                {"seq": seq, "nFrames": seq + 1, "j0": int(j0),
                 "first": int(first), "promptLen": len(job.prompt),
                 "nBlocks": int(n_blocks),
                 "fingerprint": self.fingerprint(),
                 "tDone": float(t_done)}, arrays)
            return

    def serving_status(self) -> Dict[str, Any]:
        """The prefill pod's status block.  ``role: "prefill"`` is the
        marker ``aggregate_fleet_serving`` keys on so a pool that
        never decodes cannot skew the fleet's token-weighted tok/s or
        hit-rate aggregates; ``tokensPerSec`` here is PREFILL
        tokens/s (folded into the fleet's ``prefillTokensPerSec``)."""
        elapsed = max(1e-9, time.monotonic() - self._t_start)
        with self._lock:
            return {
                "role": "prefill",
                "prefillQueueDepth": self._depth,
                "prefillMsAvg": round(self.prefill_ms_avg, 3),
                "tokensPerSec": round(
                    self.stats["prompt_tokens"] / elapsed, 2),
                "tokensTotal": self.stats["prompt_tokens"],
                "prefillJobs": self.stats["jobs"],
                "prefillErrors": self.stats["errors"],
                "refusedHandoffs": self.stats["refused"],
                # prefill-pool throughput (ISSUE 14): engine width,
                # batch occupancy EMA (busy lanes / N per iteration)
                # and head-of-line wait p95 — what the SLO autoscaler
                # divides by so a half-empty batch never reads as a
                # saturated pool
                "prefillLanes": self.lanes,
                "prefillBatchOccupancy": self.exec.batch_occupancy(),
                "prefillHolWaitMs": self.exec.hol_wait_ms_p95(),
                "prefillPrefixHits": self.exec.prefix_hits,
                "draining": self.draining,
            }

    def metrics_text(self, job: str, replica: str) -> str:
        """Prometheus exposition for the router's scrape — reuses the
        fleet gauge NAMES (queue depth under mode="remote", tok/s,
        draining) plus the prefill-only service-time gauge, so one
        scrape parser serves both pools."""
        st = self.serving_status()
        rep = f',replica="{replica}"' if replica else ""
        lbl = f'{{job="{job}"{rep}}}'
        lines = [
            (f'tpujob_serve_prefill_queue_depth{{job="{job}"{rep},'
             f'mode="remote"}} {float(st["prefillQueueDepth"])}'),
            f'tpujob_serve_prefill_ms_avg{lbl} '
            f'{float(st["prefillMsAvg"])}',
            f'tpujob_serve_prefill_jobs_total{lbl} '
            f'{float(st["prefillJobs"])}',
            f'tpujob_serve_tokens_per_sec{lbl} '
            f'{float(st["tokensPerSec"])}',
            # prefill-pool throughput gauges (ISSUE 14) — the router
            # scrapes these into /statusz and the autoscaler's prefill
            # denominator reads occupancy + lanes
            f'tpujob_serve_prefill_lanes{lbl} '
            f'{float(st["prefillLanes"])}',
            f'tpujob_serve_prefill_batch_occupancy{lbl} '
            f'{float(st["prefillBatchOccupancy"])}',
            f'tpujob_serve_prefill_hol_wait_ms{lbl} '
            f'{float(st["prefillHolWaitMs"])}',
            f'tpujob_serve_draining{lbl} '
            f'{1.0 if st["draining"] else 0.0}',
        ]
        return "\n".join(lines) + "\n"

    def close(self) -> None:
        self._stop.set()
        self.exec.close()
        self._matcher.join(timeout=10)


class _PrefillHandler(BaseHTTPRequestHandler):
    frontend: PrefillFrontend    # injected
    job_key = "local"
    replica_id = ""
    protocol_version = "HTTP/1.1"
    timeout = 120

    def log_message(self, *a):
        pass

    def _send_json(self, code: int, obj, headers=None) -> None:
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        fe = self.frontend
        if self.path == "/healthz":
            self._send_json(200, {"ok": True})
        elif self.path == "/readyz":
            if fe.draining:
                self._send_json(503, {"ready": False,
                                      "reason": "draining"},
                                headers={"Retry-After": 5})
            else:
                self._send_json(200, {"ready": True})
        elif self.path == "/statusz":
            st = fe.serving_status()
            if self.replica_id:
                st["replica"] = self.replica_id
            self._send_json(200, st)
        elif self.path == "/metrics":
            body = fe.metrics_text(self.job_key,
                                   self.replica_id).encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/debug/flightrec":
            # the prefill pod's event ring (ISSUE 15) — same contract
            # as the decode replicas' endpoint
            self._send_json(200, fe.flightrec.dump("debug_endpoint"))
        else:
            self._send_json(404, {})

    def do_POST(self):
        from paddle_operator_tpu.utils.fleetkv import (
            EnvelopeError,
            check_fingerprint,
        )

        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n) if n else b""
        if self.path != "/v1/prefill":
            self._send_json(404, {})
            return
        fe = self.frontend
        if fe.draining:
            # refusing handoffs IS the prefill pod's drain protocol:
            # the decode side retries another pod, and the in-flight
            # jobs below this point finish and flush
            with fe._lock:
                fe.stats["refused"] += 1
            fe.flightrec.record("handoff_refused", reason="draining")
            self._send_json(503, {"error": "draining"},
                            headers={"Retry-After": 2})
            return
        try:
            req = json.loads(body)
            tokens = [int(t) for t in req["tokens"]]
            if not tokens:
                raise ValueError("empty prompt")
            theirs = req.get("fingerprint")
            if theirs is not None:
                check_fingerprint({"fingerprint": theirs},
                                  fe.fingerprint())
        except EnvelopeError as e:
            self._send_json(409, {"error": str(e)})
            return
        except (ValueError, KeyError, TypeError,
                json.JSONDecodeError) as e:
            self._send_json(400, {"error": str(e)})
            return
        if req.get("stream"):
            return self._stream_prefill(fe, req, tokens)
        try:
            buf = fe.prefill(tokens,
                             float(req.get("temperature", 0.0)),
                             int(req.get("seed", 0)))
        except TimeoutError as e:
            # overload (a backlogged pod), not a per-prompt defect:
            # 503 like draining so the decode side / router walks to
            # the next candidate instead of hard-failing the request
            self._send_json(503, {"error": str(e)},
                            headers={"Retry-After": 2})
            return
        except Exception as e:      # noqa: BLE001 — isolate per job
            # a deterministic per-prompt failure (bucket overflow,
            # compile error): NOT retriable — the decode side fails
            # that one request instead of hammering every pod
            self._send_json(500, {"error": str(e)})
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(buf)))
        self.end_headers()
        self.wfile.write(buf)

    def _stream_prefill(self, fe, req, tokens) -> None:
        """``"stream": true`` (ISSUE 14): chunked transfer of
        length-prefixed handoff frames as block groups complete — the
        decode side uploads each frame while this pod still computes
        the rest of the prompt.  Errors BEFORE the first frame map to
        HTTP statuses exactly like the monolithic path; after it the
        only honest signal is dropping the connection, which the
        receiver refuses wholesale (per-frame CRC + the terminal
        frame's count make any partial stream unusable by
        construction)."""
        gen = fe.prefill_stream(tokens,
                                float(req.get("temperature", 0.0)),
                                int(req.get("seed", 0)))
        try:
            first_frame = next(gen)
        except TimeoutError as e:
            self._send_json(503, {"error": str(e)},
                            headers={"Retry-After": 2})
            return
        except StopIteration:
            self._send_json(500, {"error": "empty handoff stream"})
            return
        except Exception as e:      # noqa: BLE001
            self._send_json(500, {"error": str(e)})
            return
        try:
            self.send_response(200)
            self.send_header("Content-Type",
                             "application/octet-stream")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def emit(wire: bytes) -> None:
                self.wfile.write(f"{len(wire):x}\r\n".encode() + wire
                                 + b"\r\n")
                self.wfile.flush()

            emit(first_frame)
            for wire in gen:
                emit(wire)
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            return      # client gone mid-stream: nothing to say
        except Exception:   # noqa: BLE001 — engine died mid-stream
            # drop the connection: the receiver sees a truncated
            # frame and refuses the whole stream
            try:
                self.wfile.flush()
            except OSError:
                pass
            self.close_connection = True


def make_prefill_server(host: str, port: int, params: Any, cfg, *,
                        block_size: int = 256,
                        max_len: Optional[int] = None,
                        buckets: Tuple[int, ...] = (),
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None, mesh=None,
                        kv_quant: str = "none", job: str = "local",
                        replica: str = "", lanes: int = 1,
                        prefill_chunk: int = 64,
                        prefix_blocks: int = 0,
                        generation: int = 0) -> ThreadingHTTPServer:
    """HTTP shell around a PrefillFrontend.  The returned server
    carries ``.frontend`` — close it when tearing down."""
    fe = PrefillFrontend(params, cfg, block_size=block_size,
                         max_len=max_len or cfg.max_seq_len,
                         buckets=buckets, top_k=top_k, top_p=top_p,
                         mesh=mesh, kv_quant=kv_quant, lanes=lanes,
                         prefill_chunk=prefill_chunk,
                         prefix_blocks=prefix_blocks,
                         generation=generation)
    handler = type("PrefillHandler", (_PrefillHandler,),
                   {"frontend": fe, "job_key": job,
                    "replica_id": replica})
    srv = ThreadingHTTPServer((host, port), handler)
    srv.frontend = fe
    return srv


# ---------------------------------------------------------------------------
# Decode-side client: the network stand-in for the in-process executor
# ---------------------------------------------------------------------------


class RemotePrefillClient:
    """The decode replica's prefill-pool client — a drop-in for the
    in-process :class:`PrefillExecutor` at the scheduler seam (same
    ``submit(req, slot)`` / ``results`` queue contract, marked
    ``remote = True`` so the handoff drain lands host payloads through
    the promote scatter instead of the device-to-device copy).

    POSTs run on worker threads, never the ring thread.  ``broker``
    (the fleet router, which forwards ``/v1/prefill`` to the
    least-loaded ready prefill pod) is preferred; static ``peers``
    are the router-less fallback.  Prefill is SIDE-EFFECT-FREE, so —
    unlike lane migration — every failure mode retries freely:
    connection errors and 503s (draining pod) walk to the next
    attempt, and only a deterministic 4xx/5xx fails the request.
    Exhausted attempts post a retriable error: the request 503s and
    the client's fleet-level retry re-routes it."""

    remote = True

    def __init__(self, broker: str = "", peers: Sequence[str] = (), *,
                 timeout: float = PREFILL_TIMEOUT_S, workers: int = 2,
                 max_attempts: int = 4,
                 backoff_s: float = 0.2,
                 stream: bool = False) -> None:
        self.broker = broker.strip().rstrip("/")
        self.peers = [p.strip() for p in peers if p.strip()]
        if not self.broker and not self.peers:
            raise ValueError("remote prefill needs a broker or peers")
        self.timeout = timeout
        self.max_attempts = max_attempts
        self.backoff_s = backoff_s
        # streamed handoff (ISSUE 14): frames post to the scheduler as
        # they arrive off the wire, so the promote upload overlaps the
        # pod's remaining prefill compute AND the DCN transfer
        self.stream = bool(stream)
        # the ring's handoff fingerprint — stamped by the scheduler at
        # construction (it owns cfg/block_size/quant/top-k/top-p)
        self.fingerprint: Optional[Dict[str, Any]] = None
        self.jobs: "queue.Queue[tuple]" = queue.Queue()
        self.results: "queue.Queue[tuple]" = queue.Queue()
        self.stats = {"posted": 0, "retries": 0, "failed": 0,
                      # streams refused WHOLESALE: mid-stream pod
                      # death, truncated / CRC-bad / out-of-order
                      # frames (each walked to the next candidate)
                      "refused_streams": 0}
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"remote-prefill-{i}")
            for i in range(max(1, int(workers)))]
        for t in self._threads:
            t.start()

    def submit(self, req, slot: int) -> None:
        self.jobs.put((req, slot))

    def _targets(self) -> list:
        if self.broker:
            return [self.broker] * self.max_attempts
        reps = -(-self.max_attempts // len(self.peers))
        return (self.peers * reps)[:self.max_attempts]

    def _worker(self) -> None:
        from paddle_operator_tpu.infer.resilience import RetriableError
        from paddle_operator_tpu.utils import fleetkv as FK

        while not self._stop.is_set():
            try:
                req, slot = self.jobs.get(timeout=0.05)
            except queue.Empty:
                continue
            if req.done.is_set() or req._cancel:
                continue            # resolved while queued: drop
            body = json.dumps({
                "tokens": [int(t) for t in req.prompt],
                "temperature": float(req.temperature),
                "seed": int(req.seed),
                "requestId": getattr(req, "request_id", None),
                "fingerprint": self.fingerprint,
                "stream": self.stream,
            }).encode()
            outcome = None
            t_wire0 = time.monotonic()
            if self.stream:
                for i, ep in enumerate(self._targets()):
                    if req.done.is_set() or req._cancel:
                        break       # late resolution: stop POSTing
                    if i:
                        self.stats["retries"] += 1
                        # shared fleet backoff law (ISSUE 20
                        # satellite) — jittered exponential, same as
                        # the non-stream path below
                        time.sleep(FK.backoff_delay(
                            i - 1, base_s=self.backoff_s, max_s=1.0))
                    res = self._stream_attempt(ep, body, req, slot)
                    if res == "next":
                        continue
                    if res == "done":
                        self._wire_span(req, t_wire0, ep, i,
                                        stream=True)
                    outcome = res
                    break
            else:
                # the whole walk — conn errors, 503 (draining pod) and
                # 409 (fingerprint mismatch mid rolling swap, an
                # already-rolled peer may match) retry to the next
                # candidate with jittered backoff, Retry-After honored
                # — is the shared bounded-retry helper (ISSUE 20
                # satellite); prefill is side-effect-free so retrying
                # freely is always safe
                attempts = [0]

                def _on_retry(ep, i):
                    attempts[0] = i + 1
                    self.stats["retries"] += 1

                code, raw, used = FK.http_post_retry(
                    [self.broker] if self.broker else self.peers,
                    "/v1/prefill", body,
                    content_type="application/json",
                    timeout=self.timeout,
                    max_attempts=self.max_attempts,
                    backoff_base_s=self.backoff_s, backoff_max_s=1.0,
                    retry_statuses=(503, 409),
                    on_retry=_on_retry,
                    abort=lambda: req.done.is_set() or req._cancel)
                if used is not None and code not in (0, 503, 409):
                    if code != 200:
                        try:
                            msg = json.loads(raw).get("error",
                                                      raw[:120])
                        except Exception:
                            msg = raw[:120]
                        outcome = (req, slot, RuntimeError(
                            f"remote prefill rejected ({code}): "
                            f"{msg}"))
                    else:
                        try:
                            meta, arrays = FK.decode_handoff(raw)
                            if self.fingerprint is not None:
                                FK.check_fingerprint(meta,
                                                     self.fingerprint)
                            self.stats["posted"] += 1
                            self._wire_span(req, t_wire0, used,
                                            attempts[0], stream=False)
                            outcome = (req, slot, arrays,
                                       int(meta["nBlocks"]),
                                       int(meta["first"]))
                        except FK.EnvelopeError as e:
                            outcome = (req, slot, e)
            if outcome == "done":
                continue    # streamed final already posted
            if outcome is None:
                self.stats["failed"] += 1
                outcome = (req, slot, RetriableError(
                    "no prefill pod accepted the handoff "
                    f"({self.max_attempts} attempts); retry"))
            self.results.put(outcome)

    @staticmethod
    def _wire_span(req, t0: float, ep: str, attempts: int,
                   stream: bool) -> None:
        """Remote-handoff wire span (ISSUE 15): POST -> decoded
        envelope (streamed: first frame -> terminal frame), stamped
        from this worker thread onto the request's trace — the
        RequestTrace is thread-safe for exactly this.  Covers pod
        queue + prefill compute + the DCN transfer; the pod's own
        ``prefillMsAvg`` gauge splits out the compute share."""
        tr = getattr(req, "trace", None)
        if tr is not None:
            # NB: "pod" is make_span's own field (the POSTING pod);
            # the serving prefill pod rides as the target attr
            tr.add("remote_prefill", t0, target=ep,
                   attempts=attempts + 1, stream=stream)

    def _stream_attempt(self, ep: str, body: bytes, req, slot: int):
        """One STREAMED prefill attempt against ``ep``: frames post to
        the scheduler AS THEY ARRIVE (the decode upload overlaps both
        the wire and the pod's remaining compute); the terminal frame
        posts the remainder + first token.  Returns ``"done"`` (final
        posted), ``"next"`` (retry another candidate — 503, connection
        failure, mid-stream death, or a truncated/CRC-bad/out-of-order
        frame, all refused WHOLESALE; prefill is side-effect-free and
        already-uploaded frames are idempotently overwritten by the
        retry), or a terminal error outcome tuple (deterministic
        rejection)."""
        import json as _json

        from http.client import HTTPConnection, HTTPException

        from paddle_operator_tpu.utils import fleetkv as FK

        host, _, port = ep.rpartition(":")
        conn = HTTPConnection(host, int(port), timeout=self.timeout)
        streaming = False       # past the 200: failures = broken stream
        try:
            # Connection: close — one stream per connection, and the
            # server tears it down cleanly after the terminal frame
            # (a lingering keep-alive would just log a reset when
            # this side closes)
            conn.request("POST", "/v1/prefill", body=body,
                         headers={"Content-Type": "application/json",
                                  "Connection": "close"})
            resp = conn.getresponse()
            if resp.status in (503, 409):
                # 503: draining / backlogged pod.  409: weight-
                # generation fingerprint mismatch mid rolling swap
                # (ISSUE 19) — an already-rolled peer may match.
                resp.read()
                return "next"
            if resp.status != 200:
                raw = resp.read()
                try:
                    msg = _json.loads(raw).get("error", raw[:120])
                except Exception:   # noqa: BLE001
                    msg = raw[:120]
                return (req, slot, RuntimeError(
                    f"remote prefill rejected ({resp.status}): {msg}"))
            streaming = True
            seq = 0
            while True:
                buf = FK.read_wire_frame(resp.read)
                if buf is None:
                    raise FK.EnvelopeError(
                        "handoff stream ended before its terminal "
                        "frame")
                kind, meta, arrays = FK.decode_handoff_frame(buf, seq)
                if kind == FK.FRAME_KIND:
                    width = arrays["k"].shape[1]
                    self.results.put(
                        ("frame", req, slot, arrays, None,
                         int(meta["j0"]), int(meta["j0"]) + width))
                    seq += 1
                    continue
                if self.fingerprint is not None:
                    FK.check_fingerprint(meta, self.fingerprint)
                self.stats["posted"] += 1
                self.results.put(
                    ("final", req, slot, arrays, None,
                     int(meta["j0"]), int(meta["nBlocks"]),
                     int(meta["first"]), time.monotonic()))
                return "done"
        except FK.EnvelopeError:
            self.stats["refused_streams"] += 1
            return "next"
        except (OSError, ValueError, HTTPException):
            # connection refused/reset, or the pod died mid-chunk
            # (IncompleteRead) — a started stream refuses WHOLESALE
            # either way; retry elsewhere
            if streaming:
                self.stats["refused_streams"] += 1
            return "next"
        finally:
            conn.close()

    def close(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)


def remote_prefill_client_from_env() -> Optional[RemotePrefillClient]:
    """serve.py wiring: SERVE_PREFILL_REMOTE=1 (with
    SERVE_PREFILL=disagg) moves cold prefills to the prefill POOL —
    SERVE_PREFILL_BROKER names the router (it forwards to the
    least-loaded ready prefill pod), SERVE_PREFILL_PEERS is the
    router-less static list.  Returns None when remote prefill is
    off."""
    import os

    if os.environ.get("SERVE_PREFILL_REMOTE", "0") != "1":
        return None
    broker = os.environ.get("SERVE_PREFILL_BROKER", "")
    peers = [p for p in os.environ.get("SERVE_PREFILL_PEERS",
                                       "").split(",") if p.strip()]
    if not broker and not peers:
        print("SERVE_PREFILL_REMOTE=1 ignored: set "
              "SERVE_PREFILL_BROKER or SERVE_PREFILL_PEERS",
              flush=True)
        return None
    # wire chaos (ISSUE 20): with TPUJOB_WIRE_CHAOS scheduling faults
    # on the decode->prefill edge, the broker/peer endpoints are
    # replaced by an injured in-process proxy — the env contract that
    # lets a chaos run injure THIS edge without touching either pod
    from paddle_operator_tpu.utils import wirechaos as WC

    broker = WC.wire_endpoint_from_env("decode-prefill", broker)
    peers = [WC.wire_endpoint_from_env("decode-prefill", p)
             for p in peers]
    # SERVE_PREFILL_STREAM=1 (ISSUE 14): consume the pool's chunked
    # handoff frames, uploading each block group while the pod still
    # prefills the rest — long-prompt TTFT ≈ last chunk + attach
    return RemotePrefillClient(
        broker=broker, peers=peers,
        stream=os.environ.get("SERVE_PREFILL_STREAM", "0") == "1")


def main() -> int:
    """Prefill-pod entrypoint (``python -m
    paddle_operator_tpu.infer.prefill_serve``): restore params exactly
    as serve.py does, serve /v1/prefill on TPUJOB_PORT, drain on
    SIGTERM by refusing new handoffs and finishing in-flight jobs,
    exit EXIT_PREEMPTED."""
    import os

    import jax

    from paddle_operator_tpu.api.types import EXIT_PREEMPTED
    from paddle_operator_tpu.ft.preemption import PreemptionWatcher
    from paddle_operator_tpu.infer.serve import load_serving_params
    from paddle_operator_tpu.launch.launcher import JobEnv
    from paddle_operator_tpu.models.llama import CONFIGS
    from paddle_operator_tpu.train.checkpoint import CheckpointManager
    from paddle_operator_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every phase of this process is a TraceMe on the profiler's host
    # plane from here on (utils/tracing.py): whoever starts the profiler
    # finds the loop's phases on the device's clock
    TRC.set_annotator(jax.profiler.TraceAnnotation)
    env = JobEnv.from_env()
    cfg = CONFIGS[os.environ.get("MODEL_PRESET", "7b")]
    from paddle_operator_tpu.infer import afmoe_serve as AF

    AF.refuse_modes(cfg, {"SERVE_PREFILL=disagg (a prefill pod)": True})
    mesh = None
    tp = int(os.environ.get("SERVE_TP", "1"))
    if tp > 1:
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        mesh = make_serving_mesh(tp)
    params, resumed = load_serving_params(cfg, CheckpointManager(),
                                          mesh=mesh)
    # SERVE_WEIGHT_QUANT=int8|int4: match the decode fleet's weight
    # quantization — handed-off KV is a function of the weights that
    # produced it, so a mixed fleet breaks token-identity with the
    # in-process cold path.  builders.py derives this pod's env from
    # the serving container, so the knob arrives automatically; the
    # handoff fingerprint refuses skew regardless.
    wq = os.environ.get("SERVE_WEIGHT_QUANT", "none") or "none"
    if wq != "none":
        from paddle_operator_tpu.infer.quant import (
            SERVING_SKIP,
            quantize_params,
        )

        params = quantize_params(params, cfg, mode=wq, skip=SERVING_SKIP)
    max_len = int(os.environ.get("SERVE_MAX_LEN", "0")) \
        or cfg.max_seq_len
    kv_quant = os.environ.get("SERVE_KV_QUANT", "none")
    # ISSUE 14: SERVE_PREFILL_LANES widens the pool into an N-lane
    # batched, chunk-interleaved engine (1 keeps the monolithic
    # oracle); SERVE_PREFILL_CHUNK is the interleave slice width;
    # SERVE_PREFILL_PREFIX_BLOCKS caps the pod's own radix prefix
    # cache (0 disables; engine-only)
    lanes = int(os.environ.get("SERVE_PREFILL_LANES", "1") or 1)
    srv = make_prefill_server(
        "0.0.0.0", env.port, params, cfg,
        block_size=int(os.environ.get("SERVE_BLOCK_SIZE", "256")),
        max_len=max_len, kv_quant=kv_quant, mesh=mesh,
        job=os.environ.get("TPUJOB_NAME", "local"),
        replica=os.environ.get("TPUJOB_REPLICA_ID", ""),
        lanes=lanes,
        prefill_chunk=int(os.environ.get("SERVE_PREFILL_CHUNK",
                                         "64") or 64),
        prefix_blocks=int(os.environ.get(
            "SERVE_PREFILL_PREFIX_BLOCKS", "256") or 0),
        generation=int(os.environ.get("SERVE_GENERATION", "0") or 0))
    print(f"prefill pool {os.environ.get('MODEL_PRESET', '7b')} "
          f"(resumed={resumed}, tp={tp}, kv_quant={kv_quant}, "
          f"weight_quant={wq}, "
          f"lanes={lanes}, max_len={max_len}) on :{env.port}",
          flush=True)
    budget = float(os.environ.get("SERVE_DRAIN_BUDGET_S", "30"))
    code = [0]

    def drain(reason: str) -> None:
        fe = srv.frontend
        fe.flightrec.record("drain_start", reason=str(reason))
        fe.flightrec.dump_file("sigterm")
        fe.draining = True          # /readyz false, new prefills 503
        deadline = time.monotonic() + budget
        while fe.depth() > 0 and time.monotonic() < deadline:
            time.sleep(0.05)        # in-flight jobs finish + flush
        # a short grace so finished jobs' responses leave the socket
        time.sleep(0.2)
        code[0] = EXIT_PREEMPTED
        srv.shutdown()

    watcher = PreemptionWatcher.install()
    watcher.on_drain(lambda reason: threading.Thread(
        target=drain, args=(reason,), daemon=True).start())
    srv.serve_forever()
    srv.frontend.close()
    return code[0]


if __name__ == "__main__":
    raise SystemExit(main())
