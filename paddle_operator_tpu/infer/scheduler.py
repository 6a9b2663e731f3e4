"""Host half of the serving ring: the continuous-batching scheduler.

ISSUE 6 split the one-file ring into this scheduler (admission,
queues, deadlines, request lifecycle, resilience hooks — pure host
code; the only jax it touches is sequencing dispatches on its
executor) and ``infer/executor.py`` (compiled programs + device state).
:class:`ContinuousBatcher` kept its name, constructor surface and
behavior, and gained the prefill modes the split exists for:

- ``prefill_mode="inline"``: admission prefills the whole prompt in one
  compiled dispatch on the ring thread (the original behavior — one
  cold 2k prompt stalls every resident decode lane for a full prefill).
- ``prefill_mode="chunked"``: prefill runs in ``prefill_chunk``-token
  slices, at most ONE slice per ring iteration interleaved with the
  decode chunk — resident lanes never wait more than one slice
  (Sarathi-Serve).  Works on the contiguous and the paged ring.
- ``prefill_mode="disagg"``: cold prompts prefill on a separate
  :class:`~paddle_operator_tpu.infer.executor.PrefillExecutor` thread
  into its own block pool; the ring's only admission work is a
  device-to-device block copy + a tiny attach dispatch (DistServe,
  in-process).  Requires the paged ring; radix prefix HITS still admit
  through the suffix insert on the ring thread, so only uncached
  suffix tokens are ever prefilled anywhere.

All three modes are greedy-bit-identical to the inline ring and compose
with spec decode, paged KV, deadlines, drain, and the watchdog rebuild
(tests/test_prefill_modes.py; dryrun ``serve-disagg``).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from paddle_operator_tpu.infer import executor as X
from paddle_operator_tpu.infer import qos as QOS
from paddle_operator_tpu.utils import tracing as TR
from paddle_operator_tpu.infer.resilience import (
    DispatchWatchdog,
    LaneMigrated,
    LaneQuarantined,
    RestartBudget,
    RetriableError,
    RingResilience,
    ShuttingDown,
)
from paddle_operator_tpu.models.llama import LlamaConfig

PREFILL_MODES = ("inline", "chunked", "disagg")


def _fold_seed(seed: int) -> int:
    """Fold an out-of-int32-range seed to [0, 2**31) via the splitmix64
    finalizer (a bijection on 64-bit ints before the final fold) —
    distinct wide seeds stay distinct with overwhelming probability,
    unlike the ``& 0x7FFFFFFF`` mask that mapped s and s + 2**31 to the
    same sampling stream."""
    x = seed & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 31
    return x & 0x7FFFFFFF


class QueueFull(RuntimeError):
    """submit() backpressure signal: the bounded request queue stayed
    full past the put timeout.  A RuntimeError subclass so serve.py's
    generic 503 mapping already handles it (retry/fail-over, not a
    client error) while callers that care can catch it specifically."""


class _Request:
    __slots__ = ("prompt", "max_new", "temperature", "seed", "eos",
                 "done", "out", "error", "_stream", "_cancel",
                 "dev_prompt", "bucket", "accepted", "drafted",
                 "deadline", "deadline_exceeded",
                 "priority", "adapter", "adapter_idx", "ns", "preempts",
                 "request_id", "migrate_state",
                 "trace", "t_submit", "t_first", "t_last_tok",
                 "t_prefill0")

    def __init__(self, prompt, max_new, temperature, seed, eos,
                 wants_stream=False, deadline=None):
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.seed = seed
        self.eos = eos
        self.done = threading.Event()
        self.out: Optional[List[int]] = None
        self.error: Optional[Exception] = None
        self._cancel = False
        # absolute time.monotonic() deadline (or None): the ring retires
        # the lane when it passes — the request RESOLVES with the tokens
        # produced so far and this flag set (the 504-style partial), so
        # a slow client can never pin a lane / its paged blocks
        self.deadline: Optional[float] = deadline
        self.deadline_exceeded = False
        # speculative-decoding telemetry (spec_k > 0 rings): drafts
        # offered / accepted for THIS request — serve.py surfaces the
        # rate per response
        self.accepted = 0
        self.drafted = 0
        # multi-tenant QoS (ISSUE 10, infer/qos.py): admission class
        # (0 most urgent), the request's adapter (name, registry slot,
        # and radix-cache namespace) and how many times it has been
        # preemption-spilled (the per-request anti-thrash cap)
        self.priority = 0
        self.adapter: Optional[str] = None
        self.adapter_idx = 0
        self.ns = 0
        self.preempts = 0
        # fleet-level KV (ISSUE 12): the client's idempotent id (the
        # migration retrieval key) and this request's migration state —
        # None (never offered), "inflight" (envelope on the wire) or
        # "failed" (peer refused; never re-offered, resumes locally)
        self.request_id: Optional[str] = None
        self.migrate_state: Optional[str] = None
        # observability (ISSUE 15): per-request span accumulator
        # (None = tracing off for this request — every capture site is
        # one attribute check) + the host timestamps the latency
        # histograms observe at the scheduler's EXISTING blocking
        # points (submit, first-token materialization, chunk consume)
        self.trace: Optional[TR.RequestTrace] = None
        self.t_submit = time.monotonic()
        self.t_first: Optional[float] = None
        self.t_last_tok: Optional[float] = None
        self.t_prefill0: Optional[float] = None
        # padded prompt, transferred to device on the SUBMIT thread
        # (batcher.submit): a host->device copy paid on the decode-ring
        # thread stalls every lane; caller threads pay it concurrently
        # instead
        self.dev_prompt: Optional[Any] = None
        self.bucket: int = 0
        # token streaming is opt-in (submit(stream=True)): the dominant
        # result()-only path must not pay per-token queue puts inside
        # the decode-ring thread that gates every lane's throughput
        self._stream: Optional["queue.Queue"] = (
            queue.Queue() if wants_stream else None)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self.done.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self.error is not None:
            raise self.error
        return self.out

    @property
    def accept_rate(self) -> Optional[float]:
        """Speculative acceptance rate for this request (accepted
        drafts / offered drafts), or None when the ring is not
        speculative (or no round has consumed yet)."""
        if not self.drafted:
            return None
        return round(self.accepted / self.drafted, 4)

    def cancel(self) -> None:
        """Stop decoding this request: the ring evicts its lane at the
        next chunk boundary (or drops it from the queue if not yet
        admitted) and ``result()`` returns the tokens produced so far.
        A disconnect-abandoned long stream must not keep occupying a
        decode lane to its full token budget."""
        self._cancel = True

    def stream(self, timeout: Optional[float] = None):
        """Yield generated tokens as the ring emits them (one int at a
        time, arriving in chunk-sized bursts).  Raises the request's
        error at the point of failure; `timeout` bounds the wait for
        EACH burst, not the whole generation."""
        if self._stream is None:
            raise RuntimeError("request was not submitted with "
                               "stream=True")
        while True:
            try:
                item = self._stream.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError("no tokens within timeout") from None
            if item is None:
                if self.error is not None:
                    raise self.error
                return
            yield item


class _PrefillState:
    """Host bookkeeping for one mid-flight CHUNKED prefill: the slice
    frontier plus (contiguous only) the staging K/V the slices append
    into."""

    __slots__ = ("req", "start", "hit_len", "seq", "lane_k", "lane_v")

    def __init__(self, req, start, hit_len, seq, lane_k=None, lane_v=None):
        self.req = req
        self.start = start          # next absolute row to prefill
        self.hit_len = hit_len      # radix-hit rows (paged; 0 otherwise)
        self.seq = seq              # admission order — oldest advances
        self.lane_k = lane_k
        self.lane_v = lane_v


class _ParkedLane:
    """Host bookkeeping for one PREEMPTED lane (ISSUE 10): the
    byte-exact device spill (RingExecutor.spill_lane) plus the host
    mirrors a restore re-attaches — the request itself stays
    unresolved, invisible to the client except as latency."""

    __slots__ = ("req", "spill", "out", "left", "pos", "seq",
                 "migrating", "t_parked")

    def __init__(self, req, spill, out, left, pos, seq):
        self.req = req
        self.spill = spill
        self.out = out          # tokens emitted before the spill
        self.left = left        # remaining token budget
        self.pos = pos          # fill position at the spill boundary
        self.seq = seq          # park order — FIFO within a class
        # fleet-level KV (ISSUE 12): envelope on the wire to a peer —
        # the restore path must not resume a lane mid-migration
        self.migrating = False
        self.t_parked = time.monotonic()


# sentinel: swap_weights(mesh=...) distinguishes "keep the current
# mesh" (the common checkpoint bump) from "resize to mesh=None" (an
# explicit tp=1 downsize) — None is a legal target, so a default of
# None cannot carry "unchanged"
_KEEP_MESH = object()


class _SwapRequest:
    """One posted live weight swap (ISSUE 19), handed from the caller's
    thread to the ring loop: the NEW param trees (already loaded,
    quantized, host- or device-resident — the expensive I/O happened
    off the ring thread), the target mesh for a TP resize, and the
    completion event the caller blocks on.  ``error`` is set instead
    of ``result`` when the swap aborted — the ring then still serves
    the OLD generation (all-or-nothing)."""

    __slots__ = ("params", "draft_params", "mesh", "generation",
                 "done", "error", "result")

    def __init__(self, params, draft_params, mesh, generation):
        self.params = params
        self.draft_params = draft_params
        self.mesh = mesh                # _KEEP_MESH = no resize
        self.generation = generation    # None = bump by one
        self.done = threading.Event()
        self.error: Optional[Exception] = None
        self.result: Optional[Dict[str, Any]] = None


class ContinuousBatcher:
    """Slot scheduler over the resident chunk step.

    ``submit()`` is thread-safe and returns a handle whose ``result()``
    blocks until the sequence finishes; the decode loop runs on a
    background thread, admitting queued requests into free lanes at
    chunk boundaries (bucketed prefill) and evicting lanes on eos /
    budget.  ``stats`` counts admissions, evictions, decoded chunks and
    the high-water mark of concurrently active lanes — the numbers the
    slot-reuse tests pin.

    Device state and compiled programs live on the
    :class:`~paddle_operator_tpu.infer.executor.RingExecutor`
    (``self.executor``); this object only sequences dispatches on it.
    The legacy attribute surface (``cache``/``pool``/``_step``/...)
    forwards there so tests and the chaos injector keep working.

    ``paged=True`` (infer/paged.py) swaps the per-lane contiguous KV
    region for a global block pool + per-lane block tables with a radix
    prefix cache; greedy token streams stay BIT-IDENTICAL to the
    contiguous ring (``paged=False`` is both the fallback and the
    parity oracle).  ``prefill_mode``/``prefill_chunk`` select how
    admission prefill reaches the device (module docstring);
    ``prewarm`` compiles the admission/step programs off-thread at
    construction so the first long prompt pays no compile cliff
    (SERVE_PREWARM=0 opts out).
    """

    SUFFIX_PREFILL_MAX_ROWS = X.RingExecutor.SUFFIX_PREFILL_MAX_ROWS

    def __init__(self, params: Any, cfg: LlamaConfig, *, slots: int = 8,
                 max_len: Optional[int] = None, chunk_tokens: int = 8,
                 prefill_buckets=(), top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 pipeline_depth: int = 2, mesh=None,
                 draft_params: Any = None,
                 draft_cfg: Optional[LlamaConfig] = None,
                 spec_k: int = 0,
                 max_queue: int = 0,
                 queue_timeout: float = 5.0,
                 paged: bool = False,
                 block_size: int = 256,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefill_mode: str = "inline",
                 prefill_chunk: int = 64,
                 prewarm: bool = False,
                 kv_quant: str = "none",
                 host_cache_blocks: int = 0,
                 resilience: Optional[RingResilience] = None,
                 qos: Optional[QOS.QoSConfig] = None,
                 adapters: Optional[QOS.AdapterRegistry] = None,
                 megastep: int = 1,
                 prefill_client=None,
                 prefill_lanes: int = 1,
                 prefill_stream: bool = False,
                 prefill_prefix_blocks: int = 0,
                 trace: Optional[bool] = None,
                 generation: int = 0) -> None:
        if prefill_mode not in PREFILL_MODES:
            raise ValueError(f"prefill_mode {prefill_mode!r} not in "
                             f"{PREFILL_MODES}")
        if prefill_mode == "disagg":
            # the disaggregated handoff is block-granular by design —
            # the paged pool IS the transfer unit
            paged = True
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len or cfg.max_seq_len
        self.chunk = chunk_tokens
        self.prefill_mode = prefill_mode
        # device-resident megastep (ISSUE 11, SERVE_MEGASTEP): fuse N
        # ring iterations into ONE compiled dispatch, with eos /
        # token-budget / deadline-tick continuation carried on device.
        # Admission, preemption, promotions, CoW and handoff attaches
        # happen only at megastep boundaries; N=1 (default) dispatches
        # the byte-identical legacy program (the oracle).
        self.megastep = int(megastep)
        if self.megastep < 1:
            raise ValueError(f"megastep must be >= 1 (got {megastep})")
        # rolling per-iteration wall estimate (EMA over consumed
        # dispatches): the deadline-tick budget converts a request's
        # remaining seconds into fused iterations with it.  0 = no
        # estimate yet (deadlines then bind at megastep boundaries
        # only, exactly like N=1 binds at chunk boundaries).
        self._step_s_est = 0.0
        # fault tolerance (infer/resilience.py): with a RingResilience a
        # ring-level dispatch fault fails the RESIDENT requests with a
        # retriable 503 and rebuilds the ring from scratch (fresh
        # cache/pool; queued work re-admitted) behind exponential
        # backoff, until the restart budget flips ``healthy`` — without
        # one the batcher keeps its legacy die-on-first-error behavior.
        self.resilience = resilience
        self._budget = (RestartBudget(resilience)
                        if resilience is not None else None)
        self._check_finite = bool(resilience and resilience.nan_check)
        if self._check_finite and spec_k:
            raise ValueError("nan_check is not supported on speculative "
                             "rings (the spec round has no per-lane "
                             "finite fold); disable one of them")
        self.healthy = True
        self._draining = False
        self._rebuilding = False
        # ring-level fault observed (by the loop thread or the watchdog
        # monitor) and not yet healed; the loop rebuilds at the next top
        self._fault: Optional[Exception] = None
        self._watchdog: Optional[DispatchWatchdog] = None
        if resilience is not None and resilience.watchdog:
            self._watchdog = DispatchWatchdog(
                resilience, self._on_stall, self._on_hard_stall)
        # max dispatched-but-unconsumed chunks; the oldest is consumed
        # once `depth` are in flight, so depth 2 = one chunk always
        # decoding while the host consumes the previous one (depth 1
        # disables the overlap entirely).  Deeper than 2 delays the
        # eviction bookkeeping by depth-1 chunks, so freed lanes sit
        # idle before re-admission — lane turnover costs more than the
        # extra hidden round-trip saves (measured).
        self.pipeline_depth = max(1, pipeline_depth)

        # multi-tenant QoS (ISSUE 10, infer/qos.py): priority classes,
        # preemption knobs, and the optional adapter registry — the
        # defaults (2 classes, everything defaulting to the least
        # urgent one, no adapters) keep single-tenant behavior
        # byte-identical to the pre-QoS ring
        self.qos = qos if qos is not None else QOS.QoSConfig()
        self.adapters = adapters

        # observability (ISSUE 15, utils/tracing.py).  Span capture is
        # OPT-IN (``trace=`` / SERVE_TRACE=1) and zero-cost when off:
        # requests then carry ``trace=None`` and every capture site is
        # one attribute check.  Spans only wrap host timestamps around
        # blocking points the loop already has — capture never adds a
        # device sync, and greedy token streams are byte-identical
        # either way (dryrun ``serve-trace``).  The latency histograms
        # (TTFT / inter-token / e2e / queue-wait) and the flight
        # recorder are always-on metrics, like the gauges.
        pod = os.environ.get("TPUJOB_REPLICA_ID", "")
        if trace is None:
            trace = TR.trace_enabled()
        self.tracer: Optional[TR.Tracer] = (
            TR.Tracer(pod=pod) if trace else None)
        self.hist = TR.ServeHistograms()
        self.flightrec = TR.FlightRecorder(pod=pod)

        # the device half: compiled programs + cache/pool/lane state.
        # The kwargs are kept (ISSUE 19): a live TP resize rebuilds the
        # executor around a NEW mesh with the geometry otherwise
        # byte-identical — one construction site, one swap site, no
        # drift between them.
        self._exec_kw = dict(
            slots=slots, max_len=self.max_len,
            chunk_tokens=chunk_tokens, prefill_buckets=prefill_buckets,
            top_k=top_k, top_p=top_p, draft_cfg=draft_cfg,
            spec_k=spec_k, paged=paged, block_size=block_size,
            num_blocks=num_blocks, prefix_cache=prefix_cache,
            prefill_mode=prefill_mode, prefill_chunk=prefill_chunk,
            check_finite=self._check_finite, kv_quant=kv_quant,
            host_cache_blocks=host_cache_blocks, adapters=adapters,
            megastep=self.megastep, prefill_client=prefill_client,
            prefill_lanes=prefill_lanes, prefill_stream=prefill_stream,
            prefill_prefix_blocks=prefill_prefix_blocks)
        self.executor = X.RingExecutor(
            params, cfg, mesh=mesh, draft_params=draft_params,
            **self._exec_kw)
        self.mesh = mesh
        # live weight swap (ISSUE 19): the generation of the params
        # currently dispatched (SERVE_GENERATION seeds it; each swap
        # bumps or sets it), and the single-slot pending-swap request
        # the ring loop consumes at a quiesced boundary
        self.generation = int(generation)
        self._swap_req: Optional[_SwapRequest] = None
        self._swap_lock = threading.Lock()
        self.paged = self.executor.paged
        self.kv_quant = self.executor.kv_quant
        self.spec_k = self.executor.spec_k
        self.draft_cfg = self.executor.draft_cfg
        self._top_k, self._top_p = top_k, top_p
        # cross-host disaggregation (ISSUE 13): stamp the remote
        # prefill client with THIS ring's handoff fingerprint — every
        # POST carries it, the prefill pod refuses a mismatch with
        # 409, and the client re-validates the returned envelope
        # before the scheduler ever touches its bytes
        if self.executor.prefill_remote:
            self.executor.prefill_exec.fingerprint = \
                self.handoff_fingerprint()

        self.lane: List[Optional[_Request]] = [None] * slots
        self._lane_out: List[List[int]] = [[] for _ in range(slots)]
        self._lane_left = [0] * slots
        # host mirror of each lane's device fill position — set by
        # admission, advanced at consume, ZEROED on eviction so
        # serving_status never reports a retired lane's stale pos (and,
        # paged, so on-demand block mapping tracks the true frontier)
        self._lane_pos = [0] * slots
        # per-lane device future of the admission-sampled first token,
        # materialized at the next chunk consume (async admission)
        self._lane_first: List[Optional[Any]] = [None] * slots
        # prefill-in-flight bookkeeping: lanes reserved but not yet
        # decode-active — chunked slices mid-flight, or a disagg prompt
        # away on the prefill executor (slot -> _PrefillState / request)
        self._prefilling: Dict[int, _PrefillState] = {}
        self._disagg_waiting: Dict[int, _Request] = {}
        # streamed handoff (ISSUE 14): per-slot upload timestamps of
        # frames landed BEFORE the terminal item — the overlap proof
        # (an uploaded frame whose stamp precedes the engine's
        # prefill-done stamp provably overlapped prefill compute)
        self._handoff_frame_t: Dict[int, List[float]] = {}
        self._admit_seq = 0

        # bounded admission queue (max_queue > 0): submit() blocks up to
        # queue_timeout for a slot, then REJECTS (QueueFull) — saturation
        # degrades into backpressure instead of unbounded request RAM.
        # The bound is PER CLASS (infer/qos.py MultiClassQueue): a
        # lower-priority flood sheds its own overflow without eating the
        # express class's admission budget.
        self.max_queue = int(max_queue)
        self._queue_timeout = queue_timeout
        self._pending = QOS.MultiClassQueue(
            self.qos.priorities, maxsize=self.max_queue)
        # preemption-spilled lanes awaiting re-admission (ISSUE 10) +
        # the rolling anti-thrash budget bounding how often residents
        # may be spilled at all
        self._parked: List[_ParkedLane] = []
        # dispatches in flight, oldest first: [(chunk_reqs, res,
        # t_dispatch)] — the ring thread's pipeline (_loop_body)
        self._inflight: List[tuple] = []
        self._preempt_budget = QOS.PreemptionBudget(
            self.qos.preempt_budget, self.qos.preempt_window_s)
        # fleet-level KV (ISSUE 12).  ``migrate_out(meta, spill)`` —
        # wired by serve.py to a utils/fleetkv.FleetKVClient — offers a
        # parked lane's envelope to the fleet (router-brokered);
        # ``peer_fetch(tokens, ns)`` asks the fleet for demoted prefix
        # blocks.  Both default None = the pod-local pre-fleet ring.
        self.migrate_out = None
        self.peer_fetch = None
        # durable prefix store (ISSUE 17): the persistent tier below
        # host/peer — wired by serve.py via attach_kv_store().  The
        # submit-thread probe order becomes peer -> store: on a peer
        # miss (or with no fleet wired at all) the store is consulted
        # directly and hits land through the same import -> host-hit
        # -> batched-promote path.  None = pre-store behavior.
        self.kv_store = None
        # drain-by-migration: SIGTERM/scale-down drain parks residents
        # and migrates them out instead of waiting out completions
        # (completion-wait remains the fallback for lanes no peer takes)
        self._migrate_on_drain = False
        # parked lanes older than this migrate to an idle peer even
        # outside a drain (None/<=0 disables)
        self.migrate_parked_s: Optional[float] = None
        # cross-thread handoffs, all drained by the ring loop: lanes
        # adopted FROM peers (HTTP thread -> loop), migration-attempt
        # completions (worker thread -> loop), and fetched peer prefix
        # payloads awaiting radix import (submit thread -> loop)
        self._adopt_q: "queue.Queue[_ParkedLane]" = queue.Queue()
        self._migr_done: "queue.Queue[tuple]" = queue.Queue()
        self._host_imports: "queue.Queue[tuple]" = queue.Queue()
        # chains already asked of the fleet (hit or miss) — a cold
        # prefix must not trigger one fetch per request in a burst
        self._peer_fetch_seen: "OrderedDict[Any, bool]" = OrderedDict()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self.stats = {"admitted": 0, "evicted": 0, "chunks": 0,
                      "max_active": 0, "rejected_queue_full": 0,
                      # QoS accounting (ISSUE 10): lanes spilled for
                      # more urgent work and spilled lanes resumed —
                      # the tpujob_serve_lane_preemptions_total gauge
                      "preempted_lanes": 0, "restored_lanes": 0,
                      # fleet-level KV (ISSUE 12): lanes migrated OUT
                      # to a peer (completed handoffs), lanes adopted
                      # IN from peers, and prefix chains fetched from
                      # a peer's host tier
                      "lane_migrations": 0, "adopted_lanes": 0,
                      "peer_prefix_fetches": 0,
                      # durable prefix store (ISSUE 17): submit-thread
                      # store consults and the subset that returned
                      # blocks — kvStoreHitRate's numerator/denominator
                      # fold the store's own counters at status time
                      "kv_store_probes": 0, "kv_store_hits": 0,
                      "spec_accepted": 0, "spec_drafted": 0,
                      # prefill accounting: the prefix-cache acceptance
                      # gate — a full prefix hit admits with ZERO
                      # prefill forward passes over cached blocks.
                      # chunked_prefill_tokens counts the share that
                      # arrived in interleaved slices; disagg_prefills
                      # the prompts prefilled off the ring thread.
                      "prefill_calls": 0, "prefill_tokens": 0,
                      "chunked_prefill_tokens": 0, "disagg_prefills": 0,
                      # what the dispatched work was shaped like
                      # (_count_prefill, the decode dispatch): the width
                      # of every insert program dispatched, real tokens
                      # or padding, by width; and decode iterations,
                      # alone and times the lanes live in the plan —
                      # the padding share and the lane occupancy are
                      # ratios of these, taken by whoever reads them
                      "prefill_bucket_tokens": 0,
                      "prefill_calls_by_bucket": {},
                      "decode_steps": 0, "decode_lane_steps": 0,
                      # cold inserts whose program carried a decode
                      # step of the ring (paged.make_paged_prefill_
                      # insert), and the lanes those steps advanced;
                      # the step itself counts above like any other
                      "insert_steps": 0, "insert_step_lanes": 0,
                      # the paged decode kernel's work list against the
                      # lanes x blocks rectangle, a layer's call of each
                      # decode iteration (positions as the host holds
                      # them at the dispatch)
                      "decode_cells_live": 0, "decode_cells_grid": 0,
                      # routing counters of an expert stack
                      # (infer/afmoe_serve.py): computed on the device,
                      # added up as each dispatch's results are consumed
                      "moe_layer_steps": 0, "moe_experts_touched": 0,
                      "moe_expert_load": None,
                      "moe_prefill_expert_load": None,
                      # cross-host disaggregation (ISSUE 13): cold
                      # prompts whose prefill ran in a PREFILL POOL
                      # pod and handed off over the wire
                      "remote_prefills": 0,
                      # streamed handoff (ISSUE 14): block-group
                      # frames landed ahead of their terminal item,
                      # and the subset whose upload stamp PRECEDES the
                      # engine's prefill-done stamp — the
                      # transfer-overlaps-compute proof the gate pins
                      "handoff_frames": 0, "overlapped_frames": 0,
                      "cow_copies": 0,
                      # hierarchical-cache accounting (ISSUE 8): blocks
                      # uploaded back from the host tier — cumulative
                      # across watchdog rebuilds (the pool's own stats
                      # reset with the allocator)
                      "promoted_blocks": 0,
                      # fault-tolerance accounting (infer/resilience.py):
                      # deadline partials delivered, self-healing ring
                      # rebuilds, and NaN-quarantined lanes — surfaced
                      # through serving_status -> tpujob_serve_* gauges
                      "deadline_exceeded": 0, "watchdog_restarts": 0,
                      "quarantined_lanes": 0,
                      # live weight swap (ISSUE 19): completed in-place
                      # flips (checkpoint bumps and TP resizes; aborted
                      # swaps do not count — the ring kept serving the
                      # old generation)
                      "weight_swaps": 0}
        # served-token telemetry for serving_status(): cumulative emitted
        # tokens since construction (the /metrics tokens-per-sec gauge)
        self._tokens_emitted = 0
        self._t_start = time.monotonic()
        # where the loop thread's time goes (utils/tracing.py): its
        # phases record here, tiled end to end by ``_tile`` — one ring,
        # one table, however many rings a process (a test) holds
        self.phases = TR.PhaseTable()
        self._tile = TR.Tiling()
        # off-thread compile prewarm (opt-in param; serve.py flips it on
        # unless SERVE_PREWARM=0): without it the per-bucket insert (and
        # the chunked slice programs) compile lazily on the FIRST prompt
        # that needs them, charging one unlucky request a full XLA
        # compile — tens of seconds for a big model.
        self.prewarmed = threading.Event()
        self.prewarmed.set()
        if prewarm:
            self.start_prewarm()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="decode-ring")
        self._thread.start()

    # -- executor state forwarding (legacy surface: tests + chaos) ---------

    @property
    def params(self):
        return self.executor.params

    @property
    def draft_params(self):
        return self.executor.draft_params

    @property
    def buckets(self):
        return self.executor.buckets

    @property
    def block_size(self):
        return self.executor.block_size

    @property
    def cache(self):
        return self.executor.cache

    @cache.setter
    def cache(self, v):
        self.executor.cache = v

    @property
    def dcache(self):
        return self.executor.dcache

    @dcache.setter
    def dcache(self, v):
        self.executor.dcache = v

    @property
    def tok(self):
        return self.executor.tok

    @tok.setter
    def tok(self, v):
        self.executor.tok = v

    @property
    def temp(self):
        return self.executor.temp

    @temp.setter
    def temp(self, v):
        self.executor.temp = v

    @property
    def keys(self):
        return self.executor.keys

    @keys.setter
    def keys(self, v):
        self.executor.keys = v

    @property
    def pool(self):
        return self.executor.pool

    @property
    def _step(self):
        return self.executor.step

    @_step.setter
    def _step(self, fn):
        self.executor.step = fn

    @property
    def _spec_step(self):
        return self.executor.spec_step

    @_spec_step.setter
    def _spec_step(self, fn):
        self.executor.spec_step = fn

    @property
    def _inserts(self):
        return self.executor.inserts

    @property
    def _suffix_inserts(self):
        return self.executor._suffix_inserts

    def start_prewarm(self) -> None:
        """Run the executor's prewarm off-thread; ``prewarmed`` is set
        again when it is done.  What ``prewarm=True`` does at
        construction, for a caller that has something to do first
        (infer/serve.py main compiles the inserts ahead)."""
        self.prewarmed.clear()
        threading.Thread(target=self._prewarm, daemon=True,
                         name="prefill-prewarm").start()

    def _prewarm(self) -> None:
        try:
            self.executor.prewarm()
        except Exception:
            # a prewarm failure must never take the server down — the
            # lazily-compiling fallback path still works
            pass
        finally:
            self.prewarmed.set()

    # -- public ------------------------------------------------------------

    def submit(self, prompt, *, max_new_tokens: int,
               temperature: float = 0.0, seed: int = 0,
               eos_token: Optional[int] = None,
               stream: bool = False,
               request_id: Optional[str] = None,
               deadline_s: Optional[float] = None,
               priority: Optional[int] = None,
               adapter: Optional[str] = None,
               trace_ctx: Optional[tuple] = None) -> _Request:
        """Queue one generation request; returns a handle whose
        ``result()``/``stream()`` deliver the tokens.

        ``trace_ctx`` (ISSUE 15): ``(trace_id, parent_span_id|None)``
        from the ``X-Tpujob-Trace`` header — on a tracing-enabled ring
        (SERVE_TRACE=1) the request accumulates phase spans under that
        context and ``handle.trace`` rides response metadata so the
        router stitches one cross-pod timeline.  Ignored (zero-cost)
        when tracing is off; a tracing ring with no context still
        traces under a locally-minted trace id.

        ``deadline_s`` (serve.py: the ``X-Request-Deadline`` header):
        relative budget in seconds for the WHOLE generation.  When it
        expires the ring retires the lane at the next chunk boundary —
        its paged blocks freed, the request resolving with the tokens
        produced so far and ``handle.deadline_exceeded`` set (the
        504-style partial) — so one slow/greedy client can never pin a
        lane indefinitely.  Requests still queued at expiry resolve
        prompt-only with the same flag.

        ``request_id`` (optional, e.g. serve.py's per-row id) is woven
        into every validation error so an operator reading a rejection
        in a multi-request log knows WHICH request overflowed —
        validation runs (and raises) BEFORE the host-side tokenize copy
        and device transfer below, so a rejected request costs no
        bandwidth.

        ``seed``: sampling seed with an effective range of [0, 2**31) —
        it rides into the compiled insert as an int32 traced argument.
        In-range seeds are used as-is (streams are stable across
        versions for the common case); anything outside (negative or
        >= 2**31 — clients send arbitrary 64-bit ints, serve.py even
        derives seed+i per row) is folded through a splitmix64 hash
        rather than truncated, so distinct wide seeds keep distinct
        streams (masking would collide s with s + 2**31)."""
        rid = f" [request {request_id}]" if request_id is not None else ""
        n = len(prompt)
        if not n:
            raise ValueError(f"empty prompt{rid}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1{rid}")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0{rid}")
        # QoS class (ISSUE 10): 0 most urgent; unannotated requests get
        # the least urgent class (priorities are opt-in boosts)
        prio = (self.qos.default_priority if priority is None
                else int(priority))
        if not 0 <= prio < self.qos.priorities:
            raise ValueError(
                f"priority {prio} outside [0, {self.qos.priorities}) — "
                f"this ring serves {self.qos.priorities} class(es){rid}")
        adapter_idx = adapter_ns = 0
        if adapter is not None:
            if self.spec_k:
                raise ValueError(
                    f"adapters are not supported on speculative rings "
                    f"(the draft proposes base-only){rid}")
            if self.adapters is None:
                raise ValueError(
                    f"no adapter registry on this ring (SERVE_ADAPTERS "
                    f"unset) for adapter {adapter!r}{rid}")
            adapter_idx, adapter_ns = \
                self.adapters.resolve_ns(adapter)      # ValueError
        if self._draining:
            raise ShuttingDown("server draining; retry another replica")
        if self._stop.is_set() or not self._thread.is_alive():
            raise ShuttingDown("batcher closed")
        if n > self.buckets[-1]:
            raise ValueError(
                f"prompt length {n} exceeds the largest prefill "
                f"bucket ({self.buckets[-1]}){rid}")
        if self.spec_k:
            # a verify round starting at the last in-budget position
            # (prompt + max_new - 2) writes rows through pos + spec_k,
            # so spec_k - 1 positions of headroom must exist past
            # prompt + max_new (infer/speculative.py has the derivation)
            if n + max_new_tokens + self.spec_k - 1 > self.max_len:
                raise ValueError(
                    f"prompt ({n}) + max_new_tokens "
                    f"({max_new_tokens}) + speculative headroom "
                    f"({self.spec_k - 1}) exceeds max_len "
                    f"({self.max_len}){rid}")
        else:
            # the FIRST token is sampled from the prefill logits, so only
            # max_new-1 tokens ride chunk steps; the worst-case cache
            # position is prompt + ceil((max_new-1)/chunk)*chunk
            # (validating with ceil(max_new/chunk) rejected requests up
            # to chunk-1 tokens INSIDE capacity)
            budget = -(-(max_new_tokens - 1) // self.chunk) * self.chunk
            if n + budget > self.max_len:
                raise ValueError(
                    f"prompt ({n}) + chunk-rounded budget "
                    f"({budget}) exceeds max_len ({self.max_len}){rid}")
        # validation passed: NOW pay the tokenize copy
        prompt = list(map(int, prompt))
        # int32-range seeds pass through untouched; wide/negative seeds
        # hash-fold (see docstring)
        seed = int(seed)
        if not 0 <= seed < 0x80000000:
            seed = _fold_seed(seed)
        if self.max_queue and self._pending.full(prio):
            # shed BEFORE the host->device prompt transfer below: the
            # rejection path is the overload path, and a device copy
            # per shed request would spend exactly the bandwidth
            # backpressure exists to protect.
            # Non-authoritative (racy) — the timed put below enforces
            # the bound; this only waits for space to appear first.
            # Per-CLASS bound: a flooded batch class sheds its own
            # overflow here while the other classes stay admittable.
            deadline = time.monotonic() + self._queue_timeout
            while self._pending.full(prio):
                if self._stop.is_set() or self._draining:
                    raise ShuttingDown("batcher shutting down")
                if time.monotonic() >= deadline:
                    self.stats["rejected_queue_full"] += 1
                    raise QueueFull(
                        f"request queue full (max_queue={self.max_queue},"
                        f" priority {prio},"
                        f" waited {self._queue_timeout}s)")
                time.sleep(0.005)
        req = _Request(prompt, max_new_tokens, temperature, seed,
                       eos_token, wants_stream=stream,
                       deadline=(time.monotonic() + deadline_s
                                 if deadline_s is not None else None))
        if self.tracer is not None:
            req.trace = self.tracer.begin(ctx=trace_ctx,
                                          request_id=request_id)
            # workload-shape stamps (ISSUE 18): with these on the root
            # span, an exported span tree alone reconstructs the
            # request the fleet served — router/replay.py rebuilds
            # open-loop replay schedules from exactly these attrs
            req.trace.annotate(promptLen=len(prompt),
                               maxNew=int(max_new_tokens),
                               prio=prio,
                               adapter=adapter)
        req.priority = prio
        req.adapter = adapter
        req.adapter_idx = adapter_idx
        req.ns = adapter_ns if adapter_idx else 0
        req.request_id = request_id
        # fleet-level KV (ISSUE 12): a cold prefix may be warm in a
        # PEER's host tier — fetch its demoted blocks now, on the
        # caller's thread, so the admission below host-hits them.
        # Base-namespace chains only: adapter namespaces are salted
        # per-LOAD per-replica, so their chain keys never agree across
        # pods by design.
        # Probe order peer -> store (ISSUE 17): the durable store is
        # consulted on a peer miss, or directly when no fleet peer
        # fetch is wired (single-replica rings still warm-start).
        if ((self.peer_fetch is not None or self.kv_store is not None)
                and req.ns == 0 and self.pool is not None
                and self.pool.host is not None):
            try:
                self._maybe_peer_fetch(prompt)
            except Exception:
                pass    # fetch is an optimization, never a failure
        # pad + ship the prompt to the device HERE, on the caller's
        # thread — see _Request.dev_prompt
        req.bucket = self._bucket_for(len(prompt))
        padded = np.zeros((1, req.bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        req.dev_prompt = jnp.asarray(padded)
        # bounded queue: poll briefly for a slot (smooths bursts) then
        # reject — the caller's thread, not the decode ring, pays the
        # wait.  Short put ticks so close()/drain() interrupt a BLOCKED
        # submitter with ShuttingDown immediately instead of leaving it
        # hanging out the full queue timeout against a dead ring.
        deadline = time.monotonic() + self._queue_timeout
        while True:
            if self._stop.is_set() or self._draining:
                raise ShuttingDown("batcher shutting down")
            try:
                self._pending.put(req, prio, timeout=0.05)
                break
            except queue.Full:
                if time.monotonic() >= deadline:
                    self.stats["rejected_queue_full"] += 1
                    raise QueueFull(
                        f"request queue full (max_queue={self.max_queue},"
                        f" priority {prio},"
                        f" waited {self._queue_timeout}s)") from None
        if self._stop.is_set() and not req.done.is_set():
            # loop died between the liveness check above and the put:
            # fail the request instead of letting result() hang
            self._finish(req, ShuttingDown("batcher closed"))
            return req
        self._wake.set()
        return req

    def prefill_queue_depth(self) -> int:
        """Requests admitted to a lane but still PREFILLING: chunked
        slices mid-flight plus disagg jobs queued/running on the
        prefill executor or awaiting handoff — the
        ``tpujob_serve_prefill_queue_depth`` gauge."""
        depth = len(self._prefilling) + len(self._disagg_waiting)
        return depth

    def _prefill_engine_stat(self, name: str, default):
        """A LOCAL prefill engine's telemetry (lanes, batch occupancy,
        HOL wait) — 0s on rings without one (inline/chunked/remote):
        remote pools export their own via prefill_serve."""
        pe = self.executor.prefill_exec
        if pe is None or self.executor.prefill_remote:
            return default
        if name == "lanes":
            return pe.lanes
        return getattr(pe, name)()

    def weight_quant_mode(self) -> str:
        """Weight-quant storage mode of the TARGET params actually
        dispatched ("none"/"int8"/"int4") — detected from leaf dtypes
        (infer/quant.py), not a threaded flag, so the status block
        stays truthful about the tree on device."""
        from paddle_operator_tpu.infer import quant as Q

        return Q.weight_quant_mode(getattr(self.executor, "params", {}))

    def draft_quant_mode(self) -> str:
        """Weight-quant mode of the DRAFT params ("none" on
        non-speculative rings) — SERVE_DRAFT_QUANT's visibility."""
        from paddle_operator_tpu.infer import quant as Q

        dp = getattr(self.executor, "draft_params", None)
        return Q.weight_quant_mode(dp) if dp is not None else "none"

    def _kv_store_usage(self) -> Tuple[int, int]:
        """``(blocks, bytes)`` resident in the durable store — (0, 0)
        with the store off, and degrades to (0, 0) on a backend listing
        error (telemetry must never fail a scrape)."""
        if self.kv_store is None:
            return 0, 0
        try:
            return self.kv_store.usage()
        except OSError:
            return 0, 0

    def serving_status(self) -> Dict[str, Any]:
        """The ``TPUJob.status.serving`` block (camelCase, like
        GoodputTracker.to_status): cumulative served-token throughput,
        speculative acceptance rate, and current queue depth — what the
        manager exports as ``tpujob_serve_*`` gauges on /metrics
        (utils/observability.py serving_gauges)."""
        elapsed = max(1e-9, time.monotonic() - self._t_start)
        drafted = self.stats["spec_drafted"]
        pf_tok = self.stats["prefill_tokens"]
        kv_store_blocks, kv_store_bytes = self._kv_store_usage()
        # per-lane visibility EXCLUDES retired lanes: _evict zeroes the
        # host pos mirror (and the compiled step zeroes the device pos),
        # so a freed lane can never leak its last request's fill
        # position or tokens into the telemetry (test_serve_metrics)
        return {
            "tokensPerSec": round(self._tokens_emitted / elapsed, 2),
            "acceptRate": (round(self.stats["spec_accepted"] / drafted, 4)
                           if drafted else 0.0),
            "queueDepth": self._pending.qsize(),
            "tokensTotal": self._tokens_emitted,
            "activeLanes": sum(r is not None for r in self.lane),
            "lanePos": [int(p) for p in self._lane_pos],
            "prefixHitRate": (self.pool.hit_rate() if self.pool is not None
                              else 0.0),
            "kvBlocksFree": (self.pool.blocks_free()
                             if self.pool is not None else 0),
            "kvBlocksHwm": (self.pool.stats["blocks_hwm"]
                            if self.pool is not None else 0),
            # hierarchical cache (ISSUE 8): blocks resident in the host
            # spill tier, the share of looked-up prefix tokens served
            # from host payloads, and cumulative promotions — the
            # tpujob_serve_host_* gauges (all 0 with the tier off)
            "hostCacheBlocks": (self.pool.host_blocks()
                                if self.pool is not None else 0),
            "hostHitRate": (self.pool.host_hit_rate()
                            if self.pool is not None else 0.0),
            "promotedBlocks": self.stats["promoted_blocks"],
            # prefill-path visibility (ISSUE 6): which admission path
            # this ring runs, how many admitted requests are still
            # prefilling, and the share of prefill tokens that arrived
            # in interleaved chunked slices
            "prefillMode": self.prefill_mode,
            "prefillQueueDepth": self.prefill_queue_depth(),
            # prefill-pool throughput (ISSUE 14): engine lanes, batch
            # occupancy EMA, head-of-line wait p95 and streamed-frame
            # counters — the tpujob_serve_prefill_batch_occupancy /
            # _hol_wait_ms / _lanes gauges (a REMOTE ring reports 0s
            # here; the prefill pods export their own)
            "prefillLanes": self._prefill_engine_stat("lanes", 0),
            "prefillBatchOccupancy": self._prefill_engine_stat(
                "batch_occupancy", 0.0),
            "prefillHolWaitMs": self._prefill_engine_stat(
                "hol_wait_ms_p95", 0.0),
            "handoffFrames": self.stats["handoff_frames"],
            "overlappedFrames": self.stats["overlapped_frames"],
            # quantized-pool visibility (SERVE_KV_QUANT): which storage
            # mode the pool runs and its device bytes (codes + scales +
            # staging tails, or the bf16 pool/ring) — the capacity an
            # operator sizes num_blocks against
            "kvQuantMode": self.kv_quant,
            "kvPoolBytes": self.executor.pool_bytes(),
            # weight quantization (SERVE_WEIGHT_QUANT /
            # SERVE_DRAFT_QUANT): storage mode of the target and draft
            # param trees actually dispatched (detected from leaf
            # dtypes) and their summed HBM bytes — the
            # tpujob_serve_weight_quant_mode / _param_bytes gauges; the
            # bytes gauge shows the quantization saving directly
            "weightQuantMode": self.weight_quant_mode(),
            "draftQuantMode": self.draft_quant_mode(),
            "paramBytes": self.executor.param_bytes(),
            "chunkedPrefillTokenShare": (
                round(self.stats["chunked_prefill_tokens"] / pf_tok, 4)
                if pf_tok else 0.0),
            # multi-tenant QoS (ISSUE 10): per-class queue depth (index
            # = class, 0 most urgent), cumulative preemption spills,
            # lanes currently parked awaiting re-admission, and the
            # adapter registry's live set (names feed the router's
            # adapter-affinity scrape; the count is the
            # tpujob_serve_active_adapters gauge)
            "priorityQueueDepth": self._pending.qsize_by_class(),
            "preemptedLanes": self.stats["preempted_lanes"],
            "parkedLanes": len(self._parked),
            # fleet-level KV (ISSUE 12): lanes migrated out / adopted
            # in, peer prefix-chain fetches, and the previously
            # invisible host-tier dropped-oldest overflows — the
            # tpujob_serve_lane_migrations_total /
            # _peer_prefix_fetches_total / _host_cache_evictions_total
            # gauges
            "laneMigrations": self.stats["lane_migrations"],
            "adoptedLanes": self.stats["adopted_lanes"],
            "peerPrefixFetches": self.stats["peer_prefix_fetches"],
            # cross-host disaggregation (ISSUE 13): handoffs landed
            # from the prefill pool — the
            # tpujob_serve_remote_prefills_total gauge
            "remotePrefills": self.stats["remote_prefills"],
            "hostCacheEvictions": (self.pool.host_evictions()
                                   if self.pool is not None else 0),
            # durable prefix store (ISSUE 17): blocks/bytes resident in
            # the persistent tier, the share of submit-thread store
            # probes that returned blocks, and janitor removals
            # (TTL + size budget) — the tpujob_serve_kv_store_* gauges
            # (all 0 with the store off)
            "kvStoreBlocks": kv_store_blocks,
            "kvStoreBytes": kv_store_bytes,
            "kvStoreHitRate": (self.kv_store.hit_rate()
                               if self.kv_store is not None else 0.0),
            "kvStoreEvictions": (self.kv_store.evictions()
                                 if self.kv_store is not None else 0),
            "activeAdapters": (len(self.adapters)
                               if self.adapters is not None else 0),
            "adapterNames": (self.adapters.names()
                             if self.adapters is not None else []),
            # device-resident megastep (ISSUE 11): fused iterations per
            # dispatch and the measured host-dispatch amortization —
            # the tpujob_serve_megastep_n / _dispatches_per_token gauges
            "megastepN": self.megastep,
            "dispatchesPerToken": (
                round(self.stats["chunks"] / self._tokens_emitted, 4)
                if self._tokens_emitted else 0.0),
            # raw cumulative counters, incremented where the work is
            # dispatched and never a ratio (a reader takes the
            # difference between two scrapes): decode dispatches, the
            # device decode iterations they ran, those times the lanes
            # live in each plan, and the paged decode kernel's cells
            # (live, and the rectangle's); insert programs dispatched,
            # the real tokens they prefilled and the positions they computed
            # (the program's width), by width; and the loop thread's
            # self seconds and counts by phase
            "dispatchesTotal": self.stats["chunks"],
            "decodeStepsTotal": self.stats["decode_steps"],
            "decodeLaneStepsTotal": self.stats["decode_lane_steps"],
            "decodeCellsLive": self.stats["decode_cells_live"],
            "decodeCellsGrid": self.stats["decode_cells_grid"],
            "insertStepsTotal": self.stats["insert_steps"],
            "insertStepLanesTotal": self.stats["insert_step_lanes"],
            "prefillCallsTotal": self.stats["prefill_calls"],
            "prefillTokensTotal": pf_tok,
            "prefillBucketTokensTotal":
                self.stats["prefill_bucket_tokens"],
            # (copied first: the ring thread adds a key the first
            # time a width is dispatched)
            "prefillCallsByBucket": {
                str(k): v for k, v in
                dict(self.stats["prefill_calls_by_bucket"]).items()},
            # static: the attention of each rung's whole-prompt insert,
            # "flash" (the pallas kernel) or "einsum"
            "prefillAttnByBucket": {
                str(k): v for k, v in self.executor.prefill_attn.items()},
            # static: bytes a token a layer the cache holds — K and V
            # over the kv heads, int8 codes, or one latent row — so that
            # a reader of the pool's fill need not know the architecture
            "cacheRowBytes": self.executor.cache_row_bytes,
            **self._moe_status(),
            "phaseSeconds": {k: round(v, 6) for k, v in
                             self.phases.self_seconds().items()},
            "phaseCounts": self.phases.counts(),
            # observability (ISSUE 15): the four latency histogram
            # snapshots (cumulative counts for /metrics exposition,
            # rolling-window counts for folding) and the window's TTFT
            # p95 — what aggregate_fleet_serving folds fleet-wide and
            # the SLO autoscaler reads instead of a point gauge
            "latencyHist": self.hist.snapshot(),
            "ttftP95Ms": round(self.hist.ttft.p95() or 0.0, 3),
            # fault tolerance (infer/resilience.py): drain/rebuild
            # visibility for /readyz and the CRD's status.serving block
            "draining": self._draining,
            "healthy": self.healthy,
            "deadlineExceeded": self.stats["deadline_exceeded"],
            "watchdogRestarts": self.stats["watchdog_restarts"],
            "quarantinedLanes": self.stats["quarantined_lanes"],
            # live weight swap / elastic TP resize (ISSUE 19): the
            # generation this replica serves and its current TP degree
            # — the tpujob_serve_generation gauge, the reconciler's
            # roll trigger, and the router's /statusz mid-roll view
            "weightGeneration": int(self.generation),
            "servingTp": self.serving_tp(),
            "weightSwaps": self.stats["weight_swaps"],
        }

    @property
    def accepting(self) -> bool:
        """Readiness (/readyz): the ring takes new admissions — not
        draining, not mid-rebuild, not mid-swap, loop alive, budget
        unspent.  Mid-swap is a READINESS event, not an availability
        one: the router marks the replica down and routes new traffic
        elsewhere while requests already here queue through the flip
        (bounded TTFT inflation, zero 5xx)."""
        return (self.healthy and not self._draining
                and not self._rebuilding and self._swap_req is None
                and not self._stop.is_set()
                and self._thread.is_alive())

    # -- live weight swap / elastic TP resize (ISSUE 19) -------------------

    @property
    def swapping(self) -> bool:
        """True while a posted swap awaits (or is executing) its
        quiesced boundary — the /readyz mark-down window."""
        return self._swap_req is not None

    def serving_tp(self) -> int:
        """Tensor-parallel degree of the CURRENT executor's mesh — the
        ``servingTp`` status key; tracks a live TP resize."""
        mesh = self.executor.mesh
        return int(X.D.mesh_tp(mesh)) if mesh is not None else 1

    def swap_weights(self, params: Any, *, draft_params: Any = None,
                     mesh: Any = _KEEP_MESH,
                     generation: Optional[int] = None,
                     timeout: Optional[float] = 120.0
                     ) -> Dict[str, Any]:
        """Live weight swap / elastic TP resize (ISSUE 19): flip the
        served param trees — and, with ``mesh=``, the TP mesh — without
        restarting the process or dropping a single request.

        Call from any thread (serve.py's ``/v1/swap`` handler).  The
        expensive work (checkpoint load, quantize) happened on the
        CALLER's thread before this call; here the request posts to
        the ring loop, which at the next megastep/chunk boundary:
        quiesces the dispatch pipeline, parks every resident lane via
        the PR 10 spill (full unsharded host bytes), flips params —
        rebuilding the executor when the mesh changes — drops the old
        generation's radix/host cache (its KV must never serve the new
        weights), and restores the parked lanes through the promote
        scatter, which re-shards, so a tp=1 lane legally resumes on a
        tp=2 ring.  LoRA adapters re-gather automatically: the
        registry's delta stacks ride every dispatch as operands
        against whatever base is current.  All-or-nothing: any flip
        failure (and a watchdog rebuild racing the swap) restores the
        old params and generation, and this raises.

        ``generation=None`` bumps the generation by one; an explicit
        value sets it (the fleet roll passes spec.serving.generation).
        Returns the post-swap status summary."""
        if self.pool is None:
            raise ValueError(
                "live weight swap requires the paged ring "
                "(SERVE_PAGED=1): resident lanes park through the "
                "block-granular spill")
        if not self.accepting and self._swap_req is None:
            raise ShuttingDown(
                "ring is draining/rebuilding/stopped; not swapping")
        sw = _SwapRequest(params, draft_params, mesh, generation)
        with self._swap_lock:
            if self._swap_req is not None:
                raise ValueError("a weight swap is already in flight")
            self._swap_req = sw
        self._wake.set()
        if not sw.done.wait(timeout):
            # the ring never reached a boundary (wedged dispatch): the
            # watchdog/heal path will fail the request; un-post so the
            # replica does not stay unready forever
            with self._swap_lock:
                if self._swap_req is sw:
                    self._swap_req = None
            raise RetriableError(
                f"weight swap timed out after {timeout}s awaiting a "
                "quiesced boundary; the ring still serves generation "
                f"{self.generation} — retry")
        if sw.error is not None:
            raise sw.error
        return sw.result or {}

    def _park_residents_for_swap(self) -> int:
        """Park every resident decode lane at THE boundary (the caller
        consumed all in-flight dispatches, so device state and host
        mirrors agree).  Same spill the QoS preemption and the
        drain-by-migration path use — the restore after the flip is
        the existing promote-scatter re-admission."""
        parked = 0
        for i, r in enumerate(self.lane):
            if r is None or r.done.is_set() or r._cancel:
                continue
            self._preempt(i)
            parked += 1
        return parked

    def _do_swap(self) -> None:
        """Execute the posted swap at the quiesced boundary (ring loop
        only; ``pending`` already drained by the caller).  The flip is
        all-or-nothing: the OLD executor/params stay authoritative
        until the new state is fully built, and any failure rolls back
        to them — parked lanes then restore onto the old ring and the
        generation never moves."""
        with self._swap_lock:
            sw, self._swap_req = self._swap_req, None
        if sw is None:
            return
        t0 = time.monotonic()
        ex = self.executor
        resize = sw.mesh is not _KEEP_MESH and sw.mesh is not ex.mesh
        self.flightrec.record(
            "swap_begin", generation=sw.generation,
            resize=bool(resize),
            residents=sum(r is not None for r in self.lane))
        try:
            parked = self._park_residents_for_swap()
            if resize:
                # TP resize: build the NEW executor first (fresh
                # programs compiled against the new mesh, fresh
                # pool/cache) while the old one stays intact — a
                # construction failure leaves the ring exactly as it
                # was.  Peak HBM transiently holds both param sets and
                # both pools (docs/serving.md sizes the headroom).
                new_ex = X.RingExecutor(
                    sw.params, self.cfg, mesh=sw.mesh,
                    draft_params=sw.draft_params, **self._exec_kw)
                old_ex, self.executor = self.executor, new_ex
                self.mesh = sw.mesh
                if (old_ex.prefill_exec is not None
                        and not old_ex.prefill_remote):
                    old_ex.prefill_exec.close()
            else:
                old_params, old_draft = ex.swap_weights(
                    sw.params, sw.draft_params)
                try:
                    # fresh pool + radix: KV computed under the old
                    # generation must never serve the new one
                    ex.reset_state()
                except Exception:
                    ex.swap_weights(old_params, old_draft)
                    ex.reset_state()
                    raise
                del old_params, old_draft    # last refs free the HBM
        except Exception as e:
            self.flightrec.record("swap_failed", error=str(e)[:200])
            sw.error = e
            sw.done.set()
            return
        self.generation = (int(sw.generation)
                           if sw.generation is not None
                           else self.generation + 1)
        # the rebuilt pool is fresh: re-attach the durable store and
        # re-stamp its fingerprint (generation rides the fingerprint,
        # so old-generation store entries refuse wholesale instead of
        # warming the new weights with stale KV)
        if (self.kv_store is not None and self.pool is not None
                and self.pool.host is not None):
            self.pool.attach_store(self.kv_store)
            if getattr(self.kv_store, "fingerprint", None) is not None:
                self.kv_store.fingerprint = self._fingerprint()
        # cross-host disaggregation: the prefill pods must serve the
        # same generation/quant mode — re-stamp the client fingerprint
        # so a mismatched pool 409s instead of handing off stale KV
        if self.executor.prefill_remote:
            self.executor.prefill_exec.fingerprint = \
                self.handoff_fingerprint()
        self._peer_fetch_seen.clear()   # re-ask the fleet post-swap
        self.stats["weight_swaps"] += 1
        self.flightrec.record(
            "swap_done", generation=self.generation,
            tp=self.serving_tp(), parked=parked,
            ms=round((time.monotonic() - t0) * 1e3, 1))
        sw.result = {"generation": self.generation,
                     "servingTp": self.serving_tp(),
                     "parkedLanes": parked,
                     "weightQuantMode": self.weight_quant_mode(),
                     "swapMs": round((time.monotonic() - t0) * 1e3, 1)}
        sw.done.set()
        self._wake.set()    # restores run on the next pass

    def drain(self, budget_s: float = 30.0) -> None:
        """SIGTERM drain (the serving half of docs/fault-tolerance.md):
        stop admissions — queued and newly submitted requests fail with
        :class:`ShuttingDown` (503 + Retry-After upstream) — let the
        RESIDENT lanes finish within ``budget_s`` (lanes still
        PREFILLING — chunked slices or a disagg handoff — finish their
        prefill and their decode like any resident), cancel stragglers
        at the budget (their callers receive the tokens produced so
        far; paged blocks verifiably return to the pool), then close."""
        self.flightrec.record(
            "drain_start", residents=sum(r is not None
                                         for r in self.lane),
            parked=len(self._parked), queued=self._pending.qsize())
        self._draining = True
        self._wake.set()
        deadline = time.monotonic() + budget_s
        while time.monotonic() < deadline and self._thread.is_alive():
            if all(r is None for r in self.lane) \
                    and self._pending.empty() and not self._parked:
                break
            time.sleep(0.02)
        for req in list(self.lane):
            if req is not None:
                req.cancel()            # partial flush at chunk boundary
        for pk in list(self._parked):
            pk.req.cancel()             # parked partials flush too
        grace = time.monotonic() + max(5.0, budget_s)
        while ((any(r is not None for r in self.lane) or self._parked)
               and self._thread.is_alive()
               and time.monotonic() < grace):
            time.sleep(0.02)
        self.flightrec.record(
            "drain_done", stragglers=sum(r is not None
                                         for r in self.lane))
        self.close()

    def abort(self, error: Optional[Exception] = None) -> None:
        """Second-SIGTERM semantics: immediate teardown.  Resident
        requests RESOLVE with their partial tokens (best-effort flush —
        an undrained kill would have lost them entirely); queued ones
        fail with ShuttingDown."""
        self.flightrec.record("abort",
                              error=(str(error)[:200] if error
                                     else None))
        self._draining = True
        self._stop.set()
        self._wake.set()
        for i, req in enumerate(self.lane):
            if req is not None and not req.done.is_set():
                req.out = req.prompt + self._lane_out[i]
                self._finish(req)
        for pk in self._parked:         # parked partials resolve too
            if not pk.req.done.is_set():
                pk.req.out = pk.req.prompt + pk.out
                self._finish(pk.req)
        self._parked.clear()
        self._shed_queue(error or ShuttingDown("server killed"))

    def close(self) -> None:
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=30)
        if self._watchdog is not None:
            self._watchdog.close()
        if self.executor.prefill_exec is not None:
            self.executor.prefill_exec.close()
        # late blocked submitters can land requests after the loop's own
        # drain pass — sweep again so none hangs at result()
        self._shed_queue(ShuttingDown("batcher closed"))

    # -- fault handling ----------------------------------------------------

    def _shed_queue(self, error: Exception) -> None:
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                return
            self._finish(req, error)

    def _on_stall(self, elapsed: float) -> None:
        """Watchdog monitor callback: a dispatch/consume wait crossed
        N x rolling-p95.  Fail the resident requests NOW — their
        clients get retriable 503s while the ring thread is still stuck
        inside the wedged dispatch — and flag the rebuild the loop runs
        once it unwedges."""
        err = RetriableError(
            f"compiled dispatch stalled {elapsed:.1f}s (watchdog "
            f"threshold {self._watchdog.threshold():.1f}s); ring "
            "rebuilding — retry")
        for req in list(self.lane):
            if req is not None and not req.done.is_set():
                self._finish(req, err)
        self._fault = err

    def _on_hard_stall(self, elapsed: float) -> None:
        """The stall outlived hard_stall_factor x threshold: the host
        thread is unrecoverably stuck inside the runtime.  Flip
        /healthz so the orchestrator replaces the pod (crash-only)."""
        self.healthy = False

    def _heal(self, err: Exception) -> bool:
        """Self-heal after a ring-level fault: fail whatever is still
        resident with a retriable error, rebuild every piece of device
        state from scratch (cache, paged pool + radix cache, lane
        state — RingExecutor.reset_state), back off exponentially.
        Requests mid-prefill (chunked or away on the prefill executor)
        fail with the residents; a disagg result for a healed-away
        request is dropped at handoff.  Returns False — and flips
        ``healthy`` — when the restart budget is exhausted (the loop
        then dies the legacy way and /healthz goes unhealthy)."""
        wrapped = (err if isinstance(err, RetriableError)
                   else RetriableError(
                       f"ring dispatch failed ({err}); rebuilt — retry"))
        # decide + account for the restart BEFORE unblocking any client:
        # a caller released by the _finish below may immediately read
        # stats/healthy, and must see the restart it was shed for
        healing = self._budget is not None and not self._budget.exhausted
        if healing:
            self._rebuilding = True
            self.stats["watchdog_restarts"] += 1
        else:
            self.healthy = False
        # flight recorder (ISSUE 15): the rebuild is exactly the event
        # a crash-time dump exists for — record it and persist the
        # whole ring NOW, before the backoff sleep a hard kill could
        # land inside
        self.flightrec.record("watchdog_rebuild",
                              error=str(err)[:200], healing=healing,
                              residents=sum(r is not None
                                            for r in self.lane))
        self.flightrec.dump_file("watchdog_rebuild")
        for req in list(self.lane):
            if req is not None and not req.done.is_set():
                self._finish(req, wrapped)
        # parked lanes fail with the residents: their spills reference
        # nothing device-side (host bytes), but their CLIENTS deserve
        # the same retriable signal the rebuild sends everyone else
        for pk in self._parked:
            if not pk.req.done.is_set():
                self._finish(pk.req, wrapped)
        self._parked.clear()
        self.lane = [None] * self.slots
        self._lane_out = [[] for _ in range(self.slots)]
        self._lane_left = [0] * self.slots
        self._lane_pos = [0] * self.slots
        self._lane_first = [None] * self.slots
        self._prefilling.clear()
        self._disagg_waiting.clear()
        self._handoff_frame_t.clear()
        # a watchdog rebuild ABORTS any pending live swap (ISSUE 19):
        # the rebuild restores the OLD generation's params (reset_state
        # keeps self.executor.params), so the swap caller must retry —
        # all-or-nothing, never a half-flipped ring
        with self._swap_lock:
            sw, self._swap_req = self._swap_req, None
        if sw is not None:
            sw.error = RetriableError(
                "ring rebuilt mid-swap; the old generation was "
                "restored — retry the swap")
            sw.done.set()
        if not healing:
            return False
        backoff = self._budget.spend()
        self.executor.reset_state()
        # the rebuilt pool is fresh (store=None): re-attach the durable
        # store (ISSUE 17) — surviving restarts is its whole point, and
        # the rebuilt radix re-fills from it via the normal store probe
        if (self.kv_store is not None and self.pool is not None
                and self.pool.host is not None):
            self.pool.attach_store(self.kv_store)
        self._stop.wait(backoff)
        self._rebuilding = False
        return True

    def _expire_deadlines(self) -> None:
        now = time.monotonic()
        for i, req in enumerate(self.lane):
            if (req is not None and req.deadline is not None
                    and now >= req.deadline and not req.done.is_set()):
                req.deadline_exceeded = True
                self.stats["deadline_exceeded"] += 1
                self.flightrec.record("deadline_expired", lane=i,
                                      rid=req.request_id)
                self._evict(i)        # resolves with the partial tokens
        # parked lanes keep their deadline semantics: an expired one
        # resolves with the tokens it had at the spill boundary (the
        # same 504-style partial a resident gets).  A lane whose
        # envelope is ON THE WIRE is left alone until the outcome
        # lands: expiring it here while a peer adopts would deliver a
        # 504 partial the dedupe LRU records as final AND decode the
        # full stream on the adopter (the deadline travels in the
        # envelope, so the adopter enforces it after a success).
        for pk in list(self._parked):
            req = pk.req
            if pk.migrating:
                continue
            if (req.deadline is not None and now >= req.deadline
                    and not req.done.is_set()):
                req.deadline_exceeded = True
                self.stats["deadline_exceeded"] += 1
                req.out = req.prompt + pk.out
                self._finish(req)
                self._parked.remove(pk)

    # -- admission ---------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"no bucket fits prompt length {n}")

    def _count_prefill(self, width: int, tokens: int, bucket=None) -> None:
        """One insert program dispatched: ``tokens`` real prompt (or
        suffix, or slice) tokens through a program ``width`` positions
        wide — the full-prompt bucket, the suffix bucket or the chunked
        slice — counted by width, or under ``bucket`` where given."""
        st = self.stats
        st["prefill_calls"] += 1
        st["prefill_tokens"] += tokens
        st["prefill_bucket_tokens"] += width
        by = st["prefill_calls_by_bucket"]
        key = width if bucket is None else bucket
        by[key] = by.get(key, 0) + 1

    def _dispatch_cow(self, slot: int, cow, hit_len: int) -> None:
        """Dispatch the admission's copy-on-write block copies (codes +
        scales under SERVE_KV_QUANT=int8), then — quant only — seed the
        lane's bf16 staging tail when the radix hit lands MID-BLOCK:
        the lane's write-frontier block already holds quantized prefix
        rows (its CoW'd private copy), and both the suffix forward's
        tail-substituted read of [block_start, hit_len) and the
        eventual on-completion requantize of the WHOLE block need those
        rows present in the tail (paged.make_tail_init).

        Runs the admission's host-tier PROMOTIONS first (ISSUE 8): any
        radix hit the walk classified as host-resident reserved its
        device block inside pool.admit — the batched donated upload
        must reach the stream BEFORE a CoW that may copy a promoted
        block and before the insert that reads it.  All dispatches are
        async, so the transfer overlaps whatever chunk is already
        decoding; activation (the insert) is stream-ordered behind the
        transfer's completion."""
        ex = self.executor
        promotes = self.pool.take_promotions()
        if promotes:
            ex.dispatch_promotions(promotes)
            self.stats["promoted_blocks"] += len(promotes)
        if ex.quant:
            for src, dst in cow:
                (ex.cache["k"], ex.cache["v"], ex.cache["ks"],
                 ex.cache["vs"]) = ex._copy_block(
                    ex.cache["k"], ex.cache["v"], ex.cache["ks"],
                    ex.cache["vs"], src, dst)
        else:
            for src, dst in cow:
                ex.cache["k"], ex.cache["v"] = ex._copy_block(
                    ex.cache["k"], ex.cache["v"], src, dst)
        self.stats["cow_copies"] = self.pool.stats["cow_copies"]
        if ex.quant and hit_len % self.block_size:
            blk = int(self.pool.table[slot][hit_len // self.block_size])
            ex.cache["kt"], ex.cache["vt"] = ex._tail_init(
                ex.cache["kt"], ex.cache["vt"], ex.cache["k"],
                ex.cache["ks"], ex.cache["v"], ex.cache["vs"], slot, blk)

    def _activate(self, slot: int, req: _Request, first) -> None:
        """A lane's prefill completed (whatever path delivered it):
        wire up the decode-side bookkeeping so the next chunk dispatch
        includes it."""
        try:                            # ship the first token host-ward
            first.copy_to_host_async()  # early: TTFT then needs no
        except AttributeError:          # extra round-trip at consume
            pass
        n = len(req.prompt)
        self._lane_out[slot] = []
        self._lane_first[slot] = first
        self._lane_left[slot] = req.max_new
        self._lane_pos[slot] = n
        if req.max_new == 1:
            # degenerate budget: sync now and free the lane immediately
            # rather than riding a whole wasted chunk
            self._materialize_first(slot, req)
            self._evict(slot)

    def _admit(self, slot: int, req: _Request) -> None:
        """Admission entry: reserve the lane, then route by prefill
        mode.  ``inline`` is ONE compiled dispatch and nothing else on
        the device path (make_prefill_insert does the splice,
        first-token sample and all lane-state updates in a single jit):
        eager ops here would block behind whatever chunk is decoding
        (cost on the v5e not re-measured).  ``chunked`` maps
        blocks / allocates staging and lets the loop interleave slices;
        ``disagg`` ships cold prompts to the prefill executor (prefix
        hits stay inline — the suffix insert is already cheap)."""
        ex = self.executor
        n = len(req.prompt)
        # queue-wait telemetry (ISSUE 15): submit -> this admission,
        # observed into the queue-wait histogram (and, traced, a span
        # carrying the QoS class) — the p95 the autoscaler's depth
        # model can finally be checked against
        now = time.monotonic()
        self.hist.queue_wait.observe((now - req.t_submit) * 1e3)
        if req.trace is not None:
            req.trace.add("queue_wait", req.t_submit, now,
                          prio=req.priority)
        self.flightrec.record("admit", rid=req.request_id, slot=slot,
                              prio=req.priority,
                              mode=self.prefill_mode)
        # reserve the lane FIRST: the admin surface's in-use snapshot
        # (serve.py lanes_in_use) reads lane/parked/queue from another
        # thread, and a request popped from the queue but not yet
        # lane-visible would otherwise slip through an evict guard
        self.lane[slot] = req
        if req.adapter_idx and self.adapters is not None:
            # re-validate at admission: the adapter could have been
            # evicted (and its slot even reloaded with ANOTHER tenant's
            # deltas) while this request sat queued — the load
            # generation captured at submit is the identity check (the
            # admission exception path releases the lane)
            try:
                live_ns = self.adapters.ns_of(req.adapter_idx)
            except KeyError:
                live_ns = -1
            if live_ns != req.ns:
                raise ValueError(
                    f"adapter {req.adapter!r} was evicted/replaced "
                    "while this request was queued; resubmit")
        # the lane's adapter id (host mirror): every adapter-aware
        # dispatch from here on gathers this lane's LoRA pair
        ex.aid[slot] = req.adapter_idx
        # reset the lane's host mirrors NOW, not at activation: a
        # chunked/disagg lane evicted MID-PREFILL (cancel, deadline,
        # drain) resolves through ``req.prompt + _lane_out[slot]``, and
        # the previous occupant's tokens must never leak into it
        self._lane_out[slot] = []
        self._lane_first[slot] = None
        if self.prefill_mode == "chunked":
            self._admit_chunked(slot, req)
            self.stats["admitted"] += 1
            return
        if self.prefill_mode == "disagg":
            self._admit_disagg(slot, req)
            self.stats["admitted"] += 1
            return
        if self.paged:
            first = self._admit_paged(slot, req)
        elif self.spec_k:
            with TR.phase("exec.insert", width=req.bucket, tokens=n):
                (ex.cache, ex.dcache, ex.tok, ex.temp, ex.keys,
                 first) = ex.inserts[req.bucket](
                    ex.params, ex.draft_params, ex.cache, ex.dcache,
                    ex.tok, ex.temp, ex.keys, req.dev_prompt,
                    n, slot, float(req.temperature), req.seed)
            self._count_prefill(req.bucket, n)
        else:
            with TR.phase("exec.insert", width=req.bucket, tokens=n):
                ex.cache, ex.tok, ex.temp, ex.keys, first = \
                    ex.inserts[req.bucket](
                        ex.params, ex.cache, ex.tok, ex.temp,
                        ex.keys, req.dev_prompt, n, slot,
                        float(req.temperature), req.seed,
                        *ex.lora_insert_tail(req.adapter_idx))
            self._count_prefill(req.bucket, n)
        # counted only once the insert dispatched: a NoFreeBlocks /
        # insert failure above fails the request and must not drift
        # ``admitted`` past real admissions (the slot-reuse tests and
        # the bench saturation wait both read it)
        self.stats["admitted"] += 1
        self._activate(slot, req, first)

    def _pool_admit(self, slot: int, req: _Request) -> int:
        """The pool's half of a paged admission: map the lane's blocks
        (radix hits read-only, fresh for the rest) and dispatch the
        copy-on-write copies and promotions the mapping asked for.
        Returns the radix hit's length in tokens."""
        with TR.phase("pool.admit", prompt_len=len(req.prompt)):
            hit_len, cow = self.pool.admit(
                slot, req.prompt,
                max_suffix=self.SUFFIX_PREFILL_MAX_ROWS, ns=req.ns)
            self._dispatch_cow(slot, cow, hit_len)
        return hit_len

    def _admit_paged(self, slot: int, req: _Request):
        """Inline paged admission: map blocks (radix hits read-only,
        CoW'd where the suffix will write, fresh for the rest), then
        ONE compiled insert — the full-prompt scatter insert cold, the
        suffix-only insert on a prefix hit.  A full prefix hit runs a
        ONE-token forward (the first sampled token needs the last
        prompt position's logits — logits are not cached, KV is) and
        zero forwards over cached blocks; the prefill-call counters are
        the tests' acceptance gate for that claim."""
        ex = self.executor
        n = len(req.prompt)
        # max_suffix: beyond it a prefix hit is not worth taking — the
        # suffix insert's per-row pool writes (paged._write_rows_paged)
        # unroll O(rows), so a long divergent suffix admits faster
        # through the cold block-granular scatter prefill; the
        # allocator then maps fresh blocks instead of the cached ones
        # (never written over) when spec mode is off
        hit_len = self._pool_admit(slot, req)   # NoFreeBlocks -> req fails
        if self.spec_k:
            with TR.phase("exec.insert", width=req.bucket, tokens=n):
                (ex.cache, ex.dcache, ex.tok, ex.temp, ex.keys,
                 first) = ex.inserts[req.bucket](
                    ex.params, ex.draft_params, ex.cache, ex.dcache,
                    jnp.asarray(self.pool.table[slot]), ex.tok, ex.temp,
                    ex.keys, req.dev_prompt, n, slot,
                    float(req.temperature), req.seed)
            self._count_prefill(req.bucket, n)
        elif hit_len:
            first = self._suffix_admit(
                slot, req, jnp.asarray(self.pool.table[slot]), hit_len)
        else:
            riders = (self._insert_riders(slot)
                      if req.bucket in ex.insert_steps else [])
            with TR.phase("exec.insert", width=req.bucket, tokens=n,
                          lanes_stepped=len(riders)) as ph:
                first, res = ex.cold_insert(
                    req.bucket, slot, self.pool.table, riders,
                    req.dev_prompt, n, float(req.temperature), req.seed,
                    req.adapter_idx)
            self._count_prefill(req.bucket, n)
            if res is not None:
                self._queue_insert_step(riders, res, ph.t0)
        # register this lane's full prompt blocks for future admissions
        # (content is valid for any later dispatch — same device stream;
        # adapter lanes publish under their namespace, so reuse happens
        # within a tenant's fine-tune and never across)
        self.pool.publish(slot, req.prompt, ns=req.ns)
        return first

    def _insert_riders(self, slot: int) -> List[int]:
        """The lanes a cold insert into ``slot`` advances by a token
        (the step its program carries): every lane with a request and
        no prefill pending but the inserted one — a lane an earlier
        insert activated and no chunk has included yet rides too.  The
        step writes lane i's row at its device position, the host's
        mirror plus what is in flight for it, so the pool must map that
        position's block first (the plan's projection with one more
        row); a lane that cannot grow sits this step out — it is not
        failed here, its next chunk's plan asks again."""
        waiting = self._pending_prefill_slots()
        riders = []
        for i, r in enumerate(self.lane):
            if r is None or i == slot or i in waiting:
                continue
            try:
                self.pool.ensure(i, self._lane_pos[i]
                                 + self._rows_in_flight(i) + 1)
            except self.executor._pg.NoFreeBlocks:
                continue
            riders.append(i)
        return riders

    def _rows_in_flight(self, i: int) -> int:
        """Positions lane ``i`` advances in the dispatches not yet
        consumed: the host's position mirror lags them."""
        return sum(res.rows for chunk_reqs, res, _ in self._inflight
                   for j, r in chunk_reqs
                   if j == i and r is self.lane[i])

    def _queue_insert_step(self, riders: List[int], res, t0: float) -> None:
        """An insert returned the tokens of the step it carried: queue
        them behind the dispatches in flight, as the one-step result
        they are, so :meth:`_consume` delivers them in device order —
        after the chunk before the insert, before the chunk after —
        with budgets, EOS, deadlines and eviction as for any step."""
        if not riders:
            # nobody rode (an idle ring, or no lane could grow): the
            # program's step advanced no lane, so it is no decode step
            # to the counters and there is nothing to deliver
            return
        st = self.stats
        st["insert_steps"] += 1
        st["insert_step_lanes"] += len(riders)
        st["decode_steps"] += 1
        st["decode_lane_steps"] += len(riders)
        live, grid = self.pool.decode_cell_counts(self._lane_pos,
                                                  set(riders))
        st["decode_cells_live"] += live
        st["decode_cells_grid"] += grid
        for dev in (res.toks, res.ok):
            try:
                dev.copy_to_host_async()
            except AttributeError:      # None / interpret-mode ndarray
                pass
        self._inflight.append(([(i, self.lane[i]) for i in riders], res,
                               t0))

    def _suffix_admit(self, slot: int, req: _Request, tbl_row, hit_len):
        """Prefix-hit admission: one suffix-only insert over the
        uncached tail — shared by the inline paged path and disagg's
        hit short-circuit."""
        ex = self.executor
        suffix = req.prompt[hit_len:]
        sb = ex.suffix_bucket(len(suffix))
        ins = ex.suffix_insert(sb)
        padded = np.zeros((1, sb), np.int32)
        padded[0, :len(suffix)] = suffix
        with TR.phase("exec.insert", width=sb, tokens=len(suffix),
                      hit_len=hit_len):
            ex.cache, ex.tok, ex.temp, ex.keys, first = ins(
                ex.params, ex.cache, tbl_row, ex.tok, ex.temp,
                ex.keys, jnp.asarray(padded), len(suffix), hit_len,
                slot, float(req.temperature), req.seed,
                *ex.lora_insert_tail(req.adapter_idx))
        self._count_prefill(sb, len(suffix))
        return first

    def _admit_chunked(self, slot: int, req: _Request) -> None:
        """Chunked admission: reserve the lane and (paged) map its
        blocks now — the loop then advances ONE prefill slice per ring
        iteration (:meth:`_advance_prefill`), so resident decode lanes
        never wait more than one slice."""
        ex = self.executor
        hit_len = 0
        if self.paged:
            hit_len = self._pool_admit(slot, req)
            lane_k = lane_v = None
        else:
            lane_k, lane_v = ex.make_staging(req.bucket)
        self._admit_seq += 1
        self._prefilling[slot] = _PrefillState(
            req, hit_len, hit_len, self._admit_seq, lane_k, lane_v)

    def _advance_prefill(self, slot: int) -> None:
        """Dispatch the NEXT chunked-prefill slice for lane ``slot``:
        an intermediate slice appends KV only; the final slice runs the
        suffix/final insert (first-token sample + lane activation) and
        publishes the prompt's blocks to the radix cache."""
        ex = self.executor
        st = self._prefilling[slot]
        req = st.req
        n = len(req.prompt)
        sb = ex.prefill_chunk
        remaining = n - st.start
        if remaining > sb:
            # intermediate slice: KV only, no logits, no lane state
            toks = np.zeros((1, sb), np.int32)
            toks[0, :] = req.prompt[st.start:st.start + sb]
            with TR.phase("exec.insert", width=sb, tokens=sb) as ph:
                if self.paged:
                    tbl_row = jnp.asarray(self.pool.table[slot])
                    args = (ex.params, ex.cache, tbl_row,
                            jnp.asarray(toks), st.start, st.start + sb)
                    if ex.quant:  # quant slices address the lane's tail
                        args += (slot,)
                    ex.cache = ex.chunk_prog(None)(
                        *args, *ex.lora_insert_tail(req.adapter_idx))
                else:
                    sl = ex.staging_len(req.bucket)
                    st.lane_k, st.lane_v = ex.chunk_prog(sl)(
                        ex.params, st.lane_k, st.lane_v,
                        jnp.asarray(toks), st.start,
                        *ex.lora_insert_tail(req.adapter_idx))
            st.start += sb
            self._count_prefill(sb, sb)
            self.stats["chunked_prefill_tokens"] += sb
            if req.trace is not None:
                req.trace.add("prefill_slice", ph.t0, ph.t1,
                              start=st.start - sb, tokens=sb)
            return
        # final slice
        toks = np.zeros((1, sb), np.int32)
        toks[0, :remaining] = req.prompt[st.start:]
        toks = jnp.asarray(toks)
        with TR.phase("exec.insert", width=sb, tokens=remaining) as ph:
            first = self._final_slice(slot, st, toks, remaining)
        self._count_prefill(sb, remaining)
        self.stats["chunked_prefill_tokens"] += remaining
        if req.trace is not None:
            req.trace.add("prefill_slice", ph.t0, ph.t1, start=st.start,
                          tokens=remaining, final=True)
        del self._prefilling[slot]
        if self.paged:
            self.pool.publish(slot, req.prompt, ns=req.ns)
        self._activate(slot, req, first)

    def _final_slice(self, slot: int, st: _PrefillState, toks,
                     remaining: int):
        """Dispatch the chunked prefill's LAST slice — the final insert
        of this ring's kind (paged or staged, plain or speculative) —
        and return the first sampled token (a device future)."""
        ex, req = self.executor, st.req
        n = len(req.prompt)
        if self.paged and not self.spec_k:
            ins = ex.final_insert(None)
            ex.cache, ex.tok, ex.temp, ex.keys, first = ins(
                ex.params, ex.cache, jnp.asarray(self.pool.table[slot]),
                ex.tok, ex.temp, ex.keys, toks, remaining, st.start,
                slot, float(req.temperature), req.seed,
                *ex.lora_insert_tail(req.adapter_idx))
        elif self.paged:
            ins = ex.final_insert(None, req.bucket)
            (ex.cache, ex.dcache, ex.tok, ex.temp, ex.keys, first) = ins(
                ex.params, ex.draft_params, ex.cache, ex.dcache,
                jnp.asarray(self.pool.table[slot]), ex.tok, ex.temp,
                ex.keys, toks, remaining, st.start, slot,
                req.dev_prompt, n, float(req.temperature), req.seed)
        elif self.spec_k:
            sl = ex.staging_len(req.bucket)
            ins = ex.final_insert(sl, req.bucket)
            (ex.cache, ex.dcache, ex.tok, ex.temp, ex.keys, first) = ins(
                ex.params, ex.draft_params, ex.cache, ex.dcache,
                st.lane_k, st.lane_v, ex.tok, ex.temp, ex.keys, toks,
                remaining, st.start, req.dev_prompt, n, slot,
                float(req.temperature), req.seed)
        else:
            sl = ex.staging_len(req.bucket)
            ins = ex.final_insert(sl)
            ex.cache, ex.tok, ex.temp, ex.keys, first = ins(
                ex.params, ex.cache, st.lane_k, st.lane_v, ex.tok,
                ex.temp, ex.keys, toks, remaining, st.start, n, slot,
                float(req.temperature), req.seed,
                *ex.lora_insert_tail(req.adapter_idx))
        return first

    def _admit_disagg(self, slot: int, req: _Request) -> None:
        """Disaggregated admission: a radix prefix HIT admits inline
        through the suffix insert (the cached blocks live in the decode
        pool; the suffix forward is already cheap).  A COLD prompt maps
        fresh decode-pool blocks now (reserved — the handoff can never
        fail on NoFreeBlocks) and ships the prefill to the executor
        thread; the loop attaches the lane when the result lands."""
        # the post-admit hook (_dispatch_cow) runs on the cold path
        # too: a hit_len-0 PARTIAL-tail hit can map (and host-promote)
        # one block whose upload/CoW must not stay pending — the
        # handoff overwrites the lane's view, but the promoted entry
        # re-anchored in the radix cache and a later hit on it must
        # read real bytes
        hit_len = self._pool_admit(slot, req)
        if hit_len and not self.spec_k:
            first = self._suffix_admit(
                slot, req, jnp.asarray(self.pool.table[slot]), hit_len)
            self.pool.publish(slot, req.prompt, ns=req.ns)
            self._activate(slot, req, first)
            return
        # cold: fresh blocks are already mapped by admit (hit_len == 0
        # here unless spec, whose prefix cache is off -> also 0)
        ex = self.executor
        if ex.prefill_remote and req.adapter_idx:
            # remote prefill pods serve the BASE param set: an adapter
            # prompt prefilled there would hand off base-model KV under
            # a tenant's namespace.  Admit it inline on the ring thread
            # instead (exactly the SERVE_PREFILL=inline cold path) —
            # correctness first; adapter traffic simply skips the
            # remote TTFT win.
            n = len(req.prompt)
            with TR.phase("exec.insert", width=req.bucket, tokens=n):
                ex.cache, ex.tok, ex.temp, ex.keys, first = \
                    ex.inserts[req.bucket](
                        ex.params, ex.cache,
                        jnp.asarray(self.pool.table[slot]), ex.tok,
                        ex.temp, ex.keys, req.dev_prompt, n, slot,
                        float(req.temperature), req.seed,
                        *ex.lora_insert_tail(req.adapter_idx))
            self._count_prefill(req.bucket, n)
            self.pool.publish(slot, req.prompt, ns=req.ns)
            self._activate(slot, req, first)
            return
        self._disagg_waiting[slot] = req
        req.t_prefill0 = time.monotonic()
        ex.prefill_exec.submit(req, slot)

    def _land_handoff_blocks(self, slot: int, payload, lane, j0: int,
                             j1: int) -> None:
        """Upload one handoff block group ``[j0, j1)`` into the lane's
        already-reserved decode-pool blocks: the batched promote
        scatter for remote (host) payloads, the frame transfer for
        in-process (device snapshot) payloads.  Shared by streamed
        frames and the terminal item's remainder — both async
        dispatches that overlap whatever chunk is decoding."""
        if j1 <= j0:
            return
        ex = self.executor
        if ex.prefill_remote:
            promotes = []
            for i, j in enumerate(range(j0, j1)):
                p = {"k": payload["k"][:, i:i + 1],
                     "v": payload["v"][:, i:i + 1]}
                if ex.quant:
                    p["ks"] = payload["ks"][:, i:i + 1]
                    p["vs"] = payload["vs"][:, i:i + 1]
                promotes.append(
                    (int(self.pool.table[slot][j]), p, None))
            ex.dispatch_promotions(promotes)
            return
        m = self.pool.max_blocks
        n = j1 - j0
        src_ids = np.zeros((m,), np.int32)
        dst_ids = np.zeros((m,), np.int32)
        src_ids[:n] = ex.prefill_exec.tables[lane][j0:j1]
        dst_ids[:n] = self.pool.table[slot][j0:j1]
        if ex.quant:
            (ex.cache["k"], ex.cache["v"], ex.cache["ks"],
             ex.cache["vs"]) = ex._frame_transfer(
                ex.cache["k"], ex.cache["v"], ex.cache["ks"],
                ex.cache["vs"], payload["k"], payload["v"],
                payload["ks"], payload["vs"], jnp.asarray(src_ids),
                jnp.asarray(dst_ids))
        else:
            ex.cache["k"], ex.cache["v"] = ex._frame_transfer(
                ex.cache["k"], ex.cache["v"], payload["k"],
                payload["v"], jnp.asarray(src_ids),
                jnp.asarray(dst_ids))

    def _land_remote_tail(self, slot: int, payload) -> None:
        """A REMOTE handoff's (int8) staging tail: the wire payload's
        exact bf16 tail row lands in decode tail row ``slot``."""
        ex = self.executor
        ex.cache["kt"] = ex.cache["kt"].at[:, slot].set(
            jnp.asarray(payload["kt"][:, 0]))
        ex.cache["vt"] = ex.cache["vt"].at[:, slot].set(
            jnp.asarray(payload["vt"][:, 0]))

    def _drain_handoffs(self) -> None:
        """Attach completed disaggregated prefills: device-to-device
        block copy from the prefill executor's pool into the lane's
        already-mapped decode-pool blocks, then one tiny attach
        dispatch (pos/tok/temp/keys).  Results for requests that
        resolved meanwhile (cancel, deadline, heal) are dropped — their
        decode blocks were already retired with the lane.

        STREAMED handoff (ISSUE 14): the N-lane engine (and the
        streaming remote client) post ``("frame", req, slot, payload,
        lane, j0, j1)`` block-group items WHILE the prompt is still
        prefilling, then a terminal ``("final", req, slot, payload,
        lane, j0, n_blocks, first, t_done)`` with the remainder +
        (int8) staging tail + first token — so the decode-side upload
        (and the DCN wire, remote) overlaps the remaining prefill
        compute.  Frames for a resolved request drop exactly like
        stale results; a retried stream simply re-uploads from block
        0 (uploads are idempotent by destination — the blocks were
        reserved at admission)."""
        ex = self.executor
        pexec = ex.prefill_exec
        while True:
            try:
                item = pexec.results.get_nowait()
            except queue.Empty:
                return
            if isinstance(item[0], str):
                kind, req, slot = item[0], item[1], item[2]
                if (self._disagg_waiting.get(slot) is not req
                        or self.lane[slot] is not req
                        or req.done.is_set()):
                    continue                # stale frame/final: drop
                if kind == "frame":
                    _, _, _, payload, lane, j0, j1 = item
                    t_fr0 = time.monotonic()
                    self._land_handoff_blocks(slot, payload, lane,
                                              j0, j1)
                    self.stats["handoff_frames"] += 1
                    self._handoff_frame_t.setdefault(slot, []).append(
                        time.monotonic())
                    if req.trace is not None:
                        # host time of the streamed-frame upload
                        # dispatch (async — it overlaps the decoding
                        # chunk; the overlap proof is the stats
                        # counter, the span is the timeline marker)
                        req.trace.add("handoff_frame", t_fr0, j0=j0,
                                      j1=j1)
                    continue
                _, _, _, payload, lane, j0, n_blocks, first, t_done = \
                    item
                del self._disagg_waiting[slot]
                self._land_handoff_blocks(slot, payload, lane, j0,
                                          n_blocks)
                if ex.quant:
                    if ex.prefill_remote:
                        self._land_remote_tail(slot, payload)
                    else:
                        ex.cache["kt"], ex.cache["vt"] = ex._tail_copy(
                            ex.cache["kt"], ex.cache["vt"],
                            payload["kt"], payload["vt"], lane, slot)
                stamps = self._handoff_frame_t.pop(slot, [])
                self.stats["overlapped_frames"] += sum(
                    1 for t in stamps if t < t_done)
                if ex.prefill_remote:
                    self.stats["remote_prefills"] += 1
                self._attach_handoff(slot, req, len(req.prompt), first)
                continue
            req, slot = item[0], item[1]
            if (self._disagg_waiting.get(slot) is not req
                    or self.lane[slot] is not req or req.done.is_set()):
                continue                    # stale result: drop
            del self._disagg_waiting[slot]
            if len(item) == 3:              # (req, slot, error)
                self._finish(req, item[2])
                self._evict(slot)
                continue
            _, _, snap, n_blocks, first = item
            n = len(req.prompt)
            if ex.prefill_remote:
                # cross-host handoff (ISSUE 13): ``snap`` is the wire
                # envelope's HOST payload — per-block pool bytes the
                # prefill pod captured.  Land the whole range through
                # the streamed path's shared helper (the batched
                # promote scatter a host-tier hit uses, PR 8 — byte-
                # exact upload, codes+scales verbatim under int8) +
                # the exact wire tail, then the identical attach path
                # as in-process.
                self._land_handoff_blocks(slot, snap, None, 0,
                                          n_blocks)
                if ex.quant:
                    self._land_remote_tail(slot, snap)
                self.stats["remote_prefills"] += 1
                self._attach_handoff(slot, req, n, first)
                continue
            # src blocks are the executor's fixed identity row 1..M;
            # dst blocks were mapped at admission.  Both id vectors pad
            # to the table width with the TRASH block — garbage written
            # there is the trash block's job — so ONE transfer compile
            # serves every prompt length.
            m = self.pool.max_blocks
            src_ids = np.zeros((m,), np.int32)
            dst_ids = np.zeros((m,), np.int32)
            src_ids[:n_blocks] = np.arange(1, n_blocks + 1)
            dst_ids[:n_blocks] = self.pool.table[slot][:n_blocks]
            if ex.quant:
                # codes, scales AND the prompt's partial-block staging
                # tail cross the handoff (src tail row 0 — the executor
                # pool is one lane wide — lands in decode tail ``slot``)
                (ex.cache["k"], ex.cache["v"], ex.cache["ks"],
                 ex.cache["vs"], ex.cache["kt"],
                 ex.cache["vt"]) = ex._transfer(
                    ex.cache["k"], ex.cache["v"], ex.cache["ks"],
                    ex.cache["vs"], ex.cache["kt"], ex.cache["vt"],
                    snap["k"], snap["v"], snap["ks"], snap["vs"],
                    snap["kt"], snap["vt"], jnp.asarray(src_ids),
                    jnp.asarray(dst_ids), slot)
            else:
                ex.cache["k"], ex.cache["v"] = ex._transfer(
                    ex.cache["k"], ex.cache["v"], snap["k"], snap["v"],
                    jnp.asarray(src_ids), jnp.asarray(dst_ids))
            self._attach_handoff(slot, req, n, first)

    def _attach_handoff(self, slot: int, req: _Request, n: int,
                        first) -> None:
        """The handoff's decode-side tail, shared by the in-process
        (device block copy) and remote (promote-scatter upload) paths:
        one tiny attach dispatch — spec rings additionally prefill the
        DRAFT lane here, which is why the handoff snapshot never
        carries draft state — then publish + activate."""
        ex = self.executor
        t_att0 = time.monotonic()
        if req.trace is not None and req.t_prefill0 is not None:
            # the whole off-ring prefill phase: executor-queue wait +
            # prefill compute (+ the DCN wire, remote — whose own span
            # the RemotePrefillClient stamps) up to this attach
            req.trace.add("disagg_prefill", req.t_prefill0, t_att0,
                          remote=bool(ex.prefill_remote))
        if self.spec_k:
            (ex.dcache, ex.cache["pos"], ex.tok, ex.temp,
             ex.keys) = ex.spec_attach(req.bucket)(
                ex.draft_params, ex.dcache, ex.cache["pos"], ex.tok,
                ex.temp, ex.keys, req.dev_prompt, n, slot, first,
                float(req.temperature), req.seed)
        else:
            (ex.cache["pos"], ex.tok, ex.temp,
             ex.keys) = ex._attach(
                ex.cache["pos"], ex.tok, ex.temp, ex.keys, slot,
                first, n, float(req.temperature), req.seed)
        # the attach computes no position here (the prefill engine
        # did): the prompt's tokens, no padding, under their own key
        self._count_prefill(n, n, bucket="handoff")
        self.stats["disagg_prefills"] += 1
        if req.trace is not None:
            req.trace.add("handoff_attach", t_att0, slot=slot)
        self.pool.publish(slot, req.prompt, ns=req.ns)
        self._activate(slot, req, first)

    # -- consume / evict ---------------------------------------------------

    def _materialize_first(self, i: int, req: _Request) -> None:
        """Bring the admission-sampled first token to the host (the only
        per-request sync, folded into a chunk consume) and run it through
        the same budget/eos/stream bookkeeping as chunk tokens."""
        fd = self._lane_first[i]
        if fd is None:
            return
        self._lane_first[i] = None
        t = int(fd)
        # TTFT (ISSUE 15): submit -> the first token's host
        # materialization, observed ONCE per request (adopted lanes
        # produced their first token at the origin — ``t_first`` is
        # pre-stamped there, so a migrated stream never double-counts)
        now = time.monotonic()
        if req.t_first is None:
            req.t_first = now
            self.hist.ttft.observe((now - req.t_submit) * 1e3)
            if req.trace is not None:
                req.trace.add("ttft", req.t_submit, now)
        req.t_last_tok = now
        self._lane_out[i].append(t)
        self._tokens_emitted += 1
        if req._stream is not None:
            req._stream.put(t)
        self._lane_left[i] -= 1
        if req.eos is not None and t == req.eos:
            self._lane_left[i] = 0

    def _finish(self, req: _Request,
                error: Optional[Exception] = None) -> None:
        # a request that already RESOLVED keeps its outcome: attaching a
        # late error (e.g. the loop's shutdown sweep racing abort()'s
        # partial flush) would turn a delivered partial into a raise
        if error is not None and req.error is None \
                and not req.done.is_set():
            req.error = error
        if not req.done.is_set():
            # e2e latency (ISSUE 15): successful resolutions only —
            # deadline partials included (they ARE the request's e2e),
            # errors excluded (a 503 shed in 2ms is not a latency)
            if req.error is None:
                self.hist.e2e.observe(
                    (time.monotonic() - req.t_submit) * 1e3)
            if req.trace is not None:
                req.trace.finish(
                    error=(type(req.error).__name__
                           if req.error is not None else None))
        # done BEFORE the stream sentinel: a stream() consumer that sees
        # the close must find result() already resolvable
        req.done.set()
        if req._stream is not None:
            req._stream.put(None)

    def _evict(self, slot: int) -> None:
        # host bookkeeping ONLY — no device ops (an eager .at[].set here
        # blocks behind the in-flight chunk).  The
        # lane's stale temp/keys are harmless: inactive lanes' tokens
        # are ignored, and the next admission overwrites all lane state
        # inside its compiled insert.
        req = self.lane[slot]
        self.lane[slot] = None
        self._lane_pos[slot] = 0        # retired lanes report no pos
        self.executor.aid[slot] = 0     # adapter hygiene (host mirror)
        # a lane evicted MID-PREFILL (cancel, deadline, drain) drops its
        # slice/handoff state; a late disagg result is dropped by the
        # identity check in _drain_handoffs
        self._prefilling.pop(slot, None)
        self._disagg_waiting.pop(slot, None)
        self._handoff_frame_t.pop(slot, None)
        if self.pool is not None:
            # return the lane's blocks: published prompt blocks become
            # reclaimable cache, private ones rejoin the free list; the
            # zeroed table row routes any in-flight pipelined write for
            # this lane into the trash block
            self.pool.retire(slot)
        self.stats["evicted"] += 1
        if req is not None and not req.done.is_set():
            # error-path evictions can race ahead of the first consume
            self._materialize_first(slot, req)
            req.out = req.prompt + self._lane_out[slot]
            self._finish(req)
        else:
            # already resolved (watchdog stall / quarantine failed it
            # from another thread): just release the lane state
            self._lane_first[slot] = None

    # -- preemptive lane spill (ISSUE 10) ----------------------------------

    def _best_parked(self) -> Optional[_ParkedLane]:
        """The parked lane that should resume next: most urgent class
        first, then park order (FIFO within a class).  Lanes whose
        envelope is on the wire to a peer (ISSUE 12) are not
        restorable — resuming one locally while a peer adopts it would
        decode the same stream twice."""
        candidates = [p for p in self._parked if not p.migrating]
        if not candidates:
            return None
        return min(candidates, key=lambda p: (p.req.priority, p.seq))

    def _waiting_class(self) -> Optional[int]:
        """Most urgent class with WAITING work (queued head or parked
        head) — the demand side of the preemption decision."""
        cq = self._pending.peek_class()
        pk = self._best_parked()
        cp = pk.req.priority if pk is not None else None
        if cq is None:
            return cp
        return cq if cp is None else min(cq, cp)

    def _preempt_victim(self) -> Optional[int]:
        """Pick the lane to spill for waiting more-urgent work, or None
        when preemption should not fire: needs the paged pool (the
        spill rides it), a fully busy ring, a STRICTLY less urgent
        resident than the waiting head, anti-thrash budget headroom,
        and a victim not already bounced past its per-request cap.
        Lanes still mid-prefill are never victims (their spill state
        is not yet well-defined — they finish their prefill first)."""
        if (self.pool is None or not self.qos.preempt or self._draining
                or any(r is None for r in self.lane)):
            return None
        demand = self._waiting_class()
        if demand is None or not self._preempt_budget.ok():
            return None
        prefill_pending = self._pending_prefill_slots()
        best, best_key = None, None
        for i, r in enumerate(self.lane):
            if (r is None or i in prefill_pending or r.done.is_set()
                    or r.priority <= demand
                    or r.preempts >= self.qos.max_preempts_per_request):
                continue
            # least urgent first; among equals the SHORTEST lane spills
            # (smallest byte capture, least to re-upload)
            key = (r.priority, -self._lane_pos[i])
            if best_key is None or key > best_key:
                best, best_key = i, key
        return best

    def _preempt(self, slot: int) -> None:
        """Spill resident lane ``slot`` to host and free its lane and
        blocks for more urgent work.  The caller has QUIESCED the
        dispatch pipeline, so device state and host mirrors agree at a
        chunk boundary — the spill captures exactly the consumed
        stream, and the later restore resumes bit-identically
        (tests/test_qos.py pins it against unpreempted oracles).  The
        request stays UNRESOLVED: its client sees added latency, never
        an error or a truncated stream."""
        req = self.lane[slot]
        self._materialize_first(slot, req)
        if self._lane_left[slot] <= 0 or req.done.is_set():
            self._evict(slot)       # finished at the boundary anyway
            return
        with TR.phase("exec.spill", slot=slot) as ph:
            spill = self.executor.spill_lane(slot)
        if req.trace is not None:
            req.trace.add("spill", ph.t0, ph.t1,
                          pos=int(self._lane_pos[slot]))
        self.flightrec.record("preempt", rid=req.request_id,
                              slot=slot, prio=req.priority)
        self._admit_seq += 1
        self._parked.append(_ParkedLane(
            req, spill, self._lane_out[slot], self._lane_left[slot],
            self._lane_pos[slot], self._admit_seq))
        self.lane[slot] = None
        self._lane_out[slot] = []
        self._lane_pos[slot] = 0
        self._lane_first[slot] = None
        self.executor.aid[slot] = 0
        self.pool.retire(slot)      # blocks free for the preemptor
        req.preempts += 1
        self._preempt_budget.spend()
        self.stats["preempted_lanes"] += 1

    def _try_restore(self, pk: _ParkedLane) -> bool:
        """Re-admit parked lane ``pk`` into a free slot: re-map fresh
        blocks, upload the spilled bytes, re-attach the host mirrors.
        Returns False (lane stays parked) when the pool cannot hold its
        blocks right now — the next loop pass retries as blocks free."""
        req = pk.req
        if req._cancel or req.done.is_set():
            self._parked.remove(pk)
            if not req.done.is_set():
                req.out = req.prompt + pk.out
                self._finish(req)
            return True
        slot = self.lane.index(None)
        with TR.phase("exec.restore", slot=slot) as ph:
            try:
                self.executor.restore_lane(slot, pk.spill)
            except self.executor._pg.NoFreeBlocks:
                self.pool.retire(slot)  # roll back ensure's partial mapping
                return False
        if req.trace is not None:
            req.trace.add("restore", ph.t0, ph.t1, slot=slot)
        self._parked.remove(pk)
        self.lane[slot] = req
        self._lane_out[slot] = pk.out
        self._lane_left[slot] = pk.left
        self._lane_pos[slot] = pk.pos
        self._lane_first[slot] = None
        self.stats["restored_lanes"] += 1
        return True

    # -- fleet-level KV: migration + peer prefix fetch (ISSUE 12) ----------

    def _fingerprint(self) -> Dict[str, Any]:
        """The ring geometry an envelope must match byte-layout-wise.
        tp is deliberately ABSENT: spills are full host bytes (the
        capture gathers across shards) and restores re-shard through
        the promote scatter, so a tp=1 lane may adopt onto a tp=2 ring
        and vice versa."""
        ex = self.executor
        return {"layers": int(self.cfg.n_layers),
                "kvHeads": int(self.cfg.n_kv_heads),
                "headDim": int(self.cfg.head_dim),
                "blockSize": int(ex.block_size),
                "quant": ex.kv_quant,
                "specK": int(ex.spec_k),
                # live swap (ISSUE 19): generation IS part of the
                # envelope — KV computed under generation r must never
                # serve generation r+1's weights (migration, peer
                # fetch, and the durable store all refuse across a
                # bump).  A TP resize without a generation bump keeps
                # fleet KV flowing, exactly as the tp-absent rule
                # intends.
                "generation": int(self.generation)}

    def attach_kv_store(self, store) -> None:
        """Wire the durable prefix store (ISSUE 17,
        infer/kvstore.KVBlockStore) into both halves: the POOL's spill
        path (host-tier overflow drops persist instead of discarding,
        their radix nodes surviving store-resident) and the SUBMIT
        probe (peer -> store order).  Requires a paged pool with the
        host tier — there is nothing to spill or promote without
        them."""
        if self.pool is None or self.pool.host is None:
            raise ValueError(
                "KV store requires paged attention with the host cache "
                "tier (host_cache_blocks > 0)")
        self.pool.attach_store(store)
        self.kv_store = store

    def handoff_fingerprint(self) -> Dict[str, Any]:
        """The geometry + sampling rule a remote-prefill HANDOFF
        envelope must match (ISSUE 13) — narrower than the migration
        fingerprint: spec depth is absent (the draft lane prefills
        decode-side at attach) and top-k/top-p are PRESENT (the
        prefill pod samples the first token through the shared
        rule)."""
        from paddle_operator_tpu.infer.prefill_serve import (
            handoff_fingerprint,
        )

        return handoff_fingerprint(
            self.cfg, block_size=self.executor.block_size,
            kv_quant=self.kv_quant, top_k=self._top_k,
            top_p=self._top_p, wquant=self.weight_quant_mode(),
            generation=self.generation)

    def _migration_meta(self, pk: _ParkedLane) -> Dict[str, Any]:
        """The JSON half of a lane envelope: request identity + stream
        state + the ring fingerprint the adopter validates against."""
        req = pk.req
        return {"requestId": req.request_id,
                "prompt": [int(t) for t in req.prompt],
                "out": [int(t) for t in pk.out],
                "left": int(pk.left),
                "maxNew": int(req.max_new),
                "temperature": float(req.temperature),
                "seed": int(req.seed),
                "eos": req.eos,
                "priority": int(req.priority),
                "adapter": req.adapter,
                # the REMAINING deadline budget travels (absolute
                # monotonic stamps are process-local): the adopter
                # re-anchors it, so a migrated lane keeps the PR 10
                # 504-partial-at-deadline contract
                "deadlineS": (round(req.deadline - time.monotonic(), 3)
                              if req.deadline is not None else None),
                # ISSUE 15: the origin's completed spans travel with
                # the lane so the adopter's trace seeds from them and
                # the stitched cross-pod timeline stays ONE tree (the
                # adopter's request root parents onto the origin's)
                "trace": (req.trace.to_wire()
                          if req.trace is not None else None),
                "fingerprint": self._fingerprint()}

    def adopt(self, meta: Dict[str, Any],
              spill: Dict[str, Any]) -> _Request:
        """Adopt a migrated lane from a peer (the ``/v1/kv/restore``
        entry point, called on an HTTP handler thread): validate the
        envelope against THIS ring, re-resolve the adapter by name,
        and park it — the ring loop re-admits it through the exact
        promote-scatter + attach path a local preemption uses, so the
        resumed stream is bit-identical to the unmigrated one.
        Raises :class:`~paddle_operator_tpu.utils.fleetkv.
        EnvelopeError` (409 upstream) on any mismatch — a refused
        migration falls back to completion-wait at the origin, never
        to a corrupted lane here."""
        from paddle_operator_tpu.utils import fleetkv as FK

        if self.pool is None:
            raise FK.EnvelopeError(
                "lane adoption requires the paged ring (the spill is "
                "block-granular); this replica is contiguous")
        FK.check_fingerprint(meta, self._fingerprint())
        if self._draining or self._stop.is_set() or not self.healthy:
            raise ShuttingDown("replica not accepting migrations")
        left = int(meta["left"])
        if left <= 0:
            raise FK.EnvelopeError(
                "migrated lane has no remaining token budget")
        ex = self.executor
        m = int(spill["n_blocks"])
        exp = (self.cfg.n_layers, m, self.cfg.n_kv_heads,
               ex.block_size, self.cfg.head_dim)
        for name in ("k", "v"):
            if tuple(spill[name].shape) != exp:
                raise FK.EnvelopeError(
                    f"lane payload {name} shape "
                    f"{tuple(spill[name].shape)} != expected {exp}")
        if ex.quant and not all(k in spill for k in
                                ("ks", "vs", "kt", "vt")):
            raise FK.EnvelopeError(
                "int8 ring: lane envelope missing scale/tail planes")
        if ex.spec_k and not all(k in spill for k in
                                 ("dk", "dv", "dpos")):
            raise FK.EnvelopeError(
                "speculative ring: lane envelope missing draft lane")
        adapter = meta.get("adapter")
        aidx = ns = 0
        if adapter:
            if self.adapters is None:
                raise FK.EnvelopeError(
                    f"adapter {adapter!r} is not served here "
                    "(no registry)")
            try:
                aidx, ns = self.adapters.resolve_ns(adapter)
            except ValueError as e:
                raise FK.EnvelopeError(str(e)) from None
        prompt = [int(t) for t in meta["prompt"]]
        out = [int(t) for t in meta.get("out", ())]
        dl = meta.get("deadlineS")
        req = _Request(prompt,
                       int(meta.get("maxNew", left + len(out))),
                       float(meta.get("temperature", spill["temp"])),
                       int(meta.get("seed", 0)), meta.get("eos"),
                       deadline=(time.monotonic() + max(0.0, float(dl))
                                 if dl is not None else None))
        req.priority = min(max(0, int(meta.get(
            "priority", self.qos.default_priority))),
            self.qos.priorities - 1)
        req.adapter = adapter
        req.adapter_idx = aidx
        req.ns = ns if aidx else 0
        req.request_id = meta.get("requestId")
        # TTFT was produced (and observed) at the ORIGIN — pre-stamp
        # t_first so this ring can never double-count a migrated
        # stream's first token into its own TTFT histogram
        req.t_first = time.monotonic()
        if self.tracer is not None:
            wire = meta.get("trace")
            if isinstance(wire, dict) and wire.get("spans"):
                # same trace id, parented on the ORIGIN's request root:
                # the stitched timeline stays one parentless-root tree
                req.trace = self.tracer.begin(
                    ctx=(wire.get("traceId"), wire.get("rootId")),
                    request_id=req.request_id)
                req.trace.seed(wire["spans"])
            else:
                req.trace = self.tracer.begin(
                    request_id=req.request_id)
            req.trace.add("adopt", time.monotonic(),
                          blocks=int(spill["n_blocks"]))
        self.flightrec.record("adopt", rid=req.request_id,
                              blocks=int(spill["n_blocks"]))
        spill = dict(spill)
        # adapter SLOT ids are replica-local: re-stamp with OUR slot
        if self.adapters is not None:
            spill["aid"] = aidx
        else:
            spill.pop("aid", None)
        pk = _ParkedLane(req, spill, out, left, int(spill["pos"]), 0)
        self.stats["adopted_lanes"] += 1
        self._adopt_q.put(pk)
        self._wake.set()
        return req

    def _maybe_peer_fetch(self, prompt) -> None:
        """Submit-thread half of the fleet prefix probe, order
        peer -> store (ISSUE 17): when the prompt's full-block chain
        is not fully covered locally, ask the fleet (one bounded HTTP
        round-trip on the CALLER's thread — never the ring's) for
        demoted payloads; on a peer miss consult the durable store
        directly (a bounded disk read, same thread discipline).
        Either hit queues payloads for radix import at the next loop
        pass, so this request's admission host-hits them."""
        from paddle_operator_tpu.utils import fleetkv as FK
        from paddle_operator_tpu.utils.radixkey import chain_key

        pool = self.pool
        bs = pool.bs
        tokens = [int(t) for t in prompt]
        n_full = len(tokens) // bs
        if n_full == 0:
            return
        keys: List[Any] = []
        key = None
        for j in range(n_full):
            key = chain_key(key, tuple(tokens[j * bs:(j + 1) * bs]))
            keys.append(key)
        # local coverage probe — a racy read against the ring thread's
        # radix mutations; any surprise is caught by submit's except
        # and the fetch simply skipped.  An entry counts as covered
        # only if it is SERVABLE (device- or host-resident): a
        # store-resident node is exactly what the probe below re-fills.
        covered = 0
        for k in keys:
            e = pool.entries.get(k)
            if e is None or not pool._servable(e):
                break
            covered += 1
        if covered >= n_full:
            return
        # the seen-cache dedupes the PEER round-trip only (one HTTP
        # ask per distinct chain — a repeat miss must not hammer the
        # fleet); the store probe below stays outside it: a clean
        # store miss costs one local file stat, and a store-resident
        # node's whole purpose is to be RE-probed on a later walk
        tail = keys[-1]
        seen = tail in self._peer_fetch_seen
        if seen:
            self._peer_fetch_seen.move_to_end(tail)
        else:
            self._peer_fetch_seen[tail] = True
            while len(self._peer_fetch_seen) > 1024:
                self._peer_fetch_seen.popitem(last=False)
        if self.peer_fetch is not None and not seen:
            buf = self.peer_fetch(tokens, 0)
            if buf:
                meta, chunks, idx, payloads = FK.decode_prefix(buf)
                FK.check_fingerprint(meta, self._fingerprint())
                if idx:
                    self._host_imports.put((chunks, idx, payloads, 0))
                    self.stats["peer_prefix_fetches"] += 1
                    self._wake.set()
                    return
        if self.kv_store is None:
            return
        self.stats["kv_store_probes"] += 1
        chunks, idx, payloads, _fp = self.kv_store.fetch(
            tokens, bs, ns=0, skip=covered)
        if not idx:
            return
        self.stats["kv_store_hits"] += 1
        self._host_imports.put((chunks, idx, payloads, 0))
        self._wake.set()

    def _kick_migration(self, pk: _ParkedLane) -> None:
        """Offer one parked lane to the fleet on a side thread (the
        POST must never stall the ring)."""
        pk.migrating = True
        pk.req.migrate_state = "inflight"
        threading.Thread(target=self._migrate_worker, args=(pk,),
                         daemon=True, name="kv-migrate").start()

    def _migrate_worker(self, pk: _ParkedLane) -> None:
        ok = False
        try:
            ok = bool(self.migrate_out(self._migration_meta(pk),
                                       pk.spill))
        except Exception:
            ok = False
        self._migr_done.put((pk, ok))
        self._wake.set()

    def _pump_fleetkv(self, pending: List[tuple]) -> None:
        """One loop pass of fleet-KV work: land adopted lanes in the
        parked list, apply migration-attempt outcomes, import fetched
        peer prefix payloads, and — draining with migration on, or a
        parked lane past its patience — offer lanes to the fleet."""
        # the ring loop is the ONLY consumer of these queues, so the
        # empty() pre-checks (cheap, no exception) are race-free
        while not self._adopt_q.empty():
            pk = self._adopt_q.get_nowait()
            if self._stop.is_set() or self._draining:
                # raced shutdown: the adopter promised nothing yet —
                # fail retriably so the client's next retry re-routes
                self._finish(pk.req, ShuttingDown(
                    "replica shut down before the adopted lane ran"))
                continue
            self._admit_seq += 1
            pk.seq = self._admit_seq
            self._parked.append(pk)
        while not self._migr_done.empty():
            pk, ok = self._migr_done.get_nowait()
            if pk not in self._parked:
                continue    # healed/cancelled away mid-flight
            self.flightrec.record("migrate_out", ok=bool(ok),
                                  rid=pk.req.request_id)
            if ok:
                self._parked.remove(pk)
                self.stats["lane_migrations"] += 1
                pk.req.migrate_state = "done"
                self._finish(pk.req, LaneMigrated(
                    "lane migrated to a peer replica; retry with the "
                    "same request_id to collect the result"))
            else:
                # peer refused / unreachable: resume locally, never
                # re-offer (completion-wait is the drain fallback)
                pk.req.migrate_state = "failed"
                pk.migrating = False
        while not self._host_imports.empty():
            chunks, idx, payloads, ns = self._host_imports.get_nowait()
            if self.pool is not None:
                try:
                    self.pool.import_host_blocks(chunks, idx, payloads,
                                                 ns=ns)
                except Exception:
                    pass    # an import is an optimization, never a fault
        if self.migrate_out is None:
            return
        drain_migrate = (self._draining and self._migrate_on_drain
                         and self.pool is not None)
        if drain_migrate:
            # park every resident decode lane at THE boundary (all
            # in-flight chunks consumed, device state and host mirrors
            # agree) so its spill captures exactly the consumed stream
            prefill_pending = self._pending_prefill_slots()
            todo = [i for i, r in enumerate(self.lane)
                    if r is not None and i not in prefill_pending
                    and not r.done.is_set() and not r._cancel
                    and r.migrate_state is None and r._stream is None
                    and r.request_id is not None]
            if todo:
                try:
                    while pending:
                        self._consume_oldest(pending)
                except Exception as e:
                    self._fault = e
                    return
                self._tile.to("sched.preempt", lanes=len(todo))
                for i in todo:
                    r = self.lane[i]
                    if (r is not None and not r.done.is_set()
                            and r.migrate_state is None):
                        self._preempt(i)
        now = time.monotonic()
        for pk in list(self._parked):
            r = pk.req
            if (pk.migrating or r.migrate_state is not None
                    or r.request_id is None or r._stream is not None
                    or r._cancel or r.done.is_set()):
                continue
            if drain_migrate or (
                    self.migrate_parked_s is not None
                    and self.migrate_parked_s > 0
                    and now - pk.t_parked >= self.migrate_parked_s):
                self._kick_migration(pk)

    def _loop(self) -> None:
        TR.use_table(self.phases)
        try:
            self._loop_body()
        except Exception as e:       # unrecoverable failure: fail loudly
            # flip dead-state BEFORE unblocking any client: a caller
            # released by the _finish below may immediately submit
            # again, and must be refused rather than queued into a void
            self.healthy = False
            self._stop.set()
            for req in self.lane:
                if req is not None:
                    self._finish(req, e)
            self.lane = [None] * self.slots
        # drain: fail whatever is still queued, resident or parked
        for i, req in enumerate(self.lane):
            if req is not None:
                self._finish(req, ShuttingDown("batcher closed"))
                self.lane[i] = None
        for pk in self._parked:
            self._finish(pk.req, ShuttingDown("batcher closed"))
        self._parked.clear()
        self._shed_queue(ShuttingDown("batcher closed"))
        self._tile.close()

    def _scrub_lane_blocks(self, slot: int, req=None) -> None:
        """Zero lane ``slot``'s PRIVATE pool blocks before they return
        to the free list: a NaN row in a re-mapped block would poison
        the next lane through the masked-tail contraction (softmax
        underflows masked columns to exactly 0, but 0 * NaN = NaN) —
        the same invariant the contiguous ring keeps by zeroing the
        whole lane at splice, block-granular.

        PUBLISHED (radix-cached) blocks are skipped: they hold shared
        prefix KV other admissions still read, and this lane cannot
        have poisoned them — every block the lane writes is private by
        construction (admit CoWs any hit block at/after the first
        written position).  One fused scatter over all victim blocks
        per pool (not one eager update per block): each ``.at[].set``
        materializes a full pool copy, and this runs on the ring
        thread behind the in-flight chunk."""
        ex = self.executor
        row = self.pool.table[slot]
        blks = [int(row[j]) for j in range(self.pool.mapped_count[slot])
                if self.pool.ref[int(row[j])] == 1
                and int(row[j]) not in self.pool.by_block]
        if blks:
            idx = jnp.asarray(blks)
            ex.cache["k"] = ex.cache["k"].at[:, idx].set(0)
            ex.cache["v"] = ex.cache["v"].at[:, idx].set(0)
            if ex.quant:
                # reset the victims' scale planes to the all-zero-block
                # sentinel (paged.quantize_kv): zero codes x a stale
                # (possibly garbage) scale must still dequantize finite
                ex.cache["ks"] = ex.cache["ks"].at[:, idx].set(1.0)
                ex.cache["vs"] = ex.cache["vs"].at[:, idx].set(1.0)
        if ex.quant:
            # the lane's bf16 staging tail is private write-frontier
            # state — the poisoned rows may live ONLY there (an
            # incomplete block never reached the pool)
            ex.cache["kt"] = ex.cache["kt"].at[:, slot].set(0)
            ex.cache["vt"] = ex.cache["vt"].at[:, slot].set(0)
        if req is not None:
            # host tier (ISSUE 8): demoted payloads on the quarantined
            # lane's prompt chain are opaque host bytes that cannot be
            # re-verified — drop them so the prefix re-prefills clean
            self.pool.scrub_host_chain(req.prompt, ns=req.ns)

    def _consume(self, chunk_reqs, toks, counts=None, ok=None,
                 spec_raw=None) -> None:
        """Apply one finished chunk's tokens ([chunk, slots] on host).
        ``chunk_reqs`` pins each lane to the REQUEST the chunk was
        dispatched for: under pipelining a lane may have been evicted
        (and even re-admitted) since dispatch — such in-flight tokens
        belong to the old request and are dropped.

        ``counts`` (speculative mode, and every fused megastep
        boundary): per-lane count of VALID rows in ``toks``.  Lane i
        takes ``toks[:counts[i], i]``; None means every row is valid
        (plain 1-step chunk mode).  The budget/eos walk below is
        shared, so an eos landing mid-speculated-block truncates
        exactly like one landing mid-chunk — no tokens after eos ever
        reach the result or the stream.

        ``spec_raw`` (speculative mode only): per-lane DEVICE commit
        counts — the acceptance-telemetry numbers and the device
        position advance (a megastep boundary's ``counts`` may be
        eos/budget-truncated below it; a raw count of 0 marks a fused
        round the lane sat out, which must not feed the stats).

        ``ok`` (nan_check mode): per-lane isfinite verdict for this
        chunk — a False lane is QUARANTINED: its request fails
        (:class:`LaneQuarantined`), its blocks are scrubbed + freed,
        and no token of the poisoned chunk reaches any consumer.  The
        other lanes are attention-independent, so their streams stay
        bit-identical to a fault-free run."""
        now = time.monotonic()
        for i, req in chunk_reqs:
            if req is None or self.lane[i] is not req \
                    or req.done.is_set():
                continue
            if ok is not None and not bool(ok[i]):
                self.stats["quarantined_lanes"] += 1
                self.flightrec.record("nan_quarantine", lane=i,
                                      rid=req.request_id)
                if self.pool is not None:
                    self._scrub_lane_blocks(i, req)
                self._finish(req, LaneQuarantined(
                    f"lane {i} produced non-finite logits; request "
                    "failed, lane quarantined (ring unaffected)"))
                self._evict(i)
                continue
            self._materialize_first(i, req)
            n = toks.shape[0] if counts is None else int(counts[i])
            if spec_raw is not None:
                n_raw = int(spec_raw[i])
                if n_raw == 0:
                    continue    # fused round the (dead) lane sat out
                # the host fill-position mirror advances like the
                # device pos: the round's full commit count, even when
                # the eos/budget walk below stops earlier (the lane is
                # then evicted and its pos zeroed regardless)
                self._lane_pos[i] += n_raw
                self.stats["spec_drafted"] += self.spec_k
                self.stats["spec_accepted"] += max(0, n_raw - 1)
                req.drafted += self.spec_k
                req.accepted += max(0, n_raw - 1)
            else:
                # plain chunks advance chunk ticks while the lane runs
                # (a fused boundary's count is the device advance: full
                # chunks while live, 0 once dead)
                self._lane_pos[i] += n
            emitted = 0
            for t in toks[:n, i]:
                if self._lane_left[i] <= 0:
                    break
                self._lane_out[i].append(int(t))
                self._tokens_emitted += 1
                emitted += 1
                if req._stream is not None:
                    req._stream.put(int(t))
                self._lane_left[i] -= 1
                if req.eos is not None and int(t) == req.eos:
                    self._lane_left[i] = 0
            if emitted:
                # chunk-granular inter-token latency (ISSUE 15): the
                # consume boundary is the host's only per-token clock;
                # the mean gap over the chunk's tokens is observed once
                # per lane-consume (docs/observability.md notes the
                # granularity)
                if req.t_last_tok is not None and now > req.t_last_tok:
                    self.hist.itl.observe(
                        (now - req.t_last_tok) * 1e3 / emitted)
                req.t_last_tok = now
            if self._lane_left[i] <= 0:
                self._evict(i)

    def _consume_oldest(self, pending: List[tuple]) -> None:
        """Pop + apply the oldest in-flight dispatch (one chunk, or one
        megastep's N fused boundaries).  The blocking device->host
        completion wait sits under the watchdog: a wedged dispatch
        surfaces HERE on real chips (dispatches are async), and the
        monitor fails the waiting clients while this thread is still
        stuck.  The watchdog region scales with the dispatch's fused
        iteration count — a legal N-step wait is ~N x a 1-step one."""
        chunk_reqs, res, t0 = pending.pop(0)
        # the host blocked on the device: everything between here and
        # the next phase is the completion wait
        self._tile.to("sched.consume_wait", n_steps=res.n_steps)
        wd = self._watchdog
        if wd is not None:
            wd.begin(scale=res.n_steps)
        try:
            toks = np.asarray(res.toks)
            moe = None if res.moe is None else np.asarray(res.moe)
            counts = None if res.counts is None else np.asarray(res.counts)
            ok = None if res.ok is None else np.asarray(res.ok)
            raw = None if res.raw is None else np.asarray(res.raw)
        finally:
            if wd is not None:
                wd.end()
        # token bookkeeping, stream puts, evictions — until the caller
        # moves the loop on
        t1 = self._tile.to("sched.consume").t0
        # per-iteration wall estimate for the deadline-tick budget:
        # dispatch->consume covers the pipeline wait too, so the EMA
        # overestimates — conservative (a lane freezes a little early
        # and resumes next dispatch, never late)
        per = (t1 - t0) / res.n_steps
        self._step_s_est = (per if not self._step_s_est
                            else 0.8 * self._step_s_est + 0.2 * per)
        # decode-phase spans (ISSUE 15): one span per consumed
        # dispatch per traced lane, covering dispatch (exec.dispatch's
        # start) -> completion wait (sched.consume_wait's end) —
        # megastep-granular by construction, and bounded by the
        # RequestTrace span cap on long generations
        for _, r in chunk_reqs:
            if r is not None and r.trace is not None:
                r.trace.add("decode_dispatch", t0, t1,
                            steps=res.n_steps)
        if self._fault is not None:
            return              # stall-failed chunks must not apply
        if moe is not None:
            self._count_moe(moe, res.n_steps)
        if res.n_steps == 1:
            if self.spec_k:
                self._consume(chunk_reqs, toks, counts=counts, ok=ok,
                              spec_raw=counts)
            else:
                self._consume(chunk_reqs, toks, ok=ok)
            return
        # fused megastep: apply the N boundaries in order — each is
        # exactly one 1-step consume, with the eos/budget walk the
        # device precomputed (counts) and the spec telemetry counts
        # (raw).  A lane evicted at boundary r drops out of rounds
        # r+1.. through the chunk_reqs identity guard.
        for r in range(res.n_steps):
            self._consume(chunk_reqs, toks[r], counts=counts[r],
                          ok=None if ok is None else ok[r],
                          spec_raw=None if raw is None else raw[r])

    def _moe_status(self) -> dict:
        """The routing counters' block of ``serving_status`` — raw and
        cumulative; empty for an architecture without expert layers."""
        load = self.stats["moe_expert_load"]
        if load is None:
            return {}
        prefill = self.stats["moe_prefill_expert_load"]
        return {"moeLayerStepsTotal": self.stats["moe_layer_steps"],
                "moeAssignmentsTotal": int(load.sum()),
                "moeExpertsTouchedTotal": self.stats["moe_experts_touched"],
                "moeExpertLoadTotal": [int(n) for n in load],
                "moePrefillAssignmentsTotal": int(prefill.sum()),
                "moePrefillExpertLoadTotal": [int(n) for n in prefill]}

    def _count_moe(self, moe: np.ndarray, n_steps: int) -> None:
        """One consumed dispatch's routing counters into the cumulative
        ones: the decode steps' assignments by expert and experts
        touched over its layer-steps, and the assignments by expert of
        the inserts since the dispatch before it."""
        from paddle_operator_tpu.infer.afmoe_serve import split_moe

        cfg = self.cfg
        load, touched, prefill = split_moe(cfg, moe.astype(np.int64))
        st = self.stats
        st["moe_layer_steps"] += n_steps * self.chunk * cfg.n_moe_layers
        st["moe_experts_touched"] += touched
        for key, add in (("moe_expert_load", load),
                         ("moe_prefill_expert_load", prefill)):
            st[key] = add if st[key] is None else st[key] + add

    def _pending_prefill_slots(self) -> set:
        """Lanes reserved but not yet decode-active."""
        return set(self._prefilling) | set(self._disagg_waiting)

    def _loop_body(self) -> None:
        # Up to ``pipeline_depth`` chunks in flight at all times (when
        # lanes are active): the host consumes chunk N's tokens — per-
        # token queue pushes, evict bookkeeping, and crucially the
        # device->host transfer latency — WHILE the device decodes
        # chunks N+1..N+depth.  Without this the ring serializes the
        # host's share with compute.  Depth 2 by default; whether depth
        # 1 suffices on a directly attached chip is not re-measured.
        # (an insert's carried step joins the same list: _admit_paged)
        pending = self._inflight    # [(chunk_reqs, res, t_dispatch)]
        pending.clear()
        # the loop thread's time, tiled: at every moment it is inside
        # exactly one top-level phase (utils/tracing.py Tiling), so the
        # phase table's self seconds are shares of this loop's wall
        # time and every gap on the device's clock has a name.  A
        # ``continue`` lands in sched.housekeeping again.
        tile = self._tile
        while not self._stop.is_set():
            # fleet-KV pump, deadlines, cancels, handoff drain, and the
            # admission loop's own checks
            tile.to("sched.housekeeping")
            # re-bound every pass: a live swap (ISSUE 19) may have
            # replaced the executor object at the previous boundary
            ex = self.executor
            # ring-level fault (dispatch raised, or the watchdog
            # declared a stall): drop the in-flight chunks and self-heal
            # — rebuild everything device-side, re-admit queued work —
            # or die (legacy / budget exhausted) via the raise, which
            # the _loop wrapper turns into fail-everything + unhealthy
            if self._fault is not None:
                err, self._fault = self._fault, None
                pending.clear()
                tile.to("sched.heal")
                if not self._heal(err):
                    raise err
                continue
            if self._draining:
                # drain: no new admissions; whatever is queued sheds
                # with ShuttingDown (clients retry another replica)
                self._shed_queue(ShuttingDown(
                    "server draining; retry another replica"))
            # fleet-level KV (ISSUE 12): adopted lanes land, migration
            # outcomes apply, peer prefix payloads import, and — when
            # draining with migration on — residents park + offer out
            self._pump_fleetkv(pending)
            if self._fault is not None:
                continue
            self._expire_deadlines()
            # cancelled lanes leave at the chunk boundary: the request
            # resolves with whatever tokens it has, the lane frees for
            # the next admission (serve.py calls cancel() when a stream
            # consumer disconnects mid-generation)
            for i, r in enumerate(self.lane):
                if r is not None and r._cancel:
                    self._evict(i)
            # parked lanes honor cancel too — a disconnect-abandoned
            # preempted request must not wait for a free lane to die.
            # Mid-migration lanes wait for the wire outcome first
            # (the _expire_deadlines rationale)
            for pk in list(self._parked):
                if pk.migrating:
                    continue
                if pk.req._cancel or pk.req.done.is_set():
                    self._parked.remove(pk)
                    if not pk.req.done.is_set():
                        pk.req.out = pk.req.prompt + pk.out
                        self._finish(pk.req)
            # disaggregated prefills that completed since last pass:
            # block-copy handoff + lane attach (cheap dispatches).
            # Gated on the ENGINE, not on _disagg_waiting: a result
            # posted for an evicted request must still be popped (and
            # dropped), or its full prefill-pool K/V snapshot stays
            # pinned in the results queue until the next cold admission
            if ex.prefill_exec is not None:
                try:
                    self._drain_handoffs()
                except Exception as e:
                    self._fault = e
                    continue
            # live weight swap (ISSUE 19): a posted swap fires at THE
            # quiesced boundary — every in-flight dispatch consumed,
            # no lane mid-prefill (admissions pause below while the
            # swap is pending, so prefills drain within a few passes).
            # The flip parks residents, swaps params/mesh, and the
            # parked lanes restore through the normal path right after.
            if self._swap_req is not None:
                if not self._prefilling and not self._disagg_waiting:
                    try:
                        while pending:
                            self._consume_oldest(pending)
                    except Exception as e:
                        self._fault = e
                        continue
                    if self._fault is None:
                        tile.to("sched.swap")
                        self._do_swap()
                    continue
                # lanes still prefilling: fall through (slices advance,
                # handoffs land); the swap fires once they finish
            # admit into free lanes: parked (preempted) lanes resume
            # ahead of queued work of the same class — they were
            # admitted first and already hold tokens — and queued work
            # pops in class-then-FIFO order (infer/qos.py).  Restores
            # run even while DRAINING: a parked lane is admitted work
            # the drain budget promises to finish.
            while any(r is None for r in self.lane):
                if self._swap_req is not None:
                    # swap pending: admissions/restores pause so the
                    # quiesce converges (restores would re-fill lanes
                    # the flip is about to park); both resume on the
                    # pass after _do_swap
                    break
                pk = self._best_parked()
                cq = (None if self._draining
                      else self._pending.peek_class())
                if pk is not None and (cq is None
                                       or pk.req.priority <= cq):
                    if not self._try_restore(pk):
                        break       # free blocks tight: retry next pass
                    continue
                if cq is None:
                    break
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                if req._cancel:                 # cancelled while queued
                    req.out = list(req.prompt)
                    self._finish(req)
                    continue
                if (req.deadline is not None
                        and time.monotonic() >= req.deadline):
                    # expired while queued: prompt-only 504 partial —
                    # resolved, never silently dropped
                    req.deadline_exceeded = True
                    self.stats["deadline_exceeded"] += 1
                    req.out = list(req.prompt)
                    self._finish(req)
                    continue
                slot = self.lane.index(None)
                # one per admitted request; pool.admit (block mapping,
                # CoW) and exec.insert (the insert program's dispatch,
                # which carries ``hit_len``) nest inside
                ph = tile.to("sched.admit", bucket=req.bucket,
                             prompt_len=len(req.prompt))
                try:
                    self._admit(slot, req)
                except Exception as e:          # bad request: fail it only
                    self._finish(req, e)
                    self.lane[slot] = None
                    self._lane_pos[slot] = 0
                    self._prefilling.pop(slot, None)
                    self._disagg_waiting.pop(slot, None)
                    if self.pool is not None:
                        # admission may have mapped blocks before the
                        # dispatch failed — unmap them (no-op when the
                        # allocator itself rejected)
                        self.pool.retire(slot)
                t1 = tile.to("sched.housekeeping").t0
                if req.trace is not None and req.error is None:
                    # host time of the admission dispatch (inline:
                    # the one compiled insert; chunked/disagg: the
                    # block map/reserve — the slices/handoff get
                    # their own spans)
                    req.trace.add("admit", ph.t0, t1, slot=slot,
                                  mode=self.prefill_mode)
            # preemptive lane spill (ISSUE 10): more urgent work is
            # waiting and every lane is busy — quiesce the dispatch
            # pipeline (THE chunk boundary: device state and host
            # mirrors agree), re-pick the victim (a consumed chunk may
            # have evicted it, or freed a lane outright), spill it, and
            # re-run admission with the freed lane/blocks
            if self._preempt_victim() is not None:
                while pending:
                    try:
                        self._consume_oldest(pending)
                    except Exception as e:
                        self._fault = e
                        break
                if self._fault is None:
                    victim = self._preempt_victim()
                    if victim is not None:
                        tile.to("sched.preempt", lanes=1)
                        self._preempt(victim)
                continue

            # chunked prefill: advance exactly ONE slice per iteration
            # (oldest admission first) — the interleave that bounds how
            # long resident decode lanes ever wait
            if self._prefilling:
                slot = min(self._prefilling,
                           key=lambda s: self._prefilling[s].seq)
                req = self._prefilling[slot].req
                tile.to("sched.prefill_slice", slot=slot)
                wd = self._watchdog
                if wd is not None:
                    wd.begin()
                try:
                    self._advance_prefill(slot)
                except Exception as e:          # fail THIS request only
                    self._finish(req, e)
                    self._evict(slot)
                finally:
                    if wd is not None:
                        wd.end()

            prefill_pending = self._pending_prefill_slots()
            active_idx = [i for i, r in enumerate(self.lane)
                          if r is not None and i not in prefill_pending]
            if not active_idx:
                if pending:
                    try:
                        self._consume_oldest(pending)
                    except Exception as e:
                        self._fault = e
                    continue            # eviction may have freed lanes
                if prefill_pending:
                    # no decode work, but prefill in flight: spin the
                    # loop (chunked slices run back-to-back; disagg
                    # handoffs land as soon as they arrive)
                    tile.to("sched.idle.prefill_pending")
                    self._wake.wait(timeout=0.002)
                    self._wake.clear()
                    continue
                # nothing resident, nothing queued: the ring waits for
                # an arrival — at a fixed arrival rate, its headroom
                tile.to("sched.idle.no_work")
                self._wake.wait(timeout=0.1)
                self._wake.clear()
                continue
            self.stats["max_active"] = max(self.stats["max_active"],
                                           len(active_idx))

            # pool.ensure for the active lanes, the table snapshot, the
            # ExecPlan's fill
            tile.to("sched.plan", lanes_live=len(active_idx))
            n_mega = self.megastep
            advance = (self.spec_k + 1) if self.spec_k else self.chunk
            tbl_np = None
            if self.paged:
                # on-demand block mapping: grow each active lane's table
                # to cover this dispatch PLUS every chunk already in
                # flight for it (the host pos mirror lags dispatched-
                # but-unconsumed work; spec rounds advance a
                # data-dependent 1..K+1, so the bound is the worst case;
                # a fused megastep advances up to n_steps iterations,
                # capped by the lane's own remaining token budget — the
                # pipelining-aware projection extended to N steps).
                # An UNDERSIZED pool (num_blocks oversubscription) can
                # run dry mid-generation: only the lane that cannot
                # grow fails — evicting it (its request resolves with
                # the error) frees its blocks for the rest of the ring,
                # which must keep serving.
                for i in list(active_idx):
                    left_i = max(1, self._lane_left[i])
                    my_steps = (min(n_mega, left_i) if self.spec_k
                                else min(n_mega, -(-left_i // self.chunk)))
                    try:
                        self.pool.ensure(
                            i, self._lane_pos[i] + self._rows_in_flight(i)
                            + my_steps * advance)
                    except self.executor._pg.NoFreeBlocks as e:
                        r = self.lane[i]
                        if r is not None and r.error is None:
                            r.error = e
                        self._evict(i)
                        active_idx.remove(i)
                if not active_idx:
                    continue        # every lane starved: retry the loop
                tbl_np = self.pool.table
                if prefill_pending:
                    # lanes mid-prefill hold REAL mapped blocks, but the
                    # chunk step writes every lane's (ignored) token at
                    # its zeroed pos — mask their rows to the trash
                    # block so an inactive write can never touch a
                    # block a prefill slice / handoff is filling
                    tbl_np = tbl_np.copy()
                    tbl_np[sorted(prefill_pending)] = \
                        self.executor._pg.TRASH_BLOCK
            # fill the plan (ISSUE 11): which lanes step, the table
            # snapshot, the adapter tail, the fused iteration count and
            # — N>1 — the per-lane continuation budgets the device
            # carries across boundaries (eos id, remaining tokens, and
            # the deadline-tick step budget)
            eos_v = left_v = steps_v = None
            if n_mega > 1:
                eos_v = np.full((self.slots,), -1, np.int32)
                left_v = np.zeros((self.slots,), np.int32)
                steps_v = np.full((self.slots,), n_mega, np.int32)
                now = time.monotonic()
                for i in active_idx:
                    r = self.lane[i]
                    if r.eos is not None:
                        eos_v[i] = int(r.eos)
                    # the device budget EXCLUDES the admission-sampled
                    # first token when it is still unmaterialized — the
                    # host consumes it out of the same max_new
                    left_v[i] = max(
                        0, self._lane_left[i]
                        - (1 if self._lane_first[i] is not None else 0))
                    if (self.paged and r.deadline is not None
                            and self._step_s_est > 0):
                        # deadline-tick budget: stop the lane at the
                        # boundary nearest its deadline instead of
                        # free-running the whole megastep past it.
                        # Paged only — a step-frozen lane resumes
                        # through the trash-redirect invariants the
                        # contiguous ring does not have.
                        remaining = r.deadline - now
                        steps_v[i] = max(1, min(
                            n_mega, int(remaining / self._step_s_est)))
            plan = X.ExecPlan(
                n_mega,
                [r is not None and i not in prefill_pending
                 for i, r in enumerate(self.lane)],
                table=tbl_np, lora=ex.lora_step_tail(),
                eos=eos_v, left=left_v, steps=steps_v)
            # async dispatch through THE plan replayer: returns device
            # futures immediately.  The watchdog brackets it (scaled by
            # the fused iteration count — a legal N-step dispatch is
            # ~N x a 1-step one) — a chaos-injected host-side hang (and
            # a synchronous-dispatch backend) wedges HERE — and any
            # raise becomes a ring fault handled at the loop top (fail
            # resident requests retriably, rebuild, back off).
            cells_live = cells_grid = 0
            if self.paged and not self.spec_k:
                cells_live, cells_grid = self.pool.decode_cell_counts(
                    self._lane_pos, set(active_idx))
            ph = tile.to("exec.dispatch", n_steps=n_mega,
                         lanes_live=len(active_idx), cells_live=cells_live,
                         cells_grid=cells_grid)
            wd = self._watchdog
            if wd is not None:
                wd.begin(scale=n_mega)
            try:
                res = ex.replay(plan)
            except Exception as e:
                self._fault = e
                continue
            finally:
                if wd is not None:
                    wd.end()
            self.stats["chunks"] += 1
            # device decode iterations this dispatch runs (a spec
            # round counts its K+1 positions), alone and times the
            # lanes live in the plan
            self.stats["decode_steps"] += n_mega * advance
            self.stats["decode_lane_steps"] += (
                n_mega * advance * len(active_idx))
            self.stats["decode_cells_live"] += n_mega * advance * cells_live
            self.stats["decode_cells_grid"] += n_mega * advance * cells_grid
            # kick the device->host copy NOW, before the consume wait:
            # by consume time the tokens are already on the wire and
            # np.asarray is a cheap completion wait instead of a full
            # round-trip on the ring's critical path
            for dev in (res.toks, res.counts, res.ok, res.raw, res.moe):
                try:
                    dev.copy_to_host_async()
                except AttributeError:  # None / interpret-mode ndarray
                    pass
            pending.append(([(i, self.lane[i]) for i in active_idx],
                            res, ph.t0))
            # (while, not if: each insert since the last pass queued a
            # one-step result of its own)
            while len(pending) >= self.pipeline_depth:
                try:
                    self._consume_oldest(pending)
                except Exception as e:
                    self._fault = e
                    break
