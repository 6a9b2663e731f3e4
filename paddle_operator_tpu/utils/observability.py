"""Tracing / profiling / structured logging.

The reference has no tracing or profiling at all (SURVEY.md §5: zap
structured logging only).  This module is the framework's observability
kit:

- :func:`get_logger` — structured (key=value) logging with rank prefix.
- :class:`StepTimer` — rolling step-time/throughput/MFU accounting for
  training loops (what bench.py measures, as a reusable component).
- :func:`serving_gauges` / :func:`histogram_exposition` — the
  ``tpujob_serve_*`` Prometheus rendering of a ``status.serving`` block.

Spans live in ``utils/tracing.py`` (:func:`~tracing.phase`); a profile
of a live process is started from outside it (docs/observability.md).
"""

from __future__ import annotations

import logging
import os
import time
from collections import deque
from typing import Optional

_FMT = "%(asctime)s %(levelname).1s %(name)s %(message)s"


def get_logger(name: str = "tpujob") -> logging.Logger:
    """Structured logger with a rank prefix derived from the
    ENVIRONMENT AT CALL TIME.

    The prefix/level are re-derived on every call (ISSUE 15
    satellite): the original handlers-already-attached check froze the
    FIRST caller's ``TPUJOB_RANK``/``TPUJOB_LOG_LEVEL`` forever —
    subprocess test workers (tests/ft_worker.py) and re-launched
    trainers inherit the parent's logger registry and logged under a
    stale rank.  Still idempotent: exactly one handler per logger no
    matter how often this is called; the formatter/level only update
    when the env actually changed."""
    logger = logging.getLogger(name)
    rank = os.environ.get("TPUJOB_RANK", "0")
    level = os.environ.get("TPUJOB_LOG_LEVEL", "INFO")
    h = next((h for h in logger.handlers
              if getattr(h, "_tpujob_rank", None) is not None), None)
    if h is None:
        if logger.handlers:
            # an application configured this logger itself (its own
            # handlers, its own level) — defer to it, exactly as the
            # original handlers-present check did; only OUR handler
            # is ever re-stamped
            return logger
        h = logging.StreamHandler()
        h._tpujob_rank = ""          # marks OUR handler; set below
        logger.addHandler(h)
    if h._tpujob_rank != rank:
        h.setFormatter(logging.Formatter(f"[rank {rank}] {_FMT}"))
        h._tpujob_rank = rank
    if logging.getLevelName(logger.level) != level:
        logger.setLevel(level)
    return logger


class StepTimer:
    """Rolling window of step times -> tokens/s and MFU."""

    def __init__(self, tokens_per_step: int,
                 flops_per_token: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 window: int = 20,
                 clock=time.perf_counter) -> None:
        self.tokens_per_step = tokens_per_step
        self.flops_per_token = flops_per_token
        self.peak_flops = peak_flops
        self.times: deque = deque(maxlen=window)
        self._last: Optional[float] = None
        self._clock = clock

    def tick(self) -> None:
        now = self._clock()
        if self._last is not None:
            self.times.append(now - self._last)
        self._last = now

    @property
    def step_time(self) -> float:
        return sum(self.times) / len(self.times) if self.times else 0.0

    @property
    def tokens_per_sec(self) -> float:
        st = self.step_time
        return self.tokens_per_step / st if st else 0.0

    @property
    def mfu(self) -> Optional[float]:
        if not (self.flops_per_token and self.peak_flops):
            return None
        return self.tokens_per_sec * self.flops_per_token / self.peak_flops

    def report(self) -> str:
        s = f"step_time={self.step_time:.3f}s tok/s={self.tokens_per_sec:.0f}"
        if self.mfu is not None:
            s += f" mfu={self.mfu:.3f}"
        return s


def serving_gauges(status_serving: dict, job: str,
                   replica: str = None) -> dict:
    """Prometheus gauge lines for one job's workload-published
    ``status.serving`` block (infer/scheduler.py
    ContinuousBatcher.serving_status) — shared by the manager's
    /metrics export (controller/manager.py) so names cannot drift from
    docs/serving.md.  ``job`` is ``namespace/name``.  Lives here (not
    in infer/) because the manager process must not import jax.

    Fleet shape (ISSUE 9): with ``replica`` set (a serving replica's
    own /metrics, infer/serve.py), or for each entry of the status
    block's ``replicas`` sub-map (the operator-aggregated fleet
    block), every gauge carries a ``replica`` label so per-replica
    readings never collide under one job key.  The single-pod
    (unlabeled) shape is byte-identical to the pre-fleet export — the
    fleet aggregate's top-level keys render exactly as a single pod's
    block always did, so existing dashboards keep reading."""
    out = _serving_gauges_one(status_serving, job, replica)
    _qos_gauges(out, status_serving, job, replica)
    _counter_gauges(out, status_serving, job, replica)
    for rid, blk in sorted(
            (status_serving.get("replicas") or {}).items()):
        if isinstance(blk, dict):
            out.update(_serving_gauges_one(blk, job, str(rid)))
    # operator-owned fleet block (controller/reconciler.py
    # _reconcile_serving): desired/ready replica counts, router
    # readiness, drain accounting — only rendered when present, so the
    # single-pod gauge set is untouched
    fleet = status_serving.get("fleet")
    if isinstance(fleet, dict):
        lbl = f'{{job="{job}"}}'
        out[f"tpujob_serve_fleet_replicas_desired{lbl}"] = \
            float(fleet.get("replicasDesired", 0))
        out[f"tpujob_serve_fleet_replicas_ready{lbl}"] = \
            float(fleet.get("replicasReady", 0))
        out[f"tpujob_serve_fleet_router_ready{lbl}"] = \
            1.0 if fleet.get("routerReady") else 0.0
        out[f"tpujob_serve_fleet_drained_replicas{lbl}"] = \
            float(fleet.get("drainedReplicas", 0))
        out[f"tpujob_serve_fleet_replica_restarts{lbl}"] = \
            float(fleet.get("replicaRestarts", 0))
        # prefill pool (ISSUE 13) — rendered only when the fleet runs
        # one, so the decode-only gauge set is untouched
        if "prefillReplicasDesired" in fleet:
            out[f"tpujob_serve_fleet_prefill_replicas_desired{lbl}"] = \
                float(fleet.get("prefillReplicasDesired", 0))
            out[f"tpujob_serve_fleet_prefill_replicas_ready{lbl}"] = \
                float(fleet.get("prefillReplicasReady", 0))
            out[f"tpujob_serve_fleet_prefill_drained{lbl}"] = \
                float(fleet.get("prefillDrained", 0))
        # rolling weight swap (ISSUE 19): the fleet's generation
        # SPREAD — min == max means the roll converged; rendered only
        # when the aggregation saw generation-labeled replicas, so
        # pre-swap fleets keep their exact gauge set
        if "generationMin" in fleet:
            out[f"tpujob_serve_fleet_generation_min{lbl}"] = \
                float(fleet.get("generationMin", 0))
            out[f"tpujob_serve_fleet_generation_max{lbl}"] = \
                float(fleet.get("generationMax", 0))
            out[f"tpujob_serve_fleet_mixed_generations{lbl}"] = \
                1.0 if fleet.get("mixedGenerations") else 0.0
    return out


def _qos_gauges(out: dict, status_serving: dict, job: str,
                replica: str = None) -> None:
    """Multi-tenant QoS gauges (ISSUE 10), rendered for the top-level
    block only (per-replica QoS reads ride each replica's own
    /metrics): per-class queue depth labeled ``prio``, cumulative lane
    preemption spills, the loaded-adapter count, and one
    ``adapter_loaded`` marker gauge per adapter NAME — the labeled
    shape the fleet router scrapes to prefer replicas that already
    hold a request's adapter."""
    rep = f',replica="{replica}"' if replica else ""
    depths = status_serving.get("priorityQueueDepth") or [0.0]
    for prio, depth in enumerate(depths):
        out[("tpujob_serve_priority_queue_depth"
             f'{{job="{job}"{rep},prio="{prio}"}}')] = float(depth)
    out[f'tpujob_serve_lane_preemptions_total{{job="{job}"{rep}}}'] = \
        float(status_serving.get("preemptedLanes", 0.0))
    out[f'tpujob_serve_active_adapters{{job="{job}"{rep}}}'] = \
        float(status_serving.get("activeAdapters", 0.0))
    for name in status_serving.get("adapterNames") or ():
        out[("tpujob_serve_adapter_loaded"
             f'{{job="{job}"{rep},adapter="{name}"}}')] = 1.0


def _counter_gauges(out: dict, status_serving: dict, job: str,
                    replica: str = None) -> None:
    """The ring's raw cumulative counters (top-level block only, like
    the QoS gauges): decode dispatches, the device decode iterations
    they ran, those times the lanes live in each plan, and the paged
    decode kernel's live cells against its old rectangle; the cold
    inserts that carried a decode step and the lanes those advanced;
    insert programs dispatched (by program width), the real tokens they
    prefilled and the positions they computed; and the loop thread's
    self seconds and counts by phase.  Counters, so a dashboard takes
    ``rate()`` and forms the ratios itself (padding share, lane
    occupancy, the loop's time by phase: docs/observability.md)."""
    rep = f',replica="{replica}"' if replica else ""
    lbl = f'{{job="{job}"{rep}}}'
    for key, name in (
            ("dispatchesTotal", "dispatches"),
            ("decodeStepsTotal", "decode_steps"),
            ("decodeLaneStepsTotal", "decode_lane_steps"),
            ("decodeCellsLive", "decode_cells_live"),
            ("decodeCellsGrid", "decode_cells_grid"),
            ("insertStepsTotal", "insert_steps"),
            ("insertStepLanesTotal", "insert_step_lanes"),
            ("prefillTokensTotal", "prefill_tokens"),
            ("prefillBucketTokensTotal", "prefill_bucket_tokens")):
        out[f"tpujob_serve_{name}_total{lbl}"] = \
            float(status_serving.get(key, 0.0))
    # routing counters, where the architecture has expert layers
    # (infer/afmoe_serve.py): per-expert loads labeled ``expert``
    for key, name in (
            ("moeLayerStepsTotal", "moe_layer_steps"),
            ("moeAssignmentsTotal", "moe_assignments"),
            ("moeExpertsTouchedTotal", "moe_experts_touched"),
            ("moePrefillAssignmentsTotal", "moe_prefill_assignments")):
        if key in status_serving:
            out[f"tpujob_serve_{name}_total{lbl}"] = \
                float(status_serving[key])
    for key, name in (("moeExpertLoadTotal", "moe_expert_load"),
                      ("moePrefillExpertLoadTotal",
                       "moe_prefill_expert_load")):
        for e, n in enumerate(status_serving.get(key) or ()):
            out[(f"tpujob_serve_{name}_total"
                 f'{{job="{job}"{rep},expert="{e}"}}')] = float(n)
    for bucket, n in (status_serving.get("prefillCallsByBucket")
                      or {}).items():
        out[("tpujob_serve_prefill_calls_total"
             f'{{job="{job}"{rep},bucket="{bucket}"}}')] = float(n)
    counts = status_serving.get("phaseCounts") or {}
    for ph, sec in (status_serving.get("phaseSeconds") or {}).items():
        plbl = f'{{job="{job}"{rep},phase="{ph}"}}'
        out[f"tpujob_serve_phase_seconds_total{plbl}"] = float(sec)
        out[f"tpujob_serve_phase_count_total{plbl}"] = \
            float(counts.get(ph, 0))


def _serving_gauges_one(status_serving: dict, job: str,
                        replica: str = None) -> dict:
    """One pod's (or one replica's) gauge set.  ``replica=None``
    renders the historical unlabeled shape byte-for-byte."""
    rep = f',replica="{replica}"' if replica else ""
    lbl = f'{{job="{job}"{rep}}}'
    return {
        f"tpujob_serve_tokens_per_sec{lbl}":
            float(status_serving.get("tokensPerSec", 0.0)),
        f"tpujob_serve_accept_rate{lbl}":
            float(status_serving.get("acceptRate", 0.0)),
        f"tpujob_serve_queue_depth{lbl}":
            float(status_serving.get("queueDepth", 0.0)),
        # paged-KV serving (SERVE_PAGED=1): radix prefix-cache token
        # hit rate and free pool blocks — both 0 on contiguous rings
        f"tpujob_serve_prefix_hit_rate{lbl}":
            float(status_serving.get("prefixHitRate", 0.0)),
        f"tpujob_serve_kv_blocks_free{lbl}":
            float(status_serving.get("kvBlocksFree", 0.0)),
        # bytes a token a layer the cache holds (K and V over the kv
        # heads, int8 codes, or one latent row): blocks x block size x
        # layers x this is the pool's bytes, whatever the architecture
        f"tpujob_serve_cache_row_bytes{lbl}":
            float(status_serving.get("cacheRowBytes", 0.0)),
        # prefill path (ISSUE 6 scheduler/executor split): requests
        # admitted but still prefilling (chunked slices mid-flight or
        # disagg jobs on the prefill executor), labeled with the ring's
        # prefill mode so dashboards can split inline/chunked/disagg
        # fleets, plus the share of prefill tokens that arrived in
        # interleaved chunked slices
        ("tpujob_serve_prefill_queue_depth"
         f'{{job="{job}"{rep},mode="{status_serving.get("prefillMode", "inline")}"}}'):
            float(status_serving.get("prefillQueueDepth", 0.0)),
        f"tpujob_serve_chunked_prefill_token_share{lbl}":
            float(status_serving.get("chunkedPrefillTokenShare", 0.0)),
        # prefill-pool throughput (ISSUE 14): engine lanes, batch
        # occupancy EMA (busy lanes / N per engine iteration) and
        # head-of-line queue-wait p95 — exported by in-process disagg
        # rings AND prefill_serve pods; the SLO autoscaler divides the
        # pool's load by occupancy x lanes so a half-empty batch never
        # reads as a saturated pool
        f"tpujob_serve_prefill_lanes{lbl}":
            float(status_serving.get("prefillLanes", 0.0)),
        f"tpujob_serve_prefill_batch_occupancy{lbl}":
            float(status_serving.get("prefillBatchOccupancy", 0.0)),
        f"tpujob_serve_prefill_hol_wait_ms{lbl}":
            float(status_serving.get("prefillHolWaitMs", 0.0)),
        # quantized-pool serving (SERVE_KV_QUANT): device bytes held by
        # the KV pool (int8 codes + scale planes + staging tails, or
        # the bf16 pool/ring), labeled with the storage mode so
        # capacity dashboards can split int8 and bf16 fleets on one
        # metric name
        ("tpujob_serve_kv_pool_bytes"
         f'{{job="{job}"{rep},mode="{status_serving.get("kvQuantMode", "none")}"}}'):
            float(status_serving.get("kvPoolBytes", 0.0)),
        # weight quantization (SERVE_WEIGHT_QUANT / SERVE_DRAFT_QUANT):
        # a marker gauge labeled with the target and draft storage
        # modes (value 1 when either tree is quantized, 0 on bf16
        # fleets — the labels, not the value, carry the modes), and
        # the params-tree HBM bytes (target + draft; codes + scale
        # planes) so dashboards show the weight-side saving next to
        # the KV pool's
        ("tpujob_serve_weight_quant_mode"
         f'{{job="{job}"{rep}'
         f',mode="{status_serving.get("weightQuantMode", "none")}"'
         f',draft="{status_serving.get("draftQuantMode", "none")}"}}'):
            float(status_serving.get("weightQuantMode", "none") != "none"
                  or status_serving.get("draftQuantMode", "none")
                  != "none"),
        f"tpujob_serve_param_bytes{lbl}":
            float(status_serving.get("paramBytes", 0.0)),
        # hierarchical KV cache (SERVE_HOST_CACHE_MB/_BLOCKS): blocks
        # resident in the host spill tier, the share of looked-up
        # prefix tokens served from host payloads (promote path), and
        # cumulative blocks promoted host->device — all 0 when the
        # tier is off
        f"tpujob_serve_host_cache_blocks{lbl}":
            float(status_serving.get("hostCacheBlocks", 0.0)),
        f"tpujob_serve_host_hit_rate{lbl}":
            float(status_serving.get("hostHitRate", 0.0)),
        f"tpujob_serve_promoted_blocks_total{lbl}":
            float(status_serving.get("promotedBlocks", 0.0)),
        # fleet-level KV (ISSUE 12): host-tier dropped-oldest overflow
        # evictions (previously INVISIBLE — a silently thrashing tier
        # read as a healthy one), lanes migrated out to / adopted from
        # peers, prefix chains fetched from a peer's host tier, and
        # the parked-lane count the router's migration broker reads to
        # pick adopters
        f"tpujob_serve_host_cache_evictions_total{lbl}":
            float(status_serving.get("hostCacheEvictions", 0.0)),
        # durable prefix store (ISSUE 17, SERVE_KV_STORE): blocks and
        # bytes resident in the persistent tier below host/peer cache,
        # the share of store probes that hit, and cumulative
        # TTL/budget-janitor evictions — all 0 when no store is wired
        f"tpujob_serve_kv_store_blocks{lbl}":
            float(status_serving.get("kvStoreBlocks", 0.0)),
        f"tpujob_serve_kv_store_bytes{lbl}":
            float(status_serving.get("kvStoreBytes", 0.0)),
        f"tpujob_serve_kv_store_hit_rate{lbl}":
            float(status_serving.get("kvStoreHitRate", 0.0)),
        f"tpujob_serve_kv_store_evictions_total{lbl}":
            float(status_serving.get("kvStoreEvictions", 0.0)),
        f"tpujob_serve_lane_migrations_total{lbl}":
            float(status_serving.get("laneMigrations", 0.0)),
        f"tpujob_serve_adopted_lanes_total{lbl}":
            float(status_serving.get("adoptedLanes", 0.0)),
        f"tpujob_serve_peer_prefix_fetches_total{lbl}":
            float(status_serving.get("peerPrefixFetches", 0.0)),
        f"tpujob_serve_parked_lanes{lbl}":
            float(status_serving.get("parkedLanes", 0.0)),
        # cross-host disaggregation (ISSUE 13): cold prompts prefilled
        # in the PREFILL POOL's pods and handed off over the wire —
        # zero on in-process/inline rings
        f"tpujob_serve_remote_prefills_total{lbl}":
            float(status_serving.get("remotePrefills", 0.0)),
        # device-resident megastep (ISSUE 11, SERVE_MEGASTEP): fused
        # ring iterations per compiled dispatch and the measured
        # resident dispatches per emitted token — dispatches_per_token
        # ~ 1/(N*chunk) when the fusion is doing its job, and a value
        # drifting toward 1/chunk under N>1 means lanes are dying
        # early (eos/deadline) and burning fused iterations masked
        f"tpujob_serve_megastep_n{lbl}":
            float(status_serving.get("megastepN", 0.0)),
        f"tpujob_serve_dispatches_per_token{lbl}":
            float(status_serving.get("dispatchesPerToken", 0.0)),
        # serving fault tolerance (infer/resilience.py): deadline
        # partials served, self-healing ring rebuilds, NaN-quarantined
        # lanes, and the drain flag (1 while the pod sheds admissions)
        f"tpujob_serve_deadline_exceeded{lbl}":
            float(status_serving.get("deadlineExceeded", 0.0)),
        f"tpujob_serve_watchdog_restarts{lbl}":
            float(status_serving.get("watchdogRestarts", 0.0)),
        f"tpujob_serve_quarantined_lanes{lbl}":
            float(status_serving.get("quarantinedLanes", 0.0)),
        f"tpujob_serve_draining{lbl}":
            1.0 if status_serving.get("draining") else 0.0,
        # live weight swap / elastic TP resize (ISSUE 19): the weight
        # generation this replica serves, its current tensor-parallel
        # degree, and cumulative in-place swaps — a mid-roll fleet
        # shows a generation spread (the fleet block's min/max below)
        f"tpujob_serve_generation{lbl}":
            float(status_serving.get("weightGeneration", 0.0)),
        f"tpujob_serve_tp{lbl}":
            float(status_serving.get("servingTp", 0.0)),
        f"tpujob_serve_weight_swaps_total{lbl}":
            float(status_serving.get("weightSwaps", 0.0)),
    }


def histogram_exposition(latency_hist: Optional[dict], job: str,
                         replica: str = None) -> str:
    """Prometheus ``_bucket``/``_sum``/``_count`` exposition for one
    pod's ``status.serving.latencyHist`` block (ISSUE 15) — rendered
    NEXT TO the gauges on a replica's ``/metrics`` (serve.py) so the
    router's scrape folds real latency distributions fleet-wide.

    Lives here (not inline in serve.py) so the metric names cannot
    drift from the docs/observability.md catalog the doc-drift test
    pins.  Separate from :func:`serving_gauges` on purpose: gauges are
    a flat name->float dict callers sort, which would interleave
    bucket lines lexicographically (le="16" before le="2"); histogram
    exposition must keep its bounds in increasing order."""
    if not isinstance(latency_hist, dict) or not latency_hist:
        return ""
    from paddle_operator_tpu.utils import tracing as TR

    rep = f',replica="{replica}"' if replica else ""
    labels = f'{{job="{job}"{rep}}}'
    lines = []
    for fam, name in sorted(TR.HIST_FAMILIES.items()):
        entry = latency_hist.get(fam)
        if isinstance(entry, dict):
            lines.extend(render_histogram_lines(name, entry, labels))
    return "\n".join(lines) + "\n" if lines else ""


def render_histogram_lines(name: str, entry: dict,
                           labels: str = "") -> list:
    """One histogram snapshot entry -> Prometheus
    ``_bucket``/``_sum``/``_count`` lines (cumulative buckets in bound
    order, then +Inf).  THE one renderer — the replica-level
    ``tpujob_serve_*`` exposition above and the router's fleet-folded
    ``tpujob_fleet_*`` re-export both call it, so the two surfaces'
    bucket/rounding format cannot drift apart."""
    bounds = entry.get("buckets") or []
    counts = entry.get("counts") or []
    base = labels[:-1] + "," if labels else "{"
    lines, cum = [], 0
    for b, c in zip(bounds, counts):
        cum += int(c)
        le = int(b) if float(b).is_integer() else b
        lines.append(f'{name}_bucket{base}le="{le}"}} {cum}')
    lines.append(f'{name}_bucket{base}le="+Inf"}} '
                 f'{int(entry.get("count", 0))}')
    lines.append(f'{name}_sum{labels} '
                 f'{round(float(entry.get("sum", 0.0)), 3)}')
    lines.append(f'{name}_count{labels} '
                 f'{int(entry.get("count", 0))}')
    return lines
