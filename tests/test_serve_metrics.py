"""Serving telemetry: the ``status.serving`` block
(infer/scheduler.py ContinuousBatcher.serving_status) plumbed through the
CRD status, preserved by the reconciler's status sync, and exported by
the manager as ``tpujob_serve_*`` gauges on /metrics — the speculative
acceptance rate, served-token throughput, and queue depth next to the
PR 2 goodput gauges."""

import socket
import urllib.request

from paddle_operator_tpu.api import ResourceSpec, TPUJob, TPUJobSpec
from paddle_operator_tpu.controller.fake_api import FakeAPI, FakeFleet
from paddle_operator_tpu.controller.manager import Manager, _serve
from paddle_operator_tpu.controller.reconciler import (
    KIND_JOB,
    TPUJobReconciler,
    run_to_settled,
)
from paddle_operator_tpu.utils.observability import serving_gauges

NS = "default"
TMPL = {"spec": {"containers": [{"name": "m", "image": "jax:latest"}]}}

SERVING = {"tokensPerSec": 123.4, "acceptRate": 0.72, "queueDepth": 3,
           "tokensTotal": 9000, "prefixHitRate": 0.31, "kvBlocksFree": 17,
           "prefillMode": "chunked", "prefillQueueDepth": 2,
           "chunkedPrefillTokenShare": 0.85,
           "kvQuantMode": "int8", "kvPoolBytes": 4096,
           "weightQuantMode": "int8", "draftQuantMode": "int4",
           "paramBytes": 8192,
           "hostCacheBlocks": 5, "hostHitRate": 0.12,
           "promotedBlocks": 42,
           "priorityQueueDepth": [1, 2], "preemptedLanes": 3,
           "activeAdapters": 2, "adapterNames": ["acme", "zen"],
           "megastepN": 4, "dispatchesPerToken": 0.0313,
           "parkedLanes": 1, "laneMigrations": 4, "adoptedLanes": 2,
           "peerPrefixFetches": 6, "hostCacheEvictions": 7,
           "kvStoreBlocks": 11, "kvStoreBytes": 2048,
           "kvStoreHitRate": 0.44, "kvStoreEvictions": 9,
           "weightGeneration": 3, "servingTp": 2, "weightSwaps": 1}


class TestGaugeNaming:
    def test_serving_gauges(self):
        g = serving_gauges(SERVING, "default/j")
        assert g['tpujob_serve_tokens_per_sec{job="default/j"}'] == 123.4
        assert g['tpujob_serve_accept_rate{job="default/j"}'] == 0.72
        assert g['tpujob_serve_queue_depth{job="default/j"}'] == 3.0
        assert g['tpujob_serve_prefix_hit_rate{job="default/j"}'] == 0.31
        assert g['tpujob_serve_kv_blocks_free{job="default/j"}'] == 17.0
        # prefill-path gauges (ISSUE 6): the queue-depth gauge carries
        # the ring's mode as a label so dashboards can split
        # inline/chunked/disagg fleets on one metric name
        assert g['tpujob_serve_prefill_queue_depth'
                 '{job="default/j",mode="chunked"}'] == 2.0
        assert g['tpujob_serve_chunked_prefill_token_share'
                 '{job="default/j"}'] == 0.85
        # quantized-pool gauge (ISSUE 7): pool bytes labeled with the
        # storage mode, mirroring the prefill queue-depth label scheme
        assert g['tpujob_serve_kv_pool_bytes'
                 '{job="default/j",mode="int8"}'] == 4096.0
        # weight-quant gauges (ISSUE 16): a marker carrying both the
        # target and draft storage modes as labels (value 1 when either
        # is quantized) plus the params-tree HBM bytes
        assert g['tpujob_serve_weight_quant_mode'
                 '{job="default/j",mode="int8",draft="int4"}'] == 1.0
        assert g['tpujob_serve_param_bytes{job="default/j"}'] == 8192.0
        # hierarchical-cache gauges (ISSUE 8): host-tier residency,
        # host-served prefix-token share, cumulative promotions
        assert g['tpujob_serve_host_cache_blocks'
                 '{job="default/j"}'] == 5.0
        assert g['tpujob_serve_host_hit_rate{job="default/j"}'] == 0.12
        assert g['tpujob_serve_promoted_blocks_total'
                 '{job="default/j"}'] == 42.0
        # multi-tenant QoS gauges (ISSUE 10): per-class queue depth
        # with the class as a label, cumulative preemption spills, the
        # loaded-adapter count, and one marker gauge per adapter NAME
        # (the labeled shape the fleet router's adapter affinity
        # scrapes)
        assert g['tpujob_serve_priority_queue_depth'
                 '{job="default/j",prio="0"}'] == 1.0
        assert g['tpujob_serve_priority_queue_depth'
                 '{job="default/j",prio="1"}'] == 2.0
        assert g['tpujob_serve_lane_preemptions_total'
                 '{job="default/j"}'] == 3.0
        assert g['tpujob_serve_active_adapters'
                 '{job="default/j"}'] == 2.0
        assert g['tpujob_serve_adapter_loaded'
                 '{job="default/j",adapter="acme"}'] == 1.0
        assert g['tpujob_serve_adapter_loaded'
                 '{job="default/j",adapter="zen"}'] == 1.0
        # device-resident megastep gauges (ISSUE 11): fused iterations
        # per dispatch + measured host-dispatch amortization
        assert g['tpujob_serve_megastep_n{job="default/j"}'] == 4.0
        assert g['tpujob_serve_dispatches_per_token'
                 '{job="default/j"}'] == 0.0313
        # fleet-level KV gauges (ISSUE 12): the previously invisible
        # host-tier overflow evictions plus the migration/fetch
        # counter pair, and the parked-lane count the router's
        # migration broker scrapes for target choice
        assert g['tpujob_serve_host_cache_evictions_total'
                 '{job="default/j"}'] == 7.0
        assert g['tpujob_serve_lane_migrations_total'
                 '{job="default/j"}'] == 4.0
        assert g['tpujob_serve_adopted_lanes_total'
                 '{job="default/j"}'] == 2.0
        assert g['tpujob_serve_peer_prefix_fetches_total'
                 '{job="default/j"}'] == 6.0
        assert g['tpujob_serve_parked_lanes{job="default/j"}'] == 1.0
        # durable prefix store gauges (ISSUE 17): persistent-tier
        # residency (blocks + bytes), store-probe hit share, and
        # cumulative TTL/budget-janitor evictions
        assert g['tpujob_serve_kv_store_blocks'
                 '{job="default/j"}'] == 11.0
        assert g['tpujob_serve_kv_store_bytes'
                 '{job="default/j"}'] == 2048.0
        assert g['tpujob_serve_kv_store_hit_rate'
                 '{job="default/j"}'] == 0.44
        assert g['tpujob_serve_kv_store_evictions_total'
                 '{job="default/j"}'] == 9.0
        # live-swap gauges (ISSUE 19): the weight generation this
        # replica serves, its TP degree, cumulative in-place swaps
        assert g['tpujob_serve_generation{job="default/j"}'] == 3.0
        assert g['tpujob_serve_tp{job="default/j"}'] == 2.0
        assert g['tpujob_serve_weight_swaps_total'
                 '{job="default/j"}'] == 1.0

    def test_prefill_mode_label_defaults_inline(self):
        g = serving_gauges({}, "ns/x")
        assert ('tpujob_serve_prefill_queue_depth'
                '{job="ns/x",mode="inline"}') in g
        assert ('tpujob_serve_kv_pool_bytes'
                '{job="ns/x",mode="none"}') in g
        assert ('tpujob_serve_weight_quant_mode'
                '{job="ns/x",mode="none",draft="none"}') in g

    def test_missing_keys_default_zero(self):
        g = serving_gauges({}, "ns/x")
        assert all(v == 0.0 for v in g.values())

    def test_single_pod_key_set_byte_identical(self):
        """ISSUE 9 satellite pin: the fleet work must NOT change the
        single-pod (unlabeled) gauge shape — existing dashboards key on
        these exact strings."""
        g = serving_gauges(SERVING, "default/j")
        assert set(g) == {
            'tpujob_serve_tokens_per_sec{job="default/j"}',
            'tpujob_serve_accept_rate{job="default/j"}',
            'tpujob_serve_queue_depth{job="default/j"}',
            'tpujob_serve_prefix_hit_rate{job="default/j"}',
            'tpujob_serve_kv_blocks_free{job="default/j"}',
            # ISSUE 32: bytes a token a layer the cache holds
            'tpujob_serve_cache_row_bytes{job="default/j"}',
            'tpujob_serve_prefill_queue_depth'
            '{job="default/j",mode="chunked"}',
            'tpujob_serve_chunked_prefill_token_share'
            '{job="default/j"}',
            'tpujob_serve_kv_pool_bytes'
            '{job="default/j",mode="int8"}',
            # weight-quant shape (ISSUE 16): mode marker (target +
            # draft labels) and the params-tree bytes gauge
            'tpujob_serve_weight_quant_mode'
            '{job="default/j",mode="int8",draft="int4"}',
            'tpujob_serve_param_bytes{job="default/j"}',
            'tpujob_serve_host_cache_blocks{job="default/j"}',
            'tpujob_serve_host_hit_rate{job="default/j"}',
            'tpujob_serve_promoted_blocks_total{job="default/j"}',
            # fleet-level KV shape (ISSUE 12): tier overflow
            # evictions, the migration/fetch counter pair, and the
            # parked-lane gauge the migration broker scrapes
            'tpujob_serve_host_cache_evictions_total'
            '{job="default/j"}',
            'tpujob_serve_lane_migrations_total{job="default/j"}',
            'tpujob_serve_adopted_lanes_total{job="default/j"}',
            'tpujob_serve_peer_prefix_fetches_total'
            '{job="default/j"}',
            'tpujob_serve_parked_lanes{job="default/j"}',
            # durable prefix store shape (ISSUE 17): persistent-tier
            # residency, probe hit share, janitor evictions
            'tpujob_serve_kv_store_blocks{job="default/j"}',
            'tpujob_serve_kv_store_bytes{job="default/j"}',
            'tpujob_serve_kv_store_hit_rate{job="default/j"}',
            'tpujob_serve_kv_store_evictions_total'
            '{job="default/j"}',
            # cross-host disaggregation shape (ISSUE 13): cold prompts
            # prefilled in the prefill pool and handed off over the
            # wire (zero on in-process/inline rings)
            'tpujob_serve_remote_prefills_total{job="default/j"}',
            # prefill-pool throughput shape (ISSUE 14): engine width,
            # batch occupancy EMA and head-of-line wait p95 (zero on
            # rings without a local engine; prefill pods export their
            # own)
            'tpujob_serve_prefill_lanes{job="default/j"}',
            'tpujob_serve_prefill_batch_occupancy{job="default/j"}',
            'tpujob_serve_prefill_hol_wait_ms{job="default/j"}',
            # multi-tenant QoS shape (ISSUE 10): one queue-depth gauge
            # per class in the block, preemptions, adapter count + one
            # marker per loaded adapter name
            'tpujob_serve_priority_queue_depth'
            '{job="default/j",prio="0"}',
            'tpujob_serve_priority_queue_depth'
            '{job="default/j",prio="1"}',
            'tpujob_serve_lane_preemptions_total{job="default/j"}',
            'tpujob_serve_active_adapters{job="default/j"}',
            # megastep shape (ISSUE 11)
            'tpujob_serve_megastep_n{job="default/j"}',
            'tpujob_serve_dispatches_per_token{job="default/j"}',
            'tpujob_serve_adapter_loaded'
            '{job="default/j",adapter="acme"}',
            'tpujob_serve_adapter_loaded'
            '{job="default/j",adapter="zen"}',
            'tpujob_serve_deadline_exceeded{job="default/j"}',
            'tpujob_serve_watchdog_restarts{job="default/j"}',
            'tpujob_serve_quarantined_lanes{job="default/j"}',
            'tpujob_serve_draining{job="default/j"}',
            # live weight swap / elastic TP shape (ISSUE 19): the
            # weight generation this replica serves, its TP degree,
            # and cumulative in-place swaps
            'tpujob_serve_generation{job="default/j"}',
            'tpujob_serve_tp{job="default/j"}',
            'tpujob_serve_weight_swaps_total{job="default/j"}',
            # raw counters (ISSUE 26): always rendered; the per-bucket
            # and per-phase series only with their sub-blocks
            'tpujob_serve_dispatches_total{job="default/j"}',
            'tpujob_serve_decode_steps_total{job="default/j"}',
            'tpujob_serve_decode_lane_steps_total{job="default/j"}',
            'tpujob_serve_decode_cells_live_total{job="default/j"}',
            'tpujob_serve_decode_cells_grid_total{job="default/j"}',
            'tpujob_serve_insert_steps_total{job="default/j"}',
            'tpujob_serve_insert_step_lanes_total{job="default/j"}',
            'tpujob_serve_prefill_tokens_total{job="default/j"}',
            'tpujob_serve_prefill_bucket_tokens_total'
            '{job="default/j"}',
        }

    def test_counter_gauges_by_bucket_and_phase(self):
        """ISSUE 26: the raw counters render as ``_total`` series, the
        per-width insert counts labeled ``bucket`` and the loop
        thread's self seconds and counts labeled ``phase``."""
        g = serving_gauges(
            {"dispatchesTotal": 7, "decodeStepsTotal": 56,
             "decodeLaneStepsTotal": 600, "decodeCellsLive": 2800,
             "decodeCellsGrid": 14336, "prefillTokensTotal": 900,
             "prefillBucketTokensTotal": 4608,
             "prefillCallsByBucket": {"512": 1, "4096": 1},
             "phaseSeconds": {"sched.idle.no_work": 1.5,
                              "exec.dispatch": 0.25},
             "phaseCounts": {"sched.idle.no_work": 15,
                             "exec.dispatch": 7}}, "ns/x", replica="r0")
        lbl = 'job="ns/x",replica="r0"'
        assert g[f"tpujob_serve_dispatches_total{{{lbl}}}"] == 7.0
        assert g[f"tpujob_serve_decode_lane_steps_total{{{lbl}}}"] == 600.0
        assert g[f"tpujob_serve_decode_cells_live_total{{{lbl}}}"] == 2800.0
        assert g[f"tpujob_serve_decode_cells_grid_total{{{lbl}}}"] == 14336.0
        assert g["tpujob_serve_prefill_calls_total"
                 f'{{{lbl},bucket="4096"}}'] == 1.0
        assert g["tpujob_serve_phase_seconds_total"
                 f'{{{lbl},phase="sched.idle.no_work"}}'] == 1.5
        assert g["tpujob_serve_phase_count_total"
                 f'{{{lbl},phase="exec.dispatch"}}'] == 7.0

    def test_fleet_block_adds_replica_labeled_gauges(self):
        """ISSUE 9: per-replica blocks under ``replicas`` render with a
        ``replica`` label so they never collide under one job key; the
        aggregate top-level keys keep the single-pod shape; the
        operator's ``fleet`` block adds its own gauges."""
        fleet_status = dict(
            SERVING,
            replicas={
                "0": {"tokensPerSec": 23.4, "queueDepth": 1,
                      "prefillMode": "inline", "kvQuantMode": "none"},
                "1": {"tokensPerSec": 100.0, "queueDepth": 2,
                      "prefillMode": "inline", "kvQuantMode": "none"},
            },
            fleet={"replicasDesired": 2, "replicasReady": 2,
                   "routerReady": True, "drainedReplicas": 1,
                   "replicaRestarts": 0},
        )
        g = serving_gauges(fleet_status, "default/j")
        # aggregate: byte-identical single-pod shape
        assert g['tpujob_serve_tokens_per_sec{job="default/j"}'] \
            == 123.4
        # per-replica: labeled, no collisions
        assert g['tpujob_serve_tokens_per_sec'
                 '{job="default/j",replica="0"}'] == 23.4
        assert g['tpujob_serve_tokens_per_sec'
                 '{job="default/j",replica="1"}'] == 100.0
        assert g['tpujob_serve_prefill_queue_depth'
                 '{job="default/j",replica="0",mode="inline"}'] == 0.0
        # operator fleet block
        assert g['tpujob_serve_fleet_replicas_desired'
                 '{job="default/j"}'] == 2.0
        assert g['tpujob_serve_fleet_replicas_ready'
                 '{job="default/j"}'] == 2.0
        assert g['tpujob_serve_fleet_router_ready'
                 '{job="default/j"}'] == 1.0
        assert g['tpujob_serve_fleet_drained_replicas'
                 '{job="default/j"}'] == 1.0
        # and every gauge name is one of: unlabeled aggregate,
        # replica-labeled, or a fleet_* gauge — nothing else leaked
        for k in g:
            assert ('replica="' in k or 'tpujob_serve_fleet_' in k
                    or k in serving_gauges(SERVING, "default/j"))


def _running_job_with_serving(api, rec, fleet, serving, name="sj"):
    job = TPUJob(name=name, namespace=NS, spec=TPUJobSpec(
        worker=ResourceSpec(replicas=2, template=TMPL)))
    api.create(KIND_JOB, job.to_dict())
    run_to_settled(rec, NS, name)
    fleet.run_all()
    run_to_settled(rec, NS, name)
    # serving worker publishes its telemetry block into the status
    raw = api.get(KIND_JOB, NS, name)
    raw["status"]["serving"] = serving
    api.update_status(KIND_JOB, raw)


class TestStatusPlumbing:
    def test_reconciler_preserves_serving_block(self):
        api = FakeAPI()
        rec = TPUJobReconciler(api)
        fleet = FakeFleet(api, NS)
        _running_job_with_serving(api, rec, fleet, SERVING)
        run_to_settled(rec, NS, "sj")     # status sync must NOT wipe it
        got = TPUJob.from_dict(api.get(KIND_JOB, NS, "sj"))
        assert got.status.serving["acceptRate"] == 0.72
        assert got.status.serving["tokensPerSec"] == 123.4

    def test_crd_schema_keeps_serving(self):
        """A structural-schema apiserver prunes unknown status fields —
        the CRD must declare the serving block."""
        from paddle_operator_tpu.api.crd import generate_crd

        crd = generate_crd()
        status = crd["spec"]["versions"][0]["schema"][
            "openAPIV3Schema"]["properties"]["status"]["properties"]
        assert "serving" in status
        assert status["serving"]["x-kubernetes-preserve-unknown-fields"]

    def test_manager_serves_serving_gauges_on_metrics_endpoint(self):
        """Acceptance: tpujob_serve_* gauges are scrapeable from the
        manager's /metrics, next to the goodput gauges."""
        api = FakeAPI()
        mgr = Manager(api, namespace=NS)
        fleet = FakeFleet(api, NS)
        _running_job_with_serving(api, mgr.reconciler, fleet, SERVING)
        # goodput riding alongside proves both blocks export together
        raw = api.get(KIND_JOB, NS, "sj")
        raw["status"]["goodput"] = {"ratio": 0.9, "productiveSeconds": 9,
                                    "wallclockSeconds": 10}
        api.update_status(KIND_JOB, raw)
        mgr.run_once()

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        _serve(("127.0.0.1", port), mgr.metrics, lambda: True)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as r:
            body = r.read().decode()
        assert 'tpujob_serve_tokens_per_sec{job="default/sj"} 123.4' in body
        assert 'tpujob_serve_accept_rate{job="default/sj"} 0.72' in body
        assert 'tpujob_serve_queue_depth{job="default/sj"} 3.0' in body
        assert 'tpujob_goodput_ratio{job="default/sj"} 0.9' in body

    def test_stale_serving_gauges_pruned(self):
        """A job that stops publishing serving telemetry must disappear
        from /metrics (bounded registry, no stale readings)."""
        api = FakeAPI()
        mgr = Manager(api, namespace=NS)
        fleet = FakeFleet(api, NS)
        _running_job_with_serving(api, mgr.reconciler, fleet, SERVING)
        mgr.run_once()
        assert any("tpujob_serve_tokens_per_sec" in k
                   for k in mgr.metrics.counters)
        raw = api.get(KIND_JOB, NS, "sj")
        raw["status"].pop("serving")
        api.update_status(KIND_JOB, raw)
        mgr.run_once()
        assert not any("tpujob_serve_tokens_per_sec" in k
                       for k in mgr.metrics.counters)


class TestBatcherServingStatus:
    def test_serving_status_block_shape(self):
        """The producer side: a live ring reports the camelCase block
        the gauges consume, with emitted tokens counted."""
        import numpy as np
        import jax
        import jax.numpy as jnp

        from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
        from paddle_operator_tpu.models.llama import make_model

        model, cfg = make_model("tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        b = ContinuousBatcher(params, cfg, slots=1, max_len=32,
                              chunk_tokens=2, prefill_buckets=(16, 32))
        try:
            b.submit([1, 2, 3], max_new_tokens=4).result(timeout=300)
            st = b.serving_status()
        finally:
            b.close()
        # observability block (ISSUE 15): one TTFT/e2e observation per
        # resolved request, snapshot shape the fold consumes
        assert st["latencyHist"]["ttft"]["count"] == 1
        assert st["latencyHist"]["e2e"]["count"] == 1
        assert st["ttftP95Ms"] > 0
        assert set(st) == {"tokensPerSec", "acceptRate", "queueDepth",
                           "tokensTotal", "activeLanes", "lanePos",
                           "prefixHitRate", "kvBlocksFree", "kvBlocksHwm",
                           # prefill-path block (ISSUE 6 split)
                           "prefillMode", "prefillQueueDepth",
                           "chunkedPrefillTokenShare",
                           # quantized-pool block (ISSUE 7)
                           "kvQuantMode", "kvPoolBytes",
                           # bytes a token a layer the cache holds
                           # (ISSUE 32: K and V, codes, or a latent row)
                           "cacheRowBytes",
                           # weight-quant block (ISSUE 16)
                           "weightQuantMode", "draftQuantMode",
                           "paramBytes",
                           # hierarchical-cache block (ISSUE 8)
                           "hostCacheBlocks", "hostHitRate",
                           "promotedBlocks",
                           # multi-tenant QoS block (ISSUE 10)
                           "priorityQueueDepth", "preemptedLanes",
                           "parkedLanes", "activeAdapters",
                           "adapterNames",
                           # megastep block (ISSUE 11)
                           "megastepN", "dispatchesPerToken",
                           # fleet-level KV block (ISSUE 12)
                           "laneMigrations", "adoptedLanes",
                           "peerPrefixFetches", "hostCacheEvictions",
                           # durable prefix store block (ISSUE 17)
                           "kvStoreBlocks", "kvStoreBytes",
                           "kvStoreHitRate", "kvStoreEvictions",
                           # cross-host disaggregation block (ISSUE 13)
                           "remotePrefills",
                           # prefill-pool throughput block (ISSUE 14)
                           "prefillLanes", "prefillBatchOccupancy",
                           "prefillHolWaitMs", "handoffFrames",
                           "overlappedFrames",
                           # observability block (ISSUE 15): latency
                           # histogram snapshots + the windowed TTFT
                           # p95 the SLO autoscaler reads
                           "latencyHist", "ttftP95Ms",
                           # fault-tolerance block (infer/resilience.py)
                           "draining", "healthy", "deadlineExceeded",
                           "watchdogRestarts", "quarantinedLanes",
                           # live weight swap block (ISSUE 19)
                           "weightGeneration", "servingTp",
                           "weightSwaps",
                           # raw counters and the loop's phase table
                           # (ISSUE 26)
                           "dispatchesTotal", "decodeStepsTotal",
                           "decodeLaneStepsTotal", "decodeCellsLive",
                           "decodeCellsGrid", "insertStepsTotal",
                           "insertStepLanesTotal", "prefillCallsTotal",
                           "prefillTokensTotal",
                           "prefillBucketTokensTotal",
                           "prefillCallsByBucket",
                           "prefillAttnByBucket", "phaseSeconds",
                           "phaseCounts"}
        # one 3-token prompt through the 16-wide insert, 3 more tokens
        # from 2-tick chunks on the one lane
        assert st["prefillCallsTotal"] == 1
        assert st["prefillTokensTotal"] == 3
        assert st["prefillBucketTokensTotal"] == 16
        assert st["prefillCallsByBucket"] == {"16": 1}
        assert st["prefillAttnByBucket"] == {"16": "einsum", "32": "einsum"}
        assert st["decodeStepsTotal"] == 2 * st["dispatchesTotal"]
        assert st["insertStepsTotal"] == 0     # the contiguous insert's
        assert st["decodeLaneStepsTotal"] == st["decodeStepsTotal"] >= 3
        assert st["phaseCounts"]["sched.admit"] == 1
        assert st["phaseSeconds"]["exec.dispatch"] > 0
        assert st["prefillMode"] == "inline"
        assert st["prefillQueueDepth"] == 0
        assert st["kvQuantMode"] == "none"     # bf16 default
        assert st["weightQuantMode"] == "none"  # bf16 params default
        assert st["draftQuantMode"] == "none"  # non-speculative ring
        assert st["paramBytes"] > 0
        assert st["hostCacheBlocks"] == 0      # tier off by default
        assert st["hostHitRate"] == 0.0
        assert st["promotedBlocks"] == 0
        assert st["priorityQueueDepth"] == [0, 0]   # 2 classes default
        assert st["preemptedLanes"] == 0
        assert st["remotePrefills"] == 0       # no prefill pool by default
        assert st["prefillLanes"] == 0         # no local engine (inline)
        assert st["prefillBatchOccupancy"] == 0.0
        assert st["prefillHolWaitMs"] == 0.0
        assert st["handoffFrames"] == 0
        assert st["overlappedFrames"] == 0
        assert st["laneMigrations"] == 0       # fleet KV off by default
        assert st["adoptedLanes"] == 0
        assert st["peerPrefixFetches"] == 0
        assert st["hostCacheEvictions"] == 0
        assert st["kvStoreBlocks"] == 0        # no store by default
        assert st["kvStoreBytes"] == 0
        assert st["kvStoreHitRate"] == 0.0
        assert st["kvStoreEvictions"] == 0
        assert st["activeAdapters"] == 0       # no registry by default
        assert st["megastepN"] == 1            # single-step default
        assert st["dispatchesPerToken"] > 0
        assert st["kvPoolBytes"] > 0
        assert st["tokensTotal"] == 4
        assert st["tokensPerSec"] > 0
        assert st["acceptRate"] == 0.0         # non-speculative ring
        g = serving_gauges(st, "ns/j")
        assert g['tpujob_serve_tokens_per_sec{job="ns/j"}'] > 0

    def test_retired_lane_leaves_no_stale_pos(self):
        """Regression (PR 4 satellite): slot retirement used to leave
        the lane's fill position visible until the slot was reused —
        a finished ring must report zero active lanes and zeroed
        per-lane positions, not the dead request's."""
        import jax
        import jax.numpy as jnp

        from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
        from paddle_operator_tpu.models.llama import make_model

        model, cfg = make_model("tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        b = ContinuousBatcher(params, cfg, slots=2, max_len=32,
                              chunk_tokens=2, prefill_buckets=(16, 32))
        try:
            b.submit([1, 2, 3, 4, 5], max_new_tokens=4).result(timeout=300)
            st = b.serving_status()
            assert st["activeLanes"] == 0
            assert st["lanePos"] == [0, 0]     # not 5 + generated
            assert st["queueDepth"] == 0
        finally:
            b.close()

    def test_paged_ring_reports_prefix_and_block_gauges(self):
        """SERVE_PAGED ring: the serving block carries the prefix-hit
        rate and free-block gauges the manager exports."""
        import numpy as np
        import jax
        import jax.numpy as jnp

        from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
        from paddle_operator_tpu.models.llama import make_model

        model, cfg = make_model("tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        b = ContinuousBatcher(params, cfg, slots=2, max_len=32,
                              chunk_tokens=2, prefill_buckets=(16, 32),
                              paged=True, block_size=8)
        try:
            prompt = np.arange(1, 17, dtype=np.int32)   # two full blocks
            b.submit(prompt, max_new_tokens=3).result(timeout=300)
            b.submit(prompt, max_new_tokens=3).result(timeout=300)
            st = b.serving_status()
            assert st["prefixHitRate"] > 0      # second request hit
            assert st["kvBlocksFree"] > 0       # lanes retired
            assert st["kvBlocksHwm"] >= 2
            # the decode kernel's cells: the rectangle is lanes x blocks a
            # decode iteration; a 16-20 token context fills 3 of its 8
            # (its 3 blocks, and the idle lane's one cell: 4) at most
            assert st["decodeCellsGrid"] == st["decodeStepsTotal"] * 2 * 4
            assert (2 * st["decodeStepsTotal"] <= st["decodeCellsLive"]
                    <= 4 * st["decodeStepsTotal"])
            g = serving_gauges(st, "ns/j")
            assert g['tpujob_serve_prefix_hit_rate{job="ns/j"}'] > 0
            assert g['tpujob_serve_kv_blocks_free{job="ns/j"}'] > 0
            b.pool.check_invariant()
        finally:
            b.close()
