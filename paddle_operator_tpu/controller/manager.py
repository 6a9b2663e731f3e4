"""Controller manager entrypoint.

Capability parity with the reference's ``main.go`` (C1, main.go:51-129):
flag surface, health/readiness endpoints, metrics endpoint, leader election,
host-port pool seeding, then the reconcile loop.

Differences, by design:

- **Watch-driven reconcile** (reference: the SetupWithManager Owns chain
  feeds a workqueue, controllers/paddlejob_controller.go:442-447): watch
  streams on TPUJobs and every owned kind map events to the owning job and
  enqueue it on a deduplicating :class:`Workqueue`; ``requeue_after`` is
  honored with timers instead of being dropped after one follow-up.  A
  periodic full list remains as the resync backstop (level-triggered
  semantics survive missed events), and :meth:`Manager.run_poll` keeps the
  pure poll mode for API servers without watch support.
- **Leader election** via compare-and-swap on a ConfigMap (the reference
  uses controller-runtime's Lease-based election with ID
  ``b2a304f2.paddlepaddle.org``, main.go:78); a ConfigMap carries the same
  fencing-by-resourceVersion property and needs no coordination.k8s.io
  RBAC.  Expiry is decided on each candidate's own monotonic clock (the
  client-go observedRenewTime scheme), so cross-replica clock skew cannot
  elect two leaders.
- **Metrics** are Prometheus text format served from the process
  (controller-runtime binds :8080, main.go:57,75).
"""

from __future__ import annotations

import argparse
import http.server
import json
import os
import queue
import threading
import time
from typing import Dict, Optional, Set

from paddle_operator_tpu.api.types import HOST_PORT_RANGE, PORT_NUM
from paddle_operator_tpu.controller.api_client import APIClient, NotFound
from paddle_operator_tpu.controller.builders import GANG_LABEL
from paddle_operator_tpu.controller.hostport import make_allocator
from paddle_operator_tpu.controller.reconciler import KIND_JOB, TPUJobReconciler

LEASE_NAME = "tpujob-controller-leader"

# Owned kinds whose events re-trigger the owning job's reconcile
# (reference Owns(Pod).Owns(Service).Owns(ConfigMap), controller.go:442-447)
WATCHED_KINDS = (KIND_JOB, "Pod", "Service", "ConfigMap")


class Workqueue:
    """Deduplicating work queue with delayed re-adds — the shape of the
    controller-runtime workqueue the reference relies on.  A key already
    pending is not enqueued twice; ``add_after`` arms a timer (this is what
    fixes the round-1 lossy requeue: every ``requeue_after`` is honored,
    not just the first per sync pass)."""

    def __init__(self) -> None:
        self._q: "queue.Queue[str]" = queue.Queue()
        self._pending: Set[str] = set()
        self._lock = threading.Lock()
        self._timers: list = []

    def add(self, key: str) -> None:
        with self._lock:
            if key in self._pending:
                return
            self._pending.add(key)
        self._q.put(key)

    def add_after(self, key: str, delay: float) -> None:
        t = threading.Timer(delay, self.add, args=(key,))
        t.daemon = True
        t.start()
        with self._lock:
            self._timers = [x for x in self._timers if x.is_alive()]
            self._timers.append(t)

    def get(self, timeout: Optional[float] = None) -> str:
        key = self._q.get(timeout=timeout)
        with self._lock:
            self._pending.discard(key)
        return key

    def stop(self) -> None:
        with self._lock:
            for t in self._timers:
                t.cancel()


class Metrics:
    """Minimal prometheus-text counters and gauges (reference:
    controller-runtime metrics at :8080).  Keys may carry prometheus
    labels inline (``name{job="ns/x"}``) — the renderer treats the whole
    key as opaque."""

    def __init__(self) -> None:
        self.counters: Dict[str, float] = {
            "tpujob_reconcile_total": 0,
            "tpujob_reconcile_errors_total": 0,
            "tpujob_active_jobs": 0,
        }
        self._lock = threading.Lock()

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def set(self, name: str, v: float) -> None:
        with self._lock:
            self.counters[name] = v

    def remove(self, name: str) -> None:
        with self._lock:
            self.counters.pop(name, None)

    def render(self) -> str:
        with self._lock:
            return "".join(f"{k} {v}\n" for k, v in sorted(self.counters.items()))


def _serve(addr, metrics: Metrics, ready_fn) -> threading.Thread:
    """healthz/readyz/metrics HTTP endpoints (reference main.go:115-122).
    ``addr`` is ``(host, port)``; host defaults to all interfaces, and the
    rendered Deployment binds metrics to 127.0.0.1 so only the
    kube-rbac-proxy sidecar can reach them."""

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802
            if self.path == "/healthz":
                body, code = b"ok", 200
            elif self.path == "/readyz":
                ok = ready_fn()
                body, code = (b"ok", 200) if ok else (b"not ready", 503)
            elif self.path == "/metrics":
                body, code = metrics.render().encode(), 200
            else:
                body, code = b"not found", 404
            self.send_response(code)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # silence
            pass

    srv = http.server.ThreadingHTTPServer(addr, Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return t


class LeaderElector:
    """ConfigMap-CAS leader election (parity: manager leaderElection,
    main.go:77-79), clock-skew free.

    The lease record is ``{holder, renewals}`` where ``renewals`` is a
    fencing counter the holder bumps via compare-and-swap (the apiserver's
    resourceVersion optimistic concurrency IS the fence — a stale holder's
    renewal loses the CAS and is demoted).  Expiry never compares wall
    clocks across replicas: each candidate watches the (holder, renewals)
    pair and takes over only after it has stayed unchanged for
    ``lease_seconds`` on the candidate's OWN monotonic clock — the same
    observedRenewTime scheme as client-go's leaderelection package.

    The holder renews at most every ``lease_seconds/3`` and otherwise
    returns cached leadership, so an idle leader does not rewrite the
    ConfigMap (and fan out MODIFIED events to its watchers) on every loop
    iteration."""

    def __init__(self, api, identity: str, namespace: str,
                 lease_seconds: float = 15, clock=time.monotonic) -> None:
        self.api = api
        self.identity = identity
        self.namespace = namespace
        self.lease_seconds = lease_seconds
        self._clock = clock               # injectable for skew tests
        self._is_leader = False
        self._last_renew = 0.0            # local monotonic, ours
        self._observed = None             # (holder, renewals) last seen
        self._observed_at = 0.0           # local monotonic at last change

    def try_acquire(self) -> bool:
        now = self._clock()
        if self._is_leader and now - self._last_renew < self.lease_seconds / 3:
            return True                   # cached: no API traffic
        try:
            lease = self.api.get("ConfigMap", self.namespace, LEASE_NAME)
        except NotFound:
            lease = {
                "apiVersion": "v1", "kind": "ConfigMap",
                "metadata": {"name": LEASE_NAME, "namespace": self.namespace},
                "data": {},
            }
            try:
                lease = self.api.create("ConfigMap", lease)
            except Exception:
                self._is_leader = False
                return False
        data = lease.get("data") or {}
        holder = data.get("holder")
        # the record includes resourceVersion so ANY write to the lease —
        # even one by a replica running a different record format (e.g.
        # during a rolling update) — resets the takeover timer
        record = (holder, data.get("renewals"),
                  lease.get("metadata", {}).get("resourceVersion"))
        if record != self._observed:
            self._observed = record
            self._observed_at = now
        if holder not in (None, "", self.identity):
            # someone else holds it: take over only once the record has
            # been still for a full lease on OUR clock
            if now - self._observed_at < self.lease_seconds:
                self._is_leader = False
                return False
        lease["data"] = {
            "holder": self.identity,
            "renewals": str(int(data.get("renewals") or 0) + 1),
        }
        try:
            updated = self.api.update("ConfigMap", lease)
            self._is_leader = True
            self._last_renew = now
            self._observed = (self.identity, lease["data"]["renewals"],
                              updated.get("metadata", {})
                              .get("resourceVersion"))
            self._observed_at = now
            return True
        except Exception:
            # lost the CAS: someone renewed/acquired under us (fencing)
            self._is_leader = False
            return False


class Manager:
    def __init__(self, api: APIClient, *, namespace: str = "",
                 sync_period: float = 2.0,
                 port_range=HOST_PORT_RANGE,
                 leader_elect: bool = False,
                 identity: str = "tpujob-controller-0",
                 metrics: Optional[Metrics] = None) -> None:
        self.api = api
        self.namespace = namespace or "default"
        self.sync_period = sync_period
        self.metrics = metrics or Metrics()
        allocator = make_allocator(port_range[0], port_range[1], PORT_NUM)
        self.reconciler = TPUJobReconciler(api, allocator=allocator)
        self.leader = (LeaderElector(api, identity, self.namespace)
                       if leader_elect else None)
        self._stop = threading.Event()
        self._ready = False
        # job key -> gauge names last exported for it (stale-prune state)
        self._goodput_gauges: Dict[str, Set[str]] = {}

    def ready(self) -> bool:
        return self._ready

    def stop(self) -> None:
        self._stop.set()

    def run_once(self, max_followups: int = 8) -> int:
        """One sync pass over all jobs; returns the number reconciled.
        Requeue-requesting jobs get follow-up passes until settled (bounded
        by `max_followups` — the watch loop, not this poll backstop, is the
        production path)."""
        jobs = self._list_jobs()
        self.metrics.set("tpujob_active_jobs", len(jobs))
        self._export_goodput(jobs)
        n = 0
        for j in jobs:
            name = j["metadata"]["name"]
            try:
                result = self.reconciler.reconcile(self.namespace, name)
                self.metrics.inc("tpujob_reconcile_total")
                n += 1
                for _ in range(max_followups):
                    if not result.wants_requeue:
                        break
                    result = self.reconciler.reconcile(self.namespace, name)
                    self.metrics.inc("tpujob_reconcile_total")
            except Exception:
                self.metrics.inc("tpujob_reconcile_errors_total")
        return n

    def _export_goodput(self, jobs) -> None:
        """Mirror each job's workload-published telemetry blocks into
        per-job gauges on ``/metrics``: ``status.goodput``
        (ft/goodput.py -> ``tpujob_goodput_*``/``tpujob_badput_seconds``)
        and ``status.serving`` (infer/scheduler.py serving_status ->
        ``tpujob_serve_tokens_per_sec``/``tpujob_serve_accept_rate``/
        ``tpujob_serve_queue_depth``, plus the fault-tolerance gauges
        ``tpujob_serve_watchdog_restarts``/``..._deadline_exceeded``/
        ``..._quarantined_lanes``/``..._draining`` from
        infer/resilience.py).  Gauges of deleted jobs (and
        gauge names a job stopped publishing) are pruned, so /metrics
        never serves stale readings and the registry stays bounded."""
        from paddle_operator_tpu.ft.goodput import goodput_gauges
        from paddle_operator_tpu.utils.observability import serving_gauges

        exported: Dict[str, Set[str]] = {}
        for j in jobs:
            st = j.get("status") or {}
            gauges: Dict[str, float] = {}
            ns = j["metadata"].get("namespace", self.namespace)
            key = f'{ns}/{j["metadata"]["name"]}'
            if st.get("goodput"):
                gauges.update(goodput_gauges(st["goodput"], key))
            if st.get("serving"):
                gauges.update(serving_gauges(st["serving"], key))
            if not gauges:
                continue
            for name, val in gauges.items():
                self.metrics.set(name, val)
            exported[key] = set(gauges)
        for key, names in self._goodput_gauges.items():
            for stale in names - exported.get(key, set()):
                self.metrics.remove(stale)
        self._goodput_gauges = exported

    def _list_jobs(self):
        if hasattr(self.api, "list_kind"):  # FakeAPI (locked snapshot)
            return self.api.list_kind(KIND_JOB, self.namespace)
        # KubeAPI: list the collection
        from paddle_operator_tpu import GROUP, PLURAL, VERSION

        url = (f"{self.api.host}/apis/{GROUP}/{VERSION}/namespaces/"
               f"{self.namespace}/{PLURAL}")
        return self.api._request("GET", url).get("items", [])

    def run_poll(self) -> None:
        """Pure poll mode, for API clients without watch support."""
        self._ready = True
        while not self._stop.is_set():
            if self.leader is not None and not self.leader.try_acquire():
                time.sleep(self.sync_period)
                continue
            self.run_once()
            self._stop.wait(self.sync_period)

    def _job_key_for(self, kind: str, obj: Dict) -> Optional[str]:
        """Map a watch event's object to the owning job name."""
        meta = obj.get("metadata", {})
        if kind == KIND_JOB:
            return meta.get("name")
        owner = self.api.controller_of(obj)
        if owner:
            return owner
        return (meta.get("labels") or {}).get(GANG_LABEL)

    def run(self) -> None:
        """Watch-driven loop (falls back to polling when the API client has
        no `watch`).  Watch pumps on the job kind and every owned kind feed
        the workqueue; a resync thread lists all jobs every sync_period as
        the level-trigger backstop; one worker drains the queue and honors
        requeue/requeue_after."""
        if not hasattr(self.api, "watch"):
            return self.run_poll()
        self._ready = True
        wq = self._wq = Workqueue()
        stop = self._stop

        def pump(kind: str) -> None:
            while not stop.is_set():
                try:
                    for evt in self.api.watch(kind, self.namespace,
                                              stop=stop):
                        key = self._job_key_for(kind, evt.get("object", {}))
                        if key:
                            wq.add(key)
                        if stop.is_set():
                            break
                except Exception as e:
                    # Surface the degradation: with a dead watch the loop
                    # falls back to resync-only latency.
                    self.metrics.inc("tpujob_watch_errors_total")
                    print(f"watch[{kind}] error, reconnecting: {e!r}",
                          flush=True)
                stop.wait(0.5)   # stream closed or errored: reconnect

        for kind in WATCHED_KINDS:
            threading.Thread(target=pump, args=(kind,), daemon=True).start()

        def resync() -> None:
            while not stop.is_set():
                try:
                    jobs = self._list_jobs()
                    self.metrics.set("tpujob_active_jobs", len(jobs))
                    self._export_goodput(jobs)
                    for j in jobs:
                        wq.add(j["metadata"]["name"])
                except Exception as e:
                    self.metrics.inc("tpujob_resync_errors_total")
                    print(f"resync error: {e!r}", flush=True)
                stop.wait(self.sync_period)

        threading.Thread(target=resync, daemon=True).start()

        while not stop.is_set():
            if self.leader is not None and not self.leader.try_acquire():
                stop.wait(1.0)
                continue
            try:
                name = wq.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                result = self.reconciler.reconcile(self.namespace, name)
                self.metrics.inc("tpujob_reconcile_total")
                if result.requeue:
                    wq.add(name)
                elif result.requeue_after:
                    wq.add_after(name, result.requeue_after)
            except Exception:
                self.metrics.inc("tpujob_reconcile_errors_total")
                wq.add_after(name, 1.0)
        wq.stop()


def load_config_file(path: str) -> Dict:
    """Read the ControllerManagerConfig tier (reference:
    config/manager/controller_manager_config.yaml mounted into the manager
    Deployment).  Returns {} when the file is absent/empty."""
    import yaml

    with open(path) as f:
        return yaml.safe_load(f) or {}


def main(argv=None) -> int:
    """CLI parity with reference main.go:57-63, plus the --config file
    tier (flags explicitly set on the command line win over the file)."""
    p = argparse.ArgumentParser(prog="tpujob-controller")
    p.add_argument("--metrics-bind-address", default=":8080")
    p.add_argument("--health-probe-bind-address", default=":8081")
    p.add_argument("--namespace", default="",
                   help="restrict the controller to one namespace")
    p.add_argument("--port-range", default="35000,65000",
                   help="host-port allocation range 'start,end'")
    p.add_argument("--leader-elect", action="store_true")
    p.add_argument("--sync-period", type=float, default=2.0)
    p.add_argument("--webhook-bind-address", default="",
                   help="serve admission webhooks (validate/default) on "
                        "host:port, e.g. ':9443' (reference main.go:76); "
                        "empty disables")
    p.add_argument("--webhook-cert-dir",
                   default="/tmp/k8s-webhook-server/serving-certs",
                   help="dir with tls.crt/tls.key (cert-manager Secret "
                        "mount); the server waits for the cert to "
                        "appear before listening.  Pass an EMPTY value "
                        "to serve plain HTTP immediately (local dev — "
                        "the apiserver itself only dials HTTPS)")
    p.add_argument("--config", default="",
                   help="YAML ControllerManagerConfig file; CLI flags "
                        "left at their defaults take the file's values")
    args = p.parse_args(argv)

    file_cfg = load_config_file(args.config) if args.config else {}

    def pick(flag: str, key: str):
        val = getattr(args, flag)
        if val == p.get_default(flag) and key in file_cfg:
            return file_cfg[key]
        return val

    metrics_addr = pick("metrics_bind_address", "metricsBindAddress")
    probe_addr = pick("health_probe_bind_address", "healthProbeBindAddress")
    # --namespace > config file > the pod's own namespace (downward-API
    # POD_NAMESPACE env in the rendered Deployment) — baking a literal
    # namespace into container args would survive a kustomize
    # namespace transform and leave a re-namespaced install watching
    # the wrong place
    namespace = pick("namespace", "namespace") \
        or os.environ.get("POD_NAMESPACE", "")
    port_range = str(pick("port_range", "portRange"))
    leader_elect = bool(pick("leader_elect", "leaderElect"))
    sync_period = float(pick("sync_period", "syncPeriod"))

    lo, hi = (int(x) for x in port_range.split(","))

    from paddle_operator_tpu.controller.kube_api import KubeAPI

    api = KubeAPI()
    metrics = Metrics()
    mgr = Manager(api, namespace=namespace or "default",
                  sync_period=sync_period, port_range=(lo, hi),
                  leader_elect=leader_elect, metrics=metrics)

    def addr_of(addr: str, default_port: int):
        host, _, port = addr.rpartition(":")
        try:
            return (host or "0.0.0.0", int(port))
        except ValueError:
            return ("0.0.0.0", default_port)

    _serve(addr_of(probe_addr, 8081), metrics, mgr.ready)
    _serve(addr_of(metrics_addr, 8080), metrics, mgr.ready)
    webhook_addr = pick("webhook_bind_address", "webhookBindAddress")
    if webhook_addr:
        from paddle_operator_tpu.controller.webhook import \
            make_webhook_server

        host, port = addr_of(webhook_addr, 9443)
        cert_dir = pick("webhook_cert_dir", "webhookCertDir")

        def run_webhook():
            # On a fresh install the pod starts BEFORE cert-manager
            # issues the serving cert into the (optional) secret mount
            # — checking once and falling back to plain HTTP would
            # leave the webhooks permanently inert (the apiserver only
            # dials HTTPS).  Wait for the cert (logged, so a missing
            # cert-manager is diagnosable); serve plain HTTP only when
            # the cert dir is explicitly emptied (local dev).  Serving
            # failures (port clash, mismatched key pair mid-rotation)
            # retry instead of silently killing the thread.
            if cert_dir:
                crt = os.path.join(cert_dir, "tls.crt")
                waited = 0
                while not os.path.exists(crt):
                    if waited % 300 == 0:
                        print(f"webhook: waiting for serving cert at "
                              f"{crt} (cert-manager installed?)",
                              flush=True)
                    time.sleep(5)
                    waited += 5
            while True:
                try:
                    srv = make_webhook_server(host, port,
                                              cert_dir=cert_dir or None)
                    print(f"webhook: serving on {host}:{port} "
                          f"(tls={'on' if cert_dir else 'off'})",
                          flush=True)
                    srv.serve_forever()
                    return
                except OSError as e:
                    print(f"webhook: serve failed ({e}); retrying in "
                          f"10s", flush=True)
                    time.sleep(10)

        threading.Thread(target=run_webhook, daemon=True,
                         name="webhook").start()
    mgr.run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
