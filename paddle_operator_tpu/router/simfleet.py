"""Simulated serving fleet: N REAL ring servers behind the REAL router.

The fake_api.py pattern applied to the serving fleet: everything above
the pod boundary is the production code path — infer/serve.py HTTP
servers around real continuous-batching rings, the router proxying,
scraping and deduping exactly as deployed — only the pods themselves
are simulated (in-process threads, or subprocesses for honest
multi-core scaling in bench.py).  Tests, the dryrun ``serve-fleet``
gate and ``bench.py measure_fleet`` all drive fleets through this.

This is the one module under router/ that may import jax (the replicas
are real rings); the router process itself (``python -m
paddle_operator_tpu.router``) never imports it.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional

from paddle_operator_tpu.router.router import (
    FleetRouter,
    make_router_server,
)

# Subprocess replicas always run on the CPU backend: a chip belongs to
# one process, and the parent that spawns them may hold it.  Benchmark
# rows built on such replicas carry this value.
REPLICA_PLATFORM = "cpu"

_CLIENT_DIR = os.path.join(os.path.dirname(__file__), "..", "..",
                           "client")


def _client_module():
    """client/client.py, imported once (it lives outside the package
    tree; repeated sys.path.insert per request would grow sys.path
    without bound under bench load)."""
    if _CLIENT_DIR not in sys.path:
        sys.path.insert(0, _CLIENT_DIR)
    import client as client_cli

    return client_cli


class _Replica:
    def __init__(self, endpoint: str) -> None:
        self.endpoint = endpoint
        self.srv = None            # in-process: ThreadingHTTPServer
        self.proc = None           # subprocess: Popen
        self.thread = None
        self.exit_code: Optional[int] = None
        self.drained = False

    @property
    def batcher(self):
        return self.srv.generator.batcher if self.srv is not None \
            else None


def _tiny_params():
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models.llama import make_model

    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return params, cfg


class SimFleet:
    """``SimFleet(2)`` -> two paged tiny-model rings + a router.

    - ``add_replica()``      scale up: the router routes to it only
      after its /readyz goes true (the scrape loop's admission gate);
    - ``drain_replica(i)``   scale down, the PR 5 way: readiness drops,
      residents finish, stragglers cancel at the budget, the server
      exits "83" (recorded — no real process to kill in-process);
    - ``kill_replica(i)``    unplanned loss: the socket just dies.

    ``affinity=False`` builds the round-robin-ish control (pure
    least-loaded routing) the affinity comparison benches against.
    """

    def __init__(self, n: int = 2, *, affinity: bool = True,
                 block_size: int = 8, slots: int = 2,
                 max_len: int = 64, chunk_tokens: int = 4,
                 prefill_buckets=(16, 32), num_blocks: int = None,
                 hot_queue_depth: int = 4,
                 scrape_interval: float = 0.2,
                 subprocess_replicas: bool = False,
                 host_env: Optional[Dict[str, str]] = None,
                 ring_extra: Optional[Dict[str, Any]] = None,
                 fleet_kv: bool = False,
                 prefill_pool: int = 0,
                 trace: bool = False,
                 state_dir: Optional[str] = None,
                 router_extra: Optional[Dict[str, Any]] = None) -> None:
        self.block_size = block_size
        self.ring_kw: Dict[str, Any] = dict(
            slots=slots, max_len=max_len, chunk_tokens=chunk_tokens,
            prefill_buckets=tuple(prefill_buckets), paged=True,
            block_size=block_size, prefix_cache=True)
        if trace:
            # span capture on every replica ring + timeline stitching
            # in the router (ISSUE 18: the replay harness records
            # fleets with trace=True and exports
            # /debug/tracez?format=jsonl as its workload format)
            self.ring_kw["trace"] = True
        if num_blocks is not None:
            self.ring_kw["num_blocks"] = num_blocks
        # extra ring knobs (ISSUE 12 fleet-KV tests size a host tier
        # with host_cache_blocks=, quant fleets pass kv_quant=, ...)
        self.ring_kw.update(ring_extra or {})
        self.fleet_kv = fleet_kv
        self.subprocess_replicas = subprocess_replicas
        self.host_env = host_env or {}
        self.replicas: List[_Replica] = []
        self._params = self._cfg = None
        if not subprocess_replicas:
            self._params, self._cfg = _tiny_params()
        # cross-host disaggregation (ISSUE 13): N REAL prefill servers
        # (infer/prefill_serve.py) spawned BEFORE the decode replicas
        # — each decode ring boots with a RemotePrefillClient pointed
        # at this fleet's router, exactly the pod wiring
        # (SERVE_PREFILL=disagg + SERVE_PREFILL_REMOTE=1 +
        # SERVE_PREFILL_BROKER=<fleet service>) produces
        self.prefill_servers: List[Any] = []
        self._prefill_exits: List[Optional[int]] = []
        if prefill_pool:
            if subprocess_replicas:
                raise ValueError("prefill_pool needs in-process "
                                 "replicas (the client wires at ring "
                                 "construction)")
            self.ring_kw["prefill_mode"] = "disagg"
            for _ in range(prefill_pool):
                self._spawn_prefill()
        # router FIRST (empty decode membership): replicas constructed
        # below need its address for their remote-prefill broker
        # state_dir (ISSUE 20): a crash-safe journal under the fleet's
        # router, so kill/restart tests can rebuild a SECOND router on
        # the same dedupe window; router_extra passes breaker knobs
        # and friends straight through to FleetRouter
        self.state_dir = state_dir
        self.router = FleetRouter(
            [],
            block_size=block_size,
            affinity_blocks=2 if affinity else 0,
            hot_queue_depth=hot_queue_depth,
            scrape_interval=scrape_interval,
            prefill_endpoints=self.prefill_endpoints(),
            trace=trace or None,
            state_dir=state_dir,
            **(router_extra or {}))
        self.router_srv = make_router_server("127.0.0.1", 0,
                                             self.router)
        # short poll: shutdown() blocks a full poll interval per
        # server, and test fleets tear down three of them
        self._router_thread = threading.Thread(
            target=lambda: self.router_srv.serve_forever(
                poll_interval=0.05), daemon=True)
        self._router_thread.start()
        self.router_url = ("http://127.0.0.1:"
                           f"{self.router_srv.server_address[1]}")
        for _ in range(n):
            self.add_replica(wait_ready=False)
        if self.fleet_kv:
            self.enable_fleet_kv()
        self.wait_ready()

    # -- prefill pool (ISSUE 13) -------------------------------------------

    def prefill_endpoints(self) -> List[str]:
        return [f"127.0.0.1:{s.server_address[1]}"
                for i, s in enumerate(self.prefill_servers)
                if self._prefill_exits[i] is None]

    def _spawn_prefill(self):
        from paddle_operator_tpu.infer.prefill_serve import (
            make_prefill_server,
        )

        srv = make_prefill_server(
            "127.0.0.1", 0, self._params, self._cfg,
            block_size=self.block_size,
            max_len=self.ring_kw["max_len"],
            buckets=self.ring_kw["prefill_buckets"],
            kv_quant=self.ring_kw.get("kv_quant", "none"),
            # sampling rule is part of the handoff fingerprint: a
            # ring_extra top-k/top-p the pool didn't carry would 409
            # every handoff
            top_k=self.ring_kw.get("top_k"),
            top_p=self.ring_kw.get("top_p"),
            job="sim/fleet",
            replica=f"pf{len(self.prefill_servers)}")
        threading.Thread(
            target=lambda: srv.serve_forever(poll_interval=0.05),
            daemon=True).start()
        self.prefill_servers.append(srv)
        self._prefill_exits.append(None)
        return srv

    def add_prefill(self) -> str:
        """Scale the prefill pool up (the autoscaler's join): the
        router routes jobs to it once its scrape sees /readyz true."""
        srv = self._spawn_prefill()
        self.router.set_prefill_endpoints(self.prefill_endpoints())
        return f"127.0.0.1:{srv.server_address[1]}"

    def drain_prefill(self, idx: int, budget_s: float = 30.0) -> None:
        """The prefill pod's drain protocol (docs/fault-tolerance.md):
        /readyz false and new handoffs 503 (the decode side retries
        another pod), in-flight jobs finish and flush, exit 83."""
        import time as _time

        from paddle_operator_tpu.api.types import EXIT_PREEMPTED

        srv = self.prefill_servers[idx]
        srv.frontend.draining = True
        deadline = _time.monotonic() + budget_s
        while srv.frontend.depth() > 0 \
                and _time.monotonic() < deadline:
            _time.sleep(0.02)
        srv.shutdown()
        srv.server_close()      # refuse, don't backlog (drain_replica)
        srv.frontend.close()
        self._prefill_exits[idx] = EXIT_PREEMPTED
        self.router.set_prefill_endpoints(self.prefill_endpoints())

    def enable_fleet_kv(self, *, migrate: bool = True,
                        peer_fetch: bool = True,
                        parked_s: Optional[float] = None) -> None:
        """Wire every LIVE in-process replica with a FleetKVClient
        pointed at this fleet's router (ISSUE 12): drain-by-migration
        + router-brokered parked-lane shed + peer prefix fetch — the
        same wiring serve.py's SERVE_KV_MIGRATE / SERVE_KV_PEER_FETCH
        envs produce in a pod.  Idempotent; call again after
        add_replica()."""
        from paddle_operator_tpu.utils import fleetkv as FK

        broker = f"127.0.0.1:{self.router_srv.server_address[1]}"
        for rep in self.replicas:
            b = rep.batcher
            if b is None or rep.exit_code is not None \
                    or b.pool is None:
                continue
            client = FK.FleetKVClient(broker=broker,
                                      origin=rep.endpoint)
            if migrate:
                b.migrate_out = (
                    lambda c: lambda meta, spill:
                    c.migrate_out(FK.encode_lane(meta, spill)))(client)
                b._migrate_on_drain = True
                if parked_s:
                    b.migrate_parked_s = parked_s
            if peer_fetch and b.pool.host is not None:
                b.peer_fetch = client.fetch_prefix

    # -- replica lifecycle -------------------------------------------------

    def add_replica(self, wait_ready: bool = True) -> str:
        idx = len(self.replicas)
        if self.subprocess_replicas:
            rep = self._spawn_subprocess(idx)
        else:
            rep = self._spawn_inprocess(idx)
        self.replicas.append(rep)
        if hasattr(self, "router"):
            self.router.set_endpoints(
                [r.endpoint for r in self.replicas
                 if r.exit_code is None])
            if wait_ready:
                self.wait_ready()
        return rep.endpoint

    def _spawn_inprocess(self, idx: int) -> _Replica:
        from paddle_operator_tpu.infer.serve import make_server

        ring_kw = dict(self.ring_kw)
        if self.prefill_servers:
            from paddle_operator_tpu.infer.prefill_serve import (
                RemotePrefillClient,
            )

            ring_kw["prefill_client"] = RemotePrefillClient(
                broker="127.0.0.1:"
                       f"{self.router_srv.server_address[1]}")
        srv = make_server("127.0.0.1", 0, self._params, self._cfg,
                          continuous=True, job="sim/fleet",
                          replica=str(idx), **ring_kw)
        rep = _Replica(f"127.0.0.1:{srv.server_address[1]}")
        rep.srv = srv
        rep.thread = threading.Thread(
            target=lambda: srv.serve_forever(poll_interval=0.05),
            daemon=True)
        rep.thread.start()
        return rep

    def _spawn_subprocess(self, idx: int) -> _Replica:
        """A REAL replica process (bench.py: honest multi-core tok/s —
        in-process rings share one GIL for their host-side work)."""
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = dict(os.environ,
                   JAX_PLATFORMS=REPLICA_PLATFORM,
                   TPUJOB_REPLICA_PORT=str(port),
                   TPUJOB_REPLICA_ID=str(idx),
                   SIMFLEET_RING_KW=repr(self.ring_kw),
                   **self.host_env)
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "paddle_operator_tpu.router.simfleet"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        rep = _Replica(f"127.0.0.1:{port}")
        rep.proc = proc
        return rep

    def wait_ready(self, timeout: float = 120.0,
                   n: Optional[int] = None) -> None:
        """Block until ``n`` (default: all live) replicas are routable
        THROUGH the router — i.e. its scrape loop has admitted them."""
        want = n if n is not None else sum(
            1 for r in self.replicas if r.exit_code is None)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            ready = sum(1 for st in self.router.replicas.values()
                        if st.ready)
            if ready >= want:
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"fleet not ready: want {want}, have "
            f"{sum(1 for st in self.router.replicas.values() if st.ready)}")

    def drain_replica(self, idx: int, budget_s: float = 30.0) -> None:
        """The scale-down protocol, replica side: stop admissions
        (/readyz false, new submits 503), finish residents within the
        budget, exit EXIT_PREEMPTED.  The router's scrape loop observes
        the readiness drop and stops routing here — the same sequence a
        SIGTERM-d pod runs through resilience.ServingDrain."""
        from paddle_operator_tpu.api.types import EXIT_PREEMPTED

        rep = self.replicas[idx]
        if rep.proc is not None:
            import signal

            rep.proc.send_signal(signal.SIGTERM)
            rep.exit_code = rep.proc.wait(timeout=budget_s + 30)
            rep.drained = rep.exit_code == EXIT_PREEMPTED
        else:
            rep.srv.state.draining = True      # /readyz false, 503s
            rep.batcher.drain(budget_s)        # residents finish
            rep.srv.shutdown()
            # server_close() too: shutdown() alone leaves the LISTEN
            # socket open, and connections would sit in the dead
            # server's accept backlog instead of being refused — the
            # router must see a hard refusal to fail over immediately
            rep.srv.server_close()
            rep.exit_code = EXIT_PREEMPTED
            rep.drained = True

    def kill_replica(self, idx: int) -> None:
        rep = self.replicas[idx]
        if rep.proc is not None:
            rep.proc.kill()
            rep.exit_code = rep.proc.wait()
        else:
            rep.srv.shutdown()
            rep.srv.server_close()   # refuse, don't backlog (see drain)
            rep.batcher.close()
            rep.exit_code = 137
        rep.drained = False

    # -- traffic -----------------------------------------------------------

    def post(self, payload: Dict[str, Any], *, deadline_s=None,
             max_retries: int = 8, rng=None):
        """One request through the router with the PRODUCTION client
        retry discipline (client/client.py post_generate — 503 backoff,
        Retry-After, idempotent request_id)."""
        client_cli = _client_module()
        return client_cli.post_generate(
            self.router_url, payload, deadline_s=deadline_s,
            max_retries=max_retries, backoff_base_s=0.05,
            backoff_max_s=0.5, rng=rng)

    def replica_status(self, idx: int) -> Dict[str, Any]:
        with urllib.request.urlopen(
                f"http://{self.replicas[idx].endpoint}/statusz",
                timeout=10) as r:
            import json

            return json.loads(r.read())

    def check_invariants(self) -> None:
        """Per-replica pool invariant (free+mapped+cached==num_blocks)
        on every LIVE in-process replica."""
        for rep in self.replicas:
            b = rep.batcher
            if rep.exit_code is None and b is not None \
                    and b.pool is not None:
                b.pool.check_invariant()

    def close(self) -> None:
        self.router_srv.shutdown()
        self.router_srv.server_close()
        self.router.close()
        for i, rep in enumerate(self.replicas):
            if rep.exit_code is None:
                if rep.proc is not None:
                    rep.proc.kill()
                    rep.proc.wait()
                else:
                    rep.srv.shutdown()
                    rep.srv.server_close()
                    try:
                        rep.batcher.close()
                    except Exception:
                        pass
        for i, srv in enumerate(self.prefill_servers):
            if self._prefill_exits[i] is None:
                srv.shutdown()
                srv.server_close()
                try:
                    srv.frontend.close()
                except Exception:
                    pass


def prefix_workload(n_groups: int, per_group: int, *,
                    prefix_blocks: int = 2, block_size: int = 8,
                    suffix_len: int = 4, vocab: int = 256,
                    seed: int = 0) -> List[List[int]]:
    """``n_groups`` tenants, each with ``per_group`` prompts sharing
    ``prefix_blocks`` full blocks (the shared system prompt the radix
    cache + affinity routing exist for) and a distinct suffix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = []
    for g in range(n_groups):
        prefix = rng.integers(1, vocab,
                              (prefix_blocks * block_size,)).tolist()
        for _ in range(per_group):
            prompts.append(prefix
                           + rng.integers(1, vocab,
                                          (suffix_len,)).tolist())
    return prompts


def _replica_main() -> int:
    """Subprocess replica entry (``python -m
    paddle_operator_tpu.router.simfleet``): a tiny-model paged ring
    server with the full SIGTERM drain chain — what bench.py's
    subprocess fleets run per replica."""
    import ast

    from paddle_operator_tpu.ft.preemption import PreemptionWatcher
    from paddle_operator_tpu.infer.resilience import ServingDrain
    from paddle_operator_tpu.infer.serve import (
        make_server,
        wire_fleet_kv_from_env,
        wire_kv_store_from_env,
    )

    port = int(os.environ["TPUJOB_REPLICA_PORT"])
    ring_kw = ast.literal_eval(os.environ.get("SIMFLEET_RING_KW",
                                              "{}"))
    params, cfg = _tiny_params()
    srv = make_server("127.0.0.1", port, params, cfg,
                      continuous=True, job="sim/fleet",
                      replica=os.environ.get("TPUJOB_REPLICA_ID", ""),
                      **ring_kw)
    # fleet-level KV (ISSUE 12): the same SERVE_KV_* env contract the
    # real entrypoint honors, so bench subprocess fleets migrate too
    wire_fleet_kv_from_env(srv.generator.batcher, port)
    # durable prefix store (ISSUE 17): same env contract as the real
    # entrypoint, so bench fleets exercise the fleet-restart warm start
    wire_kv_store_from_env(srv.generator.batcher)
    watcher = PreemptionWatcher.install()
    drain = ServingDrain(
        srv, srv.state, batcher=srv.generator.batcher,
        budget_s=float(os.environ.get("SERVE_DRAIN_BUDGET_S", "30")))
    drain.install(watcher)
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(_replica_main())
