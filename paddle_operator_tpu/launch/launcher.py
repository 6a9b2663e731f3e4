"""In-pod launcher: consume the injected rendezvous contract and bring up
the distributed JAX runtime.

The reference delegates this entirely to ``paddle.distributed.launch``
inside user containers reading ``PADDLE_*`` env (SURVEY.md §3.3); our
operator injects the TPU-native contract (controller/builders.py
construct_configmap/construct_pod) and this module is the consumer:

    env (TPUJOB_*, MEGASCALE_*, TPU_WORKER_ID)
      → JobEnv.from_env()
      → initialize()            # jax.distributed over the coordinator
      → job_mesh()              # the Mesh every process agrees on

Entry point inside a container::

    python -m paddle_operator_tpu.launch.launcher -- python train.py ...
    # or, programmatically:
    from paddle_operator_tpu.launch import launcher
    env = launcher.initialize()
    mesh = launcher.job_mesh(env)
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import List, Optional

from paddle_operator_tpu.api.types import COORDINATOR_PORT, MeshSpec


@dataclass
class JobEnv:
    """Parsed view of the env contract one pod sees."""

    job_name: str = ""
    rank: int = 0                    # global rank, disjoint across roles
    role_rank: int = 0               # index within this pod's role
    res_type: str = "worker"         # worker | ps | heter
    worker_id: int = 0               # slice-local id (TPU_WORKER_ID)
    slice_id: int = 0                # MEGASCALE_SLICE_ID
    num_workers: int = 1
    workers_per_slice: int = 1
    num_slices: int = 1
    coordinator_address: str = ""
    worker_hosts: List[str] = field(default_factory=list)
    ps_endpoints: List[str] = field(default_factory=list)
    heter_endpoints: List[str] = field(default_factory=list)
    role: str = "TRAINER"
    port: int = COORDINATOR_PORT
    mesh: MeshSpec = field(default_factory=MeshSpec)
    topology: str = ""
    accelerator: str = ""
    checkpoint_path: str = ""
    max_restarts: int = 0

    @classmethod
    def from_env(cls, environ=None) -> "JobEnv":
        e = environ if environ is not None else os.environ
        mesh_json = e.get("TPUJOB_MESH", "")
        mesh = MeshSpec.from_dict(json.loads(mesh_json)) if mesh_json else MeshSpec()

        def split(key: str) -> List[str]:
            v = e.get(key, "")
            return [s for s in v.split(",") if s]

        rank = int(e.get("TPUJOB_RANK", 0))
        role = e.get("TPUJOB_ROLE", e.get("TRAINING_ROLE", "TRAINER"))
        # Fallback for env from a pre-TPUJOB_RES_TYPE controller (rolling
        # upgrade skew): PSERVER role implies the ps tier — without this an
        # old-contract PS pod would default to 'worker' and re-enter the
        # rank collision this field exists to prevent.  Old-contract HETER
        # pods are NOT distinguishable (their TRAINING_ROLE is also
        # "TRAINER") and will be misclassified as workers; finish the
        # controller upgrade before adding heter replicas.
        res_type = e.get("TPUJOB_RES_TYPE") or (
            "ps" if role == "PSERVER" else "worker"
        )
        return cls(
            job_name=e.get("TPUJOB_NAME", ""),
            rank=rank,
            role_rank=int(e.get("TPUJOB_ROLE_RANK", rank)),
            res_type=res_type,
            worker_id=int(e.get("TPU_WORKER_ID", 0)),
            slice_id=int(e.get("MEGASCALE_SLICE_ID", 0)),
            num_workers=int(e.get("TPUJOB_NUM_WORKERS", 1)),
            workers_per_slice=int(e.get("TPUJOB_WORKERS_PER_SLICE", 1) or 1),
            num_slices=int(e.get("TPUJOB_NUM_SLICES", 1) or 1),
            coordinator_address=e.get("TPUJOB_COORDINATOR_ADDRESS", ""),
            worker_hosts=split("TPUJOB_WORKER_HOSTS"),
            ps_endpoints=split("TPUJOB_PS_ENDPOINTS"),
            heter_endpoints=split("TPUJOB_HETER_ENDPOINTS"),
            role=role,
            port=int(e.get("TPUJOB_PORT", COORDINATOR_PORT)),
            mesh=mesh,
            topology=e.get("TPUJOB_TOPOLOGY", ""),
            accelerator=e.get("TPUJOB_ACCELERATOR", ""),
            checkpoint_path=e.get("TPUJOB_CHECKPOINT_PATH", ""),
            max_restarts=int(e.get("TPUJOB_MAX_RESTARTS", 0)),
        )

    @property
    def is_xla_worker(self) -> bool:
        """Whether this process belongs to the XLA collective world.

        Only ``worker`` pods do: the PS/heter tiers are CPU-side services
        (sharded-embedding hosts, preprocessors) that talk to workers over
        their own endpoints (``TPUJOB_PS_ENDPOINTS``), not via XLA
        collectives — so they must not occupy coordinator slots.  Worker
        global ranks are 0..num_workers-1 by construction
        (controller/builders.py construct_pod), so ``rank`` doubles as the
        XLA process id."""
        return self.res_type == "worker"

    def slice_local_hosts(self) -> List[str]:
        """The hostnames of this pod's slice (what the TPU runtime wants as
        TPU_WORKER_HOSTNAMES).  Derived rather than injected because the
        job-wide ConfigMap cannot carry per-slice values."""
        lo = self.slice_id * self.workers_per_slice
        return self.worker_hosts[lo:lo + self.workers_per_slice]


def initialize(env: Optional[JobEnv] = None, *, force: bool = False) -> JobEnv:
    """``jax.distributed.initialize`` from the env contract.

    No-ops for single-process jobs (the common local/dev case) unless
    `force`.  Safe to call before any other jax API (required: distributed
    init must precede backend init).
    """
    env = env or JobEnv.from_env()
    if not env.is_xla_worker and not force:
        # PS / heter pods are not part of the XLA world (see
        # JobEnv.is_xla_worker) — running the launcher in them must not
        # register with the coordinator (their global ranks are >= the
        # worker count and would be rejected; pre-fix they COLLIDED with
        # same-index worker ranks).
        return env
    if env.num_workers > 1 or force:
        import jax

        jax.distributed.initialize(
            coordinator_address=env.coordinator_address,
            num_processes=env.num_workers,
            process_id=env.rank,
        )
        # Export the slice-local host list for the libtpu runtime.  Set
        # unconditionally: the job contract is authoritative for operator-
        # managed pods — a default leaked by a base image (e.g.
        # TPU_WORKER_HOSTNAMES=localhost) would silently break multi-host
        # topology discovery.
        hosts = env.slice_local_hosts()
        if hosts:
            os.environ["TPU_WORKER_HOSTNAMES"] = ",".join(hosts)
    return env


def job_mesh(env: Optional[JobEnv] = None):
    """Build the job-wide Mesh from the contract (all processes must agree,
    which they do by construction: the MeshSpec comes from the ConfigMap)."""
    from paddle_operator_tpu.parallel.mesh import make_mesh

    env = env or JobEnv.from_env()
    return make_mesh(env.mesh)


def run_supervised(argv: List[str]) -> int:
    """Drain-aware child supervision (``TPUJOB_DRAIN=1``): run the user
    command as a child process, forward SIGTERM/SIGINT to it, and
    propagate its exit code — so a trainer that finishes its preemption
    drain with ``EXIT_PREEMPTED`` (ft/preemption.py) surfaces that exact
    code as the POD's exit code, which is what the reconciler's
    budget-free restart path reads (controller/builders.py
    is_pod_preempted).  A child killed by a signal it did not handle maps
    to the shell convention 128+N (burns the budget — correctly: it never
    drained)."""
    import signal
    import subprocess

    child = subprocess.Popen(argv)

    def forward(signum, frame):
        try:
            child.send_signal(signum)
        except (ProcessLookupError, OSError):
            pass

    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        prev[sig] = signal.signal(sig, forward)
    try:
        rc = child.wait()
    finally:
        for sig, h in prev.items():
            signal.signal(sig, h)
    return 128 - rc if rc < 0 else rc


def main(argv: Optional[List[str]] = None) -> int:
    """CLI shim: ``python -m paddle_operator_tpu.launch.launcher -- cmd...``
    enriches the environment (slice-local TPU_WORKER_HOSTNAMES etc.) and
    **execs** the user command, replacing this process.  The child — not
    the shim — calls :func:`initialize`, so exactly one process per rank
    registers with the XLA coordinator (a parent that initialized and then
    spawned a child would occupy the rank's coordinator slot).

    In a **PS pod** with no command, the shim runs the embedding parameter
    server (ps/server.py) — the default PS-tier program, the way the
    reference's PS pods run Paddle's pserver loop
    (/root/reference/docs/design-arch.md:5-12).  A **heter pod** with no
    command likewise runs the batch-preparation server (heter/server.py)."""
    import sys

    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--":
        argv = argv[1:]
    env = JobEnv.from_env()
    hosts = env.slice_local_hosts()
    if hosts:
        # unconditional for the same reason as initialize(): the contract
        # outranks any pre-set default
        os.environ["TPU_WORKER_HOSTNAMES"] = ",".join(hosts)
    if not argv:
        if env.res_type == "ps":
            from paddle_operator_tpu.ps import server as ps_server

            return ps_server.main()
        if env.res_type == "heter":
            from paddle_operator_tpu.heter import server as heter_server

            return heter_server.main()
        print(json.dumps({
            "rank": env.rank, "num_workers": env.num_workers,
            "coordinator": env.coordinator_address,
            "mesh": env.mesh.to_dict(), "topology": env.topology,
        }))
        return 0
    if os.environ.get("TPUJOB_DRAIN", "").lower() in ("1", "true", "yes"):
        # Supervised mode: as container PID 1 the exec'd trainer would
        # IGNORE an unhandled SIGTERM (kernel PID-1 semantics) and ride
        # out the grace period to SIGKILL; the shim stays alive instead,
        # forwards the signal to a normal-PID child, and propagates its
        # exit code (EXIT_PREEMPTED included) as the pod's.
        return run_supervised(argv)
    os.execvp(argv[0], argv)


if __name__ == "__main__":
    raise SystemExit(main())
