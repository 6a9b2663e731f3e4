"""Multi-tenant QoS for the serving ring: priority classes, preemptive
lane spill, and many-adapter (LoRA) serving (ISSUE 10).

Three pieces, consumed by ``infer/scheduler.py`` / ``infer/executor.py``:

- **Priority classes** (:class:`MultiClassQueue`, :class:`QoSConfig`):
  ``submit(priority=)`` / HTTP ``X-Request-Priority`` order admission in
  class-then-FIFO order (class 0 is the most urgent).  Each class gets
  its OWN bounded queue — a priority-1 flood saturating its bound must
  never backpressure a priority-0 request (that is the whole point).
  When a more urgent request would queue behind a full ring, the
  scheduler PREEMPTS the least urgent resident lane at its next chunk
  boundary: the lane spills to host byte-exactly
  (``RingExecutor.spill_lane`` — the ISSUE 8 primitive built for this),
  its blocks free for the preemptor, and the victim re-admits later
  through ``restore_lane`` with a BIT-IDENTICAL resumed stream.
  :class:`PreemptionBudget` bounds preemption density (and a per-request
  cap bounds how often one victim can be bounced) so priority inversion
  fixes cannot degenerate into spill thrash.

- **Many-adapter serving** (:class:`AdapterRegistry`): LoRA-style
  low-rank deltas (S-LoRA lineage: many fine-tunes batched off ONE base
  param set).  Adapters live in fixed-capacity stacked device arrays
  ``[L, capacity + 1, ...]`` (slot 0 is the all-zero base — a lane with
  adapter id 0 computes byte-identically to the adapterless ring, since
  ``x @ 0 @ 0`` is an exact zero), so load/evict never changes compiled
  shapes.  The decode step gathers each lane's ``(A, B)`` pair by its
  per-lane adapter id and fuses the delta matmul into the same compiled
  program — mixed-adapter batches run in ONE dispatch
  (:func:`lora_qkv` is the shared math, applied at every q/k/v
  projection site in decode/executor/paged/speculative).

- **Cache correctness across tenants**: an adapter changes wk/wv, so
  its KV is NOT the base model's — the paged radix cache namespaces
  chain keys by the adapter's load generation
  (:meth:`AdapterRegistry.ns_of` -> ``PagedCacheManager.admit(ns=)``),
  so prefix reuse happens within an adapter and never across, and an
  evict+reload at the same slot can never hit the dead adapter's
  blocks.

Spec decode: the draft stays base-only by design, so a speculative ring
refuses per-request adapters cleanly (``submit(adapter=)`` raises) —
priorities and preemption still fully apply (spill/restore captures the
draft lane too).
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import queue as _queue

import numpy as np

from paddle_operator_tpu.controller.policy import (
    DEFAULT_POLICY as _POLICY,
    PolicyConfig,
)

MAX_PRIORITIES = 8

# adapter names become Prometheus label values and routing keys — keep
# them to a charset that needs no escaping anywhere downstream
_ADAPTER_NAME_RE = re.compile(r"^[A-Za-z0-9_.\-]{1,64}$")


class AdapterInUse(ValueError):
    """Raised by :meth:`AdapterRegistry.evict`/:meth:`load` when the
    target adapter is still serving resident/parked/queued lanes — a
    typed signal so the HTTP surface can 409 exactly (substring
    matching on messages misclassifies)."""


@dataclass
class QoSConfig:
    """Knobs for the multi-tenant scheduler (env surface in
    infer/serve.py: ``SERVE_PRIORITIES`` / ``SERVE_PREEMPT*``).

    - ``priorities``: number of classes (class 0 most urgent).  1 turns
      the whole subsystem into the single-FIFO ring.
    - ``default_priority``: class for unannotated requests; ``None``
      resolves to the LEAST urgent class — priorities are opt-in
      boosts, so legacy traffic keeps today's behavior exactly.
    - ``preempt``: allow lane spill for waiting more-urgent work
      (paged rings only — the spill rides the block pool).
    - ``max_preempts_per_request``: one victim is never bounced more
      than this many times (starvation guard).
    - ``preempt_budget`` / ``preempt_window_s``: at most ``budget``
      preemptions per rolling window (anti-thrash: a pathological
      priority mix degrades to FIFO, never to spill churn).

    Defaults come from the shared policy surface
    (controller/policy.py, ISSUE 18) — the replay simulator sweeps
    these budgets as ``PolicyConfig`` fields, so the numbers a sweep
    scores ARE the numbers this config defaults to.
    """

    priorities: int = _POLICY.priorities
    default_priority: Optional[int] = None
    preempt: bool = True
    max_preempts_per_request: int = _POLICY.max_preempts_per_request
    preempt_budget: int = _POLICY.preempt_budget
    preempt_window_s: float = _POLICY.preempt_window_s

    def __post_init__(self) -> None:
        if not 1 <= self.priorities <= MAX_PRIORITIES:
            raise ValueError(f"priorities must be in [1, {MAX_PRIORITIES}]"
                             f" (got {self.priorities})")
        if self.default_priority is None:
            self.default_priority = self.priorities - 1
        if not 0 <= self.default_priority < self.priorities:
            raise ValueError(
                f"default_priority {self.default_priority} outside "
                f"[0, {self.priorities})")

    @classmethod
    def from_policy(cls, policy: PolicyConfig,
                    **overrides: Any) -> "QoSConfig":
        """Bind the QoS budgets a :class:`PolicyConfig` names — the
        constructor the scheduler's default path and the replay
        simulator share, so a swept sweep point configures the REAL
        admission machinery, not a parallel copy of its knobs."""
        kw: Dict[str, Any] = dict(
            priorities=policy.priorities,
            max_preempts_per_request=policy.max_preempts_per_request,
            preempt_budget=policy.preempt_budget,
            preempt_window_s=policy.preempt_window_s,
        )
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def from_env(cls) -> "QoSConfig":
        import os

        return cls(
            priorities=int(os.environ.get(
                "SERVE_PRIORITIES", str(_POLICY.priorities))),
            preempt=os.environ.get("SERVE_PREEMPT", "1") == "1",
            max_preempts_per_request=int(os.environ.get(
                "SERVE_PREEMPT_MAX_PER_REQ",
                str(_POLICY.max_preempts_per_request))),
            preempt_budget=int(os.environ.get(
                "SERVE_PREEMPT_BUDGET", str(_POLICY.preempt_budget))),
            preempt_window_s=float(os.environ.get(
                "SERVE_PREEMPT_WINDOW_S",
                str(_POLICY.preempt_window_s))),
        )


class MultiClassQueue:
    """Thread-safe per-class bounded FIFO with class-order pops.

    The API mirrors the slice of ``queue.Queue`` the scheduler used
    (``put_nowait``/``get_nowait``/``qsize``/``empty``/``full``) with a
    class argument where it matters.  The bound is PER CLASS: a flood
    in one class sheds ITS OWN overflow (QueueFull upstream) while the
    other classes keep their full admission budget — shared-bound
    backpressure would let a batch tenant starve the express class at
    the front door, before priority scheduling could even look at it.
    ``maxsize`` 0 = unbounded, like queue.Queue."""

    def __init__(self, n_classes: int, maxsize: int = 0) -> None:
        if n_classes < 1:
            raise ValueError("n_classes must be >= 1")
        self.n_classes = n_classes
        self.maxsize = int(maxsize)
        self._qs: List[deque] = [deque() for _ in range(n_classes)]
        self._lock = threading.Lock()
        # wakes blocked put(timeout=) callers the moment ANY class
        # drains — busy-polling would charge each blocked submitter up
        # to a full tick of avoidable latency per freed slot
        self._not_full = threading.Condition(self._lock)

    def _check_class(self, prio: int) -> int:
        prio = int(prio)
        if not 0 <= prio < self.n_classes:
            raise ValueError(f"priority {prio} outside "
                             f"[0, {self.n_classes})")
        return prio

    def put_nowait(self, item: Any, prio: int) -> None:
        prio = self._check_class(prio)
        with self._lock:
            if self.maxsize and len(self._qs[prio]) >= self.maxsize:
                raise _queue.Full
            self._qs[prio].append(item)

    def put(self, item: Any, prio: int,
            timeout: Optional[float] = None) -> None:
        """Blocking put: wait up to ``timeout`` for class ``prio`` to
        have room (condition-based — wakes the instant a slot frees,
        like queue.Queue), then raise queue.Full.  The scheduler's
        submit keeps its short ticks so close()/drain() can interrupt
        a blocked submitter between waits."""
        prio = self._check_class(prio)
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._not_full:
            while self.maxsize and len(self._qs[prio]) >= self.maxsize:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    raise _queue.Full
                self._not_full.wait(remaining)
            self._qs[prio].append(item)

    def get_nowait(self) -> Any:
        """Pop the oldest item of the MOST urgent non-empty class."""
        with self._lock:
            for q in self._qs:
                if q:
                    item = q.popleft()
                    self._not_full.notify_all()
                    return item
        raise _queue.Empty

    def peek_class(self) -> Optional[int]:
        """Most urgent non-empty class (None when empty)."""
        with self._lock:
            for c, q in enumerate(self._qs):
                if q:
                    return c
        return None

    def full(self, prio: int) -> bool:
        prio = self._check_class(prio)
        if not self.maxsize:
            return False
        with self._lock:
            return len(self._qs[prio]) >= self.maxsize

    def qsize(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._qs)

    def qsize_by_class(self) -> List[int]:
        with self._lock:
            return [len(q) for q in self._qs]

    def empty(self) -> bool:
        return self.qsize() == 0

    def items(self) -> List[Any]:
        """Snapshot of every queued item (all classes) — e.g. the
        adapter-evict guard must see requests that resolved their
        adapter slot at submit but have not been admitted yet."""
        with self._lock:
            return [item for q in self._qs for item in q]


class PreemptionBudget:
    """Rolling-window preemption counter (the anti-thrash budget): at
    most ``budget`` spends per ``window_s``.  Deliberately simple —
    preemption is a rare corrective action, and when the mix is so
    adversarial that the budget pins, degrading to in-order admission
    is the safe behavior (the spill/restore cycle itself costs a block
    upload per bounce)."""

    def __init__(self, budget: int, window_s: float,
                 clock=time.monotonic) -> None:
        self.budget = int(budget)
        self.window_s = float(window_s)
        self._clock = clock
        self._spends: deque = deque()

    def _trim(self) -> None:
        now = self._clock()
        while self._spends and now - self._spends[0] >= self.window_s:
            self._spends.popleft()

    def ok(self) -> bool:
        self._trim()
        return len(self._spends) < self.budget

    def spend(self) -> None:
        self._trim()
        self._spends.append(self._clock())


# ---------------------------------------------------------------------------
# Many-adapter (LoRA) serving
# ---------------------------------------------------------------------------

# projections the low-rank deltas target: the attention inputs (classic
# LoRA).  wo is deliberately NOT in the set: the TP-sharded pallas path
# applies wo inside its shard_map region where the pre-projection
# activation is not exposed, and q/k/v deltas apply identically on
# every attention backend.
LORA_PROJS = ("wq", "wk", "wv")


def _proj_dims(cfg) -> Dict[str, Tuple[int, int]]:
    return {
        "wq": (cfg.dim, cfg.n_heads * cfg.head_dim),
        "wk": (cfg.dim, cfg.n_kv_heads * cfg.head_dim),
        "wv": (cfg.dim, cfg.n_kv_heads * cfg.head_dim),
    }


def stable_name_seed(name: str) -> int:
    """Deterministic cross-process seed for a bare adapter name:
    ``hash(str)`` is PYTHONHASHSEED-salted (the radixkey/hashring trap
    all over again), so two fleet replicas booting ``SERVE_ADAPTERS=x``
    would synthesize DIFFERENT smoke adapters and the router would
    treat them as interchangeable holders.  A digest is stable
    everywhere."""
    import hashlib

    return int.from_bytes(
        hashlib.blake2b(name.encode(), digest_size=4).digest(),
        "little") & 0x7FFFFFFF


def make_random_adapter(cfg, rank: int, seed: int,
                        scale: float = 0.5) -> Dict[str, Any]:
    """Synthesize a deterministic random LoRA delta (smoke mode — the
    serving analogue of serve.py's fresh-init draft): per-projection
    ``A [L, dim, r]`` / ``B [L, r, out]`` f32 numpy arrays.  ``scale``
    is large enough that distinct adapters produce distinct token
    streams on a tiny model, which is what the parity tests need."""
    rng = np.random.default_rng(seed)
    out = {}
    for proj, (din, dout) in _proj_dims(cfg).items():
        a = rng.standard_normal((cfg.n_layers, din, rank)).astype(
            np.float32) * (scale / np.sqrt(din))
        b = rng.standard_normal((cfg.n_layers, rank, dout)).astype(
            np.float32) * (scale / np.sqrt(rank))
        out[proj] = {"a": a, "b": b}
    return out


def load_adapter_file(cfg, path: str, rank: int) -> Dict[str, Any]:
    """Load a LoRA delta from an ``.npz`` with keys ``{proj}_a``
    [L, dim, r] / ``{proj}_b`` [L, r, out] per projection in
    :data:`LORA_PROJS`.  A file rank SMALLER than the registry rank
    zero-pads (exact — padded rank columns contribute 0); larger
    raises."""
    import numpy as _np

    data = _np.load(path)
    dims = _proj_dims(cfg)
    out = {}
    for proj, (din, dout) in dims.items():
        a = _np.asarray(data[f"{proj}_a"], _np.float32)
        b = _np.asarray(data[f"{proj}_b"], _np.float32)
        if a.shape[0] != cfg.n_layers or a.shape[1] != din \
                or b.shape[2] != dout or a.shape[2] != b.shape[1]:
            raise ValueError(
                f"{path}: {proj} shapes {a.shape}/{b.shape} do not fit "
                f"[L={cfg.n_layers}, {din}, r]/[L, r, {dout}]")
        r = a.shape[2]
        if r > rank:
            raise ValueError(f"{path}: {proj} rank {r} exceeds the "
                             f"registry rank {rank}")
        if r < rank:
            a = _np.pad(a, ((0, 0), (0, 0), (0, rank - r)))
            b = _np.pad(b, ((0, 0), (0, rank - r), (0, 0)))
        out[proj] = {"a": a, "b": b}
    return out


class AdapterRegistry:
    """Fixed-capacity pool of LoRA adapters served off one base model.

    Device layout: per projection, stacked ``a [L, capacity+1, dim, r]``
    and ``b [L, capacity+1, r, out]`` f32 arrays whose index 0 is the
    all-zero BASE adapter.  Shapes are static, so load/evict (an
    ``.at[:, idx].set``) never invalidates a compiled program; the
    arrays are passed to every dispatch as traced operands, so updates
    reach the ring without recompiles.

    ``ns_of(idx)`` is the radix-cache namespace: a fresh token minted
    at every load, so a prefix cached under one adapter can never be
    hit by a DIFFERENT adapter later loaded into the same slot (the KV
    bytes differ — wk/wv carry the delta)."""

    def __init__(self, cfg, capacity: int = 8, rank: int = 8) -> None:
        import jax.numpy as jnp

        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if rank < 1:
            raise ValueError("rank must be >= 1")
        self.cfg = cfg
        self.capacity = int(capacity)
        self.rank = int(rank)
        self._lock = threading.Lock()
        self._by_name: Dict[str, int] = {}
        self._by_idx: Dict[int, str] = {}
        self._ns: Dict[int, int] = {}           # idx -> load generation
        self._gen = 0
        self._dev: Dict[str, Dict[str, Any]] = {}
        for proj, (din, dout) in _proj_dims(cfg).items():
            self._dev[proj] = {
                "a": jnp.zeros((cfg.n_layers, self.capacity + 1, din,
                                self.rank), jnp.float32),
                "b": jnp.zeros((cfg.n_layers, self.capacity + 1,
                                self.rank, dout), jnp.float32),
            }

    def __len__(self) -> int:
        return len(self._by_name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._by_name)

    def resolve(self, name: str) -> int:
        with self._lock:
            idx = self._by_name.get(name)
        if idx is None:
            raise ValueError(f"unknown adapter {name!r} (loaded: "
                             f"{sorted(self._by_name) or 'none'})")
        return idx

    def resolve_ns(self, name: str) -> Tuple[int, int]:
        """Atomically resolve ``name`` to ``(slot, namespace)`` under
        ONE lock acquisition — a concurrent evict between a resolve()
        and an ns_of() would otherwise surface as a raw KeyError
        instead of the ValueError every other adapter failure maps
        to."""
        with self._lock:
            idx = self._by_name.get(name)
            if idx is None:
                raise ValueError(
                    f"unknown adapter {name!r} (loaded: "
                    f"{sorted(self._by_name) or 'none'})")
            return idx, self._ns[idx]

    def ns_of(self, idx: int) -> int:
        """Radix-cache namespace token for adapter slot ``idx`` (0 for
        the base model — namespace 0 IS today's unsalted chain, so
        adapterless serving keys byte-identically)."""
        if idx == 0:
            return 0
        with self._lock:
            return self._ns[idx]

    def arrays(self) -> Dict[str, Dict[str, Any]]:
        """The stacked device arrays, passed as a traced operand pytree
        to every adapter-aware compiled program."""
        return self._dev

    def load(self, name: str, deltas: Optional[Dict[str, Any]] = None,
             *, seed: Optional[int] = None, in_use=frozenset()) -> int:
        """Install (or replace) adapter ``name``; returns its slot
        index.  ``deltas``: :func:`load_adapter_file`-shaped dict; with
        ``deltas=None`` a deterministic random adapter is synthesized
        from ``seed`` (smoke mode).  Raises when the pool is full —
        evict first; capacity is the compiled-shape contract."""
        import jax.numpy as jnp

        if not _ADAPTER_NAME_RE.match(name or ""):
            raise ValueError(
                f"adapter name {name!r} must match [A-Za-z0-9_.-]{{1,64}}"
                " (it becomes a Prometheus label value and routing key)")
        if deltas is None:
            deltas = make_random_adapter(
                self.cfg, self.rank, seed if seed is not None
                else stable_name_seed(name))
        with self._lock:
            idx = self._by_name.get(name)
            if idx is not None and idx in in_use:
                # REPLACING a live adapter would mix old-delta KV with
                # new-delta decode math mid-stream for its lanes — the
                # same hazard evict guards against
                raise AdapterInUse(
                    f"adapter {name!r} is serving resident lanes; drain "
                    "them before replacing it")
            if idx is None:
                used = set(self._by_idx)
                idx = next((i for i in range(1, self.capacity + 1)
                            if i not in used), None)
                if idx is None:
                    raise ValueError(
                        f"adapter pool full ({self.capacity}); evict one "
                        "before loading another")
            # validate EVERY projection before the first device write:
            # a replace that raises mid-loop would leave a live adapter
            # half-overwritten — new wq with old wk/wv, a silent
            # corrupted mixture no oracle matches
            staged = {}
            for proj in LORA_PROJS:
                a = jnp.asarray(deltas[proj]["a"], jnp.float32)
                b = jnp.asarray(deltas[proj]["b"], jnp.float32)
                want_a = self._dev[proj]["a"].shape[2:]
                want_b = self._dev[proj]["b"].shape[2:]
                if a.shape[2] != self.rank:
                    raise ValueError(
                        f"adapter {name!r} rank {a.shape[2]} != registry "
                        f"rank {self.rank}")
                if (a.shape[0], a.shape[1:]) != (self.cfg.n_layers,
                                                 want_a) \
                        or (b.shape[0], b.shape[1:]) != (
                            self.cfg.n_layers, want_b):
                    raise ValueError(
                        f"adapter {name!r} {proj} shapes {a.shape}/"
                        f"{b.shape} do not fit [L, *{want_a}]/"
                        f"[L, *{want_b}]")
                staged[proj] = (a, b)
            for proj, (a, b) in staged.items():
                self._dev[proj]["a"] = \
                    self._dev[proj]["a"].at[:, idx].set(a)
                self._dev[proj]["b"] = \
                    self._dev[proj]["b"].at[:, idx].set(b)
            self._by_name[name] = idx
            self._by_idx[idx] = name
            self._gen += 1
            self._ns[idx] = self._gen
            return idx

    def evict(self, name: str, in_use=frozenset()) -> None:
        """Remove adapter ``name`` (its slot zeroes and becomes
        loadable).  ``in_use``: adapter idxs with resident/parked lanes
        — evicting one of those would serve garbage deltas to a live
        request, so it refuses."""
        import jax.numpy as jnp

        with self._lock:
            idx = self._by_name.get(name)
            if idx is None:
                raise ValueError(f"unknown adapter {name!r}")
            if idx in in_use:
                raise AdapterInUse(
                    f"adapter {name!r} is serving resident lanes; drain "
                    "them before evicting")
            for proj in LORA_PROJS:
                self._dev[proj]["a"] = \
                    self._dev[proj]["a"].at[:, idx].set(0.0)
                self._dev[proj]["b"] = \
                    self._dev[proj]["b"].at[:, idx].set(0.0)
            del self._by_name[name]
            del self._by_idx[idx]
            self._ns.pop(idx, None)

    @classmethod
    def from_env(cls, cfg) -> Optional["AdapterRegistry"]:
        """Build from ``SERVE_ADAPTERS`` (comma list of ``name``,
        ``name:path.npz`` or ``name:seed:<int>`` entries;
        ``SERVE_ADAPTER_RANK``/``SERVE_MAX_ADAPTERS`` size the pool).
        Unset/empty -> None: the ring stays byte-identical to the
        adapterless build."""
        import os

        raw = os.environ.get("SERVE_ADAPTERS", "").strip()
        if not raw:
            return None
        rank = int(os.environ.get("SERVE_ADAPTER_RANK", "8"))
        cap = int(os.environ.get("SERVE_MAX_ADAPTERS", "8"))
        reg = cls(cfg, capacity=cap, rank=rank)
        for entry in raw.split(","):
            entry = entry.strip()
            if not entry:
                continue
            name, _, src = entry.partition(":")
            if not src:
                reg.load(name)
            elif src.startswith("seed:"):
                reg.load(name, seed=int(src[len("seed:"):]))
            else:
                reg.load(name, load_adapter_file(cfg, src, rank))
        return reg


def lora_qkv(h, adp_l, aid, q, k, v, dtype):
    """THE shared adapter-delta rule, applied at every q/k/v projection
    site (decode._qkv and decode._cached_layer, through _qkv_proj,
    and through them every admission insert and the resident step), so
    prefill KV and decode KV can never be computed under different
    adapter math.

    ``h`` [B, T, D] is the post-norm activation the base projections
    consumed; ``adp_l`` is ONE layer's stacked arrays (the [L, ...]
    stacks ride the layer scan as xs and arrive here layer-sliced);
    ``aid`` [B] int32 gathers each lane's (A, B) pair — the batched
    gather + adapter matmul that lets a MIXED-adapter batch run in one
    compiled program.  f32 compute, cast to the ring dtype at the add;
    adapter slot 0 is all-zero, so an aid-0 lane's delta is an exact
    zero and its stream is bit-identical to the adapterless ring."""
    import jax.numpy as jnp

    hf = h.astype(jnp.float32)
    out = []
    for proj, base in zip(LORA_PROJS, (q, k, v)):
        a = jnp.take(adp_l[proj]["a"], aid, axis=0)     # [B, D, r]
        b = jnp.take(adp_l[proj]["b"], aid, axis=0)     # [B, r, O]
        t = jnp.einsum("btd,bdr->btr", hf, a)
        delta = jnp.einsum("btr,bro->bto", t, b)
        out.append(base + delta.astype(dtype))
    return tuple(out)
