"""Paged KV cache + radix prefix reuse for the serving ring.

The contiguous ring (infer/decode.py ``init_ring_cache``) allocates
one contiguous ``[L, slots, H_kv, max_len, D]`` KV region per lane and
re-prefills every prompt from scratch: every resident lane pays
worst-case ``max_len`` HBM whether it holds 40 tokens or 2000, and a
fleet of requests sharing a 2k system prompt pays the same prefill over
and over (BENCH_r05: TTFT ~279 ms at prompt 128; decode throughput
2801 -> 1606 tok/s as cache_len grows 128 -> 2240).  This module is the
vLLM/SGLang answer (PagedAttention, Kwon et al. SOSP'23; RadixAttention,
Zheng et al. 2024) in this codebase's TPU-native terms:

- **Block pool** ``[L, num_blocks, H_kv, block_size, D]`` plus per-lane
  block tables ``[slots, max_blocks_per_lane]`` int32: lane KV is a
  list of pool blocks, allocated on demand as the lane's ``pos``
  crosses a block boundary and returned to a free list when the lane
  retires.  Pool block 0 is a reserved TRASH block — freed lanes and
  pad rows write there, so an in-flight pipelined chunk can never
  corrupt a block that was re-allocated under it.
- **Radix prefix cache** (host side): completed-prefill FULL blocks are
  keyed by a rolling hash chain of their token prefix.  A new request
  that hits a cached prefix maps those blocks READ-ONLY into its table
  (refcounted) and prefills only the suffix — a shared system prompt
  costs one prefill ever.  A partially-filled tail that matches the
  prefix of a cached block maps that block too (zero prefill beyond the
  mandatory last-token forward) and is **copied-on-write** before the
  lane's first write lands in it.
- **Kernel/fallback split**: on TPU the pallas decode kernel steps over
  a list of the lanes' live blocks, read from the block table once a
  tick (ops/decode_attention.py ``paged_decode_attention`` — blocks
  stream straight from their pool rows); the XLA einsum
  path gathers the lane view with one ``take`` per layer
  (:func:`_gather_lane_view`) — the copy the kernel exists to avoid,
  kept as the CPU/odd-shape fallback.
- **Exactness**: greedy token streams are bit-identical to the
  contiguous ring (the ``SERVE_PAGED=0`` fallback and parity oracle) —
  the gathered/paged view presents the same values at every attendable
  position and masked tail columns contribute exact zeros, the same
  invariant the contiguous ring's pad rows already rely on.  Pinned by
  tests/test_paged.py and the dryrun ``serve-paged`` line.

Mesh/TP: the pool shards over its kv-head axis exactly like the ring
cache (parallel/sharding.py kv_cache_sharding — the pool's axis 2);
tables and lengths replicate.

**Hierarchical cache (ISSUE 8)**: with ``host_cache_blocks > 0`` the
radix cache gains a HOST-RAM spill tier (:class:`HostCacheTier`,
SGLang-HiCache / CachedAttention style).  Eviction DEMOTES a
refcount-0 cached block — its exact device bytes (bf16 rows, or int8
codes + scales under SERVE_KV_QUANT=int8) fetched to pinned numpy —
instead of discarding it, keeping the radix node alive with a host
location (``_CacheEntry.block is None``).  Admission's radix walk then
classifies hits three ways: **HBM** (map read-only, as today),
**host** (reserve a device block at admission and upload the payload
via one batched donated promote jit — :func:`make_promote_blocks`,
whose bf16 path reuses the same ``scatter_prefill_blocks`` whole-block
writes the prefill path uses), or **cold** (prefill the suffix).
Demote/promote is a byte COPY, never a re-quantize, so a host hit is
bit-identical to an HBM hit; host RAM holds 10-100x more prefix blocks
than the pool at a transfer cost far below re-prefill.  The same
fetch/upload primitive backs :meth:`RingExecutor.spill_lane` /
``restore_lane`` — the lane-preemption building block ROADMAP items
4/5 consume.  ``host_cache_blocks=0`` (the default) leaves every code
path byte-identical to the pre-tier behavior.
"""

from __future__ import annotations

import functools
import heapq
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.models.llama import LlamaConfig
from paddle_operator_tpu.utils.radixkey import chain_key as _radix_chain_key

TRASH_BLOCK = 0

# SERVE_KV_QUANT: "none" keeps the bf16 pool (the default AND the
# parity oracle — byte-identical to pre-quantization behavior); "int8"
# stores pool blocks as int8 codes + one f32 scale per (layer, block,
# kv-head), with dequant fused into the paged kernels
# (ops/decode_attention.py _cells_kernel) / the gather view.
# The win is CAPACITY, not kernel latency: ~2x resident lanes per HBM
# byte, with a bounded per-step regression (the decode_attention.py
# header has the v5e physics; bench.py measure_quantized_pool the
# measured trade).
KV_QUANT_MODES = ("none", "int8")


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One pool block (…, bs, D) -> (int8 codes, f32 absmax/127 scale
    over the trailing two axes — per-(…, kv-head) when called on
    [L, 1, H, bs, D] tiles).  An all-zero block gets scale 1.0 so the
    dequant never divides by zero; round-half-even + clip to ±127 keeps
    the quantize→dequant→quantize roundtrip BIT-EXACT (the max element
    maps to ±127, so the recomputed scale is identical — pinned by
    tests/test_kvquant.py)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.round(xf / scale[..., None, None]), -127, 127)
    return codes.astype(jnp.int8), scale


def dequantize_kv(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """codes (…, bs, D) x scale (…) -> values in ``dtype``."""
    return (codes.astype(jnp.float32)
            * scale[..., None, None].astype(jnp.float32)).astype(dtype)


class NoFreeBlocks(RuntimeError):
    """The pool has no free block and no reclaimable (refcount-0)
    cached block — admission/growth must fail loudly rather than
    corrupt a mapped block."""


# ---------------------------------------------------------------------------
# Host side: block allocator + radix prefix cache
# ---------------------------------------------------------------------------


class _CacheEntry:
    __slots__ = ("key", "block", "chunk", "parent", "freed_at", "ns",
                 "stored")

    def __init__(self, key, block, chunk, parent, ns=0):
        self.key = key
        # device pool block id, or None while the entry's content lives
        # in the host tier (demoted — the radix node stays alive and a
        # later hit promotes it back into a fresh device block)
        self.block: Optional[int] = block
        self.chunk = chunk        # the bs tokens this block's KV encodes
        self.parent = parent      # chain key of the preceding block
        self.freed_at: Optional[int] = None   # LRU clock at refcount 0
        # radix namespace (0 = base model): the durable store abstains
        # for adapter namespaces (their chain salts are per-load
        # per-replica), so the spill hook needs to know
        self.ns = ns
        # durable-store residency (ISSUE 17): True while the entry's
        # bytes live ONLY in the KV store — no device block, no host
        # payload.  The radix walk treats it as a miss (it cannot be
        # served locally) but the node survives so a store fetch can
        # re-fill it through import_host_blocks.
        self.stored = False


def host_block_bytes(cfg: LlamaConfig, block_size: int,
                     quant: str = "none") -> int:
    """Host bytes one demoted block costs in the spill tier: K + V rows
    ([L, H_kv, bs, D] each — bf16 2 bytes/elem, or int8 codes plus the
    per-(layer, kv-head) f32 scale planes).  serve.py divides
    ``SERVE_HOST_CACHE_MB`` by this to size ``host_cache_blocks``."""
    rows = cfg.n_layers * cfg.n_kv_heads * block_size * cfg.head_dim
    if quant == "int8":
        return 2 * rows + 2 * cfg.n_layers * cfg.n_kv_heads * 4
    return 2 * rows * 2


class HostCacheTier:
    """The bounded host-RAM ring behind the radix cache: demoted block
    payloads (numpy dicts — ``k``/``v`` rows, plus ``ks``/``vs`` scale
    rows under int8), keyed by the entry's chain key, LRU within the
    tier.  ``put`` on a full tier drops the oldest payloads and returns
    their keys so the manager can retire the orphaned radix nodes; a
    promote ``pop`` moves the payload back out (demote/promote is a
    move, never a copy-with-two-owners — one canonical location per
    block keeps the accounting exact)."""

    def __init__(self, capacity: int) -> None:
        if capacity < 1:
            raise ValueError(f"host tier capacity must be >= 1 "
                             f"(got {capacity}); use host_cache_blocks=0 "
                             "to disable the tier")
        self.capacity = int(capacity)
        self._data: "Dict[Any, Dict[str, Any]]" = {}   # insertion = LRU age
        self.stats = {"demoted": 0, "promoted": 0, "overflow_drops": 0}
        # durable-store spill hook (ISSUE 17): called with
        # ``(key, payload)`` BEFORE an overflow drop deletes the
        # payload — the manager's last chance to persist bytes that
        # would otherwise be silently discarded.  None = pre-store
        # behavior, byte-identical.
        self.on_spill: Optional[Callable[[Any, Dict[str, Any]], None]] = None

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        return key in self._data

    def keys(self):
        return self._data.keys()

    def put(self, key, payload: Dict[str, Any],
            pinned: frozenset = frozenset()) -> List[Any]:
        """Store one demoted payload; returns the keys LRU-dropped to
        make room (the caller must drop their radix entries).

        ``pinned``: keys that must NOT be overflow-dropped — the
        current admission's host-hit chain (an eviction-triggered
        demotion mid-admit could otherwise drop the very payload the
        promotion is about to pop).  With every resident key pinned the
        tier temporarily exceeds its bound by at most the chain length;
        the manager trims back once the admission releases its pins."""
        dropped: List[Any] = []
        self._data.pop(key, None)
        while len(self._data) >= self.capacity:
            old = next((k for k in self._data if k not in pinned), None)
            if old is None:
                break                   # all pinned: exceed, trim later
            if self.on_spill is not None:
                self.on_spill(old, self._data[old])
            del self._data[old]
            dropped.append(old)
            self.stats["overflow_drops"] += 1
        self._data[key] = payload
        self.stats["demoted"] += 1
        return dropped

    def trim(self) -> List[Any]:
        """Drop oldest payloads until back within the bound (after an
        admission that pinned its chain released the pins)."""
        dropped: List[Any] = []
        while len(self._data) > self.capacity:
            old = next(iter(self._data))
            if self.on_spill is not None:
                self.on_spill(old, self._data[old])
            del self._data[old]
            dropped.append(old)
            self.stats["overflow_drops"] += 1
        return dropped

    def pop(self, key) -> Dict[str, Any]:
        """Remove + return a payload for promotion back to the pool."""
        payload = self._data.pop(key)
        self.stats["promoted"] += 1
        return payload

    def peek(self, key) -> Optional[Dict[str, Any]]:
        """Read a payload WITHOUT removing it — the peer prefix-fetch
        export (ISSUE 12): cross-replica fetch is a COPY (the wire
        serializer np.asarray's the values), so the one-canonical-
        location rule above still holds within this replica."""
        return self._data.get(key)

    def drop(self, key) -> None:
        self._data.pop(key, None)


class PagedCacheManager:
    """Host-side truth for the pool: free list, per-block lane
    refcounts, the per-slot block tables (numpy mirror shipped to the
    device with every dispatch), and the radix prefix cache.

    Block states partition the allocatable ids (1..num_blocks; 0 is the
    trash block):

    - **free**: on the free list;
    - **mapped**: referenced by >= 1 lane table (``ref[b] > 0``) —
      possibly ALSO cached (a published prompt block still in use);
    - **cached**: in the radix cache at refcount 0 — reclaimable, LRU
      by refcount-0 age when the free list runs dry.

    ``check_invariant()`` asserts the partition exactly
    (free + mapped + cached-only == num_blocks, refcounts == table
    occurrences) — the leak/double-free gate the tests run across
    admit/retire/cancel/CoW paths.
    """

    def __init__(self, slots: int, max_len: int, block_size: int,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 host_cache_blocks: int = 0) -> None:
        alloc = D.cache_alloc_len(max_len)
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1 (got {block_size})")
        self.bs = int(block_size)
        self.max_blocks = -(-alloc // self.bs)          # per-lane table width
        self.view_len = self.max_blocks * self.bs       # gathered lane view
        # default pool = contiguous-ring HBM parity: every lane can still
        # reach max_len; the paging win is that lanes that DON'T leave
        # the rest free (for more lanes, or for the prefix cache)
        self.num_blocks = int(num_blocks or slots * self.max_blocks)
        if self.num_blocks < self.max_blocks:
            raise ValueError(
                f"num_blocks ({self.num_blocks}) smaller than one lane's "
                f"worst case ({self.max_blocks} blocks)")
        self.total = self.num_blocks + 1                # + trash block 0
        self.free: List[int] = list(range(self.total - 1, 0, -1))
        self.ref = np.zeros((self.total,), np.int64)
        self.table = np.zeros((slots, self.max_blocks), np.int32)
        self.mapped_count = [0] * slots
        self.prefix_cache = bool(prefix_cache)
        self.entries: Dict[Any, _CacheEntry] = {}       # chain key -> entry
        self.by_block: Dict[int, Any] = {}              # block -> chain key
        self.children: Dict[Any, set] = {}              # parent key -> keys
        self._tick = 0
        # age-ordered refcount-0 index (the satellite O(log n) eviction
        # fix): a lazy-deletion min-heap of (freed_at, seq, key) pushed
        # at every ref -> 0 transition; pop-time validation discards
        # items whose entry was since re-mapped, dropped, or demoted.
        # Selection semantics are IDENTICAL to the old full scan
        # (:meth:`_select_victim_scan`, kept as the regression oracle).
        self._ref0_heap: List[Tuple[int, int, Any]] = []
        self._heap_seq = 0
        # host spill tier (ISSUE 8): demoted refcount-0 cached blocks
        # keep their radix node alive with their bytes in host RAM; the
        # executor wires ``demote_fetch`` (block id -> numpy payload)
        # after construction.  0 blocks = tier off = pre-tier behavior.
        self.host = (HostCacheTier(host_cache_blocks)
                     if host_cache_blocks else None)
        self.demote_fetch: Optional[Callable[[int], Dict[str, Any]]] = None
        # durable prefix store (ISSUE 17): the persistent tier below
        # the host tier — wired via attach_store().  None (the
        # default) keeps every path byte-identical to pre-store
        # behavior, including the silent overflow discard.
        self.store = None
        # the in-flight admission's host-hit chain keys: shielded from
        # tier overflow drops while the admit that will pop them runs
        # (HostCacheTier.put pinned=)
        self._pinned_host_keys: frozenset = frozenset()
        # promotions ALLOCATED by the current admit() and not yet
        # uploaded: [(dst_block, payload, key)] — the scheduler drains
        # them (take_promotions) into ONE batched donated device upload
        # BEFORE the CoW copies / admission insert it dispatches next
        self._pending_promotes: List[Tuple[int, Dict[str, Any], Any]] = []
        # chaos hook (infer/chaos.py pool_oom): the next N allocations
        # raise NoFreeBlocks regardless of free-list state, so the
        # starvation/eviction paths are exercisable deterministically
        # without actually draining the pool
        self.chaos_fail_allocs = 0
        self.stats = {
            "prefix_lookup_tokens": 0, "prefix_hit_tokens": 0,
            "prefix_lookups": 0, "prefix_full_hits": 0,
            "cow_copies": 0, "cache_evictions": 0, "blocks_hwm": 0,
            # host-tier accounting: blocks demoted to / promoted from
            # host RAM, and the prefix-hit tokens served out of host
            # payloads (the hostHitRate numerator)
            "host_demotions": 0, "host_promotions": 0,
            "host_hit_tokens": 0,
            # durable store (ISSUE 17): payloads offered to the store
            # writer on host-tier overflow (the previously-silent
            # discards), and store-fetched blocks re-filled into
            # store-resident radix nodes
            "store_spills": 0, "store_refills": 0,
            # fleet-level KV (ISSUE 12): demoted blocks imported from a
            # PEER replica's host tier (they promote through the normal
            # host-hit path on the next admission)
            "peer_blocks_imported": 0,
        }

    # -- allocation --------------------------------------------------------

    def blocks_free(self) -> int:
        return len(self.free)

    def decode_cell_counts(self, lane_pos, active) -> Tuple[int, int]:
        """``(live, grid)`` of one decode step's kernel call a layer:
        the (lane, block) cells its work list holds
        (ops/decode_attention.py ``decode_cells``: an active lane at
        position p attends p + 1 rows, ``ceil((p + 1) / block)`` cells;
        every other lane keeps one) against the ``lanes x max_blocks``
        rectangle the grid used to step through."""
        live = sum(-(-(int(p) + 1) // self.bs) if i in active else 1
                   for i, p in enumerate(lane_pos))
        return live, len(lane_pos) * self.max_blocks

    def blocks_cached(self) -> int:
        """DEVICE-resident cached blocks currently reclaimable
        (refcount 0); host-demoted entries hold no pool block."""
        return sum(1 for e in self.entries.values()
                   if e.block is not None and self.ref[e.block] == 0)

    def host_blocks(self) -> int:
        """Blocks currently resident in the host spill tier."""
        return len(self.host) if self.host is not None else 0

    def host_hit_rate(self) -> float:
        """Share of looked-up prefix tokens served from HOST payloads
        (the promote path) — the ``hostHitRate`` status key."""
        lk = self.stats["prefix_lookup_tokens"]
        return (round(self.stats["host_hit_tokens"] / lk, 4)
                if lk else 0.0)

    def _alloc_one(self) -> int:
        if self.chaos_fail_allocs > 0:
            self.chaos_fail_allocs -= 1
            raise NoFreeBlocks("chaos: injected pool OOM")
        if not self.free:
            self._evict_lru()
        blk = self.free.pop()
        used = self.num_blocks - len(self.free)
        self.stats["blocks_hwm"] = max(self.stats["blocks_hwm"], used)
        return blk

    def _promoting_blocks(self) -> set:
        """Blocks reserved by the CURRENT admission's promotions whose
        uploads have not dispatched yet.  They must never be eviction
        victims: a CoW in the same admit can drop such a block to
        refcount 0, and demoting it would fetch device bytes the
        pending upload has not written (garbage host payload) while the
        upload later scatters into whoever re-allocated the block."""
        return {dst for dst, _, _ in self._pending_promotes}

    def _select_victim_scan(self) -> Optional[_CacheEntry]:
        """The ORIGINAL O(n·children) victim scan, kept verbatim as the
        regression oracle for :meth:`_select_victim`: prefer leaves (no
        children — evicting an inner node only strands its subtree for
        later aging), oldest refcount-0 age among them."""
        promoting = self._promoting_blocks()
        victims = [e for e in self.entries.values()
                   if e.block is not None and self.ref[e.block] == 0
                   and e.block not in promoting]
        if not victims:
            return None
        leaves = [e for e in victims
                  if not self.children.get(e.key)]
        pool = leaves or victims
        return min(pool, key=lambda e: (e.freed_at
                                        if e.freed_at is not None else 0))

    def _heap_push(self, e: _CacheEntry) -> None:
        self._heap_seq += 1
        heapq.heappush(self._ref0_heap,
                       (e.freed_at if e.freed_at is not None else 0,
                        self._heap_seq, e.key))

    def _select_victim(self) -> Optional[_CacheEntry]:
        """Heap-backed victim selection, O(log n) amortized: pop the
        refcount-0 index in age order, discarding stale items (entry
        re-mapped, dropped, or demoted since push — ``freed_at`` is the
        version stamp) and setting valid NON-leaves aside; the first
        valid leaf wins (it is the min-age leaf, since the heap orders
        ALL ref-0 entries by age).  A treeful of inner nodes with no
        leaf at all falls back to the oldest set-aside entry — exactly
        the scan's semantics, pinned by the victim-parity regression
        test."""
        promoting = self._promoting_blocks()
        stash: List[Tuple[int, int, Any]] = []
        defer: List[Tuple[int, int, Any]] = []
        victim: Optional[_CacheEntry] = None
        while self._ref0_heap:
            fa, seq, key = heapq.heappop(self._ref0_heap)
            e = self.entries.get(key)
            if (e is None or e.block is None
                    or self.ref[e.block] != 0
                    or (e.freed_at if e.freed_at is not None else 0) != fa):
                continue                     # stale: lazily deleted
            if e.block in promoting:
                defer.append((fa, seq, key))  # NOT selectable this round
                continue
            if self.children.get(key):
                stash.append((fa, seq, key))  # valid, but not a leaf
                continue
            victim = e
            break
        if victim is None and stash:
            fa, seq, key = stash.pop(0)       # oldest valid non-leaf
            victim = self.entries[key]
        for item in stash:                    # survivors stay indexed
            heapq.heappush(self._ref0_heap, item)
        for item in defer:                    # evictable once uploaded
            heapq.heappush(self._ref0_heap, item)
        return victim

    def _evict_lru(self) -> None:
        """Reclaim ONE cached refcount-0 block.  With the host tier
        enabled the victim DEMOTES — its exact device bytes move to
        host RAM and the radix node stays alive at a host location
        (``block = None``), so a later admission promotes it back
        instead of re-prefilling; without the tier (the default) the
        entry is discarded exactly as before."""
        victim = self._select_victim()
        if victim is None:
            raise NoFreeBlocks(
                f"all {self.num_blocks} pool blocks are lane-mapped; "
                "grow num_blocks or retire lanes first")
        blk = victim.block
        if self.host is not None and self.demote_fetch is not None:
            payload = self.demote_fetch(blk)
            self.by_block.pop(blk, None)
            victim.block = None
            for key in self.host.put(victim.key, payload,
                                     pinned=self._pinned_host_keys):
                self._drop_host_entry(key)
            self.stats["host_demotions"] += 1
        else:
            self._drop_entry(victim)
        self.free.append(blk)
        self.stats["cache_evictions"] += 1

    def _drop_host_entry(self, key) -> None:
        """A host-tier payload aged out (LRU overflow).  Without a
        durable store: retire its radix node — the prefix is now truly
        cold again (same unlink as a device drop; ``by_block.pop(None)``
        is a no-op for host entries, whose keys there are block ints).
        With the store attached (ISSUE 17) and a base-namespace entry,
        the payload was just offered to the store writer (the tier's
        ``on_spill`` hook fires before the delete) — the node SURVIVES
        at ``block=None, stored=True`` so a later walk can re-probe the
        store instead of re-prefilling."""
        e = self.entries.get(key)
        if e is None:
            return
        if self.store is not None and not e.ns:
            e.stored = True
            return
        self._drop_entry(e)

    def attach_store(self, store) -> None:
        """Wire the durable prefix store (infer/kvstore.KVBlockStore)
        below the host tier: overflow drops persist instead of
        discarding, and their radix nodes survive store-resident.
        Requires the host tier (there is nothing to spill without
        it)."""
        if self.host is None:
            raise ValueError("KV store requires the host cache tier "
                             "(host_cache_blocks > 0)")
        self.store = store
        self.host.on_spill = self._spill_to_store

    def _spill_to_store(self, key, payload: Dict[str, Any]) -> None:
        """HostCacheTier overflow hook: offer the about-to-be-dropped
        payload to the store's background writer (bounded drop-oldest
        queue — never blocks the ring thread).  Adapter namespaces
        abstain: their chain salts are per-load per-replica, so a
        persisted entry could never be re-keyed."""
        e = self.entries.get(key)
        if e is None or e.ns or self.store is None:
            return
        self.store.offer(key, e.chunk, payload, ns=0)
        self.stats["store_spills"] += 1

    def _servable(self, e: _CacheEntry) -> bool:
        """Can this radix node serve a hit RIGHT NOW — device-resident,
        or host-resident with its payload actually in the tier?  A
        store-resident node (``stored=True``, payload on disk only)
        cannot: admit would have nothing to promote.  With the store
        off every ``block=None`` entry is in the tier by the
        demoted==host-keys invariant, so this is byte-identical to the
        pre-store walk."""
        if e.block is not None:
            return True
        return self.host is not None and e.key in self.host

    def _drop_entry(self, e: _CacheEntry) -> None:
        del self.entries[e.key]
        self.by_block.pop(e.block, None)
        kids = self.children.get(e.parent)
        if kids is not None:
            kids.discard(e.key)
            if not kids:
                del self.children[e.parent]

    def _release_block(self, blk: int) -> None:
        """One lane unmaps ``blk``: decref; at 0 it either becomes a
        reclaimable cached block (stamped with its LRU age) or goes
        straight back to the free list."""
        if blk == TRASH_BLOCK:
            return
        if self.ref[blk] <= 0:
            raise AssertionError(f"double free of pool block {blk}")
        self.ref[blk] -= 1
        if self.ref[blk] == 0:
            key = self.by_block.get(blk)
            if key is not None:
                self._tick += 1
                e = self.entries[key]
                e.freed_at = self._tick
                self._heap_push(e)      # enters the ref-0 age index
            else:
                self.free.append(blk)

    # -- radix cache -------------------------------------------------------

    @staticmethod
    def _chain_key(parent, chunk: Tuple[int, ...]):
        """Rolling key for one full block: hash-chained on the parent
        key so equal chunks under different prefixes never collide; the
        stored entry keeps the raw chunk, so a (vanishingly unlikely)
        hash collision is caught by the equality check in lookup.

        The definition lives in utils/radixkey.py (jax-free) because
        the fleet router keys its consistent-hash affinity on the SAME
        chain — one function, so router placement and replica radix
        hits cannot drift apart."""
        return _radix_chain_key(parent, chunk)

    @staticmethod
    def _root_key(ns: int):
        """Chain root for namespace ``ns`` (many-adapter serving,
        ISSUE 10): 0 is the unsalted legacy chain — byte-identical
        keying for base-model traffic — while a non-zero namespace
        (AdapterRegistry.ns_of, a fresh token per adapter LOAD) starts
        the chain at a salted key.  An adapter changes wk/wv, so its
        prefix KV is a different tensor than the base model's for the
        SAME tokens; namespacing makes cross-adapter hits impossible by
        construction, including after an evict+reload reuses a slot.
        Ints only (no str) so the value stays deterministic across
        processes, like the rest of utils/radixkey.py."""
        if not ns:
            return None
        return _radix_chain_key(0x5A17ED, (int(ns),))

    def _lookup(self, tokens: Tuple[int, ...], ns: int = 0):
        """Walk the cached chain: full-block hits, then at most one
        partial-tail hit (a cached child block whose chunk STARTS with
        the remaining < bs tokens — mappable read-only, CoW'd before
        the lane's first write into it).  Returns
        (entries, full_hit_tokens, used_partial) — each entry either
        DEVICE-resident (``block`` set: map read-only, as always) or
        HOST-resident (``block is None``: admit promotes it into a
        fresh device block before mapping)."""
        bs = self.bs
        hits: List[_CacheEntry] = []
        key = self._root_key(ns)
        j = 0
        n = len(tokens)
        while (j + 1) * bs <= n:
            chunk = tokens[j * bs:(j + 1) * bs]
            k2 = self._chain_key(key, chunk)
            e = self.entries.get(k2)
            if e is None or e.chunk != chunk or not self._servable(e):
                break
            hits.append(e)
            key = k2
            j += 1
        hit = j * bs
        partial = False
        rem = tokens[j * bs:]
        if rem and len(rem) < bs:
            for ck in self.children.get(key, ()):
                e = self.entries[ck]
                if e.chunk[:len(rem)] == rem and self._servable(e):
                    hits.append(e)
                    hit += len(rem)
                    partial = True
                    break
        return hits, hit, partial

    def take_promotions(self) -> List[Tuple[int, Dict[str, Any], Any]]:
        """Drain the promotions the last ``admit`` allocated:
        [(dst_block, host_payload, chain_key)].  The scheduler turns
        the batch into ONE donated device upload
        (RingExecutor.dispatch_promotions) dispatched BEFORE the CoW
        copies and the admission insert, so every later read on the
        stream observes the promoted bytes."""
        out, self._pending_promotes = self._pending_promotes, []
        return out

    # -- lane lifecycle ----------------------------------------------------

    def admit(self, slot: int, prompt,
              max_suffix: Optional[int] = None, ns: int = 0
              ) -> Tuple[int, List[Tuple[int, int]]]:
        """Map blocks for a new lane: radix hits read-only (refcounted),
        copy-on-write for any shared block the suffix/decode writes will
        land in, fresh blocks for the rest of the prompt.  Returns
        ``(hit_len, cow)`` — the usable prefix length (the suffix
        ``prompt[hit_len:]`` still needs a forward; always >= 1 token,
        since the first sampled token needs the last prompt position's
        logits) and the [(src, dst)] block copies the caller must run
        BEFORE the admission dispatch.

        ``max_suffix``: a hit whose remaining suffix exceeds it is NOT
        taken (fresh blocks throughout, hit_len 0) — the caller's
        suffix forward may be worse than a cold prefill past some
        width, and declining the hit up front means cached blocks are
        never mapped into a lane that will scatter over them."""
        tokens = tuple(int(t) for t in prompt)
        n = len(tokens)
        bs = self.bs
        if self.mapped_count[slot]:
            raise AssertionError(f"slot {slot} still holds blocks")
        if self.prefix_cache:
            hit_entries, hit_full, _partial = self._lookup(tokens, ns)
            self.stats["prefix_lookups"] += 1
            self.stats["prefix_lookup_tokens"] += n
            if (max_suffix is not None
                    and n - min(hit_full, n - 1) > max_suffix):
                hit_entries, hit_full = [], 0
        else:
            hit_entries, hit_full = [], 0
        hit_len = min(hit_full, n - 1)
        self.stats["prefix_hit_tokens"] += hit_len
        if hit_len and hit_len == n - 1 and hit_full >= n:
            self.stats["prefix_full_hits"] += 1

        row = self.table[slot]
        host_tokens_this_admit = 0
        # pin this admission's WHOLE hit chain: a demotion fired by one
        # of our own allocations must never overflow-drop a payload we
        # are about to pop (the tier may exceed its bound by the chain
        # length until the finally trims it back).  Device-resident hit
        # entries pin too — an entry not yet mapped by this loop is
        # refcount-0 and can itself be demoted mid-admit, at which
        # point its turn takes the promote branch and pops its payload.
        self._pinned_host_keys = frozenset(e.key for e in hit_entries)
        try:
            for j, e in enumerate(hit_entries):
                if e.block is None:
                    # HOST hit: reserve a device block NOW (so the
                    # whole admission either fits or fails up front)
                    # and queue the byte-exact upload — the scheduler
                    # dispatches the batch before the insert.  The
                    # entry re-anchors device-side (promote-on-hit):
                    # later admissions hit it in HBM again.
                    dst = self._alloc_one()
                    payload = self.host.pop(e.key)
                    e.block = dst
                    self.by_block[dst] = e.key
                    self._pending_promotes.append((dst, payload, e.key))
                    self.stats["host_promotions"] += 1
                    tok_inc = min(bs, max(0, hit_len - j * bs))
                    self.stats["host_hit_tokens"] += tok_inc
                    host_tokens_this_admit += tok_inc
                blk = e.block
                row[j] = blk
                self.ref[blk] += 1
                self.mapped_count[slot] = j + 1
            # CoW: every shared block at/after the first written block
            # (index hit_len // bs) gets a private copy — by
            # construction that is at most the last hit block
            cow: List[Tuple[int, int]] = []
            first_write_blk = hit_len // bs
            for j in range(first_write_blk, len(hit_entries)):
                src = int(row[j])
                dst = self._alloc_one()
                self.ref[dst] += 1
                self._release_block(src)
                row[j] = dst
                cow.append((src, dst))
                self.stats["cow_copies"] += 1
            # fresh blocks for the rest of the prompt
            need = -(-n // bs)
            while self.mapped_count[slot] < need:
                blk = self._alloc_one()
                self.ref[blk] += 1
                row[self.mapped_count[slot]] = blk
                self.mapped_count[slot] += 1
        except NoFreeBlocks:
            # roll back promotions this admit allocated: their uploads
            # never dispatched, so the re-anchored entries would map
            # GARBAGE device blocks as cached prefix — move each back
            # to the host tier (there is room: we just popped them) and
            # let retire() below free the reserved dst blocks
            for dst, payload, key in self._pending_promotes:
                e = self.entries.get(key)
                if e is not None:
                    for k2 in self.host.put(key, payload,
                                            pinned=self._pinned_host_keys):
                        self._drop_host_entry(k2)
                    e.block = None
                self.by_block.pop(dst, None)
                # a promoted block the CoW already released sits at
                # refcount 0 with no radix anchor left — retire() below
                # can't reach it (the lane maps its CoW copy instead),
                # so return it to the free list here or it leaks out of
                # the free/mapped/cached partition entirely
                if self.ref[dst] == 0 and dst not in self.free:
                    self.free.append(dst)
                self.stats["host_promotions"] -= 1
            self._pending_promotes = []
            # the host-served token accounting rolls back with them: a
            # failed admission served nothing, and hostHitRate must not
            # drift upward on NoFreeBlocks churn
            self.stats["host_hit_tokens"] -= host_tokens_this_admit
            self.retire(slot)
            raise
        finally:
            if self.host is not None:
                self._pinned_host_keys = frozenset()
                for key in self.host.trim():    # back within the bound
                    self._drop_host_entry(key)
        return hit_len, cow

    def publish(self, slot: int, prompt, ns: int = 0) -> None:
        """Register the lane's FULL prompt blocks in the radix cache
        (called once the admission prefill is dispatched — later
        readers are later dispatches on the same stream, so they
        observe the written blocks).  Blocks already cached under the
        same key are left alone (a racing lane prefilled the same
        prefix — its copy stays canonical)."""
        if not self.prefix_cache:
            return
        tokens = tuple(int(t) for t in prompt)
        bs = self.bs
        key = self._root_key(ns)
        for j in range(len(tokens) // bs):
            chunk = tokens[j * bs:(j + 1) * bs]
            k2 = self._chain_key(key, chunk)
            e = self.entries.get(k2)
            if e is None:
                blk = int(self.table[slot, j])
                if blk != TRASH_BLOCK and blk not in self.by_block:
                    self.entries[k2] = _CacheEntry(k2, blk, chunk, key,
                                                   ns=ns)
                    self.by_block[blk] = k2
                    self.children.setdefault(key, set()).add(k2)
            elif e.block is None and e.stored and e.chunk == chunk:
                # store-resident node whose prefix this lane just
                # re-prefilled: re-anchor it device-side (the lane's
                # block holds exactly this chunk's KV) — otherwise the
                # walk keeps breaking at the store-only node even
                # though the bytes were just computed
                blk = int(self.table[slot, j])
                if blk != TRASH_BLOCK and blk not in self.by_block:
                    e.block = blk
                    e.stored = False
                    self.by_block[blk] = k2
            key = k2

    def ensure(self, slot: int, pos_needed: int) -> None:
        """Grow the lane's table so blocks cover positions
        [0, pos_needed) — the on-demand allocation the decode loop runs
        before each dispatch as ``pos`` approaches a block boundary.
        Capped at the lane view; overshoot rows (pipelined chunks past
        the budget) self-route to the trash block / the lane's own last
        block and are discarded with the lane."""
        need = min(-(-int(pos_needed) // self.bs), self.max_blocks)
        row = self.table[slot]
        while self.mapped_count[slot] < need:
            blk = self._alloc_one()
            self.ref[blk] += 1
            row[self.mapped_count[slot]] = blk
            self.mapped_count[slot] += 1

    def retire(self, slot: int) -> None:
        """Lane done (eos/budget/cancel/error): unmap every block —
        published ones become reclaimable cache, private ones go back
        to the free list — and zero the table row so any in-flight
        pipelined chunk writes land in the trash block."""
        row = self.table[slot]
        for j in range(self.mapped_count[slot]):
            self._release_block(int(row[j]))
        row[:] = TRASH_BLOCK
        self.mapped_count[slot] = 0

    def scrub_host_chain(self, prompt, ns: int = 0) -> int:
        """Quarantine hygiene (ISSUE 8): drop every HOST-tier payload
        on ``prompt``'s radix chain.  Device-side the quarantine scrub
        can prove published blocks clean (the lane only ever writes
        private CoW'd copies), but a demoted payload is an opaque host
        byte blob that can no longer be re-verified against the pool —
        after a NaN quarantine the conservative move is to forget the
        lane's chain from the tier and let the prefix re-prefill.
        With the durable store attached (ISSUE 17) the same argument
        applies one tier down: every store copy along the chain is
        deleted and store-resident nodes are retired, never marked
        ``stored`` — a quarantined chain must not resurrect from disk.
        Returns the number of payloads dropped."""
        if self.host is None and self.store is None:
            return 0
        tokens = tuple(int(t) for t in prompt)
        key = self._root_key(ns)
        dropped = 0
        for j in range(len(tokens) // self.bs):
            chunk = tokens[j * self.bs:(j + 1) * self.bs]
            key = self._chain_key(key, chunk)
            if self.store is not None and not ns:
                # the store may hold a copy of ANY chain block (it
                # persists overflow drops, device residency since is
                # irrelevant) — delete unconditionally along the chain
                self.store.delete(key, ns=0)
            e = self.entries.get(key)
            if e is None:
                continue    # gap in the chain: deeper entries may remain
            if e.block is None:
                if self.host is not None:
                    self.host.drop(key)
                self._drop_entry(e)
                dropped += 1
        return dropped

    # -- fleet-level KV: peer prefix export/import (ISSUE 12) --------------

    def host_evictions(self) -> int:
        """Cumulative dropped-oldest tier overflows — previously
        invisible (the ``tpujob_serve_host_cache_evictions_total``
        gauge)."""
        return (self.host.stats["overflow_drops"]
                if self.host is not None else 0)

    def export_host_chain(self, prompt, ns: int = 0):
        """The peer-fetch EXPORT: walk ``prompt``'s radix chain and
        collect every HOST-resident (demoted) full block along it —
        ``(chunks, block_idx, payloads)`` where ``chunks`` lists EVERY
        full block's tokens from the chain start (the importer needs
        them to recompute parent keys) and ``block_idx``/``payloads``
        the demoted subset that actually travels.  Device-resident
        blocks are skipped but the walk continues: the importer may
        already hold the head locally, in which case a host-resident
        tail alone completes its chain.  Only demoted payloads ship —
        device blocks would need a ring-thread fetch against buffers
        the resident step donates, and host bytes are already exactly
        what the importer's promote path uploads.

        Called from an HTTP handler thread while the ring thread
        mutates the radix — callers must treat any exception as
        "nothing to export" (the serve handler returns 204)."""
        if self.host is None:
            return [], [], []
        tokens = tuple(int(t) for t in prompt)
        bs = self.bs
        chunks = []
        block_idx = []
        payloads = []
        key = self._root_key(ns)
        for j in range(len(tokens) // bs):
            chunk = tokens[j * bs:(j + 1) * bs]
            key = self._chain_key(key, chunk)
            e = self.entries.get(key)
            if e is None or e.chunk != chunk:
                break               # chain cold from here on
            chunks.append(list(chunk))
            if e.block is None:
                payload = self.host.peek(key)
                if payload is not None:
                    block_idx.append(j)
                    payloads.append(payload)
        return chunks, block_idx, payloads

    def import_host_blocks(self, chunks, block_idx, payloads,
                           ns: int = 0) -> int:
        """The peer-fetch IMPORT (ring thread only): insert fetched
        demoted payloads into OUR host tier + radix, exactly as if this
        replica had demoted them — the next admission's radix walk
        host-hits them and promotes through the normal batched upload
        (byte-exact, the ISSUE 8 path).  Keys already present (device-
        or host-resident) are left alone; tier overflow drops the
        oldest as usual.  Returns the number of blocks imported."""
        if self.host is None or not self.prefix_cache:
            return 0
        bs = self.bs
        keys = []
        key = self._root_key(ns)
        for chunk in chunks:
            if len(chunk) != bs:
                return 0            # malformed: full blocks only
            key = self._chain_key(key, tuple(int(t) for t in chunk))
            keys.append(key)
        imported = 0
        for j, payload in zip(block_idx, payloads):
            if not 0 <= j < len(keys):
                continue
            existing = self.entries.get(keys[j])
            if existing is not None:
                # store-resident node (ISSUE 17): its bytes live only
                # on disk — REFILL the host tier so the next admission
                # host-hits it; any other resident entry is left alone
                if (existing.block is None and existing.stored
                        and existing.chunk == tuple(
                            int(t) for t in chunks[j])):
                    for dropped in self.host.put(
                            keys[j], payload,
                            pinned=self._pinned_host_keys):
                        self._drop_host_entry(dropped)
                    existing.stored = False
                    self.stats["store_refills"] += 1
                    imported += 1
                continue
            if j and keys[j - 1] not in self.entries:
                # _lookup walks the chain from the root and stops at
                # the first missing key: a block whose parent is
                # present neither locally nor in this import would be
                # UNREACHABLE — stored host bytes no admission could
                # ever hit.  (Earlier imported blocks are already in
                # self.entries, so contiguous imports chain through.)
                continue
            parent = keys[j - 1] if j else self._root_key(ns)
            chunk = tuple(int(t) for t in chunks[j])
            e = _CacheEntry(keys[j], None, chunk, parent, ns=ns)
            self.entries[keys[j]] = e
            self.children.setdefault(parent, set()).add(keys[j])
            for dropped in self.host.put(keys[j], payload,
                                         pinned=self._pinned_host_keys):
                self._drop_host_entry(dropped)
            imported += 1
        self.stats["peer_blocks_imported"] += imported
        return imported

    def device_table(self) -> jax.Array:
        return jnp.asarray(self.table)

    # -- accounting --------------------------------------------------------

    def hit_rate(self) -> float:
        lk = self.stats["prefix_lookup_tokens"]
        return round(self.stats["prefix_hit_tokens"] / lk, 4) if lk else 0.0

    def check_invariant(self) -> None:
        """free + mapped + cached-only == num_blocks, with refcounts
        exactly equal to table occurrences and no id in two states."""
        free = set(self.free)
        assert len(free) == len(self.free), "free list holds duplicates"
        assert TRASH_BLOCK not in free, "trash block leaked to free list"
        occurrences: Dict[int, int] = {}
        for row in self.table:
            for blk in row:
                if blk != TRASH_BLOCK:
                    occurrences[int(blk)] = occurrences.get(int(blk), 0) + 1
        for blk, cnt in occurrences.items():
            assert self.ref[blk] == cnt, \
                f"block {blk}: ref {self.ref[blk]} != {cnt} table uses"
            assert blk not in free, f"block {blk} mapped AND free"
        mapped = set(occurrences)
        for blk in range(1, self.total):
            if self.ref[blk] and blk not in mapped:
                raise AssertionError(f"block {blk} refcounted but unmapped")
        cached_only = {e.block for e in self.entries.values()
                       if e.block is not None and self.ref[e.block] == 0}
        assert not (cached_only & free), "cached block on the free list"
        assert len(free) + len(mapped) + len(cached_only) \
            == self.num_blocks, (
            f"pool partition broken: {len(free)} free + {len(mapped)} "
            f"mapped + {len(cached_only)} cached != {self.num_blocks}")
        # host-tier accounting (ISSUE 8): every demoted entry's payload
        # is in the tier, every tier payload has a live radix node, the
        # tier respects its bound, and nothing is promoting outside an
        # admission (take_promotions drains before the dispatch) — so
        # free + mapped + cached + promoting == num_blocks holds with
        # promoting == len(_pending_promotes) counted inside `mapped`
        # (promoted blocks are lane-refcounted the moment they are
        # reserved)
        # store-resident nodes (ISSUE 17) hold NO local payload: their
        # bytes are on disk only, so they are excluded from the
        # demoted==host-keys identity and must be disjoint from the
        # tier.  With the store off no entry can be stored, so the
        # original identity is checked unchanged.
        stored_keys = {e.key for e in self.entries.values()
                       if e.block is None and e.stored}
        if self.store is None:
            assert not stored_keys, \
                "store-resident entry without a KV store"
        demoted = {e.key for e in self.entries.values()
                   if e.block is None and not e.stored}
        if self.host is not None:
            host_keys = set(self.host.keys())
            assert demoted == host_keys, (
                f"host tier desync: {len(demoted)} demoted entries vs "
                f"{len(host_keys)} host payloads")
            assert not (stored_keys & host_keys), \
                "store-resident entry also holds a host payload"
            assert len(self.host) <= self.host.capacity, \
                "host tier exceeded its bound"
            promoting = {dst for dst, _, _ in self._pending_promotes}
            assert promoting <= mapped, \
                "in-flight promotion targets an unmapped block"
        else:
            assert not demoted, "demoted entry without a host tier"


# ---------------------------------------------------------------------------
# Device side: pool init, writes, gather view, forwards
# ---------------------------------------------------------------------------


def _alloc_pool_buf(cfg: LlamaConfig, shape, dtype, mesh,
                    head_axis: int) -> jax.Array:
    """A pool-side buffer of arbitrary rank/dtype sharded over its
    kv-head axis under a serving mesh (the generalization of
    decode.alloc_kv_buffer the int8 codes/scales/tails need — their
    ranks and dtypes differ from the bf16 pool's).  Like it, born on
    its shards, never staged whole on the first device."""
    sharding = None
    if (mesh is not None and D.mesh_tp(mesh) > 1
            and cfg.n_kv_heads % D.mesh_tp(mesh) == 0):
        from jax.sharding import NamedSharding

        from paddle_operator_tpu.parallel.sharding import logical_to_mesh

        spec = tuple("kv_heads" if i == head_axis else None
                     for i in range(len(shape)))
        sharding = NamedSharding(mesh, logical_to_mesh(spec, None, mesh))
    return jnp.zeros(shape, dtype, device=sharding)


def init_paged_cache(cfg: LlamaConfig, slots: int, total_blocks: int,
                     block_size: int, mesh=None,
                     quant: str = "none") -> Dict[str, jax.Array]:
    """The paged ring state: k/v pools [L, total_blocks, H_kv, bs, D]
    (kv-head-sharded under a serving mesh, like the ring cache) plus
    the per-lane fill position vector.  ``total_blocks`` INCLUDES the
    trash block (PagedCacheManager.total).

    ``quant="int8"`` splits each pool into int8 codes (same shape, half
    the bytes) + f32 scales ``ks``/``vs`` [L, total_blocks, H_kv] (one
    per block per kv head), and adds the bf16 staging tails ``kt``/
    ``vt`` [L, slots + 1, H_kv, bs, D]: lane b's WRITE block accumulates
    exact rows in tail row b and quantizes into the pool once, on block
    completion — so a block's scale is computed exactly once from its
    full contents, never re-derived per token.  Tail row ``slots`` is
    the TRASH tail: rows that must not land anywhere (prefill pads,
    inactive-lane ticks) redirect there, the per-lane analogue of pool
    block 0.  Everything shards over the kv-head axis."""
    if quant == "none":
        # the pool's buffers are the view's: K and V a head, or one
        # latent row for all heads (:class:`LatentPagedView`)
        shapes = view_class(cfg).pool_shapes(cfg, total_blocks, block_size)
        cache = {name: D.alloc_kv_buffer(cfg, shape, mesh)
                 for name, shape in shapes.items()}
        cache["pos"] = jnp.zeros((slots,), jnp.int32)
        if not isinstance(cfg, LlamaConfig):
            # infer/afmoe_serve.py: prefill assignments by expert since
            # the last decode dispatch read them out
            cache["moe_pf"] = jnp.zeros((cfg.n_experts,), jnp.int32)
        return cache
    shape = (cfg.n_layers, total_blocks, cfg.n_kv_heads, block_size,
             cfg.head_dim)
    if quant != "int8":
        raise ValueError(f"kv_quant {quant!r} not in {KV_QUANT_MODES}")
    scale_shape = (cfg.n_layers, total_blocks, cfg.n_kv_heads)
    tail_shape = (cfg.n_layers, slots + 1, cfg.n_kv_heads, block_size,
                  cfg.head_dim)
    return {
        "k": _alloc_pool_buf(cfg, shape, jnp.int8, mesh, 2),
        "v": _alloc_pool_buf(cfg, shape, jnp.int8, mesh, 2),
        "ks": _alloc_pool_buf(cfg, scale_shape, jnp.float32, mesh, 2),
        "vs": _alloc_pool_buf(cfg, scale_shape, jnp.float32, mesh, 2),
        "kt": _alloc_pool_buf(cfg, tail_shape, cfg.dtype, mesh, 2),
        "vt": _alloc_pool_buf(cfg, tail_shape, cfg.dtype, mesh, 2),
        "pos": jnp.zeros((slots,), jnp.int32),
    }


@jax.named_scope("cache_write")
def _write_token_paged(pool: jax.Array, kv: jax.Array, li: jax.Array,
                       table: jax.Array, pos: jax.Array,
                       block_size: int, transposed: bool = False
                       ) -> jax.Array:
    """[L, N, H, bs, D] pool <- [B, H, 1, D] new rows, lane b's row at
    pool block ``table[b, pos_b // bs]`` offset ``pos_b % bs``.  Static
    unroll over lanes for the same reason as decode._write_lane_stacked
    (a vmapped ragged update lowers to a carry-copying scatter).
    ``transposed``: the pool is [L, N, H, D, bs] and the rows come as
    columns [B, H, D, 1] (:class:`LatentPagedView`'s rotated keys)."""
    for lane in range(kv.shape[0]):
        blk = table[lane, pos[lane] // block_size]
        slab = kv[lane][None, None]
        at = pos[lane] % block_size
        pool = jax.lax.dynamic_update_slice(
            pool, slab,
            (li, blk, 0, 0, at) if transposed else (li, blk, 0, at, 0))
    return pool


@jax.named_scope("cache_write")
def _write_rows_paged(pool: jax.Array, kv: jax.Array, li: jax.Array,
                      table: jax.Array, pos: jax.Array, block_size: int,
                      limit: Optional[jax.Array] = None) -> jax.Array:
    """[L, N, H, bs, D] pool <- [B, H, T, D] rows at per-lane start
    positions ``pos`` — rows land in whatever pool block the table maps
    for their absolute position (a row span may straddle blocks; every
    row is placed independently).  Rows at/after ``limit`` (per-lane;
    suffix-prefill pads) are redirected to the trash block instead of
    being masked out — the unroll stays branch-free."""
    b, _, t, _ = kv.shape
    for lane in range(b):
        for j in range(t):
            p = pos[lane] + j
            blk = table[lane, p // block_size]
            if limit is not None:
                blk = jnp.where(p < limit[lane], blk, TRASH_BLOCK)
            pool = jax.lax.dynamic_update_slice(
                pool, kv[lane, :, j][None, None, :, None, :],
                (li, blk, 0, p % block_size, 0))
    return pool


@jax.named_scope("cache_write")
def _write_blocks_paged(pool: jax.Array, kv: jax.Array, li: jax.Array,
                        table: jax.Array, pos: jax.Array,
                        block_size: int,
                        limit: Optional[jax.Array] = None) -> jax.Array:
    """:func:`_write_rows_paged` for the BLOCK-ALIGNED case (the
    N-lane prefill engine's slice programs, ISSUE 14): ``pos`` is a
    block multiple and ``t`` a multiple of ``block_size`` — both
    guaranteed statically by the caller — so the slab lands as
    whole-block writes, O(lanes x blocks) dynamic_update_slice ops
    instead of the per-row unroll's O(lanes x rows).  At production
    slice widths the per-row trace is pathological to COMPILE (the
    ops sit inside the layer scan's body), not just slow to run.

    Padding follows :func:`ops.decode_attention.scatter_prefill_blocks`
    — the exactness-with-padding contract, block-granular: a block
    whose FIRST row is real writes whole (pad rows past ``limit`` land
    in the lane's real block, never attendable — masked in-slice,
    overwritten by decode before its reads); a block entirely past
    ``limit`` routes to the trash block."""
    b, _, t, _ = kv.shape
    for lane in range(b):
        for jb in range(t // block_size):
            p0 = pos[lane] + jb * block_size
            blk = table[lane, p0 // block_size]
            if limit is not None:
                blk = jnp.where(p0 < limit[lane], blk, TRASH_BLOCK)
            pool = jax.lax.dynamic_update_slice(
                pool,
                kv[lane, :, jb * block_size:(jb + 1) * block_size][
                    None, None],
                (li, blk, 0, 0, 0))
    return pool


@jax.named_scope("cache_write")
def _write_token_quant(pool: jax.Array, scales: jax.Array,
                       tail: jax.Array, kv: jax.Array, li: jax.Array,
                       table: jax.Array, pos: jax.Array,
                       rows_idx: jax.Array, block_size: int):
    """Quantized-pool single-token write: lane b's new row ([B, H, 1, D]
    at position ``pos[b]``) lands in its bf16 staging tail (row
    ``rows_idx[b]`` — the lane's own row, or the trash tail for
    inactive lanes) at offset ``pos % bs``; a row that COMPLETES its
    block quantizes the whole tail block into the pool — codes + one
    scale — at the lane's table entry.  The commit sits behind a
    ``lax.cond`` so the 1-in-``block_size`` completing tick is the ONLY
    one paying the tile quantize + pool write (an always-computed tile
    discarded into the trash block would cost ~block_size x the bf16
    path's single-row write traffic, per lane per layer per step).
    Retired/masked lanes stay safe: their zeroed table rows send even
    a "complete" commit to the trash block."""
    hkv, d2 = kv.shape[1], kv.shape[3]
    for lane in range(kv.shape[0]):
        row = rows_idx[lane]
        tail = jax.lax.dynamic_update_slice(
            tail, kv[lane][None, None],
            (li, row, 0, pos[lane] % block_size, 0))
        complete = (pos[lane] + 1) % block_size == 0
        dst = table[lane, pos[lane] // block_size]

        def _commit(ps, row=row, dst=dst, tail=tail):
            pool, scales = ps
            tile = jax.lax.dynamic_slice(
                tail, (li, row, 0, 0, 0), (1, 1, hkv, block_size, d2))
            codes, scale = quantize_kv(tile)
            return (jax.lax.dynamic_update_slice(pool, codes,
                                                 (li, dst, 0, 0, 0)),
                    jax.lax.dynamic_update_slice(scales, scale,
                                                 (li, dst, 0)))

        pool, scales = jax.lax.cond(complete, _commit, lambda ps: ps,
                                    (pool, scales))
    return pool, scales, tail


def _gather_lane_view_quant(pool: jax.Array, scales: jax.Array,
                            tail: jax.Array, table: jax.Array,
                            li: jax.Array, wb: jax.Array) -> jax.Array:
    """:func:`_gather_lane_view` for the INT8 pool: gather codes AND
    scales through the block tables, dequantize, then substitute lane
    b's bf16 staging tail for its write-frontier block ``wb[b]`` — the
    partial block's exact rows live in the tail, not the pool.  Columns
    past the fill are masked by the caller's attention mask exactly as
    in the bf16 view (stale tail rows are finite, so masked columns
    still contribute exact zeros)."""
    layer = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)
    sl = jax.lax.dynamic_index_in_dim(scales, li, 0, keepdims=False)
    tl = jax.lax.dynamic_index_in_dim(tail, li, 0, keepdims=False)
    b, m = table.shape
    _, h, bs, d = layer.shape
    v = jnp.take(layer, table.reshape(-1), axis=0)      # [B*M, H, bs, D]
    s = jnp.take(sl, table.reshape(-1), axis=0)         # [B*M, H]
    deq = v.astype(jnp.float32) * s[..., None, None]
    deq = deq.reshape(b, m, h, bs, d).transpose(0, 2, 1, 3, 4)
    deq = deq.reshape(b, h, m * bs, d)
    lt = tl[:b].astype(jnp.float32)                     # [B, H, bs, D]
    tiled = jnp.tile(lt, (1, 1, m, 1))                  # [B, H, m*bs, D]
    use_tail = (jnp.arange(m * bs) // bs)[None, :] == wb[:, None]
    out = jnp.where(use_tail[:, None, :, None], tiled, deq)
    return out.astype(tail.dtype)


def _gather_lane_view(pool: jax.Array, table: jax.Array,
                      li: jax.Array) -> jax.Array:
    """XLA ``take`` fallback view: pool layer ``li`` gathered through
    the block tables into the contiguous [B, H, M*bs, D] layout the
    einsum attention expects.  This is a materialized copy per layer —
    exactly what the paged kernel's table-driven index map avoids — and
    exists for the CPU / odd-shape / GSPMD-einsum paths."""
    layer = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)
    b, m = table.shape
    _, h, bs, d = layer.shape
    v = jnp.take(layer, table.reshape(-1), axis=0)      # [B*M, H, bs, D]
    v = v.reshape(b, m, h, bs, d).transpose(0, 2, 1, 3, 4)
    return v.reshape(b, h, m * bs, d)


@jax.named_scope("cache_write")
def _write_rows_quant(kc: jax.Array, vc: jax.Array, ks: jax.Array,
                      vs: jax.Array, kt: jax.Array, vt: jax.Array,
                      kh: jax.Array, vh: jax.Array, li: jax.Array,
                      table: jax.Array, pos: jax.Array,
                      limit: Optional[jax.Array],
                      lane_mask: Optional[jax.Array]):
    """Quantized-pool write of [B, H, T, D] new rows (``kh``/``vh``) at
    per-lane start positions ``pos``: each row accumulates EXACT in the
    lane's bf16 staging tail; a row completing its block quantizes the
    whole tail block into the int8 pool — codes + one scale, computed
    once from the full block (the reason the tail exists: per-token
    requantization would re-derive the scale T times and perturb
    already-written rows every step).  Rows that are pads (``p >=
    limit``) or belong to masked lanes (``lane_mask``) redirect to the
    TRASH tail row (index B) — a pad row writing the lane's real tail
    would clobber live rows when the pad span wraps the block."""
    b, hkv, t, d = kh.shape
    bs = kc.shape[3]
    trash_row = kt.shape[1] - 1
    for lane in range(b):
        for j in range(t):
            p = pos[lane] + j
            real = None
            if limit is not None:
                real = p < limit[lane]
            if lane_mask is not None:
                real = (lane_mask[lane] if real is None
                        else real & lane_mask[lane])
            row = (lane if real is None
                   else jnp.where(real, lane, trash_row))
            kt = jax.lax.dynamic_update_slice(
                kt, kh[lane, :, j][None, None, :, None, :],
                (li, row, 0, p % bs, 0))
            vt = jax.lax.dynamic_update_slice(
                vt, vh[lane, :, j][None, None, :, None, :],
                (li, row, 0, p % bs, 0))
            complete = (p + 1) % bs == 0
            if real is not None:
                complete = complete & real
            dst = table[lane, p // bs]

            # block-completion commit behind a cond: only the
            # 1-in-bs completing row pays the two tile quantizes +
            # pool writes (same rationale as _write_token_quant)
            def _commit(st, row=row, dst=dst, kt=kt, vt=vt):
                kc, vc, ks, vs = st
                ktile = jax.lax.dynamic_slice(
                    kt, (li, row, 0, 0, 0), (1, 1, hkv, bs, d))
                kcodes, kscale = quantize_kv(ktile)
                kc = jax.lax.dynamic_update_slice(kc, kcodes,
                                                  (li, dst, 0, 0, 0))
                ks = jax.lax.dynamic_update_slice(ks, kscale,
                                                  (li, dst, 0))
                vtile = jax.lax.dynamic_slice(
                    vt, (li, row, 0, 0, 0), (1, 1, hkv, bs, d))
                vcodes, vscale = quantize_kv(vtile)
                vc = jax.lax.dynamic_update_slice(vc, vcodes,
                                                  (li, dst, 0, 0, 0))
                vs = jax.lax.dynamic_update_slice(vs, vscale,
                                                  (li, dst, 0))
                return kc, vc, ks, vs

            kc, vc, ks, vs = jax.lax.cond(complete, _commit,
                                          lambda st: st,
                                          (kc, vc, ks, vs))
    return kc, vc, ks, vs, kt, vt


# ---------------------------------------------------------------------------
# The pool as decode.cached_forward sees a cache: the paged views
# ---------------------------------------------------------------------------


class PagedView:
    """The bf16 pool + block table behind :func:`decode.cached_forward`
    (decode.ContiguousView has the contract): ``cache`` the pool dict
    with per-lane ``pos``, ``table`` [B, M] int32.  The pools ride the
    layer scan as CARRY beside the layer's index (block ids are dynamic;
    slicing a layer out per step would materialize it anyway).

    - One token a lane and no ``limit`` is the DECODE STEP: the row
      lands through ``_write_token_paged`` and, where the kernel is on,
      ``paged_decode_attention`` streams the table-mapped blocks (under
      a serving mesh sharded, the output projection inside its manual
      region) over the tick's work list (:meth:`cells`, built once
      outside the layer scan).  An inactive lane's write needs no mask
      here: its zeroed table row already sends its row to the trash
      block.
    - Otherwise (speculative verify, suffix insert, prefill slices) rows
      land wherever the table maps their absolute position — those at
      or past ``limit`` [B] (pads) in the trash block; whole blocks at
      once when ``aligned`` (the caller guarantees block-aligned ``pos``
      and a block-multiple row count: the N-lane prefill engine, where
      the per-row unroll is pathological to COMPILE) — and the einsum
      attends over the gathered lane view (:meth:`lanes`).

    ``lane_mask`` [B] (the step's ``active``): a masked lane attends
    nothing through the kernel — one cell of the list that fetches no
    block and writes zeros (its token is discarded by the step) — and
    under the int8 pool its rows go to the trash tail
    (:class:`PagedQuantView`)."""

    stacked = True
    names = ("k", "v")      # the pool's buffers, in the order they ride

    @staticmethod
    def pool_shapes(cfg, total_blocks: int, block_size: int) -> Dict[str, Any]:
        """The pool's buffers, name -> shape (``init_paged_cache``)."""
        shape = (cfg.n_layers, total_blocks, cfg.n_kv_heads, block_size,
                 cfg.head_dim)
        return {"k": shape, "v": shape}

    @classmethod
    def scatter_prompt(cls, cache: Dict[str, jax.Array],
                       lane: Dict[str, jax.Array], table_row: jax.Array,
                       block_size: int) -> Dict[str, jax.Array]:
        """A whole-prompt insert's contiguous lane cache into the pool's
        buffers, whole blocks at the lane's table entries."""
        return {n: scatter_prompt_blocks(cache[n], lane[n], table_row,
                                         block_size) for n in cls.names}

    def __init__(self, cfg, cache: Dict[str, jax.Array], table: jax.Array,
                 *, limit: Optional[jax.Array] = None,
                 lane_mask: Optional[jax.Array] = None,
                 aligned: bool = False, mesh=None) -> None:
        self.cfg, self.mesh = cfg, mesh
        self.cache, self.table, self.pos = cache, table, cache["pos"]
        self.limit, self.lane_mask, self.aligned = limit, lane_mask, aligned
        self.block_size = cache[self.names[0]].shape[3]

    def enter(self, t: int) -> None:
        """Fix the forward's kind from its static row count: the decode
        step (``step``), and whether its attention is the kernel."""
        self.t = t
        self.step = t == 1 and self.limit is None
        self.kernel, self.projects, self.interpret = D.decode_kernel_mode(
            self.cfg, self.mesh, self.step)

    def buffers(self):
        return tuple(self.cache[n] for n in self.names)

    def begin(self, t: int):
        self.enter(t)
        self.step_cells = self.cells() if self.kernel else None
        return self.buffers(), jnp.arange(self.cfg.n_layers)

    def cells(self, window=None):
        """The decode kernel's work list for this tick (ops/
        decode_attention.py ``decode_cells``): positions and table are
        the same for every layer, so it is built once, outside the layer
        loop — one list a ``window`` (a sliding layer attends its last
        ``window`` positions and the list leaves out the blocks wholly
        before them)."""
        from paddle_operator_tpu.ops.decode_attention import decode_cells

        lengths = self.pos + 1
        starts = (None if window is None
                  else jnp.maximum(lengths - window, 0))
        if self.lane_mask is not None:
            lengths = jnp.where(self.lane_mask, lengths, 0)
        return decode_cells(self.table, lengths, self.block_size, starts)

    def write(self, bufs, li, k: jax.Array, v: jax.Array):
        kc, vc = bufs
        bs = self.block_size
        if self.step:
            kc = _write_token_paged(kc, k.transpose(0, 2, 1, 3), li,
                                    self.table, self.pos, bs)
            vc = _write_token_paged(vc, v.transpose(0, 2, 1, 3), li,
                                    self.table, self.pos, bs)
            return kc, vc
        write = _write_blocks_paged if self.aligned else _write_rows_paged
        kc = write(kc, k.transpose(0, 2, 1, 3), li, self.table, self.pos,
                   bs, self.limit)
        vc = write(vc, v.transpose(0, 2, 1, 3), li, self.table, self.pos,
                   bs, self.limit)
        return kc, vc

    def lanes(self, bufs, li) -> Tuple[jax.Array, jax.Array]:
        """Layer ``li`` of the pool as contiguous lanes, ``(k, v)``
        [B, H, M*bs, D] — what an einsum attention reads (this block's
        :func:`decode._attend_cache`, or another architecture's own)."""
        kc, vc = bufs
        return (_gather_lane_view(kc, self.table, li),
                _gather_lane_view(vc, self.table, li))

    def _kernel_operands(self, bufs) -> Dict[str, jax.Array]:
        return {}

    def kernel_attend(self, bufs, li, q: jax.Array, wo=None, cells=None):
        """The decode kernel over the table-mapped blocks: ``q``
        [B, 1, Hq, D] -> [B, 1, Hq*D], or (``projects``) the residual
        [B, dim] already through ``wo``.  ``cells``: the layer's work
        list (:meth:`cells`; a stack with window layers holds one a
        window, tp 1); absent, the one :meth:`begin` built."""
        from paddle_operator_tpu.ops.decode_attention import (
            paged_decode_attention,
            sharded_paged_decode_attention,
        )

        kc, vc = bufs[:2]
        cells = self.step_cells if cells is None else cells
        if self.projects:
            return sharded_paged_decode_attention(
                self.mesh, q[:, 0], kc, vc, self.table, None, wo,
                layer=li, interpret=self.interpret, cells=cells,
                compute_dtype=self.cfg.dtype, **self._kernel_operands(bufs))
        out = paged_decode_attention(
            q[:, 0], kc, vc, self.table, layer=li, cells=cells,
            interpret=self.interpret, **self._kernel_operands(bufs))
        return out.reshape(q.shape[0], 1, -1).astype(self.cfg.dtype)

    def attend(self, bufs, li, q: jax.Array, rows: jax.Array, wo):
        if self.kernel:
            return self.kernel_attend(bufs, li, q, wo)
        return D._attend_cache(self.cfg, q, *self.lanes(bufs, li), rows)

    def end(self, bufs, t: int) -> Dict[str, jax.Array]:
        return {**dict(zip(self.names, bufs)), "pos": self.pos + t}


class LatentPagedView(PagedView):
    """:class:`PagedView` over a LATENT pool (multi-head latent
    attention, models/glm_moe_lite.py): ONE cached row a token a layer
    for all heads, in two buffers — ``c`` [L, N, 1, bs, C] the normed
    latents and ``pe`` [L, N, 1, R, bs] the rotated keys, TRANSPOSED
    (ops/decode_attention.py ``latent_paged_decode_attention`` has the
    reason: 64 columns are half a lane register, 256 rows are two) — the
    singleton axis where a K/V pool has its heads, so that block tables,
    the trash block, the work list and the host's ``PagedCacheManager``
    are the K/V pool's own.  (C + R) * 2 bytes a token a layer.

    Written for what the expert stack's ring runs at tp 1 with a bf16
    pool: the decode step's row (``write`` at one token a lane), a
    whole-prompt insert's blocks (:meth:`scatter_prompt`), the latent
    kernel over the work list and the gathered lanes for its einsum
    twin.  Rows written any other way (a suffix insert, a verify, a
    prefill slice) are refused: nothing that needs them serves this
    architecture."""

    names = ("c", "pe")
    REFUSAL = ("the latent pool is written by the decode step and the "
               "whole-prompt insert at tp 1 only")

    @staticmethod
    def pool_shapes(cfg, total_blocks: int, block_size: int) -> Dict[str, Any]:
        lead = (cfg.n_layers, total_blocks, 1)
        return {"c": lead + (block_size, cfg.kv_lora_rank),
                "pe": lead + (cfg.qk_rope_head_dim, block_size)}

    @classmethod
    def scatter_prompt(cls, cache, lane, table_row, block_size):
        return {
            "c": scatter_prompt_blocks(cache["c"], lane["c"], table_row,
                                       block_size),
            "pe": scatter_prompt_blocks(
                cache["pe"], lane["pe"].transpose(0, 1, 2, 4, 3), table_row,
                block_size, axis=4)}

    def __init__(self, cfg, cache, table, **kw) -> None:
        super().__init__(cfg, cache, table, **kw)
        if self.limit is not None or self.aligned or D.mesh_tp(self.mesh) > 1:
            raise ValueError(self.REFUSAL)

    def write(self, bufs, li, c: jax.Array, pe: jax.Array):
        """The step's rows ``c [B, 1, 1, C]``, ``pe [B, 1, 1, R]``."""
        if not self.step:
            raise ValueError(self.REFUSAL)
        cc, pc = bufs
        args = (li, self.table, self.pos, self.block_size)
        return (_write_token_paged(cc, c.transpose(0, 2, 1, 3), *args),
                _write_token_paged(pc, pe.transpose(0, 2, 3, 1), *args,
                                   transposed=True))

    def lanes(self, bufs, li) -> Tuple[jax.Array, jax.Array]:
        """Layer ``li`` as contiguous lanes, ``(c [B, 1, M*bs, C], pe
        [B, 1, M*bs, R])``: the rotated keys turned back into rows."""
        cc, pc = bufs
        b, m = self.table.shape
        layer = jax.lax.dynamic_index_in_dim(pc, li, 0, keepdims=False)
        pe = jnp.take(layer, self.table.reshape(-1), axis=0)  # [B*M,1,R,bs]
        pe = pe.reshape(b, m, *pe.shape[1:]).transpose(0, 2, 1, 4, 3)
        return (_gather_lane_view(cc, self.table, li),
                pe.reshape(b, 1, m * self.block_size, -1))

    def kernel_attend(self, bufs, li, q_lat: jax.Array, q_pe: jax.Array,
                      cells=None):
        """The latent kernel over the table-mapped blocks: the absorbed
        queries ``q_lat [B, 1, H, C]``, ``q_pe [B, 1, H, R]`` -> the
        latent each head attended, ``[B, 1, H, C]``."""
        from paddle_operator_tpu.ops.decode_attention import (
            latent_paged_decode_attention,
        )

        cc, pc = bufs
        out = latent_paged_decode_attention(
            q_lat[:, 0], q_pe[:, 0], cc, pc, self.table,
            scale=self.cfg.head_dim ** -0.5, layer=li,
            cells=self.step_cells if cells is None else cells,
            interpret=self.interpret)
        return out[:, None]

    def attend(self, bufs, li, q, rows, wo):
        raise ValueError("the latent pool serves models/glm_moe_lite.py's "
                         "block, not the LLaMA block's cached forward")


class PagedQuantView(PagedView):
    """:class:`PagedView` over the INT8 pool (``init_paged_cache``
    ``quant="int8"``): codes, scales and the bf16 staging tails all ride
    the scan.  New rows accumulate exact in the lane's tail and quantize
    into the pool on block completion (``_write_token_quant`` for the
    decode step, ``_write_rows_quant`` else); attention reads codes with
    the dequant fused in-kernel, or the dequantizing gather view with
    the lane's tail in place of its write-frontier block.

    ``lane_mask`` [B] (the step's ``active``, a round's, an engine's
    participating lanes) sends masked lanes' rows to the TRASH tail: a
    lane mid-prefill keeps live state in its tail that a resident
    dispatch must not touch — the tail's analogue of masking a
    prefill-pending table row to the trash block.  ``aligned`` does not
    apply: the tail protocol is per row by nature."""

    def begin(self, t: int):
        self.enter(t)
        self.step_cells = self.cells() if self.kernel else None
        c = self.cache
        if self.step:
            trash_row = c["kt"].shape[1] - 1
            lanes = jnp.arange(self.pos.shape[0])
            self.rows_idx = (jnp.where(self.lane_mask, lanes, trash_row)
                             if self.lane_mask is not None else lanes)
        li = jnp.arange(self.cfg.n_layers)
        if self.step and not self.kernel:
            self.wb = self.pos // self.block_size
        return (c["k"], c["v"], c["ks"], c["vs"], c["kt"], c["vt"]), li

    def write(self, bufs, li, k: jax.Array, v: jax.Array):
        kc, vc, ks, vs, kt, vt = bufs
        if self.step:
            kc, ks, kt = _write_token_quant(
                kc, ks, kt, k.transpose(0, 2, 1, 3), li, self.table,
                self.pos, self.rows_idx, self.block_size)
            vc, vs, vt = _write_token_quant(
                vc, vs, vt, v.transpose(0, 2, 1, 3), li, self.table,
                self.pos, self.rows_idx, self.block_size)
            return kc, vc, ks, vs, kt, vt
        return _write_rows_quant(
            kc, vc, ks, vs, kt, vt, k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), li, self.table, self.pos, self.limit,
            self.lane_mask)

    def lanes(self, bufs, li) -> Tuple[jax.Array, jax.Array]:
        kc, vc, ks, vs, kt, vt = bufs
        if self.step:
            wb = self.wb
        else:
            # per-lane write-frontier block: the last REAL row written
            # (pads never advance the tail), floor 0 for fully-masked
            # lanes
            lim_eff = (self.limit if self.limit is not None
                       else self.pos + self.t)
            wb = (jnp.maximum(jnp.minimum(self.pos + self.t, lim_eff) - 1,
                              0) // self.block_size)
        return (_gather_lane_view_quant(kc, ks, kt, self.table, li, wb),
                _gather_lane_view_quant(vc, vs, vt, self.table, li, wb))

    def _kernel_operands(self, bufs) -> Dict[str, jax.Array]:
        _, _, ks, vs, kt, vt = bufs
        return dict(k_scale=ks, v_scale=vs, k_tail=kt, v_tail=vt)

    def end(self, bufs, t: int) -> Dict[str, jax.Array]:
        kc, vc, ks, vs, kt, vt = bufs
        return {"k": kc, "v": vc, "ks": ks, "vs": vs, "kt": kt, "vt": vt,
                "pos": self.pos + t}


def view_class(cfg):
    """The bf16 pool's view for `cfg`: a configuration that caches one
    latent row a token (``kv_lora_rank``), or K and V a head."""
    return LatentPagedView if hasattr(cfg, "kv_lora_rank") else PagedView


def paged_view(cfg, cache: Dict[str, jax.Array], table: jax.Array,
               **kw) -> PagedView:
    """The view of a paged cache, chosen from the cache itself: the int8
    pool carries its scales (``ks``)."""
    return (PagedQuantView if "ks" in cache else view_class(cfg))(
        cfg, cache, table, **kw)


def cache_row_bytes(cache: Dict[str, jax.Array]) -> int:
    """Bytes a token a layer a paged cache's pool holds (``cacheRowBytes``
    on /statusz): K and V over the kv heads, int8 codes under
    SERVE_KV_QUANT, or one latent row."""
    pools = [cache[n] for n in ("k", "v", "c", "pe") if n in cache]
    block = pools[0].shape[3]
    return sum(math.prod(p.shape[2:]) * p.dtype.itemsize
               for p in pools) // block


def make_paged_chunk_step(cfg: LlamaConfig, chunk_tokens: int,
                          top_k: Optional[int] = None,
                          top_p: Optional[float] = None, mesh=None,
                          check_finite: bool = False,
                          quant: bool = False):
    """The resident compiled decode program of the PAGED ring — the
    exact contract of executor.make_chunk_step plus the block table:

    ``step(params, cache, table, tok, temp, keys, active)
    -> (cache', tok', toks [chunk, B])``

    Retired/inactive lanes additionally get their position ZEROED (the
    serving-status staleness fix) — their writes route to the trash
    block through the zeroed table row, so nothing they do can touch a
    re-allocated block.

    ``check_finite=True``: a fourth ``ok [B]`` output — the per-lane
    isfinite fold of every tick's logits (the ring's NaN-lane quarantine;
    see make_chunk_step).

    ``quant=True``: the cache is the int8 codes+scales+tails dict;
    ``active`` additionally steers inactive lanes' tail writes to the
    trash tail (see :class:`PagedQuantView`)."""
    def step(params, cache, table, tok, temp, keys, active, *lora_args):
        lora = tuple(lora_args) if lora_args else None

        def tick(carry, _):
            if check_finite:
                cache, tok, ok = carry
            else:
                cache, tok = carry
            logits, new_cache = D.cached_step(
                cfg, params, tok,
                paged_view(cfg, cache, table, lane_mask=active, mesh=mesh),
                lora=lora)
            nxt = D._sample_tokens(logits, temp, keys, cache["pos"],
                                   top_k, top_p)
            new_cache["pos"] = jnp.where(active, new_cache["pos"], 0)
            nxt = jnp.where(active, nxt, tok)
            if check_finite:
                ok = ok & jnp.all(jnp.isfinite(logits), axis=-1)
                return (new_cache, nxt, ok), nxt
            return (new_cache, nxt), nxt

        if check_finite:
            (cache, tok, ok), toks = jax.lax.scan(
                tick, (cache, tok, jnp.ones(tok.shape, bool)), None,
                length=chunk_tokens)
            return cache, tok, toks, ok
        (cache, tok), toks = jax.lax.scan(
            tick, (cache, tok), None, length=chunk_tokens)
        return cache, tok, toks

    return jax.jit(step, donate_argnums=(1,))


def make_paged_megastep(cfg: LlamaConfig, chunk_tokens: int,
                        n_steps: int, top_k: Optional[int] = None,
                        top_p: Optional[float] = None, mesh=None,
                        check_finite: bool = False,
                        quant: bool = False):
    """N fused PAGED ring iterations in one compiled dispatch
    (ISSUE 11): ``make_paged_chunk_step``'s tick scanned ``n_steps``
    chunks with the host's boundary decisions — eos, token budget,
    step budget — carried on device (decode._mega_advance).  The
    paged pool is what makes a mid-megastep finish SAFE without host
    help: each fused chunk runs against an EFFECTIVE table whose dead
    lanes' rows are replaced wholesale by the trash block (the same
    redirect ``retire`` performs host-side by zeroing the row), so a
    dead lane's free-running writes — pool rows, quantize-on-completion
    commits, staging-tail rows (``active=live`` steers those to the
    trash tail under quant) — can never touch a real block.  Its fill
    position is restored from the pre-chunk snapshot at each boundary,
    which is what makes a lane frozen by its STEP budget (deadline
    ticks) resumable bit-identically in a later dispatch: its blocks,
    tail and position are exactly as its last consumed token left them.

    ``mega(params, cache, table, tok, temp, keys, active, eos, left,
    steps, *lora) -> (cache', tok', toks [n, chunk, B], counts [n, B]
    [, oks [n, B]])`` — the same output contract as
    executor.make_megastep, table operand added."""
    def mega(params, cache, table, tok, temp, keys, active, eos, left,
             steps, *lora_args):
        lora = tuple(lora_args) if lora_args else None

        def outer(carry, _):
            cache, tok, live, lleft, lsteps = carry
            p0 = cache["pos"]
            tbl_eff = jnp.where(live[:, None], table, TRASH_BLOCK)

            def tick(c, _):
                if check_finite:
                    cache, tok, ok = c
                else:
                    cache, tok = c
                logits, new_cache = D.cached_step(
                    cfg, params, tok,
                    paged_view(cfg, cache, tbl_eff, lane_mask=live,
                               mesh=mesh),
                    lora=lora)
                nxt = D._sample_tokens(logits, temp, keys, cache["pos"],
                                       top_k, top_p)
                new_cache["pos"] = jnp.where(live, new_cache["pos"], 0)
                nxt = jnp.where(live, nxt, tok)
                if check_finite:
                    ok = ok & (jnp.all(jnp.isfinite(logits), axis=-1)
                               | ~live)
                    return (new_cache, nxt, ok), nxt
                return (new_cache, nxt), nxt

            if check_finite:
                (cache, tok, ok), toks = jax.lax.scan(
                    tick, (cache, tok, jnp.ones(tok.shape, bool)), None,
                    length=chunk_tokens)
            else:
                (cache, tok), toks = jax.lax.scan(
                    tick, (cache, tok), None, length=chunk_tokens)
            raw = jnp.where(live, chunk_tokens, 0).astype(jnp.int32)
            count, live2, left2, lsteps2 = D._mega_continue(
                toks, raw, live, lleft, lsteps, eos)
            cache["pos"] = jnp.where(live, cache["pos"], p0)
            out = (toks, count, ok) if check_finite else (toks, count)
            return (cache, tok, live2, left2, lsteps2), out

        live0 = active & (left > 0) & (steps > 0)
        if check_finite:
            (cache, tok, _, _, _), (toks, counts, oks) = jax.lax.scan(
                outer, (cache, tok, live0, left, steps), None,
                length=n_steps)
            return cache, tok, toks, counts, oks
        (cache, tok, _, _, _), (toks, counts) = jax.lax.scan(
            outer, (cache, tok, live0, left, steps), None,
            length=n_steps)
        return cache, tok, toks, counts

    return jax.jit(mega, donate_argnums=(1,))


@jax.named_scope("cache_write")
def scatter_prompt_blocks(pool: jax.Array, lane: jax.Array,
                           table_row: jax.Array,
                           block_size: int, axis: int = 3) -> jax.Array:
    """Write a contiguous [L, 1, H, bucket, D] prefilled lane cache
    into the pool as block-aligned chunks at the lane's table entries —
    the block-granular prefill-write path, shared with the kernels'
    module (ops/decode_attention.py scatter_prefill_blocks has the
    whole-block-vs-per-row story).  ``axis``: where the lane's positions
    lie (4: a transposed buffer, [L, 1, H, D, bucket])."""
    from paddle_operator_tpu.ops.decode_attention import (
        scatter_prefill_blocks,
    )

    return scatter_prefill_blocks(pool, lane, table_row, block_size,
                                  axis=axis)


def paged_prefill(params: Dict[str, Any], cfg: LlamaConfig,
                  tokens: jax.Array, pool_cache: Dict[str, jax.Array],
                  table_row: jax.Array, *, block_size: Optional[int] = None,
                  mesh=None, quant: bool = False,
                  prompt_len: Optional[jax.Array] = None, lora=None,
                  head_at=None):
    """Prefill a whole [1, bucket] prompt and write its KV into the
    PAGED block pool as block-aligned chunks at the
    lane's ``table_row`` entries — the cold-admission half of paged
    serving.  The forward itself is exactly ``decode.prefill``'s (same
    compiled ops — what keeps the paged ring's first token
    bit-identical to the contiguous ring's — and the same choice of
    attention by width, ``decode.prefill_attn_impl``: the flash kernel
    over the prompt's own q, k, v where it runs, else the einsum over
    the lane cache); only the destination
    changes: block ``j`` of the lane cache lands in pool block
    ``table_row[j]``, pad blocks land wherever the table maps them
    (the trash block when unmapped — exactness-with-padding,
    block-granular).  Returns ([1, bucket, vocab] logits — the caller
    samples at ``prompt_len - 1``; with ``head_at`` (traced) the head
    runs at that one position alone, [1, 1, vocab] — and the pool cache
    with this lane's position untouched (the caller's insert sets it).

    ``quant=True`` (needs ``prompt_len``, traced): whole blocks
    quantize ONCE on the way into the int8 pool
    (ops/decode_attention.py scatter_prefill_blocks_quant), and the
    prompt's partial last block is returned as exact bf16 tail tiles
    ``(logits, cache', tail_k, tail_v)`` [L, 1, H, bs, D] for the
    caller's insert to splice into the lane's staging tail — the one
    block whose scale cannot be final yet."""
    bs = block_size or pool_cache["k"].shape[3]
    lane = D.init_cache(cfg, 1, tokens.shape[1])
    logits, lane = D._forward(cfg, params, tokens, lane, mesh=mesh,
                              lora=lora, whole_prompt=True,
                              head_at=head_at)
    if not quant:
        k = scatter_prompt_blocks(pool_cache["k"], lane["k"], table_row,
                                   bs)
        v = scatter_prompt_blocks(pool_cache["v"], lane["v"], table_row,
                                   bs)
        return logits, {"k": k, "v": v, "pos": pool_cache["pos"]}
    from paddle_operator_tpu.ops.decode_attention import (
        scatter_prefill_blocks_quant,
    )

    if prompt_len is None:
        raise ValueError("quant paged_prefill needs prompt_len for the "
                         "staging-tail slice")
    k, ks = scatter_prefill_blocks_quant(
        pool_cache["k"], pool_cache["ks"], lane["k"], table_row, bs)
    v, vs = scatter_prefill_blocks_quant(
        pool_cache["v"], pool_cache["vs"], lane["v"], table_row, bs)
    # the write-frontier block's exact rows: [start, start + bs) of the
    # lane cache.  The lane alloc need not be a block multiple, and
    # dynamic_slice CLAMPS an out-of-range start backwards — which
    # would hand back rows of the PREVIOUS block at the wrong tail
    # offsets (positions start+o would attend K/V of start-pad+o) —
    # so pad the time axis up to a block multiple first.  The one
    # remaining clamp (block-aligned prompt filling the whole padded
    # alloc, start == padded len) is harmless: decode then begins a
    # FRESH block and every stale tail row sits behind the fill mask.
    L, _, h, t_alloc, dd = lane["k"].shape
    pad = -t_alloc % bs
    lane_k, lane_v = lane["k"], lane["v"]
    if pad:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
        lane_k = jnp.pad(lane_k, widths)
        lane_v = jnp.pad(lane_v, widths)
    start = (prompt_len // bs) * bs
    tail_k = jax.lax.dynamic_slice(lane_k, (0, 0, 0, start, 0),
                                   (L, 1, h, bs, dd))
    tail_v = jax.lax.dynamic_slice(lane_v, (0, 0, 0, start, 0),
                                   (L, 1, h, bs, dd))
    cache = {"k": k, "v": v, "ks": ks, "vs": vs, "kt": pool_cache["kt"],
             "vt": pool_cache["vt"], "pos": pool_cache["pos"]}
    return logits, cache, tail_k, tail_v


# The widest rung whose insert carries a decode step.  Every rung is
# traced and lowered before the server is ready, in Python, and the
# step's half — the decode kernel's call, its work list, sixteen lanes'
# cache writes — doubles that (1.0 -> 1.95 s a rung on the v5e's host:
# PERF.md section 6, PR 33).  The rungs above 1024 are a seventh of the
# inserts, gain the least a call (a 12 ms step beside a 90-200 ms
# insert) and would take ``setup_s`` past its bound; they keep the
# insert alone until the lanes' half is lowered once for all rungs.
# This is the one fork in the factory: both branches take the head at
# the prompt's last real token and sample it the same way.
_STEP_MAX_BUCKET = 1024


def insert_carries_step(bucket: int, mesh=None, quant: bool = False,
                        adapters: bool = False) -> bool:
    """Whether :func:`make_paged_prefill_insert`'s program for this rung
    advances the ring's live lanes by a token (and returns ``toks``):
    the plain bf16 pool at tp 1, up to ``_STEP_MAX_BUCKET``.  The int8
    pool (its step quantizes on completion through staging tails), a
    LoRA tail (the lanes' adapter ids are not the prompt's) and a tp
    mesh (the step's kernel projects inside its manual region) keep the
    insert alone."""
    return (bucket <= _STEP_MAX_BUCKET and not quant and not adapters
            and D.mesh_tp(mesh) == 1)


def make_paged_prefill_insert(cfg: LlamaConfig, bucket: int,
                              block_size: int,
                              top_k: Optional[int] = None,
                              top_p: Optional[float] = None, mesh=None,
                              quant: bool = False,
                              check_finite: bool = False):
    """Cold (no prefix hit) paged admission — the contiguous
    make_prefill_insert with the splice replaced by a block scatter —
    which on the plain bf16 pool (:func:`insert_carries_step`) CARRIES
    ONE DECODE STEP of the ring: an insert reads every weight once for
    the prompt's rows while the live lanes stand still, so their next
    token rides that read (``decode.prefill_with_step``: the lanes' rows
    concatenated to the prompt's at ``wo``, the feed-forward and the
    head; apart through q/k/v, rotation, cache write and attention)
    instead of paying a step's read of its own — on the rungs up to
    ``_STEP_MAX_BUCKET``.  The step is ``make_paged_chunk_step``'s
    tick: sampled at the lanes' own positions, ``tok`` and ``pos``
    advanced for ``active`` lanes only.  A lane outside ``active`` — the
    inserted slot always — writes its row to the trash block and keeps
    its token and position (unlike the step, which zeroes an inactive
    lane's position: a live lane whose pool block could not be mapped
    sits this one out and decodes on).

    ``quant=True``: whole blocks quantize once into the int8 pool; the
    prompt's partial last block lands exact in the lane's staging tail
    (decode.paged_prefill quant contract).

    ``insert(params, cache, table [B, M], tok, temp, keys, active [B],
    prompt [1,bucket], prompt_len, slot, temp_val, seed)
    -> (cache', tok', temp', keys', first_token, toks [1, B][, ok [B]])``
    — ``ok`` under ``check_finite``, the step's isfinite verdict a lane;
    without the carried step (a wider rung, int8, adapters, tp) the
    first five outputs alone, the slot's row taken from ``table``.
    """
    if bucket % block_size:
        raise ValueError(f"prefill bucket {bucket} not a multiple of the "
                         f"block size {block_size}")

    def insert(params, cache, table, tok, temp, keys, active, prompt,
               prompt_len, slot, temp_val, seed, *lora_args):
        table_row = jax.lax.dynamic_index_in_dim(table, slot,
                                                 keepdims=False)
        key = jax.random.PRNGKey(seed)
        temp1 = jnp.reshape(temp_val, (1,)).astype(jnp.float32)
        if insert_carries_step(bucket, mesh, quant, bool(lora_args)):
            active = active & (jnp.arange(tok.shape[0]) != slot)
            pos = cache["pos"]
            view = paged_view(
                cfg, cache, jnp.where(active[:, None], table, TRASH_BLOCK),
                lane_mask=active, mesh=mesh)
            logits, lane, stepped = D.prefill_with_step(
                cfg, params, prompt, prompt_len, tok, view, mesh=mesh)
            new_cache = dict(
                stepped,
                **view_class(cfg).scatter_prompt(stepped, lane, table_row,
                                                 block_size),
                pos=jnp.where(active, stepped["pos"], pos).at[slot].set(
                    prompt_len))
            # one draw for the prompt's row and the lanes', each by its
            # own temperature, key and position (a sampler of their own
            # costs the lanes a third of a second's lowering a rung)
            drawn = D._sample_tokens(
                logits, jnp.concatenate([temp1, temp]),
                jnp.concatenate([key[None], keys]),
                jnp.concatenate([jnp.reshape(prompt_len - 1, (1,)), pos]),
                top_k, top_p)
            first, nxt = drawn[0], jnp.where(active, drawn[1:], tok)
            out = (new_cache, nxt.at[slot].set(first),
                   temp.at[slot].set(temp_val), keys.at[slot].set(key),
                   first, nxt[None])
            if check_finite:
                out += (jnp.all(jnp.isfinite(logits[1:]), axis=-1)
                        | ~active,)
            return out
        # the insert alone: the head at the prompt's last real token, as
        # on the carrying branch (no [W, vocab] logits on any rung)
        lora = tuple(lora_args) if lora_args else None
        if quant:
            logits, new_cache, tail_k, tail_v = paged_prefill(
                params, cfg, prompt, cache, table_row,
                block_size=block_size, mesh=mesh, quant=True,
                prompt_len=prompt_len, lora=lora, head_at=prompt_len - 1)
            new_cache["kt"] = jax.lax.dynamic_update_slice(
                new_cache["kt"], tail_k, (0, slot, 0, 0, 0))
            new_cache["vt"] = jax.lax.dynamic_update_slice(
                new_cache["vt"], tail_v, (0, slot, 0, 0, 0))
        else:
            logits, new_cache = paged_prefill(params, cfg, prompt,
                                              cache, table_row,
                                              block_size=block_size,
                                              mesh=mesh, lora=lora,
                                              head_at=prompt_len - 1)
        new_cache["pos"] = new_cache["pos"].at[slot].set(prompt_len)
        first = D._sample_tokens(
            logits[0], temp1, key[None],
            jnp.reshape(prompt_len - 1, (1,)), top_k, top_p)[0]
        return (new_cache,
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(key),
                first)

    return jax.jit(insert, donate_argnums=(1, 3, 4, 5))


def _slice_lane_tails(cache: Dict[str, jax.Array], slot):
    """One lane's staging tails as 2-row mini-arrays (row 0 = the lane,
    row 1 = a zeroed trash row) for a batch-of-one quant forward —
    :class:`PagedQuantView` addresses tails by lane index with the LAST
    row as trash, so a B=1 call needs exactly this shape."""
    lcount, _, h, bs, d = cache["kt"].shape
    mk = jax.lax.dynamic_slice(cache["kt"], (0, slot, 0, 0, 0),
                               (lcount, 1, h, bs, d))
    mv = jax.lax.dynamic_slice(cache["vt"], (0, slot, 0, 0, 0),
                               (lcount, 1, h, bs, d))
    return (jnp.concatenate([mk, jnp.zeros_like(mk)], axis=1),
            jnp.concatenate([mv, jnp.zeros_like(mv)], axis=1))


def _restore_lane_tails(cache: Dict[str, jax.Array],
                        new_lane: Dict[str, jax.Array], slot):
    """Write a B=1 quant forward's mini-tail row back into the full
    per-slot tail arrays."""
    kt = jax.lax.dynamic_update_slice(
        cache["kt"], new_lane["kt"][:, :1], (0, slot, 0, 0, 0))
    vt = jax.lax.dynamic_update_slice(
        cache["vt"], new_lane["vt"][:, :1], (0, slot, 0, 0, 0))
    return kt, vt


def make_paged_suffix_insert(cfg: LlamaConfig, suffix_bucket: int,
                             block_size: int,
                             top_k: Optional[int] = None,
                             top_p: Optional[float] = None, mesh=None,
                             quant: bool = False):
    """Prefix-HIT paged admission: the lane's table already maps the
    cached prefix blocks (read-only; CoW'd where the suffix will
    write), so the forward runs over the SUFFIX ONLY — a multi-token
    per-lane-offset forward (``decode.cached_forward`` over the pool's
    view) whose attention walks the block table.  A shared 2048-token system prompt
    costs its followers exactly the suffix; the prefill-call counter
    the tests assert on never ticks for the cached prefix.

    ``quant=True``: the suffix rows accumulate in the lane's staging
    tail (sliced to a 2-row mini-tail for the B=1 forward) and whole
    blocks quantize on completion; the CoW'd hit block's content must
    already be dequantized into the tail by the scheduler's tail-init
    dispatch when ``hit_len`` lands mid-block.

    ``insert(params, cache, table_row [M], tok, temp, keys,
    suffix [1, suffix_bucket], suffix_len, hit_len, slot, temp_val,
    seed) -> (cache', tok', temp', keys', first_token)``
    """
    def insert(params, cache, table_row, tok, temp, keys, suffix,
               suffix_len, hit_len, slot, temp_val, seed, *lora_args):
        prompt_len = hit_len + suffix_len
        lane_cache = {"k": cache["k"], "v": cache["v"],
                      "pos": jnp.reshape(hit_len, (1,))}
        if quant:
            lane_cache["ks"], lane_cache["vs"] = cache["ks"], cache["vs"]
            lane_cache["kt"], lane_cache["vt"] = _slice_lane_tails(
                cache, slot)
        logits, new_lane = D.cached_forward(
            cfg, params, suffix,
            paged_view(cfg, lane_cache, table_row[None, :],
                       limit=jnp.reshape(prompt_len, (1,)), mesh=mesh),
            lora=tuple(lora_args) if lora_args else None)
        logits = logits[0, suffix_len - 1]
        new_cache = {"k": new_lane["k"], "v": new_lane["v"],
                     "pos": cache["pos"].at[slot].set(prompt_len)}
        if quant:
            new_cache["ks"], new_cache["vs"] = (new_lane["ks"],
                                                new_lane["vs"])
            new_cache["kt"], new_cache["vt"] = _restore_lane_tails(
                cache, new_lane, slot)
        key = jax.random.PRNGKey(seed)
        first = D._sample_tokens(
            logits[None], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)),
            top_k, top_p)[0]
        return (new_cache,
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(key),
                first)

    return jax.jit(insert, donate_argnums=(1, 3, 4, 5))


def make_paged_spec_prefill_insert(cfg: LlamaConfig, dcfg: LlamaConfig,
                                   bucket: int, block_size: int,
                                   top_k: Optional[int] = None,
                                   top_p: Optional[float] = None,
                                   mesh=None, quant: bool = False):
    """Speculative paged admission: target prefill scatters into the
    pool, the DRAFT lane stays a contiguous ring splice (the draft
    cache is small — paging it buys nothing, and the draft's propose
    loop keeps the fast contiguous write path).  ``quant=True``
    quantizes the TARGET pool only — the draft ring stays bf16, the
    same asymmetry (infer/speculative.py docstring).

    ``insert(params, dparams, cache, dcache, table_row, tok, temp,
    keys, prompt, prompt_len, slot, temp_val, seed)
    -> (cache', dcache', tok', temp', keys', first_token)``
    """
    if bucket % block_size:
        raise ValueError(f"prefill bucket {bucket} not a multiple of the "
                         f"block size {block_size}")

    def insert(params, dparams, cache, dcache, table_row, tok, temp, keys,
               prompt, prompt_len, slot, temp_val, seed):
        if quant:
            logits, new_cache, tail_k, tail_v = paged_prefill(
                params, cfg, prompt, cache, table_row,
                block_size=block_size, mesh=mesh, quant=True,
                prompt_len=prompt_len)
            new_cache["kt"] = jax.lax.dynamic_update_slice(
                new_cache["kt"], tail_k, (0, slot, 0, 0, 0))
            new_cache["vt"] = jax.lax.dynamic_update_slice(
                new_cache["vt"], tail_v, (0, slot, 0, 0, 0))
        else:
            logits, new_cache = paged_prefill(params, cfg, prompt,
                                              cache, table_row,
                                              block_size=block_size,
                                              mesh=mesh)
        logits = logits[0, prompt_len - 1]
        new_cache["pos"] = new_cache["pos"].at[slot].set(prompt_len)
        dlane = D.init_cache(dcfg, 1, bucket)
        _, dlane = D._forward(dcfg, dparams, prompt, dlane,
                              last_only=True, mesh=mesh,
                              whole_prompt=True)
        new_dcache = D._splice_lane(dcache, dlane, slot, prompt_len)
        key = jax.random.PRNGKey(seed)
        first = D._sample_tokens(
            logits[None], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)),
            top_k, top_p)[0]
        return (new_cache, new_dcache,
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(key),
                first)

    return jax.jit(insert, donate_argnums=(2, 3, 5, 6, 7))


def make_paged_prefill_chunk(cfg: LlamaConfig, slice_bucket: int,
                             block_size: int, mesh=None,
                             quant: bool = False):
    """One INTERMEDIATE chunked-prefill slice against the block pool
    (executor/scheduler ``prefill_mode="chunked"``): append the slice's
    KV rows at absolute positions [start, start + slice_bucket) through
    the lane's table — no lm head, no lane-state update, no first
    token; only the FINAL slice (which is exactly the SUFFIX insert
    with ``hit_len = rows already written``) does those.  Rows at or
    past ``limit`` route to the trash block, so a partial-tail radix
    hit can start a chunked prefill mid-block safely.

    ``chunk(params, cache, table_row [M], toks [1, slice_bucket],
    start, limit) -> cache'``

    ``quant=True`` adds a trailing ``slot`` argument (the tail rows
    address by lane): slices accumulate in the lane's staging tail and
    quantize whole blocks as they complete, so the tail state carried
    between slices IS the cache dict's — no extra bookkeeping.
    """
    def chunk(params, cache, table_row, toks, start, limit, *lora_args):
        lane_cache = {"k": cache["k"], "v": cache["v"],
                      "pos": jnp.reshape(start, (1,)).astype(jnp.int32)}
        _, new = D.cached_forward(
            cfg, params, toks,
            paged_view(cfg, lane_cache, table_row[None, :],
                       limit=jnp.reshape(limit, (1,)), mesh=mesh),
            head=False, lora=tuple(lora_args) if lora_args else None)
        return {"k": new["k"], "v": new["v"], "pos": cache["pos"]}

    def chunk_quant(params, cache, table_row, toks, start, limit, slot,
                    *lora_args):
        mk, mv = _slice_lane_tails(cache, slot)
        lane_cache = {"k": cache["k"], "v": cache["v"],
                      "ks": cache["ks"], "vs": cache["vs"],
                      "kt": mk, "vt": mv,
                      "pos": jnp.reshape(start, (1,)).astype(jnp.int32)}
        _, new = D.cached_forward(
            cfg, params, toks,
            paged_view(cfg, lane_cache, table_row[None, :],
                       limit=jnp.reshape(limit, (1,)), mesh=mesh),
            head=False, lora=tuple(lora_args) if lora_args else None)
        kt, vt = _restore_lane_tails(cache, new, slot)
        return {"k": new["k"], "v": new["v"], "ks": new["ks"],
                "vs": new["vs"], "kt": kt, "vt": vt,
                "pos": cache["pos"]}

    return jax.jit(chunk_quant if quant else chunk, donate_argnums=(1,))


def make_paged_spec_suffix_insert(cfg: LlamaConfig, dcfg: LlamaConfig,
                                  suffix_bucket: int, bucket: int,
                                  block_size: int,
                                  top_k: Optional[int] = None,
                                  top_p: Optional[float] = None,
                                  mesh=None, quant: bool = False):
    """Final chunked-prefill slice for the SPECULATIVE paged ring: the
    target's remaining suffix rows ride the block table exactly like
    :func:`make_paged_suffix_insert`; the DRAFT prefills its whole
    prompt in one pass (it is depth/4 x heads/2 by construction) and
    splices contiguously, as everywhere else in spec mode.

    ``insert(params, dparams, cache, dcache, table_row, tok, temp,
    keys, suffix [1, suffix_bucket], suffix_len, hit_len, slot,
    prompt [1, bucket], prompt_len, temp_val, seed)
    -> (cache', dcache', tok', temp', keys', first_token)``
    """
    def insert(params, dparams, cache, dcache, table_row, tok, temp,
               keys, suffix, suffix_len, hit_len, slot, prompt,
               prompt_len, temp_val, seed):
        lane_cache = {"k": cache["k"], "v": cache["v"],
                      "pos": jnp.reshape(hit_len, (1,))}
        if quant:
            lane_cache["ks"], lane_cache["vs"] = cache["ks"], cache["vs"]
            lane_cache["kt"], lane_cache["vt"] = _slice_lane_tails(
                cache, slot)
        logits, new_lane = D.cached_forward(
            cfg, params, suffix,
            paged_view(cfg, lane_cache, table_row[None, :],
                       limit=jnp.reshape(prompt_len, (1,)), mesh=mesh))
        logits = logits[0, suffix_len - 1]
        new_cache = {"k": new_lane["k"], "v": new_lane["v"],
                     "pos": cache["pos"].at[slot].set(prompt_len)}
        if quant:
            new_cache["ks"], new_cache["vs"] = (new_lane["ks"],
                                                new_lane["vs"])
            new_cache["kt"], new_cache["vt"] = _restore_lane_tails(
                cache, new_lane, slot)
        dlane = D.init_cache(dcfg, 1, bucket)
        _, dlane = D._forward(dcfg, dparams, prompt, dlane,
                              last_only=True, mesh=mesh,
                              whole_prompt=True)
        new_dcache = D._splice_lane(dcache, dlane, slot, prompt_len)
        key = jax.random.PRNGKey(seed)
        first = D._sample_tokens(
            logits[None], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)),
            top_k, top_p)[0]
        return (new_cache, new_dcache,
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(key),
                first)

    return jax.jit(insert, donate_argnums=(2, 3, 5, 6, 7))


@functools.lru_cache(maxsize=8)
def make_pool_transfer(max_blocks: int, quant: bool = False):
    """The disaggregated HANDOFF op: copy ``max_blocks`` pool blocks
    from the prefill executor's (small, private) pool into the decode
    pool — all layers, K and V, one donated jit.  Block-id vectors are
    PADDED to ``max_blocks`` with the trash block so one compile serves
    every prompt length (writing garbage into the trash block is its
    job; gathering src block 0 reads the executor pool's own trash).
    This is the in-process device-to-device stand-in for DistServe's
    KV transfer; a DCN-crossing variant would replace only this op.

    ``transfer(dst_k, dst_v, src_k, src_v, src_ids [M], dst_ids [M])
    -> (dst_k', dst_v')``

    ``quant=True``: codes, scales AND the prompt's staging tail all
    cross (the tail is the partial last block the prefill executor
    could not finalize) — src tail row 0 (the executor pool is one
    lane wide) lands in decode tail row ``slot``:

    ``transfer(dst_k, dst_v, dst_ks, dst_vs, dst_kt, dst_vt,
    src_k, src_v, src_ks, src_vs, src_kt, src_vt, src_ids, dst_ids,
    slot) -> (dst_k', dst_v', dst_ks', dst_vs', dst_kt', dst_vt')``
    """

    def transfer(dst_k, dst_v, src_k, src_v, src_ids, dst_ids):
        gk = jnp.take(src_k, src_ids, axis=1)     # [L, M, H, bs, D]
        gv = jnp.take(src_v, src_ids, axis=1)
        return (dst_k.at[:, dst_ids].set(gk),
                dst_v.at[:, dst_ids].set(gv))

    def transfer_quant(dst_k, dst_v, dst_ks, dst_vs, dst_kt, dst_vt,
                       src_k, src_v, src_ks, src_vs, src_kt, src_vt,
                       src_ids, dst_ids, slot):
        dst_k, dst_v = transfer(dst_k, dst_v, src_k, src_v, src_ids,
                                dst_ids)
        dst_ks = dst_ks.at[:, dst_ids].set(
            jnp.take(src_ks, src_ids, axis=1))
        dst_vs = dst_vs.at[:, dst_ids].set(
            jnp.take(src_vs, src_ids, axis=1))
        dst_kt = jax.lax.dynamic_update_slice(
            dst_kt, src_kt[:, :1], (0, slot, 0, 0, 0))
        dst_vt = jax.lax.dynamic_update_slice(
            dst_vt, src_vt[:, :1], (0, slot, 0, 0, 0))
        return dst_k, dst_v, dst_ks, dst_vs, dst_kt, dst_vt

    if quant:
        return jax.jit(transfer_quant, donate_argnums=(0, 1, 2, 3, 4, 5))
    return jax.jit(transfer, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=8)
def make_pool_frame_transfer(max_blocks: int, quant: bool = False):
    """One streamed-handoff FRAME's device-to-device copy (ISSUE 14):
    like :func:`make_pool_transfer` but blocks only — no staging tail
    and no lane addressing — because intermediate frames carry only
    COMPLETE block groups (the tail is by definition the still-moving
    write frontier, and it crosses exactly once, on the terminal
    frame via :func:`make_pool_tail_copy`).  Id vectors pad with the
    trash block as everywhere else, so ONE compile serves every frame
    width.

    ``transfer(dst_k, dst_v[, dst_ks, dst_vs], src_k, src_v[, src_ks,
    src_vs], src_ids [M], dst_ids [M]) -> dst arrays``"""

    def transfer(dst_k, dst_v, src_k, src_v, src_ids, dst_ids):
        return (dst_k.at[:, dst_ids].set(jnp.take(src_k, src_ids,
                                                  axis=1)),
                dst_v.at[:, dst_ids].set(jnp.take(src_v, src_ids,
                                                  axis=1)))

    def transfer_quant(dst_k, dst_v, dst_ks, dst_vs, src_k, src_v,
                       src_ks, src_vs, src_ids, dst_ids):
        dst_k, dst_v = transfer(dst_k, dst_v, src_k, src_v, src_ids,
                                dst_ids)
        return (dst_k, dst_v,
                dst_ks.at[:, dst_ids].set(jnp.take(src_ks, src_ids,
                                                   axis=1)),
                dst_vs.at[:, dst_ids].set(jnp.take(src_vs, src_ids,
                                                   axis=1)))

    if quant:
        return jax.jit(transfer_quant, donate_argnums=(0, 1, 2, 3))
    return jax.jit(transfer, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=2)
def make_pool_tail_copy():
    """The terminal streamed-handoff's staging-tail copy (int8 pools
    only): src tail row ``src_row`` — the prefill ENGINE lane that ran
    the job, now that the pool is N lanes wide (ISSUE 14) — lands in
    decode tail row ``slot``.  The 1-lane monolithic path keeps the
    fused tail copy inside :func:`make_pool_transfer`; this exists for
    the multi-lane engine whose tail row is job-dependent.

    ``cp(dst_kt, dst_vt, src_kt, src_vt, src_row, slot)
    -> (dst_kt', dst_vt')``"""

    def cp(dst_kt, dst_vt, src_kt, src_vt, src_row, slot):
        lcount, _, h, bs, d = src_kt.shape
        kt = jax.lax.dynamic_slice(src_kt, (0, src_row, 0, 0, 0),
                                   (lcount, 1, h, bs, d))
        vt = jax.lax.dynamic_slice(src_vt, (0, src_row, 0, 0, 0),
                                   (lcount, 1, h, bs, d))
        return (jax.lax.dynamic_update_slice(dst_kt, kt,
                                             (0, slot, 0, 0, 0)),
                jax.lax.dynamic_update_slice(dst_vt, vt,
                                             (0, slot, 0, 0, 0)))

    return jax.jit(cp, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=4)
def make_block_fetch(quant: bool = False):
    """The DEMOTE read: slice ONE pool block's exact device bytes (all
    layers, K and V — plus its scale rows under int8) for the host
    fetch the spill tier stores.  Not donated: the pool stays live.
    ``fetch(k, v, blk) -> (kb [L,1,H,bs,D], vb)``; quant adds
    ``ks``/``vs`` -> ``(kb, vb, ksb [L,1,H], vsb)``."""

    def fetch(k, v, blk):
        lcount, _, h, bs, d = k.shape
        kb = jax.lax.dynamic_slice(k, (0, blk, 0, 0, 0),
                                   (lcount, 1, h, bs, d))
        vb = jax.lax.dynamic_slice(v, (0, blk, 0, 0, 0),
                                   (lcount, 1, h, bs, d))
        return kb, vb

    def fetch_quant(k, v, ks, vs, blk):
        lcount = k.shape[0]
        h = k.shape[2]
        kb, vb = fetch(k, v, blk)
        ksb = jax.lax.dynamic_slice(ks, (0, blk, 0), (lcount, 1, h))
        vsb = jax.lax.dynamic_slice(vs, (0, blk, 0), (lcount, 1, h))
        return kb, vb, ksb, vsb

    return jax.jit(fetch_quant if quant else fetch)


@functools.lru_cache(maxsize=8)
def make_promote_blocks(block_size: int, quant: bool = False,
                        donate: bool = True):
    """The PROMOTE upload: scatter a batch of host payloads into their
    reserved pool blocks in ONE donated jit — the bf16 path is exactly
    the whole-block ``scatter_prefill_blocks`` write the prefill path
    uses (the payload batch rides as one contiguous
    ``[L, 1, H, n*bs, D]`` slab, block j landing at ``ids[j]``); the
    int8 path copies codes AND scale rows verbatim
    (ops/decode_attention.py ``scatter_promote_blocks_quant``) — a
    promote never re-quantizes, which is what makes a host hit
    bit-identical to the HBM hit it demoted from.  Callers pad ``ids``
    with the trash block (and the slab with zeros) to a small shape
    ladder so a handful of compiles serves every batch size.

    ``up(pool_k, pool_v, rows_k, rows_v, ids) -> (pool_k', pool_v')``;
    quant: ``up(pool_k, pool_v, ks, vs, rows_k, rows_v, srow_k,
    srow_v, ids) -> (pool_k', pool_v', ks', vs')`` with ``srow_*``
    [L, n, H] scale rows.

    ``donate=False`` (ISSUE 14): the multi-lane prefill engine's
    prefix-hit upload — its streamed-handoff frames hold version
    snapshots of the SAME pool arrays, and donating a buffer a posted
    frame still references would delete it under the decode side's
    transfer."""
    from paddle_operator_tpu.ops.decode_attention import (
        scatter_prefill_blocks,
        scatter_promote_blocks_quant,
    )

    def up(pool_k, pool_v, rows_k, rows_v, ids):
        pool_k = scatter_prefill_blocks(pool_k, rows_k, ids, block_size)
        pool_v = scatter_prefill_blocks(pool_v, rows_v, ids, block_size)
        return pool_k, pool_v

    def up_quant(pool_k, pool_v, ks, vs, rows_k, rows_v, srow_k, srow_v,
                 ids):
        pool_k, ks = scatter_promote_blocks_quant(
            pool_k, ks, rows_k, srow_k, ids, block_size)
        pool_v, vs = scatter_promote_blocks_quant(
            pool_v, vs, rows_v, srow_v, ids, block_size)
        return pool_k, pool_v, ks, vs

    if quant:
        return jax.jit(up_quant,
                       donate_argnums=(0, 1, 2, 3) if donate else ())
    return jax.jit(up, donate_argnums=(0, 1) if donate else ())


@functools.lru_cache(maxsize=4)
def make_block_copier(quant: bool = False):
    """The CoW device op: copy pool block ``src`` over block ``dst``
    (all layers, K and V) in one donated jit — dispatched once per
    copy-on-write admission, BEFORE the admission insert, so the
    insert's gather reads the private copy.  ``quant=True`` copies
    codes AND scales: ``cp(k, v, ks, vs, src, dst)``."""

    def cp(k, v, src, dst):
        ks = jax.lax.dynamic_slice_in_dim(k, src, 1, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, src, 1, axis=1)
        k = jax.lax.dynamic_update_slice_in_dim(k, ks, dst, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(v, vs, dst, axis=1)
        return k, v

    def cp_quant(k, v, ks, vs, src, dst):
        k, v = cp(k, v, src, dst)
        kss = jax.lax.dynamic_slice_in_dim(ks, src, 1, axis=1)
        vss = jax.lax.dynamic_slice_in_dim(vs, src, 1, axis=1)
        ks = jax.lax.dynamic_update_slice_in_dim(ks, kss, dst, axis=1)
        vs = jax.lax.dynamic_update_slice_in_dim(vs, vss, dst, axis=1)
        return k, v, ks, vs

    if quant:
        return jax.jit(cp_quant, donate_argnums=(0, 1, 2, 3))
    return jax.jit(cp, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=4)
def make_tail_init():
    """Quant-pool admission helper: a lane starting MID-BLOCK (a
    partial-tail radix hit, or a full hit capped at n-1 tokens) will
    write into a block that already holds quantized content (its CoW'd
    private copy) — seed the lane's bf16 staging tail with that block's
    DEQUANTIZED rows so the suffix forward reads [block_start, hit_len)
    exactly as every other reader does, then overwrites from hit_len
    on.  One tiny donated dispatch, scheduler-side, after the CoW copy.

    ``init(kt, vt, k, ks, v, vs, slot, blk) -> (kt', vt')``
    """

    def init(kt, vt, k, ks, v, vs, slot, blk):
        lcount, _, h, bs, d = kt.shape
        ktile = dequantize_kv(
            jax.lax.dynamic_slice(k, (0, blk, 0, 0, 0),
                                  (lcount, 1, h, bs, d)),
            jax.lax.dynamic_slice(ks, (0, blk, 0), (lcount, 1, h)),
            kt.dtype)
        vtile = dequantize_kv(
            jax.lax.dynamic_slice(v, (0, blk, 0, 0, 0),
                                  (lcount, 1, h, bs, d)),
            jax.lax.dynamic_slice(vs, (0, blk, 0), (lcount, 1, h)),
            vt.dtype)
        kt = jax.lax.dynamic_update_slice(kt, ktile, (0, slot, 0, 0, 0))
        vt = jax.lax.dynamic_update_slice(vt, vtile, (0, slot, 0, 0, 0))
        return kt, vt

    return jax.jit(init, donate_argnums=(0, 1))
