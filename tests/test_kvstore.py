"""Durable prefix store (ISSUE 17, infer/kvstore.py): the persistent
KV tier below host/peer cache — demote-on-host-evict through a
background writer, peer -> store probe order with hits landing through
the normal ``import_host_blocks`` promote path, envelope refusal at
the store boundary (truncated / CRC-bad / fingerprint-skewed entries
GC'd, never promoted), write-tmp+rename torn-write invisibility, and
TTL + size-budget janitor lifecycle.

Fast tier: jax-free backend/store/pool units plus ONE tiny-ring
bf16/tp1 restart-warm-hit leg.  The int8 x tp2 x fleet-restart matrix
rides ``-m slow``; the dryrun ``serve-kvstore`` line carries the
store-hit ≡ cold invariant every run.  ``SERVE_KV_STORE`` unset must
stay byte-identical to the store-less ring (regression-pinned here and
by the test_serve_metrics key-set pins).
"""

import os
import time

import numpy as np
import pytest

from paddle_operator_tpu.infer.kvstore import (
    KVBlockStore,
    DirBackend,
    parse_store_url,
)
from paddle_operator_tpu.infer.paged import PagedCacheManager
from paddle_operator_tpu.utils import fleetkv as FK
from paddle_operator_tpu.utils.radixkey import chain_key

MAX_LEN = 64
BS = 8

FP = {"layers": 2, "kvHeads": 1, "headDim": 4, "blockSize": BS,
      "quant": "none", "specK": 0}


def _payload(seed=0):
    rng = np.random.default_rng(seed)
    return {"k": rng.standard_normal((2, 1, BS, 4)).astype(np.float32),
            "v": rng.standard_normal((2, 1, BS, 4)).astype(np.float32)}


def _store(tmp_path, fp=FP, **kw):
    return KVBlockStore(DirBackend(str(tmp_path)), fingerprint=fp, **kw)


def _put_chain(store, tokens, n_blocks, seed=10):
    """Offer + flush a contiguous chain of ``n_blocks`` payloads;
    returns the chain keys."""
    keys, key = [], None
    for j in range(n_blocks):
        chunk = tuple(tokens[j * BS:(j + 1) * BS])
        key = chain_key(key, chunk)
        keys.append(key)
        store.offer(key, chunk, _payload(seed + j))
    assert store.flush(), "writer queue failed to drain"
    return keys


class TestParseUrl:
    def test_dir_scheme(self, tmp_path):
        b = parse_store_url(f"dir:{tmp_path}/kv")
        assert isinstance(b, DirBackend)
        assert os.path.isdir(b.root)

    def test_unknown_scheme_refused(self):
        with pytest.raises(ValueError, match="dir:/path"):
            parse_store_url("s3://bucket/kv")
        with pytest.raises(ValueError):
            parse_store_url("dir:")


class TestDirBackend:
    def test_negative_and_positive_keys_distinct_files(self, tmp_path):
        """Chain keys are tuple hashes — often NEGATIVE Python ints.
        The filename encodes the sign, so k and -k never collide."""
        b = DirBackend(str(tmp_path))
        b.put(0, 123, b"pos")
        b.put(0, -123, b"neg")
        assert b.path(0, 123) != b.path(0, -123)
        assert b.get(0, 123) == b"pos"
        assert b.get(0, -123) == b"neg"
        assert b.exists(0, -123)
        b.delete(0, -123)
        assert b.get(0, -123) is None
        assert b.get(0, 999) is None            # clean miss

    def test_namespaces_partition(self, tmp_path):
        b = DirBackend(str(tmp_path))
        b.put(0, 7, b"base")
        b.put(3, 7, b"adapter")
        assert b.get(0, 7) == b"base"
        assert b.get(3, 7) == b"adapter"

    def test_put_is_atomic_tmp_invisible(self, tmp_path):
        """A torn write (crash mid-put) leaves only a ``*.tmp`` orphan
        that get/entries never observe."""
        b = DirBackend(str(tmp_path))
        b.put(0, 5, b"published")
        # simulate the crash: a sibling tmp with garbage, never renamed
        torn = b.path(0, 5) + ".9999.0.tmp"
        with open(torn, "wb") as f:
            f.write(b"half-writ")
        assert b.get(0, 5) == b"published"
        assert [p for p, _, _ in b.entries()] == [b.path(0, 5)]
        # a FRESH tmp survives the sweep (a live writer owns it) ...
        assert b.sweep_tmp(max_age_s=300.0) == 0
        assert os.path.exists(torn)
        # ... an aged one is reaped
        old = time.time() - 600
        os.utime(torn, (old, old))
        assert b.sweep_tmp(max_age_s=300.0) == 1
        assert not os.path.exists(torn)

    def test_touch_refreshes_mtime(self, tmp_path):
        b = DirBackend(str(tmp_path))
        b.put(0, 1, b"x")
        old = time.time() - 500
        os.utime(b.path(0, 1), (old, old))
        b.touch(0, 1)
        assert abs(os.stat(b.path(0, 1)).st_mtime - time.time()) < 60


class TestStoreWriteRead:
    def test_offer_flush_fetch_roundtrip_bit_exact(self, tmp_path):
        s = _store(tmp_path)
        toks = list(range(100, 100 + 3 * BS))
        _put_chain(s, toks, 3)
        assert s.stats["puts"] == 3
        chunks, idx, payloads, fp = s.fetch(toks, BS)
        assert idx == [0, 1, 2]
        assert chunks == [toks[:BS], toks[BS:2 * BS], toks[2 * BS:]]
        assert fp == FP
        for j, p in zip(idx, payloads):
            want = _payload(10 + j)
            assert np.array_equal(p["k"], want["k"])
            assert np.array_equal(p["v"], want["v"])
        assert s.stats["hits"] == 1 and s.stats["blocks_fetched"] == 3
        assert s.hit_rate() == 1.0
        blocks, nbytes = s.usage()
        assert blocks == 3 and nbytes > 0
        s.close()

    def test_same_key_offered_twice_writes_once(self, tmp_path):
        s = _store(tmp_path)
        toks = list(range(200, 200 + BS))
        _put_chain(s, toks, 1)
        _put_chain(s, toks, 1)          # same chain: touch, not rewrite
        assert s.stats["puts"] == 1
        assert s.usage()[0] == 1
        s.close()

    def test_offer_backpressure_drops_oldest(self, tmp_path):
        s = _store(tmp_path, queue_len=2)
        s._writer = object()            # pin the writer: queue only
        for j in range(4):
            s.offer(100 + j, (j,), _payload(j))
        assert s.stats["put_drops"] == 2
        # the two NEWEST offers survive (the shed ones were coldest)
        assert [k for _, k, _, _ in s._q] == [102, 103]

    def test_adapter_namespace_abstains(self, tmp_path):
        s = _store(tmp_path)
        toks = list(range(300, 300 + BS))
        _put_chain(s, toks, 1)
        chunks, idx, payloads, _fp = s.fetch(toks, BS, ns=3)
        assert (chunks, idx, payloads) == ([], [], [])
        s.close()

    def test_fetch_skip_and_contiguity_break(self, tmp_path):
        s = _store(tmp_path)
        toks = list(range(400, 400 + 3 * BS))
        keys = _put_chain(s, toks, 3)
        _, idx, _, _ = s.fetch(toks, BS, skip=1)
        assert idx == [1, 2]            # caller covers block 0 locally
        # a hole ends the probe: deeper blocks would be parent-gapped
        s.backend.delete(0, keys[1])
        _, idx, _, _ = s.fetch(toks, BS)
        assert idx == [0]
        s.close()

    def test_partial_trailing_tokens_ignored(self, tmp_path):
        s = _store(tmp_path)
        toks = list(range(500, 500 + BS))
        _put_chain(s, toks, 1)
        chunks, idx, _, _ = s.fetch(toks + [1, 2, 3], BS)
        assert idx == [0] and chunks == [toks]
        assert s.fetch([1, 2], BS)[1] == []     # sub-block prompt
        s.close()


class TestRefusalAtStoreBoundary:
    """Satellite 3: everything the envelope refuses, the store refuses
    WHOLESALE and garbage-collects — a store can never poison a ring."""

    def _one_entry(self, tmp_path):
        s = _store(tmp_path)
        toks = list(range(600, 600 + BS))
        keys = _put_chain(s, toks, 1)
        return s, toks, keys[0]

    def test_truncated_file_refused_and_gcd(self, tmp_path):
        s, toks, key = self._one_entry(tmp_path)
        path = s.backend.path(0, key)
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[:len(blob) // 2])
        _, idx, payloads, _ = s.fetch(toks, BS)
        assert idx == [] and payloads == []
        assert s.stats["refused"] == 1
        assert not os.path.exists(path), "refused entry must be GC'd"
        s.close()

    def test_crc_corruption_refused_and_gcd(self, tmp_path):
        s, toks, key = self._one_entry(tmp_path)
        path = s.backend.path(0, key)
        blob = bytearray(open(path, "rb").read())
        blob[-3] ^= 0xFF                # flip a payload byte
        with open(path, "wb") as f:
            f.write(bytes(blob))
        _, idx, _, _ = s.fetch(toks, BS)
        assert idx == [] and s.stats["refused"] == 1
        assert not os.path.exists(path)
        s.close()

    def test_fingerprint_skew_refused_and_gcd(self, tmp_path):
        """An entry persisted by a differently-shaped ring (layer
        count, quant mode...) is refused LOUDLY and GC'd — never
        silently promoted into a mismatched pool."""
        s, toks, key = self._one_entry(tmp_path)
        s.close()
        skewed = KVBlockStore(DirBackend(str(tmp_path)),
                              fingerprint=dict(FP, quant="int8"))
        _, idx, _, _ = skewed.fetch(toks, BS)
        assert idx == [] and skewed.stats["refused"] == 1
        assert not skewed.backend.exists(0, key)

    def test_wrong_name_identity_refused(self, tmp_path):
        """A file placed under another chain key's name (operator
        mis-copy on the shared volume) fails the key/chunk identity
        check — the wrong tokens can never serve."""
        import shutil

        s, toks, key = self._one_entry(tmp_path)
        other = chain_key(None, tuple(range(700, 700 + BS)))
        dst = s.backend.path(0, other)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copyfile(s.backend.path(0, key), dst)
        _, idx, _, _ = s.fetch(list(range(700, 700 + BS)), BS)
        assert idx == [] and s.stats["refused"] == 1
        assert not os.path.exists(dst)
        s.close()

    def test_crash_mid_write_invisible_to_readers(self, tmp_path):
        """A torn ``*.tmp`` next to a chain position reads as a clean
        MISS (not a refusal): the probe sees nothing at that key."""
        s = _store(tmp_path)
        toks = list(range(800, 800 + BS))
        key = chain_key(None, tuple(toks))
        final = s.backend.path(0, key)
        os.makedirs(os.path.dirname(final), exist_ok=True)
        with open(final + ".123.0.tmp", "wb") as f:
            f.write(b"torn half-envelope")
        _, idx, _, _ = s.fetch(toks, BS)
        assert idx == [] and s.stats["refused"] == 0
        assert s.usage() == (0, 0)
        s.close()


class TestJanitor:
    def test_ttl_expires_by_last_touch(self, tmp_path):
        s = _store(tmp_path, ttl_s=100.0)
        toks = list(range(900, 900 + 2 * BS))
        keys = _put_chain(s, toks, 2)
        old = time.time() - 500
        os.utime(s.backend.path(0, keys[0]), (old, old))
        out = s.janitor()
        assert out["expired"] == 1 and s.evictions() == 1
        assert not s.backend.exists(0, keys[0])
        assert s.backend.exists(0, keys[1])
        s.close()

    def test_budget_evicts_lru_by_last_touch(self, tmp_path):
        s = _store(tmp_path, budget_mb=1)
        # four ~0.45MB entries = ~1.8MB resident, budget 1MB: the
        # janitor must evict exactly the two coldest
        arr = np.zeros((28000,), np.float64)        # 224KB per array
        keys, key = [], None
        for j in range(4):
            chunk = tuple(range(j * BS, (j + 1) * BS))
            key = chain_key(key, chunk)
            keys.append(key)
            s.offer(key, chunk, {"k": arr, "v": arr})
        assert s.flush()
        # touch order: keys[1] coldest, then 0, 2, 3
        now = time.time()
        for rank, j in enumerate([1, 0, 2, 3]):
            t = now - 400 + rank * 100
            os.utime(s.backend.path(0, keys[j]), (t, t))
        out = s.janitor()
        assert out["budget_evicted"] == 2           # down to <= 1MB
        assert s.evictions() == 2
        assert not s.backend.exists(0, keys[1])     # LRU went first
        assert not s.backend.exists(0, keys[0])
        assert s.backend.exists(0, keys[2])
        assert s.backend.exists(0, keys[3])
        assert s.usage()[1] <= 1 << 20
        s.close()

    def test_janitor_cli_one_pass(self, tmp_path, capsys):
        from paddle_operator_tpu.infer.kvstore import _janitor_main

        s = _store(tmp_path)
        _put_chain(s, list(range(1100, 1100 + BS)), 1)
        s.close()
        rc = _janitor_main([f"dir:{tmp_path}", "--ttl-s", "0"])
        assert rc == 0
        assert "1 blocks" in capsys.readouterr().out


def _mgr(**kw):
    kw.setdefault("slots", 1)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("block_size", BS)
    kw.setdefault("num_blocks", 8)
    kw.setdefault("host_cache_blocks", 2)
    m = PagedCacheManager(**kw)
    m.demote_fetch = lambda blk: {"k": np.full((4,), blk, np.float32),
                                  "v": np.full((4,), blk, np.float32)}
    return m


def _churn(m, base, n_blocks=8):
    """Serve one throwaway chain to pressure-demote prior residents
    (8 blocks = the whole pool: every prior cached block demotes)."""
    P = list(range(base, base + n_blocks * BS))
    m.admit(0, P)
    m.publish(0, P)
    m.retire(0)


class TestPoolSpill:
    """Satellite 2: the silent-overflow asymmetry fix — with a store
    attached an overflow-dropped radix node survives store-resident;
    without one, behavior stays byte-identical to the pre-store pool."""

    def test_overflow_spills_to_store_node_survives(self, tmp_path):
        m = _mgr()
        store = _store(tmp_path, fp=None)
        m.attach_store(store)
        P = list(range(100, 124))               # 3 full blocks
        m.admit(0, P)
        m.publish(0, P)
        m.retire(0)
        _churn(m, 900)                       # demotes P: 3 into cap-2
        assert m.host_evictions() >= 1
        assert m.stats["store_spills"] >= 1
        assert store.flush()
        assert store.stats["puts"] >= 1
        # the dropped node SURVIVES at block=None, stored=True ...
        stored = [e for e in m.entries.values()
                  if e.block is None and e.stored]
        assert stored, "overflow drop must leave a store-resident node"
        # ... and is NOT servable (admit would have nothing to promote)
        assert all(not m._servable(e) for e in stored)
        m.check_invariant()
        store.close()

    def test_store_off_overflow_drops_node_regression_pin(self):
        """``SERVE_KV_STORE`` unset: the overflow-dropped node is
        retired exactly as before — no ``stored`` entries can exist
        (check_invariant asserts it)."""
        m = _mgr()
        P = list(range(100, 124))
        m.admit(0, P)
        m.publish(0, P)
        m.retire(0)
        _churn(m, 900)
        assert m.host_evictions() >= 1
        assert m.stats["store_spills"] == 0
        assert not any(e.stored for e in m.entries.values())
        m.check_invariant()                     # asserts no stored keys

    def test_import_refills_store_resident_node(self, tmp_path):
        """A store hit lands through import_host_blocks: the
        store-resident node refills into the host tier
        (``stored=False``), counts ``store_refills``, and the admit
        host-hits — the normal ISSUE 8 promote path."""
        m = _mgr(host_cache_blocks=8)
        store = _store(tmp_path, fp=None)
        m.attach_store(store)
        P = list(range(100, 124))
        m.admit(0, P)
        m.publish(0, P)
        m.retire(0)
        m.host.capacity = 1                     # squeeze: force overflow
        _churn(m, 900)
        assert store.flush()
        stored_keys = [e.key for e in m.entries.values()
                       if e.block is None and e.stored]
        assert stored_keys
        m.host.capacity = 8                     # room to refill
        # the scheduler-probe shape: skip the locally-servable prefix,
        # fetch the store-resident rest.  The one payload the cap-1
        # tier kept may be ANY chain block (eviction order), so a
        # tier-resident middle block breaks on-disk contiguity — loop
        # the probe like successive scheduler walks until it dries up.
        imported = 0
        while True:
            covered, key = 0, None
            for j in range(3):
                key = m._chain_key(key, tuple(P[j * BS:(j + 1) * BS]))
                e = m.entries.get(key)
                if e is None or not m._servable(e):
                    break
                covered += 1
            if covered == 3:
                break
            chunks, idx, payloads, _fp = store.fetch(P, BS, skip=covered)
            assert idx, "spilled chain must be fetchable"
            imported += m.import_host_blocks(chunks, idx, payloads)
        assert imported == len(stored_keys)
        assert m.stats["store_refills"] >= 1
        assert not any(e.stored for e in m.entries.values()
                       if e.key in stored_keys)
        m.check_invariant()
        hit_len, _ = m.admit(0, P)
        assert hit_len == len(P) - 1            # full host hit
        assert m.take_promotions()
        m.retire(0)
        m.check_invariant()
        store.close()

    def test_scrub_host_chain_deletes_store_copies(self, tmp_path):
        """Satellite 4 (fault-tolerance doc note): quarantine scrubs
        the lane's STORE-resident chain like the host tier — a suspect
        prefix must not warm-hit a future restart."""
        m = _mgr()
        store = _store(tmp_path, fp=None)
        m.attach_store(store)
        P = list(range(100, 124))
        m.admit(0, P)
        m.publish(0, P)
        m.retire(0)
        _churn(m, 900)
        assert store.flush()
        assert store.usage()[0] >= 1
        m.scrub_host_chain(P)
        # every chain copy is gone from disk AND no node resurrects
        chunks, idx, _, _ = store.fetch(P, BS)
        assert idx == []
        assert not any(e.stored for e in m.entries.values())
        m.check_invariant()
        store.close()

    def test_publish_reanchors_store_resident_node(self, tmp_path):
        """A re-prefilled chain re-publishes over its store-resident
        node: the node re-anchors device-side (stored=False) instead
        of leaking a stale marker."""
        m = _mgr()
        store = _store(tmp_path, fp=None)
        m.attach_store(store)
        P = list(range(100, 124))
        m.admit(0, P)
        m.publish(0, P)
        m.retire(0)
        _churn(m, 900)
        assert any(e.stored for e in m.entries.values())
        m.admit(0, P)                   # tier blocks host-hit here
        m.take_promotions()             # drain, as the ring loop does
        m.publish(0, P)
        m.retire(0)
        assert not any(e.stored for e in m.entries.values()
                       if e.block is not None)
        m.check_invariant()
        store.close()

    def test_adapter_namespace_never_spills(self, tmp_path):
        m = _mgr()
        store = _store(tmp_path, fp=None)
        m.attach_store(store)
        ns = 5
        P = list(range(100, 124))
        m.admit(0, P, ns=ns)
        m.publish(0, P, ns=ns)
        m.retire(0)
        _churn(m, 900)
        assert store.flush()
        # adapter-chain payloads never persist; their dropped nodes
        # retire exactly as with the store off
        assert not any(e.stored for e in m.entries.values() if e.ns)
        assert store.stats["puts"] == store.usage()[0]
        for e in list(m.entries.values()):
            assert not (e.ns and e.stored)
        m.check_invariant()
        store.close()


class TestRouterConsult:
    """The jax-free router-side consult: a ring-less (fingerprint=None)
    store serves a standard prefix envelope stamped with the entries'
    own fingerprint — the replica's check_fingerprint stays the last
    word."""

    def test_fetch_prefix_envelope_roundtrip(self, tmp_path):
        s = _store(tmp_path)                    # ring-side: writes FP
        toks = list(range(1200, 1200 + 2 * BS))
        _put_chain(s, toks, 2)
        s.close()
        router_store = KVBlockStore(DirBackend(str(tmp_path)),
                                    fingerprint=None)
        buf = router_store.fetch_prefix_envelope(toks, BS)
        assert buf is not None
        meta, chunks, idx, payloads = FK.decode_prefix(buf)
        assert meta["fingerprint"] == FP        # stamped from entries
        FK.check_fingerprint(meta, FP)          # replica-side gate
        assert idx == [0, 1] and len(payloads) == 2
        assert router_store.fetch_prefix_envelope(
            list(range(5000, 5000 + BS)), BS) is None   # clean miss

    def test_router_import_is_jax_free(self):
        import subprocess
        import sys

        code = ("import sys; "
                "import paddle_operator_tpu.infer.kvstore; "
                "import paddle_operator_tpu.router.router; "
                "sys.exit(1 if 'jax' in sys.modules else 0)")
        assert subprocess.run([sys.executable, "-c", code]).returncode \
            == 0, "router + kvstore import must not drag in jax"


# ---------------------------------------------------------------------------
# Ring legs: store hit ≡ cold, restart warm start
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models.llama import make_model

    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _ring(cfg, params, **kw):
    from paddle_operator_tpu.infer.scheduler import ContinuousBatcher

    kw.setdefault("slots", 1)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    kw.setdefault("num_blocks", 8)
    # cap 4: small enough that two churn prompts push a 3-block chain
    # fully out to the store, big enough to land the 3-block refill
    kw.setdefault("host_cache_blocks", 4)
    return ContinuousBatcher(params, cfg, **kw)


def _attach(b, tmp_path, **kw):
    store = KVBlockStore(DirBackend(str(tmp_path)),
                         fingerprint=b._fingerprint(), **kw)
    b.attach_kv_store(store)
    return store


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, cfg.vocab_size, (n,))]


class TestStoreRing:
    """bf16/tp1 fast legs (ISSUE 9 budget discipline: the int8 x tp2 x
    restart matrix rides -m slow; the dryrun serve-kvstore line pins
    store-hit ≡ cold every run)."""

    def _spill_corpus(self, b, store, cfg):
        """Cold-serve P, then pressure it out of host into the store;
        returns (P, cold_tokens, new)."""
        P = _prompt(cfg, 24, seed=1)            # 3 full blocks
        new = 6
        cold = b.submit(P, max_new_tokens=new).result(timeout=300)
        # demote P (pool pressure), then overflow the cap-2 tier so
        # P's whole chain lands on disk
        b.submit(_prompt(cfg, 56, seed=2),
                 max_new_tokens=4).result(timeout=300)
        b.submit(_prompt(cfg, 56, seed=3),
                 max_new_tokens=4).result(timeout=300)
        assert b.pool.stats["host_demotions"] >= 3
        assert b.pool.stats["store_spills"] >= 3
        assert store.flush()
        return P, cold, new

    def test_restart_warm_hit_identical_to_cold(self, setup, tmp_path):
        """THE tentpole invariant: a fresh ring on the same store dir
        (fleet restart) serves the persisted prefix through
        peer -> store probe + import + batched promote, with the SAME
        stream as the cold serve — a store hit is bit-identical to
        cold prefill."""
        cfg, params = setup
        A = _ring(cfg, params)
        store_a = _attach(A, tmp_path)
        try:
            P, cold, new = self._spill_corpus(A, store_a, cfg)
        finally:
            A.close()
            store_a.close()
        B = _ring(cfg, params)                  # the restart
        store_b = _attach(B, tmp_path)
        try:
            got = B.submit(P, max_new_tokens=new,
                           request_id="kvs/row0").result(timeout=300)
            assert got == cold, "store-hit stream diverged from cold"
            assert B.stats["kv_store_probes"] >= 1
            assert B.stats["kv_store_hits"] == 1
            assert store_b.stats["blocks_fetched"] >= 3
            assert B.pool.stats["peer_blocks_imported"] >= 3
            assert B.pool.stats["host_promotions"] >= 3
            B.pool.check_invariant()
            st = B.serving_status()
            assert st["kvStoreBlocks"] >= 3
            assert st["kvStoreHitRate"] > 0
        finally:
            B.close()
            store_b.close()

    def test_live_ring_reprobe_of_spilled_chain(self, setup, tmp_path):
        """Satellite 2, ring leg: the SAME ring re-asks a prompt whose
        chain overflowed out of its own host tier — the store-resident
        nodes re-probe the store instead of re-prefilling blind."""
        cfg, params = setup
        b = _ring(cfg, params)
        store = _attach(b, tmp_path)
        try:
            P, cold, new = self._spill_corpus(b, store, cfg)
            assert any(e.stored for e in b.pool.entries.values())
            got = b.submit(P, max_new_tokens=new,
                           request_id="kvs/row1").result(timeout=300)
            assert got == cold
            assert b.stats["kv_store_hits"] >= 1
            assert b.pool.stats["store_refills"] >= 1
            b.pool.check_invariant()
        finally:
            b.close()
            store.close()

    def test_no_store_ring_byte_identical(self, setup):
        """Regression pin: with no store attached the ring runs the
        pre-PR paths — no probes, no stored nodes, zero status keys."""
        cfg, params = setup
        b = _ring(cfg, params)
        try:
            P = _prompt(cfg, 24, seed=1)
            b.submit(P, max_new_tokens=4).result(timeout=300)
            b.submit(_prompt(cfg, 56, seed=2),
                     max_new_tokens=4).result(timeout=300)
            assert b.stats["kv_store_probes"] == 0
            assert b.pool.stats["store_spills"] == 0
            assert not any(e.stored for e in b.pool.entries.values())
            st = b.serving_status()
            assert st["kvStoreBlocks"] == 0
            assert st["kvStoreHitRate"] == 0.0
            b.pool.check_invariant()
        finally:
            b.close()

    def test_attach_requires_host_tier(self, setup):
        cfg, params = setup
        b = _ring(cfg, params, host_cache_blocks=0)
        try:
            with pytest.raises(ValueError, match="host cache"):
                b.attach_kv_store(
                    KVBlockStore(DirBackend("/tmp/unused-kvs")))
        finally:
            b.close()


class TestStoreRingSlow:
    """The int8 x tp2 x fleet-restart matrix (dryrun serve-kvstore
    carries the fast invariants every run)."""

    @pytest.mark.slow
    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_tp2_restart_warm_hit_parity(self, setup, tmp_path,
                                         kv_quant):
        import jax.numpy as jnp

        from paddle_operator_tpu.models.llama import make_model
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        _, params = setup
        _, cfg = make_model("tiny", dtype=jnp.float32,
                            decode_attn="pallas-interpret")
        mesh = make_serving_mesh(2)

        def ring(cap):
            return _ring(cfg, params, block_size=16, num_blocks=4,
                         prefill_buckets=(16, MAX_LEN), mesh=mesh,
                         kv_quant=kv_quant, host_cache_blocks=cap)

        A = ring(1)                     # cap 1: every demote overflows
        store_a = _attach(A, tmp_path)
        try:
            P = _prompt(cfg, 33, seed=5)        # 2 full 16-blocks
            cold = A.submit(P, max_new_tokens=6).result(timeout=600)
            A.submit(_prompt(cfg, 56, seed=6),
                     max_new_tokens=6).result(timeout=600)
            A.submit(_prompt(cfg, 56, seed=7),
                     max_new_tokens=6).result(timeout=600)
            assert A.pool.stats["store_spills"] >= 2
            assert store_a.flush()
        finally:
            A.close()
            store_a.close()
        B = ring(4)                     # cap 4: the 2-block refill must land
        store_b = _attach(B, tmp_path)
        try:
            got = B.submit(P, max_new_tokens=6).result(timeout=600)
            assert got == cold, \
                f"tp=2 {kv_quant} restart store-hit diverged"
            assert B.stats["kv_store_hits"] >= 1
            assert B.pool.stats["host_promotions"] >= 2
            if kv_quant == "int8":
                # int8 payloads persist codes+scales at roughly half
                # the bf16 bytes per block
                blocks, nbytes = store_b.usage()
                assert blocks >= 2
            B.pool.check_invariant()
        finally:
            B.close()
            store_b.close()
