"""Hierarchical KV cache (ISSUE 8, infer/paged.py HostCacheTier): the
host-RAM spill tier behind the radix prefix cache — demote-on-evict,
promote-on-hit with BYTE-exact payloads (bf16 rows, or int8 codes +
scales — a promote is a copy, never a re-quantize), the extended pool
invariant across demote/promote, chaos/drain composition with the tier
enabled, quarantine scrubbing the lane's host-resident chain, and the
``spill_lane``/``restore_lane`` preemption primitive resuming
bit-identically (the building block ROADMAP items 4/5 consume).
``host_cache_blocks=0`` (the default) must stay byte-identical to the
tier-less ring.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer.executor import RingExecutor
from paddle_operator_tpu.infer.paged import HostCacheTier
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import make_model

MAX_LEN = 64
BS = 8


@pytest.fixture(scope="module")
def setup():
    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, cfg, params


def _prompt(cfg, s, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (s,), 0, cfg.vocab_size,
        dtype=jnp.int32))


def _batcher(cfg, params, **kw):
    kw.setdefault("slots", 1)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    # two buckets, not four: every fresh ring compiles one insert per
    # bucket, and this file builds many rings — tier-1 budget
    kw.setdefault("prefill_buckets", (32, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    kw.setdefault("num_blocks", 8)          # one worst-case lane
    kw.setdefault("host_cache_blocks", 16)
    return ContinuousBatcher(params, cfg, **kw)


def _ref(params, cfg, prompt, new):
    return np.asarray(D.generate(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=new, max_len=MAX_LEN)[0]).tolist()


class TestHostTierUnit:
    """The bounded host ring itself — pure host code, no jax."""

    def test_lru_overflow_drops_oldest_and_returns_keys(self):
        t = HostCacheTier(2)
        assert t.put("a", {"x": 1}) == []
        assert t.put("b", {"x": 2}) == []
        assert t.put("c", {"x": 3}) == ["a"]     # capacity 2: a ages out
        assert "a" not in t and "b" in t and "c" in t
        t.put("b", {"x": 2})                     # re-put refreshes age
        assert t.put("d", {"x": 4}) == ["c"]     # c is now the oldest
        assert len(t) == 2
        assert t.stats["overflow_drops"] == 2

    def test_pop_moves_payload_out(self):
        t = HostCacheTier(4)
        t.put("a", {"x": 1})
        assert t.pop("a") == {"x": 1}
        assert "a" not in t
        assert t.stats["promoted"] == 1

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError, match="host_cache_blocks=0"):
            HostCacheTier(0)


class TestPinnedChainUnderPressure:
    """Review regression: an eviction-triggered demotion INSIDE a
    host-hit admission overflow-dropping the very payload the promotion
    is about to pop (KeyError, lane left half-mapped).  The admission
    pins its chain; the tier may exceed its bound by the chain length
    until the admit's finally trims it back."""

    def test_promotion_survives_tier_overflow_pressure(self):
        from paddle_operator_tpu.infer.paged import PagedCacheManager

        mgr = PagedCacheManager(slots=2, max_len=32, block_size=8,
                                num_blocks=4, host_cache_blocks=2)
        mgr.demote_fetch = lambda blk: {"blk": blk}     # host-only stub
        A = list(range(16))                              # 2 blocks
        mgr.admit(0, A)
        mgr.publish(0, A)
        mgr.retire(0)
        C = [50 + i for i in range(32)]                  # 4 blocks
        mgr.admit(0, C)          # demotes A's chain; tier now FULL
        mgr.publish(0, C)
        mgr.retire(0)
        assert mgr.host_blocks() == 2 and mgr.blocks_free() == 0
        # the host hit: every promotion alloc must demote one of C's
        # cached blocks into the full tier — without pinning, the LRU
        # overflow would drop A's own about-to-be-popped payloads
        hit_len, cow = mgr.admit(1, A)
        assert hit_len == 15 and len(cow) == 1
        assert mgr.stats["host_promotions"] == 2
        promotes = mgr.take_promotions()
        assert len(promotes) == 2
        assert len(mgr.host) <= mgr.host.capacity        # trimmed back
        mgr.check_invariant()
        mgr.retire(1)
        mgr.check_invariant()


class TestDemotePromote:
    """The tentpole flow: eviction demotes instead of discarding, a
    later admission hits the host tier and promotes byte-exactly."""

    def _record_demotions(self, b):
        """Wrap the executor's demote hook to keep each demoted
        payload keyed by its chain key (captured BEFORE by_block is
        unanchored)."""
        orig = b.pool.demote_fetch
        recorded = {}

        def rec(blk):
            payload = orig(blk)
            # FIRST demotion only: a re-demoted block's fresh payload
            # must then equal this original (host->device->host is a
            # byte identity), which the comparison below checks
            recorded.setdefault(b.pool.by_block[blk], payload)
            return payload

        b.pool.demote_fetch = rec
        return recorded

    # ISSUE 9 budget: the bf16 leg joins int8 in the slow tier — the
    # dryrun serve-hostcache line pins host-hit ≡ HBM-hit ≡ cold at
    # tp=1/tp=2 × quant off/on every run
    @pytest.mark.parametrize("kv_quant", [
        pytest.param("none", marks=pytest.mark.slow),
        pytest.param("int8", marks=pytest.mark.slow)])
    def test_host_hit_bit_identical_and_payload_exact(self, setup,
                                                      kv_quant):
        """Cold -> demote (pool pressure) -> host hit: the host-hit
        token stream equals the cold AND the HBM-hit stream, and every
        promoted block's device bytes equal its demoted payload bit for
        bit (codes AND scales under int8 — promote never re-quantizes)."""
        _, cfg, params = setup
        b = _batcher(cfg, params, kv_quant=kv_quant)
        try:
            ex = b.executor
            recorded = self._record_demotions(b)
            A = _prompt(cfg, 24, seed=1)          # 3 full blocks
            new = 6
            cold = b.submit(A, max_new_tokens=new).result(timeout=300)
            if kv_quant == "none":
                assert cold == _ref(params, cfg, A, new)
            hbm_hit = b.submit(A, max_new_tokens=new).result(timeout=300)
            assert hbm_hit == cold
            b.pool.check_invariant()
            # pressure: a prompt needing 8 blocks demotes A's chain
            Bp = _prompt(cfg, 56, seed=2)
            b.submit(Bp, max_new_tokens=6).result(timeout=300)
            assert b.pool.stats["host_demotions"] >= 3
            assert b.pool.host_blocks() >= 3
            b.pool.check_invariant()
            # host hit: A promotes back, stream unchanged
            host_hit = b.submit(A, max_new_tokens=new).result(timeout=300)
            assert host_hit == cold, "host hit diverged from cold/HBM"
            assert b.pool.stats["host_promotions"] >= 3
            assert b.stats["promoted_blocks"] >= 3
            assert b.pool.host_hit_rate() > 0
            b.pool.check_invariant()
            # byte-exactness: every recorded demotion is either
            # re-anchored on device (promoted — its pool bytes must
            # equal the payload) or back in the host tier (possibly
            # RE-demoted after its promotion — the tier payload must
            # equal the original, proving the host->device->host
            # roundtrip is a byte identity)
            checked = 0
            for key, payload in recorded.items():
                e = b.pool.entries.get(key)
                if e is None:
                    continue
                if e.block is not None:
                    c = ex.cache
                    if ex.quant:
                        got = ex._fetch_prog(c["k"], c["v"], c["ks"],
                                             c["vs"], e.block)
                        names = ("k", "v", "ks", "vs")
                    else:
                        got = ex._fetch_prog(c["k"], c["v"], e.block)
                        names = ("k", "v")
                    for name, arr in zip(names, got):
                        np.testing.assert_array_equal(
                            np.asarray(arr), payload[name])
                else:
                    roundtrip = b.pool.host._data[key]
                    for name in payload:
                        np.testing.assert_array_equal(
                            roundtrip[name], payload[name])
                checked += 1
            assert checked >= 3, "no demoted block was byte-checked"
        finally:
            b.close()

    def test_tier_off_default_is_tierless(self, setup):
        """host_cache_blocks=0 (the default): no tier exists, eviction
        discards exactly as before, and the status block reports
        zeros — the byte-identical-default guarantee."""
        _, cfg, params = setup
        b = _batcher(cfg, params, host_cache_blocks=0)
        try:
            assert b.pool.host is None
            A = _prompt(cfg, 24, seed=1)
            want = _ref(params, cfg, A, 6)
            assert b.submit(A, max_new_tokens=6).result(timeout=300) == want
            b.submit(_prompt(cfg, 56, seed=2),
                     max_new_tokens=6).result(timeout=300)
            assert b.pool.stats["host_demotions"] == 0
            assert b.pool.stats["cache_evictions"] >= 3   # discarded
            # re-admission is COLD (the prefix was discarded, not spilled)
            calls0 = b.stats["prefill_tokens"]
            assert b.submit(A, max_new_tokens=6).result(timeout=300) == want
            assert b.stats["prefill_tokens"] - calls0 == 24
            st = b.serving_status()
            assert st["hostCacheBlocks"] == 0
            assert st["hostHitRate"] == 0.0
            assert st["promotedBlocks"] == 0
            b.pool.check_invariant()
        finally:
            b.close()

    def test_host_tier_bounded_with_radix_retirement(self, setup):
        """Tier overflow drops the OLDEST payload and retires its radix
        node: a re-admission of the dropped prefix is cold again, and
        the extended invariant holds throughout."""
        _, cfg, params = setup
        b = _batcher(cfg, params, host_cache_blocks=2)
        try:
            A = _prompt(cfg, 24, seed=1)            # 3 full blocks
            b.submit(A, max_new_tokens=6).result(timeout=300)
            b.submit(_prompt(cfg, 56, seed=2),
                     max_new_tokens=6).result(timeout=300)
            assert b.pool.host_blocks() <= 2         # bound respected
            assert b.pool.host.stats["overflow_drops"] >= 1
            b.pool.check_invariant()
        finally:
            b.close()


class TestHostChaosLifecycle:
    """Chaos + drain with the tier enabled: seeded dispatch-fail ->
    nan_lane -> client_drop -> drain, every request resolving exactly
    once and the EXTENDED invariant (host-tier accounting included)
    holding across demote/promote traffic."""

    # int8 chaos rides behind -m slow for the tier-1 budget (PR 6/7
    # convention): its fast-path invariants — int8 host-hit parity,
    # extended pool invariant, tier-off default — stay pinned every
    # run by the dryrun serve-hostcache line and the fast bf16 chaos
    @pytest.mark.parametrize("kv_quant", [
        "none", pytest.param("int8", marks=pytest.mark.slow)])
    def test_chaos_then_drain_exactly_once(self, setup, kv_quant):
        from paddle_operator_tpu.infer.chaos import (
            ChaosEvent,
            ChaosInjector,
        )
        from paddle_operator_tpu.infer.resilience import (
            LaneQuarantined,
            RetriableError,
            RingResilience,
            ShuttingDown,
        )

        _, cfg, params = setup
        b = _batcher(cfg, params, kv_quant=kv_quant,
                     resilience=RingResilience(
                         watchdog=False, nan_check=True, max_restarts=4,
                         backoff_base_s=0.01))
        try:
            A = _prompt(cfg, 24, seed=1)
            want = b.submit(A, max_new_tokens=6).result(timeout=300)
            # demote A's chain, then hit it from host mid-chaos
            b.submit(_prompt(cfg, 56, seed=2),
                     max_new_tokens=6).result(timeout=300)
            assert b.pool.stats["host_demotions"] >= 3
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            inj.events[nxt + 2] = [ChaosEvent("dispatch_fail", nxt + 2)]
            inj.events[nxt + 14] = [ChaosEvent("nan_lane", nxt + 14, 0)]
            outcomes = []
            for i in range(6):
                p = A if i % 2 == 0 else _prompt(cfg, 13, seed=20 + i)
                h = b.submit(p, max_new_tokens=6)
                if i == 4:
                    h.cancel()                      # client drop
                try:
                    out = h.result(timeout=300)
                    outcomes.append("ok")
                    assert isinstance(out, list)
                except (RetriableError, LaneQuarantined) as e:
                    outcomes.append(type(e).__name__)
            assert len(outcomes) == 6               # exactly-once
            assert "RetriableError" in outcomes     # the healed fault
            assert b.stats["watchdog_restarts"] >= 1
            assert b.healthy
            # flush any still-pending chaos event (dispatch indices
            # shift with the host-tier admission pattern) so the
            # parity probe below runs fault-free
            flushes = 0
            while inj.events and any(at >= inj.dispatches
                                     for at in inj.events) and flushes < 20:
                try:
                    b.submit(_prompt(cfg, 13, seed=50 + flushes),
                             max_new_tokens=6).result(timeout=300)
                except (RetriableError, LaneQuarantined):
                    pass
                flushes += 1
            # post-heal the ring serves bit-identically again (the
            # rebuild dropped the host tier with the allocator — the
            # re-walk is cold but exact).  One LaneQuarantined retry is
            # absorbed: a nan_lane whose victim request ended before
            # detection frees the poisoned block unscrubbbed, and the
            # NEXT occupant of that block quarantines instead (the
            # quarantine scrub then cleans it — the retry must match)
            try:
                got = b.submit(A, max_new_tokens=6).result(timeout=300)
            except LaneQuarantined:
                got = b.submit(A, max_new_tokens=6).result(timeout=300)
            assert got == want
            b.pool.check_invariant()
            # drain composes: residents finish, blocks return
            b.drain(budget_s=10.0)
            with pytest.raises(ShuttingDown):
                b.submit(A, max_new_tokens=2)
        finally:
            b.close()

    def test_quarantine_scrubs_host_chain(self, setup):
        """A quarantined lane's host-resident chain payloads are
        dropped (an opaque host blob cannot be re-verified after a NaN
        fault) and the prefix re-prefills cold afterwards."""
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            A = _prompt(cfg, 24, seed=1)
            b.submit(A, max_new_tokens=6).result(timeout=300)
            b.submit(_prompt(cfg, 56, seed=2),
                     max_new_tokens=6).result(timeout=300)
            demoted = b.pool.host_blocks()
            assert demoted >= 3
            # simulate the quarantine hygiene pass for a request whose
            # prompt chain is host-resident (the _consume quarantine
            # path calls exactly this)
            dropped = b.pool.scrub_host_chain(A)
            assert dropped >= 3
            assert b.pool.host_blocks() == demoted - dropped
            b.pool.check_invariant()
            # the prefix is cold again: no host promotion on re-admit
            promos0 = b.pool.stats["host_promotions"]
            toks0 = b.stats["prefill_tokens"]
            b.submit(A, max_new_tokens=6).result(timeout=300)
            assert b.pool.stats["host_promotions"] == promos0
            assert b.stats["prefill_tokens"] - toks0 == 24
            b.pool.check_invariant()
        finally:
            b.close()


class TestSpillRestore:
    """The preemption primitive: spill a live lane to host, run other
    traffic, restore, and the resumed stream is bit-identical to the
    uninterrupted one (consumed by ROADMAP items 4/5)."""

    CH = 4

    def _mk_executor(self, cfg, params, kv_quant):
        return RingExecutor(
            params, cfg, slots=2, max_len=MAX_LEN, chunk_tokens=self.CH,
            prefill_buckets=(16, MAX_LEN), paged=True,
            block_size=BS, kv_quant=kv_quant)

    def _admit(self, ex, slot, p, seed=0):
        n = len(p)
        ex.pool.admit(slot, p)
        padded = np.zeros((1, 16), np.int32)
        padded[0, :n] = p
        first, _ = ex.cold_insert(16, slot, ex.pool.table, [],
                                  jnp.asarray(padded), n, 0.0, seed)
        ex.pool.publish(slot, p)
        return int(first)

    def _chunk(self, ex, slot, pos):
        ex.pool.ensure(slot, pos + self.CH)
        tbl = jnp.asarray(ex.pool.table)
        active = jnp.asarray([i == slot for i in range(2)], bool)
        ex.cache, ex.tok, toks = ex.step(ex.params, ex.cache, tbl,
                                         ex.tok, ex.temp, ex.keys,
                                         active)
        return [int(t) for t in np.asarray(toks)[:, slot]]

    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_spill_restore_bit_identical(self, setup, kv_quant):
        _, cfg, params = setup
        ex = self._mk_executor(cfg, params, kv_quant)
        p = _prompt(cfg, 13, seed=3)
        n = len(p)

        # uninterrupted reference: first token + 3 chunks
        ref = [self._admit(ex, 0, p)]
        pos = n
        for _ in range(3):
            ref += self._chunk(ex, 0, pos)
            pos += self.CH

        ex.reset_state()
        got = [self._admit(ex, 0, p)]
        pos = n
        got += self._chunk(ex, 0, pos)
        pos += self.CH
        # preempt: capture, free the lane, serve other traffic
        spill = ex.spill_lane(0)
        assert spill["pos"] == pos and spill["n_blocks"] >= 1
        ex.pool.retire(0)
        ex.pool.check_invariant()
        q = _prompt(cfg, 11, seed=9)
        self._admit(ex, 1, q, seed=9)
        self._chunk(ex, 1, len(q))
        # resume: bit-identical continuation
        ex.restore_lane(0, spill)
        ex.pool.check_invariant()
        got += self._chunk(ex, 0, pos)
        pos += self.CH
        got += self._chunk(ex, 0, pos)
        assert got == ref, f"spilled lane resumed differently ({kv_quant})"

    def test_restore_requires_empty_slot(self, setup):
        _, cfg, params = setup
        ex = self._mk_executor(cfg, params, "none")
        p = _prompt(cfg, 13, seed=3)
        self._admit(ex, 0, p)
        spill = ex.spill_lane(0)
        with pytest.raises(AssertionError, match="still holds blocks"):
            ex.restore_lane(0, spill)        # lane not retired yet


class TestHostCacheSlow:
    """Heavyweight parity matrix (dryrun serve-hostcache pins the fast
    invariants): host-hit parity under tp=2 sharding and the quantized
    pool together."""

    @pytest.mark.slow
    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_tp2_host_hit_parity(self, setup, kv_quant):
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        _, _, params = setup
        _, cfg = make_model("tiny", dtype=jnp.float32,
                            decode_attn="pallas-interpret")
        mesh = make_serving_mesh(2)
        b = _batcher(cfg, params, block_size=16, num_blocks=4,
                     prefill_buckets=(16, MAX_LEN), mesh=mesh,
                     kv_quant=kv_quant)
        try:
            A = _prompt(cfg, 33, seed=5)          # 2 full 16-blocks
            cold = b.submit(A, max_new_tokens=6).result(timeout=600)
            b.submit(_prompt(cfg, 56, seed=6),
                     max_new_tokens=6).result(timeout=600)
            assert b.pool.stats["host_demotions"] >= 1
            host_hit = b.submit(A, max_new_tokens=6).result(timeout=600)
            assert host_hit == cold, "tp=2 host hit diverged"
            assert b.pool.stats["host_promotions"] >= 1
            b.pool.check_invariant()
        finally:
            b.close()
