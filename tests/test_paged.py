"""Paged KV cache + radix prefix reuse (infer/paged.py): the block
allocator's partition invariant across admit/retire/cancel/CoW, the
radix cache's hit/CoW semantics, the paged pallas kernel against the
einsum reference, and — the tentpole gate — greedy token streams
BIT-IDENTICAL to the contiguous ring with prefix-hit admissions running
no prefill forward over cached blocks.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer.paged import (
    NoFreeBlocks,
    PagedCacheManager,
    TRASH_BLOCK,
)
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import Llama, make_model

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
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (8, 16, 32, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    return ContinuousBatcher(params, cfg, **kw)


def _ref(params, cfg, prompt, new):
    return np.asarray(D.generate(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=new, max_len=MAX_LEN)[0]).tolist()


class TestAllocator:
    """Host-side block accounting: free + mapped + cached == num_blocks
    across every lifecycle path — the no-leak/no-double-free gate."""

    def test_admit_retire_cycles(self):
        mgr = PagedCacheManager(slots=2, max_len=64, block_size=8)
        for it in range(3):
            hit, cow = mgr.admit(0, list(range(20)))
            # only the two FULL blocks publish (the 4-token tail is
            # partial), so re-admissions hit exactly 16 tokens
            assert hit == (0 if it == 0 else 16)
            mgr.check_invariant()
            mgr.publish(0, list(range(20)))
            mgr.ensure(0, 40)
            mgr.check_invariant()
            mgr.retire(0)
            mgr.check_invariant()
        # published full blocks persist as reclaimable cache
        assert mgr.blocks_cached() == 2
        assert (mgr.table == TRASH_BLOCK).all()

    def test_double_free_raises(self):
        mgr = PagedCacheManager(slots=1, max_len=64, block_size=8)
        mgr.admit(0, list(range(10)))
        blk = int(mgr.table[0, 0])
        mgr.retire(0)
        with pytest.raises(AssertionError, match="double free"):
            mgr._release_block(blk)

    def test_shared_blocks_refcounted_across_lanes(self):
        mgr = PagedCacheManager(slots=3, max_len=64, block_size=8)
        prompt = list(range(17))                 # 2 full blocks + tail 1
        mgr.admit(0, prompt)
        mgr.publish(0, prompt)
        mgr.admit(1, prompt)                     # hits blocks 0,1
        mgr.admit(2, prompt)
        mgr.check_invariant()
        shared = int(mgr.table[0, 0])
        assert int(mgr.table[1, 0]) == shared
        assert mgr.ref[shared] == 3
        mgr.retire(1)
        assert mgr.ref[shared] == 2
        mgr.retire(0)
        mgr.retire(2)
        mgr.check_invariant()
        assert mgr.ref[shared] == 0
        assert mgr.blocks_cached() == 2          # still cached, ref 0

    def test_cow_on_partial_tail_and_aligned_full_hit(self):
        mgr = PagedCacheManager(slots=2, max_len=64, block_size=8)
        leader = list(range(24))                 # 3 full blocks
        mgr.admit(0, leader)
        mgr.publish(0, leader)
        # partial tail: 20 = 2 full hits + 4 matching block 2's prefix
        hit, cow = mgr.admit(1, leader[:20])
        assert hit == 19 and len(cow) == 1
        src, dst = cow[0]
        assert src == int(mgr.table[0, 2]) and dst == int(mgr.table[1, 2])
        assert src != dst
        mgr.check_invariant()
        mgr.retire(1)
        # aligned full-prompt hit: 16 tokens, both blocks cached ->
        # the LAST hit block gets the CoW (the 1-token forward rewrites
        # position 15 inside it)
        hit, cow = mgr.admit(1, leader[:16])
        assert hit == 15 and len(cow) == 1
        assert cow[0][0] == int(mgr.table[0, 1])
        mgr.check_invariant()
        mgr.retire(1)
        mgr.retire(0)
        mgr.check_invariant()

    def test_lru_eviction_reclaims_refzero_cached(self):
        # pool of exactly one lane's worth: the second admission must
        # reclaim the first prompt's cached blocks
        mgr = PagedCacheManager(slots=1, max_len=64, block_size=8,
                                num_blocks=8)
        a = list(range(64))
        mgr.admit(0, a)
        mgr.publish(0, a)
        mgr.retire(0)
        assert mgr.blocks_cached() == 8 and mgr.blocks_free() == 0
        b = [7] * 64                              # distinct prompt
        mgr.admit(0, b)
        mgr.check_invariant()
        assert mgr.stats["cache_evictions"] == 8
        mgr.retire(0)

    def test_heap_eviction_matches_scan_on_seeded_sequence(self):
        """Satellite regression (ISSUE 8): `_evict_lru`'s victim
        selection moved from an O(n·children) full scan to a lazy
        refcount-0 heap — the SEEDED lifecycle below must reclaim the
        SAME victims in the SAME order (and the same eviction count)
        under both selectors, or LRU behavior silently drifted."""
        import random

        def drive(mgr):
            rng = random.Random(42)
            prompts = [[rng.randrange(50) for _ in range(rng.choice(
                (8, 16, 17, 24, 33)))] for _ in range(12)]
            for it in range(40):
                p = prompts[rng.randrange(len(prompts))]
                slot = rng.randrange(2)
                if mgr.mapped_count[slot]:
                    mgr.retire(slot)
                try:
                    mgr.admit(slot, p)
                    mgr.publish(slot, p)
                except NoFreeBlocks:
                    pass
                mgr.check_invariant()
            for slot in range(2):
                if mgr.mapped_count[slot]:
                    mgr.retire(slot)

        def instrument(mgr, log):
            sel = mgr._select_victim

            def wrapped():
                v = sel()
                if v is not None:
                    log.append((v.key, tuple(v.chunk)))
                return v
            mgr._select_victim = wrapped

        fast_log, scan_log = [], []
        fast = PagedCacheManager(slots=2, max_len=64, block_size=8,
                                 num_blocks=10)
        instrument(fast, fast_log)
        drive(fast)

        scan = PagedCacheManager(slots=2, max_len=64, block_size=8,
                                 num_blocks=10)
        scan._select_victim = scan._select_victim_scan  # the old path
        instrument(scan, scan_log)
        drive(scan)

        assert fast_log, "seeded sequence never evicted — test is dead"
        assert fast_log == scan_log, "heap selector picked different victims"
        assert (fast.stats["cache_evictions"]
                == scan.stats["cache_evictions"])

    def test_no_free_blocks_raises_and_rolls_back(self):
        mgr = PagedCacheManager(slots=2, max_len=64, block_size=8,
                                num_blocks=8)
        mgr.admit(0, list(range(64)))            # lane 0 takes the pool
        with pytest.raises(NoFreeBlocks):
            mgr.admit(1, list(range(10)))
        mgr.check_invariant()                    # failed admit left no refs
        assert mgr.mapped_count[1] == 0
        mgr.retire(0)
        mgr.check_invariant()
        assert mgr.blocks_free() == 8


    @pytest.mark.parametrize("pos,active,live", [
        # ceil((p + 1) / 256) for an active lane, 1 for any other
        ([0, 254, 255, 256, 4095, 700], {0, 1, 2, 3, 4, 5},
         1 + 1 + 1 + 2 + 16 + 3),
        ([0, 254, 255, 256, 4095, 700], {3, 5}, 1 + 1 + 1 + 2 + 1 + 3),
        ([0, 0, 0, 0, 0, 0], set(), 6),
        ([4095] * 6, set(range(6)), 96),
    ])
    def test_decode_cells_of_known_positions(self, pos, active, live):
        """``decodeCellsLive`` / ``decodeCellsGrid`` on ``/statusz``: one
        decode iteration's counts from the lane positions the scheduler
        holds — and they are the kernel's own list's length."""
        from paddle_operator_tpu.ops.decode_attention import decode_cells

        mgr = PagedCacheManager(slots=6, max_len=4096, block_size=256)
        assert mgr.decode_cell_counts(pos, active) == (live, 6 * 16)
        lengths = jnp.asarray([p + 1 if i in active else 0
                               for i, p in enumerate(pos)], jnp.int32)
        assert int(decode_cells(jnp.zeros((6, 16), jnp.int32), lengths,
                                256).n) == live


class TestPagedKernel:
    def test_matches_reference_under_scrambled_block_map(self):
        from paddle_operator_tpu.ops.decode_attention import (
            decode_attention_reference,
            paged_decode_attention,
        )

        rng = np.random.default_rng(0)
        b, hq, hkv, s, d, bs = 3, 4, 2, 64, 16, 16
        m = s // bs
        k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
        lengths = jnp.asarray([5, 64, 0], jnp.int32)   # sparse/full/idle
        n = b * m + 1
        pool_k = jnp.zeros((n, hkv, bs, d), jnp.float32)
        pool_v = jnp.zeros((n, hkv, bs, d), jnp.float32)
        ids = rng.permutation(np.arange(1, n))
        table = np.zeros((b, m), np.int32)
        idx = 0
        for lane in range(b):
            for j in range(m):
                blk = int(ids[idx]); idx += 1
                table[lane, j] = blk
                pool_k = pool_k.at[blk].set(k[lane, :, j * bs:(j + 1) * bs])
                pool_v = pool_v.at[blk].set(v[lane, :, j * bs:(j + 1) * bs])
        ref = decode_attention_reference(q, k, v, lengths)
        out = paged_decode_attention(q, pool_k, pool_v,
                                     jnp.asarray(table), lengths,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # stacked (layer-indexed) pools — the decode layer-scan layout
        spk = jnp.stack([pool_k, pool_k * 2], 0)
        spv = jnp.stack([pool_v, pool_v * 2], 0)
        for li in range(2):
            out = paged_decode_attention(q, spk, spv, jnp.asarray(table),
                                         lengths, layer=jnp.asarray(li),
                                         interpret=True)
            ref = decode_attention_reference(q, k * (li + 1),
                                             v * (li + 1), lengths)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)


    @pytest.mark.parametrize("quant", ["none", "int8"])
    def test_step_masks_inactive_lanes_out_of_the_kernel(self, setup, quant):
        """The decode step through the kernel (interpret mode) with a
        lane masked out: the view hands the kernel length 0 for it, so
        its attention is zeros, its logits stay finite under
        ``check_finite``, and the live lane's tokens are the einsum
        step's."""
        from paddle_operator_tpu.infer import paged as PG

        _, cfg_x, params = setup
        _, cfg_k = make_model("tiny", dtype=jnp.float32,
                              decode_attn="pallas-interpret")
        m = MAX_LEN // BS
        active = jnp.asarray([True, False])

        def run(cfg):
            cache = PG.init_paged_cache(cfg, 2, 2 * m + 1, BS, quant=quant)
            table = jnp.asarray(1 + np.arange(2 * m).reshape(2, m),
                                jnp.int32)
            insert = PG.make_paged_prefill_insert(cfg, 16, BS,
                                                  quant=quant == "int8")
            tok = jnp.zeros((2,), jnp.int32)
            temp = jnp.zeros((2,), jnp.float32)
            keys = jnp.zeros((2, 2), jnp.uint32)
            for slot, n in enumerate((13, 9)):
                padded = np.zeros((1, 16), np.int32)
                padded[0, :n] = _prompt(cfg, n, seed=20 + slot)
                cache, tok, temp, keys = insert(
                    params, cache, table, tok, temp, keys,
                    jnp.zeros((2,), bool), jnp.asarray(padded), n, slot,
                    0.0, 0)[:4]
            view = PG.paged_view(cfg, cache, table, lane_mask=active)
            bufs, _ = view.begin(1)
            att = None
            if view.kernel:
                q = jnp.ones((2, 1, cfg.n_heads, cfg.head_dim), cfg.dtype)
                att = np.asarray(view.kernel_attend(bufs, jnp.int32(0), q))
            step = PG.make_paged_chunk_step(cfg, 3, check_finite=True,
                                            quant=quant == "int8")
            _, _, toks, ok = step(params, cache, table, tok, temp, keys,
                                  active)
            return att, np.asarray(toks), np.asarray(ok)

        att, toks_k, ok = run(cfg_k)
        assert att[0].any() and not att[1].any()
        assert ok.tolist() == [True, True]
        _, toks_x, _ = run(cfg_x)
        assert toks_k[:, 0].tolist() == toks_x[:, 0].tolist()


class TestPagedRingParity:
    """The tentpole gate: greedy paged output bit-identical to the
    contiguous ring / decode.generate — cold, prefix-hit, and CoW
    admissions alike."""

    @pytest.mark.slow      # dryrun serve-paged pins cold-admit parity
    def test_cold_admissions_match_generate(self, setup):
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            lens, new = [5, 11, 8, 13], 9
            prompts = [_prompt(cfg, n, seed=10 + i)
                       for i, n in enumerate(lens)]
            reqs = [b.submit(p, max_new_tokens=new) for p in prompts]
            outs = [r.result(timeout=300) for r in reqs]
            for p, out in zip(prompts, outs):
                assert out == _ref(params, cfg, p, new)
            b.pool.check_invariant()
            assert b.stats["admitted"] == 4 and b.stats["evicted"] == 4
        finally:
            b.close()

    def test_pallas_interpret_path_matches_generate(self, setup):
        _, _, params = setup
        _, cfg = make_model("tiny", dtype=jnp.float32,
                            decode_attn="pallas-interpret")
        b = _batcher(cfg, params, block_size=16,
                     prefill_buckets=(16, MAX_LEN))
        try:
            p = _prompt(cfg, 11, seed=3)
            out = b.submit(p, max_new_tokens=7).result(timeout=300)
            assert out == _ref(params, cfg, p, 7)
        finally:
            b.close()

    def test_prefix_hit_skips_cached_prefill_and_matches(self, setup):
        """Followers of a cached prompt run a suffix-only forward (ONE
        token on a full hit — the last prompt position's logits are not
        cached) and still emit the exact contiguous-ring stream.  The
        prefill-call counter is the acceptance gate: no forward over
        cached blocks."""
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            new = 6
            leader = _prompt(cfg, 24, seed=40)          # 3 full blocks
            want = _ref(params, cfg, leader, new)
            assert b.submit(leader, max_new_tokens=new).result(
                timeout=300) == want
            calls0 = b.stats["prefill_calls"]
            toks0 = b.stats["prefill_tokens"]
            # full hit: one 1-token forward, zero tokens re-prefilled
            # beyond it, CoW of the tail block keeps the cache intact
            assert b.submit(leader, max_new_tokens=new).result(
                timeout=300) == want
            assert b.stats["prefill_calls"] - calls0 == 1
            assert b.stats["prefill_tokens"] - toks0 == 1
            assert b.stats["cow_copies"] >= 1
            b.pool.check_invariant()
            # divergent suffix: shared 16-token prefix, fresh tail —
            # prefill covers ONLY the suffix
            toks1 = b.stats["prefill_tokens"]
            div = np.concatenate([leader[:16], _prompt(cfg, 9, seed=41)])
            assert b.submit(div, max_new_tokens=new).result(
                timeout=300) == _ref(params, cfg, div, new)
            assert b.stats["prefill_tokens"] - toks1 == 9
            # the leader's cached blocks survived both: re-hit exactly
            assert b.submit(leader, max_new_tokens=new).result(
                timeout=300) == want
            b.pool.check_invariant()
            assert b.pool.hit_rate() > 0
        finally:
            b.close()

    def test_cancel_returns_blocks(self, setup):
        _, cfg, params = setup
        b = _batcher(cfg, params, slots=1)
        orig = b._step

        def paced(*a):
            time.sleep(0.05)
            return orig(*a)

        b._step = paced
        try:
            free0 = b.pool.blocks_free() + b.pool.blocks_cached()
            h = b.submit(_prompt(cfg, 24, seed=50), max_new_tokens=30,
                         stream=True)
            next(h.stream(timeout=300))
            h.cancel()
            h.result(timeout=300)
            deadline = time.monotonic() + 30
            while b.pool.blocks_free() + b.pool.blocks_cached() < free0:
                assert time.monotonic() < deadline, "blocks never returned"
                time.sleep(0.02)
            b.pool.check_invariant()
        finally:
            b.close()

    def test_undersized_pool_starves_one_lane_not_the_ring(self, setup):
        """Oversubscription (num_blocks below worst case) running dry
        MID-GENERATION fails only the lane that cannot grow — its
        request resolves with NoFreeBlocks, its blocks free, and the
        ring keeps serving (a dead server ring would fail everything)."""
        _, cfg, params = setup
        # 8 blocks of 8 = one worst-case lane; two growing lanes collide
        b = _batcher(cfg, params, slots=2, num_blocks=8,
                     prefix_cache=False)
        try:
            p1, p2 = _prompt(cfg, 24, seed=60), _prompt(cfg, 24, seed=61)
            r1 = b.submit(p1, max_new_tokens=30)
            r2 = b.submit(p2, max_new_tokens=30)
            results, errors = [], []
            for p, r in ((p1, r1), (p2, r2)):
                try:
                    results.append((p, r.result(timeout=300)))
                except NoFreeBlocks as e:
                    errors.append(e)
            assert len(errors) == 1, "exactly one lane should starve"
            for p, out in results:
                assert out == _ref(params, cfg, p, 30)
            b.pool.check_invariant()
            # the ring survived: a fitting request still serves exactly
            p3 = _prompt(cfg, 8, seed=62)
            assert b.submit(p3, max_new_tokens=4).result(
                timeout=300) == _ref(params, cfg, p3, 4)
            b.pool.check_invariant()
        finally:
            b.close()

    def test_sampling_deterministic_per_seed(self, setup):
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            p = _prompt(cfg, 6, seed=4)
            a = b.submit(p, max_new_tokens=8, temperature=0.8,
                         seed=5).result(timeout=300)
            c = b.submit(p, max_new_tokens=8, temperature=0.8,
                         seed=5).result(timeout=300)
            d = b.submit(p, max_new_tokens=8, temperature=0.8,
                         seed=6).result(timeout=300)
            assert a == c and a != d
        finally:
            b.close()


class TestPagedSpecRing:
    """Spec-mode compat: the draft cache stays a contiguous ring, the
    target verify walks the block table — greedy output still
    bit-identical to plain generate."""

    @pytest.mark.slow      # dryrun serve-paged pins spec-on parity
    def test_spec_paged_matches_generate(self, setup):
        _, cfg, params = setup
        dcfg = cfg.draft()
        dparams = Llama(dcfg).init(jax.random.PRNGKey(1),
                                   jnp.zeros((1, 8), jnp.int32))["params"]
        b = _batcher(cfg, params, block_size=16,
                     prefill_buckets=(16, MAX_LEN), draft_params=dparams,
                     draft_cfg=dcfg, spec_k=3)
        try:
            lens, new = [5, 11, 8], 7
            prompts = [_prompt(cfg, n, seed=20 + i)
                       for i, n in enumerate(lens)]
            reqs = [b.submit(p, max_new_tokens=new) for p in prompts]
            for p, r in zip(prompts, reqs):
                assert r.result(timeout=300) == _ref(params, cfg, p, new)
            b.pool.check_invariant()
            assert b.pool.prefix_cache is False    # disabled under spec
        finally:
            b.close()


class TestShardedPagedRing:
    @pytest.mark.slow      # dryrun serve-paged pins the tp=2 parity
    def test_tp2_paged_matches_generate(self, setup):
        """The block pool sharded over its kv-head axis on a tp=2
        serving mesh (paged kernel through shard_map) — tokens
        identical to the single-device path."""
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        _, _, params = setup
        _, cfg = make_model("tiny", dtype=jnp.float32,
                            decode_attn="pallas-interpret")
        mesh = make_serving_mesh(2)
        b = _batcher(cfg, params, block_size=16,
                     prefill_buckets=(16, MAX_LEN), mesh=mesh)
        try:
            lens, new = [5, 11, 8], 7
            prompts = [_prompt(cfg, n, seed=30 + i)
                       for i, n in enumerate(lens)]
            reqs = [b.submit(p, max_new_tokens=new) for p in prompts]
            for p, r in zip(prompts, reqs):
                assert r.result(timeout=600) == _ref(params, cfg, p, new)
            b.pool.check_invariant()
        finally:
            b.close()


class TestSubmitValidation:
    def test_rejection_names_request_id(self, setup):
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            with pytest.raises(ValueError, match=r"exceeds max_len.*"
                                                 r"\[request row-7\]"):
                b.submit(list(range(1, 62)), max_new_tokens=8,
                         request_id="row-7")
            with pytest.raises(ValueError, match=r"\[request q1\]"):
                b.submit([], max_new_tokens=1, request_id="q1")
        finally:
            b.close()

    def test_rejects_before_tokenize_copy(self, setup):
        """Capacity validation must fire on the raw sequence BEFORE the
        int-coercion/tokenize copy — a poisoned over-length prompt of
        non-int garbage raises the capacity error, not a cast error."""
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            poisoned = [object()] * (MAX_LEN + 1)   # len > largest bucket
            with pytest.raises(ValueError, match="exceeds the largest"):
                b.submit(poisoned, max_new_tokens=1)
        finally:
            b.close()
