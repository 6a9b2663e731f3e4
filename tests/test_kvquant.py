"""Quantized paged KV blocks (ISSUE 7, infer/paged.py quant=... +
ops/decode_attention.py fused-dequant kernels): the int8 pool must be a
CAPACITY lever with a bounded quality cost — bit-exact quantize→dequant
roundtrips for block-aligned content, per-step logits within a pinned
error bound of the bf16 paged oracle, and every pool lifecycle path
(CoW, radix hit, suffix insert, chaos faults) preserving the allocator
partition invariant under ``SERVE_KV_QUANT=int8``.  The bf16 pool stays
the default and the parity oracle — nothing here touches its behavior.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer.paged import (
    dequantize_kv,
    init_paged_cache,
    paged_prefill,
    paged_view,
    quantize_kv,
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
    kw.setdefault("prefill_buckets", (16, 32, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    kw.setdefault("kv_quant", "int8")
    return ContinuousBatcher(params, cfg, **kw)


def _ref(params, cfg, prompt, new):
    return np.asarray(D.generate(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=new, max_len=MAX_LEN)[0]).tolist()


class TestQuantizeRoundtrip:
    def test_roundtrip_bit_exact_block_aligned(self):
        """quantize -> dequantize -> quantize must be a FIXED POINT for
        block-aligned writes: the max element maps to ±127 exactly, so
        the recomputed absmax/127 scale is identical and every code
        reproduces — the property that makes requantizing a CoW'd or
        handed-off block safe."""
        x = jax.random.normal(jax.random.PRNGKey(3),
                              (2, 1, 2, BS, 16), jnp.float32)
        codes, scale = quantize_kv(x)
        assert codes.dtype == jnp.int8
        deq = dequantize_kv(codes, scale, jnp.float32)
        codes2, scale2 = quantize_kv(deq)
        assert (np.asarray(codes) == np.asarray(codes2)).all()
        assert (np.asarray(scale) == np.asarray(scale2)).all()
        # and the dequantized values themselves are a fixed point
        deq2 = dequantize_kv(codes2, scale2, jnp.float32)
        assert (np.asarray(deq) == np.asarray(deq2)).all()

    def test_all_zero_block_gets_unit_scale(self):
        codes, scale = quantize_kv(jnp.zeros((1, 1, 1, BS, 4)))
        assert (np.asarray(scale) == 1.0).all()     # never divide by 0
        assert (np.asarray(codes) == 0).all()
        assert (np.asarray(dequantize_kv(codes, scale,
                                         jnp.float32)) == 0).all()

    def test_quantization_error_bounded(self):
        """Per-element error <= scale/2 (round-half-even over a
        127-level grid) — the arithmetic behind the logit bound."""
        x = jax.random.normal(jax.random.PRNGKey(4),
                              (1, 1, 2, BS, 16), jnp.float32)
        codes, scale = quantize_kv(x)
        err = np.abs(np.asarray(dequantize_kv(codes, scale, jnp.float32))
                     - np.asarray(x))
        bound = np.asarray(scale)[..., None, None] / 2 + 1e-7
        assert (err <= bound).all()


class TestQuantKernel:
    def test_fused_dequant_matches_dequantizing_reference(self):
        """The pallas quant kernel (interpret mode on CPU) against the
        einsum reference fed the SAME effective values: full blocks
        dequantized codes, the write-frontier block's rows exact from
        the staging tail — element-for-element the view
        ``_gather_lane_view_quant`` builds for the XLA path, so kernel
        and fallback can never drift apart."""
        from paddle_operator_tpu.ops.decode_attention import (
            decode_attention_reference,
            paged_decode_attention,
        )

        rng = np.random.default_rng(1)
        b, hq, hkv, s, d, bs = 3, 4, 2, 64, 16, 16
        m = s // bs
        k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
        lengths = jnp.asarray([5, 64, 17], jnp.int32)
        n = b * m + 1
        pool_k = jnp.zeros((n, hkv, bs, d), jnp.int8)
        pool_v = jnp.zeros((n, hkv, bs, d), jnp.int8)
        ks = jnp.ones((n, hkv), jnp.float32)
        vs = jnp.ones((n, hkv), jnp.float32)
        # per-lane staging tails (+ trash row) hold the frontier block
        kt = jnp.zeros((b + 1, hkv, bs, d), jnp.float32)
        vt = jnp.zeros((b + 1, hkv, bs, d), jnp.float32)
        ids = rng.permutation(np.arange(1, n))
        table = np.zeros((b, m), np.int32)
        k_eff, v_eff = np.asarray(k).copy(), np.asarray(v).copy()
        idx = 0
        for lane in range(b):
            wb = max(int(lengths[lane]) - 1, 0) // bs
            for j in range(m):
                blk = int(ids[idx]); idx += 1
                table[lane, j] = blk
                tile_k = k[lane, :, j * bs:(j + 1) * bs][None, None]
                tile_v = v[lane, :, j * bs:(j + 1) * bs][None, None]
                ck, sk = quantize_kv(tile_k)
                cv, sv = quantize_kv(tile_v)
                pool_k = pool_k.at[blk].set(ck[0, 0])
                pool_v = pool_v.at[blk].set(cv[0, 0])
                ks = ks.at[blk].set(sk[0, 0])
                vs = vs.at[blk].set(sv[0, 0])
                if j == wb:     # frontier: exact rows live in the tail
                    kt = kt.at[lane].set(tile_k[0, 0])
                    vt = vt.at[lane].set(tile_v[0, 0])
                else:           # non-frontier: reference reads dequant
                    k_eff[lane, :, j * bs:(j + 1) * bs] = np.asarray(
                        dequantize_kv(ck, sk, jnp.float32))[0, 0]
                    v_eff[lane, :, j * bs:(j + 1) * bs] = np.asarray(
                        dequantize_kv(cv, sv, jnp.float32))[0, 0]
        out = paged_decode_attention(
            q, pool_k, pool_v, jnp.asarray(table), lengths,
            interpret=True, k_scale=ks, v_scale=vs, k_tail=kt, v_tail=vt)
        ref = decode_attention_reference(q, jnp.asarray(k_eff),
                                         jnp.asarray(v_eff), lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # stacked (layer-indexed) pools — the decode layer-scan layout
        spk, spv = jnp.stack([pool_k] * 2), jnp.stack([pool_v] * 2)
        sks = jnp.stack([ks, ks * 2])       # layer 1: doubled scales
        svs = jnp.stack([vs, vs * 2])
        skt, svt = jnp.stack([kt, kt * 2]), jnp.stack([vt, vt * 2])
        for li in range(2):
            out = paged_decode_attention(
                q, spk, spv, jnp.asarray(table), lengths,
                layer=jnp.asarray(li), interpret=True,
                k_scale=sks, v_scale=svs, k_tail=skt, v_tail=svt)
            mul = li + 1
            ref = decode_attention_reference(
                q, jnp.asarray(k_eff) * mul, jnp.asarray(v_eff) * mul,
                lengths)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"layer {li}")

    def test_partial_operands_rejected(self):
        from paddle_operator_tpu.ops.decode_attention import (
            paged_decode_attention,
        )

        q = jnp.zeros((1, 2, 8))
        pool = jnp.zeros((3, 1, 8, 8), jnp.int8)
        with pytest.raises(ValueError, match="together"):
            paged_decode_attention(
                q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                jnp.asarray([4], jnp.int32), interpret=True,
                k_scale=jnp.ones((3, 1)))


class TestLogitBound:
    # Pinned tolerance for the tiny f32 model: measured max per-step
    # logit delta is ~0.02-0.05 at these shapes; 0.15 gives ~3x
    # headroom without ever passing a broken dequant (a missing scale
    # shows up as O(1)-O(100) deltas).  The dryrun serve-kvquant line
    # pins the same bound end-to-end through the ring.
    TOL = 0.15

    def test_decode_logits_within_bound_of_bf16_pool(self, setup):
        """Per-step decode logits of the int8 pool against the bf16
        paged oracle, same prompt, over enough steps to cross several
        block boundaries (quantize-on-completion happens mid-stream)."""
        _, cfg, params = setup
        prompt = jnp.asarray([_prompt(cfg, 19, seed=5)], jnp.int32)
        n_blocks = MAX_LEN // BS + 1
        table = jnp.arange(1, n_blocks, dtype=jnp.int32)[None, :]

        caches = {}
        logits0 = {}
        for quant in ("none", "int8"):
            cache = init_paged_cache(cfg, 1, n_blocks, BS,
                                     quant=quant)
            out = paged_prefill(params, cfg, prompt, cache, table[0],
                                block_size=BS,
                                **({"quant": True, "prompt_len": 19}
                                   if quant == "int8" else {}))
            if quant == "int8":
                logits, cache, tail_k, tail_v = out
                cache["kt"] = cache["kt"].at[:, :1].set(tail_k)
                cache["vt"] = cache["vt"].at[:, :1].set(tail_v)
            else:
                logits, cache = out
            cache["pos"] = jnp.asarray([19], jnp.int32)
            caches[quant] = cache
            logits0[quant] = np.asarray(logits[0, 18])

        d0 = np.abs(logits0["int8"] - logits0["none"]).max()
        assert d0 <= self.TOL, f"prefill logit delta {d0}"
        tok = {q: jnp.asarray([int(logits0[q].argmax())]) for q in caches}
        steps = {
            q: jax.jit(lambda pr, t, c: D.cached_step(
                cfg, pr, t, paged_view(cfg, c, table,
                                       lane_mask=jnp.ones((1,), bool))))
            for q in caches}
        worst = d0
        for _ in range(24):                  # crosses 3 block bounds
            step = {}
            for q in caches:
                logits, caches[q] = steps[q](params, tok[q], caches[q])
                step[q] = np.asarray(logits[0])
            worst = max(worst, np.abs(step["int8"] - step["none"]).max())
            assert worst <= self.TOL, f"logit delta {worst}"
            # follow the ORACLE's greedy choice in both caches so the
            # streams stay comparable even if an argmax would flip
            nxt = int(step["none"].argmax())
            tok = {q: jnp.asarray([nxt]) for q in caches}
        assert worst > 0                     # int8 is not magically exact


class TestQuantRing:
    def test_quant_requires_paged(self, setup):
        _, cfg, params = setup
        with pytest.raises(ValueError, match="paged"):
            ContinuousBatcher(params, cfg, slots=1, max_len=MAX_LEN,
                              chunk_tokens=4, prefill_buckets=(16,),
                              paged=False, kv_quant="int8")
        with pytest.raises(ValueError, match="kv_quant"):
            _batcher(cfg, params, kv_quant="int4")

    def test_bf16_pool_is_default(self, setup):
        _, cfg, params = setup
        b = _batcher(cfg, params, kv_quant="none")
        try:
            assert b.kv_quant == "none"
            assert b.cache["k"].dtype == cfg.dtype
            assert "ks" not in b.cache
            st = b.serving_status()
            assert st["kvQuantMode"] == "none"
        finally:
            b.close()

    @pytest.mark.slow   # ISSUE 9 budget: pinned every run by the
    # dryrun serve-kvquant line (cold/hit identity + logit bound)
    def test_cold_and_prefix_hit_match_oracle(self, setup):
        """Greedy generation through the int8 ring — cold admission,
        then a full-prefix-hit follower — matches decode.generate on
        the tiny model (logit gaps here dwarf the quantization error,
        so token equality is the strongest cheap signal)."""
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            p = _prompt(cfg, 16, seed=6)     # two FULL blocks publish
            want = _ref(params, cfg, p, 8)
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == want, "cold int8 admission diverged"
            cold_tokens = b.stats["prefill_tokens"]
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == want, "int8 prefix hit diverged"
            # the hit admits through the suffix insert: 1-token forward
            assert b.stats["prefill_tokens"] - cold_tokens == 1
            assert b.pool.hit_rate() > 0
            st = b.serving_status()
            assert st["kvQuantMode"] == "int8"
            assert st["kvPoolBytes"] > 0
            b.pool.check_invariant()
        finally:
            b.close()

    @pytest.mark.slow   # ISSUE 9 budget: the CoW/radix-hit/suffix int8
    # paths ride the dryrun serve-kvquant gate's prefix-hit leg
    def test_cow_mid_block_hit_suffix_insert(self, setup):
        """Partial-tail radix hit: the follower shares 19 of a cached
        24-token prompt — hit lands MID-BLOCK, the hit block CoWs
        (codes + scales), the staging tail seeds from the dequantized
        private copy (paged.make_tail_init), and the suffix insert
        produces the oracle's tokens."""
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            shared = _prompt(cfg, 24, seed=7)     # three full blocks
            assert b.submit(shared, max_new_tokens=8).result(
                timeout=300) == _ref(params, cfg, shared, 8)
            sub = shared[:20]    # 16 full-hit + partial tail -> hit 19
            got = b.submit(sub, max_new_tokens=8).result(timeout=300)
            assert got == _ref(params, cfg, sub, 8), \
                "mid-block CoW + tail-seeded suffix diverged"
            assert b.stats["cow_copies"] >= 1
            b.pool.check_invariant()
        finally:
            b.close()

    def test_chaos_lifecycle_quant(self, setup):
        """One chaos run under SERVE_KV_QUANT=int8 (the ISSUE 7
        lifecycle gate): an injected dispatch fault heals the ring, a
        NaN-poisoned lane quarantines (poison lands in the bf16
        staging tail — int8 codes cannot hold a NaN), a client drop
        cancels — every request resolves EXACTLY ONCE (token list or
        error, never neither/both) and the allocator partition
        invariant ``free + mapped + cached == num_blocks`` holds at
        the end."""
        from paddle_operator_tpu.infer.chaos import ChaosEvent, ChaosInjector
        from paddle_operator_tpu.infer.resilience import (
            LaneQuarantined,
            RetriableError,
            RingResilience,
        )

        _, cfg, params = setup
        b = _batcher(cfg, params, resilience=RingResilience(
            watchdog=False, nan_check=True, max_restarts=4,
            backoff_base_s=0.01))
        try:
            p = _prompt(cfg, 13, seed=8)
            want = _ref(params, cfg, p, 8)
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == want
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            inj.events[nxt + 2] = [ChaosEvent("dispatch_fail", nxt + 2)]
            inj.events[nxt + 14] = [ChaosEvent("nan_lane", nxt + 14, 0)]
            resolved = 0
            outcomes = []
            for i in range(6):
                h = b.submit(_prompt(cfg, 13, seed=20 + i),
                             max_new_tokens=8)
                if i == 4:
                    h.cancel()               # client drop mid-flight
                try:
                    out = h.result(timeout=300)
                    outcomes.append("ok")
                    assert isinstance(out, list) and len(out) >= 13
                except (RetriableError, LaneQuarantined) as e:
                    outcomes.append(type(e).__name__)
                resolved += 1
            assert resolved == 6             # exactly-once resolution
            assert "RetriableError" in outcomes     # the healed fault
            assert b.stats["watchdog_restarts"] >= 1
            assert b.healthy
            # the ring still serves, bit-identically, after the faults
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == want
            b.pool.check_invariant()         # free+mapped+cached == N
        finally:
            b.close()


class TestQuantModesSlow:
    """Parity is claimed MODE-vs-MODE under the SAME pool storage, not
    quant-vs-bf16 token equality: quantization legitimately flips an
    argmax whose logit gap is below the quantization error (the
    TestLogitBound tolerance governs quality vs the bf16 oracle), so
    the stable bit-level invariant is that every admission path —
    inline, chunked, disagg, speculative — produces IDENTICAL output
    over the int8 pool."""

    def _inline_quant_ref(self, cfg, params, p, new=8):
        b = _batcher(cfg, params)
        try:
            return b.submit(p, max_new_tokens=new).result(timeout=300)
        finally:
            b.close()

    @pytest.mark.slow
    @pytest.mark.parametrize("mode", ["chunked", "disagg"])
    def test_prefill_modes_quant_parity(self, setup, mode):
        """Chunked slices and the disagg handoff both carry
        codes+scales+tails; greedy output is bit-identical to the
        inline int8 ring (also pinned, with tp=2 and spec, by the
        dryrun serve-kvquant line)."""
        _, cfg, params = setup
        b = _batcher(cfg, params, prefill_mode=mode, prefill_chunk=8)
        try:
            for seed, n in ((9, 13), (10, 33)):
                p = _prompt(cfg, n, seed=seed)
                assert b.submit(p, max_new_tokens=8).result(
                    timeout=300) == self._inline_quant_ref(
                        cfg, params, p), f"{mode} int8 diverged"
            if mode == "disagg":
                assert b.stats["disagg_prefills"] > 0
            b.pool.check_invariant()
        finally:
            b.close()

    @pytest.mark.slow
    def test_speculative_quant_parity(self, setup):
        """Spec decode over the int8 target pool (draft ring stays
        bf16): the exact-greedy acceptance rule carries over, so the
        committed stream matches the NON-speculative int8 ring across
        divergent per-lane accept lengths and block-crossing rollbacks
        (fixed seeds — a deterministic regression pin)."""
        _, cfg, params = setup
        dcfg = cfg.draft()
        dparams = Llama(dcfg).init(jax.random.PRNGKey(1),
                                   jnp.zeros((1, 8), jnp.int32))["params"]
        b = _batcher(cfg, params, draft_params=dparams, draft_cfg=dcfg,
                     spec_k=3)
        try:
            for seed, n in ((11, 13), (12, 33)):
                p = _prompt(cfg, n, seed=seed)
                assert b.submit(p, max_new_tokens=8).result(
                    timeout=300) == self._inline_quant_ref(
                        cfg, params, p), "speculative int8 diverged"
            b.pool.check_invariant()
        finally:
            b.close()
