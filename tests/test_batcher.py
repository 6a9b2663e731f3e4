"""Continuous-batching decode ring (infer/scheduler.py over
infer/executor.py; the cached forward in infer/decode.py) pinned against
decode.generate: the ring generalizes the scalar cache position to
per-lane vectors, so these equivalence tests are what keeps the two
attention paths from diverging.  The scheduler tests then prove the
serving claims: staggered requests share one resident compiled step,
lanes are reused, eviction frees capacity.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer.decode import init_ring_cache
from paddle_operator_tpu.infer.executor import (
    make_chunk_step,
    make_prefill_insert,
)
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import make_model

MAX_LEN = 64


@pytest.fixture(scope="module")
def setup():
    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, cfg, params


def _prompt(cfg, s, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, s), 0,
                              cfg.vocab_size, dtype=jnp.int32)


def _batcher(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, MAX_LEN))
    return ContinuousBatcher(params, cfg, **kw)


class TestRingEquivalence:
    def test_ring_step_matches_decode_step_at_ragged_positions(self, setup):
        """Lanes at DIFFERENT fill positions must each produce exactly the
        logits decode.decode_step produces for that lane alone."""
        model, cfg, params = setup
        lens = [5, 11, 8]
        prompts = [_prompt(cfg, n, seed=i) for i, n in enumerate(lens)]

        # reference: per-sequence scalar-pos decode
        refs = []
        for p in prompts:
            logits, cache = D.prefill(params, cfg, p, max_len=MAX_LEN)
            tok = logits.argmax(-1).astype(jnp.int32)
            step_logits, _ = D.decode_step(params, cfg, tok, cache)
            refs.append((int(tok[0]), np.asarray(step_logits[0])))

        # ring: all three lanes resident at ragged positions
        cache = init_ring_cache(cfg, 3, MAX_LEN)
        insert = make_prefill_insert(cfg, 16)
        tok = jnp.zeros((3,), jnp.int32)
        temp = jnp.zeros((3,), jnp.float32)
        keys = jnp.zeros((3, 2), jnp.uint32)
        first = []
        for slot, p in enumerate(prompts):
            padded = jnp.zeros((1, 16), jnp.int32)
            padded = padded.at[0, :p.shape[1]].set(p[0])
            cache, tok, temp, keys, ftok = insert(
                params, cache, tok, temp, keys, padded,
                p.shape[1], slot, 0.0, 0)
            first.append(int(ftok))
        assert first == [r[0] for r in refs]     # prefill logits agree

        ring_logits, _ = D.cached_step(cfg, params, tok,
                                       D.ContiguousView(cfg, cache))
        for i in range(3):
            np.testing.assert_allclose(np.asarray(ring_logits[i]),
                                       refs[i][1], rtol=1e-4, atol=1e-4,
                                       err_msg=f"lane {i}")

    def test_greedy_generation_matches_generate(self, setup):
        """End-to-end through the scheduler: ragged prompts, greedy — the
        full emitted sequence must equal decode.generate's."""
        model, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            lens, new = [5, 11, 8, 13], 9
            prompts = [_prompt(cfg, n, seed=10 + i)
                       for i, n in enumerate(lens)]
            reqs = [b.submit(np.asarray(p[0]), max_new_tokens=new)
                    for p in prompts]
            outs = [r.result(timeout=120) for r in reqs]
            for p, out in zip(prompts, outs):
                ref = D.generate(params, cfg, p, max_new_tokens=new,
                                 max_len=MAX_LEN)
                assert out == np.asarray(ref[0]).tolist()
        finally:
            b.close()

    def test_eos_stops_early_and_matches_generate(self, setup):
        model, cfg, params = setup
        p = _prompt(cfg, 7, seed=3)
        new = 12
        ref = np.asarray(D.generate(params, cfg, p, max_new_tokens=new,
                                    max_len=MAX_LEN)[0]).tolist()
        # pick the token greedy decode actually emits mid-stream as "eos"
        eos = ref[7 + new // 2]
        want = ref[:ref.index(eos, 7) + 1]
        b = _batcher(cfg, params)
        try:
            out = b.submit(np.asarray(p[0]), max_new_tokens=new,
                           eos_token=eos).result(timeout=120)
            assert out == want
        finally:
            b.close()

    def test_sampling_deterministic_per_seed(self, setup):
        model, cfg, params = setup
        p = _prompt(cfg, 6, seed=4)
        b = _batcher(cfg, params)
        try:
            a = b.submit(np.asarray(p[0]), max_new_tokens=8,
                         temperature=0.8, seed=5).result(timeout=120)
            c = b.submit(np.asarray(p[0]), max_new_tokens=8,
                         temperature=0.8, seed=5).result(timeout=120)
            d = b.submit(np.asarray(p[0]), max_new_tokens=8,
                         temperature=0.8, seed=6).result(timeout=120)
            assert a == c
            assert a != d        # overwhelmingly likely at vocab 256
        finally:
            b.close()


class TestShardedRing:
    """The continuous-batching ring TP-sharded (the tentpole's serving
    half): admission and chunk steps stay single compiled dispatches on
    the mesh and every emitted sequence is token-identical to both the
    single-device ring and decode.generate."""

    # ~7s; tp=2 ring-vs-generate token parity is pinned by the dryrun
    # serve-ring gate, so this twin rides -m slow
    @pytest.mark.slow
    def test_sharded_ring_matches_generate_and_single_device(self, setup):
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        _, _, params = setup
        _, cfg = make_model("tiny", dtype=jnp.float32,
                            decode_attn="pallas-interpret")
        mesh = make_serving_mesh(2)
        b = _batcher(cfg, params, slots=2, mesh=mesh)
        try:
            lens, new = [5, 11, 8, 13], 9
            prompts = [_prompt(cfg, n, seed=10 + i)
                       for i, n in enumerate(lens)]
            reqs = [b.submit(np.asarray(p[0]), max_new_tokens=new)
                    for p in prompts]
            outs = [r.result(timeout=300) for r in reqs]
            for p, out in zip(prompts, outs):
                ref = D.generate(params, cfg, p, max_new_tokens=new,
                                 max_len=MAX_LEN)
                assert out == np.asarray(ref[0]).tolist()
            assert b.stats["admitted"] == 4 and b.stats["evicted"] == 4
        finally:
            b.close()

    def test_sharded_ring_einsum_fallback(self, setup):
        """A tp the kernel cannot split (hkv=2 over tp=4) must serve
        through the GSPMD einsum path, tokens unchanged."""
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        _, cfg, params = setup
        mesh = make_serving_mesh(4)
        b = _batcher(cfg, params, slots=2, mesh=mesh)
        try:
            p = _prompt(cfg, 7, seed=3)
            out = b.submit(np.asarray(p[0]),
                           max_new_tokens=6).result(timeout=300)
            ref = D.generate(params, cfg, p, max_new_tokens=6,
                             max_len=MAX_LEN)
            assert out == np.asarray(ref[0]).tolist()
        finally:
            b.close()


class TestSeedFolding:
    def test_wide_seeds_fold_deterministically_and_distinctly(self, setup):
        """Seeds >= 2**31 hash-fold (batcher._fold_seed): same wide seed
        -> same stream; distinct wide seeds that a mask would collide
        (s and s + 2**31) -> distinct streams."""
        from paddle_operator_tpu.infer.scheduler import _fold_seed

        s = 7
        assert _fold_seed(s + 2 ** 31) != _fold_seed(s + 2 ** 32)
        assert 0 <= _fold_seed(-1) < 2 ** 31
        _, cfg, params = setup
        p = _prompt(cfg, 6, seed=4)
        b = _batcher(cfg, params)
        try:
            a = b.submit(np.asarray(p[0]), max_new_tokens=8,
                         temperature=0.8, seed=2 ** 31 + 5
                         ).result(timeout=120)
            c = b.submit(np.asarray(p[0]), max_new_tokens=8,
                         temperature=0.8, seed=2 ** 31 + 5
                         ).result(timeout=120)
            d = b.submit(np.asarray(p[0]), max_new_tokens=8,
                         temperature=0.8, seed=5).result(timeout=120)
            assert a == c
            assert a != d      # the old mask made these the same stream
        finally:
            b.close()


class TestScheduler:
    def test_staggered_requests_reuse_slots(self, setup):
        """More requests than lanes, arriving while decode is mid-flight:
        every request completes correctly, concurrency never exceeds the
        lane count, and lanes are reused (admissions > lanes)."""
        model, cfg, params = setup
        b = _batcher(cfg, params, slots=2, chunk_tokens=2)
        try:
            lens = [5, 9, 7, 12, 6]
            prompts = [_prompt(cfg, n, seed=20 + i)
                       for i, n in enumerate(lens)]
            reqs = []
            for i, p in enumerate(prompts):
                reqs.append(b.submit(np.asarray(p[0]), max_new_tokens=6))
                time.sleep(0.05)          # stagger mid-decode
            outs = [r.result(timeout=180) for r in reqs]
            for p, out in zip(prompts, outs):
                ref = D.generate(params, cfg, p, max_new_tokens=6,
                                 max_len=MAX_LEN)
                assert out == np.asarray(ref[0]).tolist()
            assert b.stats["admitted"] == 5
            assert b.stats["evicted"] == 5
            assert b.stats["max_active"] <= 2
            assert b.stats["chunks"] >= 3     # several waves, one program
        finally:
            b.close()

    def test_concurrent_submitters(self, setup):
        """The server pattern: many HTTP threads submit and block on
        result() simultaneously."""
        model, cfg, params = setup
        b = _batcher(cfg, params, slots=3)
        outs = {}
        try:
            def client(i):
                p = _prompt(cfg, 4 + i, seed=40 + i)
                outs[i] = (p, b.submit(np.asarray(p[0]),
                                       max_new_tokens=5).result(timeout=180))

            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(6)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            assert len(outs) == 6
            for p, out in outs.values():
                ref = D.generate(params, cfg, p, max_new_tokens=5,
                                 max_len=MAX_LEN)
                assert out == np.asarray(ref[0]).tolist()
        finally:
            b.close()

    def test_rejections(self, setup):
        model, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            with pytest.raises(ValueError, match="exceeds the largest"):
                b.submit(list(range(MAX_LEN + 1)), max_new_tokens=1)
            with pytest.raises(ValueError, match="exceeds max_len"):
                b.submit(list(range(60)), max_new_tokens=32)
            with pytest.raises(ValueError, match="empty"):
                b.submit([], max_new_tokens=1)
            with pytest.raises(ValueError, match="max_new_tokens"):
                b.submit([1, 2], max_new_tokens=0)
        finally:
            b.close()
        with pytest.raises(RuntimeError, match="closed"):
            b.submit([1, 2], max_new_tokens=1)

    def test_close_fails_pending(self, setup):
        model, cfg, params = setup
        b = _batcher(cfg, params, slots=1)
        r = b.submit([1, 2, 3], max_new_tokens=4)
        b.close()
        with pytest.raises(RuntimeError, match="closed"):
            # either it finished before close (fine) or it errors
            out = r.result(timeout=10)
            pytest.skip("finished before close")

    def test_capacity_bound_counts_prefill_first_token(self, setup):
        """The FIRST token is sampled from prefill logits, so only
        max_new-1 ride chunk steps: prompt 59 + max_new 5 at chunk 4
        needs cache positions through 59 + ceil(4/4)*4 = 63 < max_len.
        The old ceil(max_new/chunk) bound (59 + 8 = 67 > 64) rejected
        this in-capacity request (ADVICE r4)."""
        model, cfg, params = setup
        p = _prompt(cfg, 59, seed=21)
        b = _batcher(cfg, params)
        try:
            out = b.submit(np.asarray(p[0]),
                           max_new_tokens=5).result(timeout=120)
            ref = D.generate(params, cfg, p, max_new_tokens=5,
                             max_len=MAX_LEN)
            assert out == np.asarray(ref[0]).tolist()
            # past the worst-case position it must still be rejected
            with pytest.raises(ValueError, match="exceeds max_len"):
                b.submit(list(range(1, 62)), max_new_tokens=5)
        finally:
            b.close()

    @staticmethod
    def _slow_step(b, delay=0.05):
        """Pace the ring's chunk step so 'cancel observed before the
        budget runs out' is a multi-second window, not a scheduler race
        (the tiny CPU model can otherwise decode a whole budget in the
        gap between stream() yielding and cancel() being set)."""
        orig = b._step

        def paced(*a):
            time.sleep(delay)
            return orig(*a)

        b._step = paced

    def test_cancel_evicts_lane_and_frees_capacity(self, setup):
        """cancel() mid-generation: the request resolves with a partial
        sequence at the next chunk boundary and its lane admits the next
        queued request (a disconnect-abandoned stream must not hold its
        lane to the full token budget — ADVICE r4)."""
        model, cfg, params = setup
        b = _batcher(cfg, params, slots=1, chunk_tokens=2)
        self._slow_step(b)
        try:
            long = b.submit([3, 1, 4, 1, 5], max_new_tokens=40,
                            stream=True)
            it = long.stream(timeout=120)
            next(it)                      # generation is under way
            long.cancel()
            out = long.result(timeout=120)
            assert 5 <= len(out) < 5 + 40   # partial, prompt included
            # the freed lane serves the next request to completion
            nxt = b.submit([2, 7, 1], max_new_tokens=4)
            ref = D.generate(params, cfg,
                             jnp.asarray([[2, 7, 1]], jnp.int32),
                             max_new_tokens=4, max_len=MAX_LEN)
            assert nxt.result(timeout=120) == np.asarray(ref[0]).tolist()
        finally:
            b.close()

    def test_cancel_before_admission_resolves_immediately(self, setup):
        model, cfg, params = setup
        b = _batcher(cfg, params, slots=1)
        self._slow_step(b)
        try:
            hog = b.submit([1, 2, 3], max_new_tokens=24)
            queued = b.submit([4, 5], max_new_tokens=24)
            queued.cancel()
            out = queued.result(timeout=120)
            assert out[:2] == [4, 5] and len(out) < 2 + 24
            hog.result(timeout=120)
        finally:
            b.close()
