"""The prefill ladder (ISSUE 27): one doubling rule (with the 3:2 midpoint
under the widest rung) for the ring and the disaggregated engine, every rung's program the same arithmetic as the
widest, and — on the path the serving entry point takes — every rung an
executable before the ring listens, so no first prompt compiles.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import serve
from paddle_operator_tpu.infer.executor import (
    PrefillExecutor,
    RingExecutor,
    _default_buckets,
)
from paddle_operator_tpu.infer.paged import (
    TRASH_BLOCK,
    init_paged_cache,
    paged_prefill,
)
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import make_model

MAX_LEN = 512
BS = 16
LADDER = (64, 128, 256, 384, 512)


@pytest.fixture(scope="module")
def setup():
    model, cfg = make_model("tiny", dtype=jnp.float32, max_seq_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


@contextlib.contextmanager
def compile_events():
    """Names of what JAX traces, lowers, compiles or asks the persistent
    cache for while the block runs (the tests keep that cache on, so a
    hit never reaches the backend: the request for it counts too)."""
    from jax._src import monitoring as M

    seen = []

    def on_duration(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            seen.append((event.rsplit("/", 1)[1], kw.get("fun_name")))

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            seen.append(("cache_request", None))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield seen
    finally:
        M.unregister_event_duration_listener(on_duration)
        M.unregister_event_listener(on_event)


class TestRule:
    @pytest.mark.parametrize("max_len,block,want", [
        (4096, 256, (256, 512, 1024, 2048, 3072, 4096)),
        (8192, 256, (256, 512, 1024, 2048, 4096, 6144, 8192)),
        (4096, 16, (64, 128, 256, 512, 1024, 2048, 3072, 4096)),
        (64, 8, (64,)),
        (48, 16, (48,)),
    ])
    def test_ladder(self, setup, max_len, block, want):
        cfg, params = setup
        ladder = _default_buckets(max_len, block)
        assert ladder == want
        assert list(ladder) == sorted(set(ladder))
        assert all(b % block == 0 for b in ladder)
        assert all(hi <= 2 * lo for lo, hi in zip(ladder, ladder[1:]))
        assert ladder[-1] == max_len
        # the ring and the disaggregated engine build the same one
        # (their programs are jitted lazily: nothing compiles here)
        ring = RingExecutor(params, cfg, slots=1, max_len=max_len,
                            chunk_tokens=1, paged=True, block_size=block)
        pe = PrefillExecutor(params, cfg, max_len=max_len,
                             block_size=block, buckets=(max_len,))
        pe.close()
        assert ring.buckets == pe.buckets == ladder

    def test_explicit_buckets_win(self, setup):
        cfg, params = setup
        ex = RingExecutor(params, cfg, slots=1, max_len=64, chunk_tokens=1,
                          paged=True, block_size=8,
                          prefill_buckets=(12, 64))
        assert ex.buckets == (16, 64)       # rounded to blocks, no more


class TestEveryRungSameArithmetic:
    @pytest.fixture(scope="class")
    def ring(self, setup):
        cfg, params = setup
        ex = RingExecutor(params, cfg, slots=2, max_len=MAX_LEN,
                          chunk_tokens=4, paged=True, block_size=BS)
        assert ex.buckets == LADDER
        return ex

    @pytest.mark.parametrize("rung", LADDER)
    def test_rung_equals_widest(self, setup, ring, rung):
        cfg, params = setup
        n = rung - 3                    # a prompt only this rung fits
        prompt = _prompt(cfg, n, seed=rung)
        m = ring.pool.max_blocks
        row = np.arange(1, m + 1, dtype=np.int32)
        used = row[:-(-n // BS)]

        @jax.jit
        def prefill(tokens):
            cache = init_paged_cache(cfg, 2, ring.pool.total, BS)
            return paged_prefill(params, cfg, tokens, cache,
                                 jnp.asarray(row), block_size=BS)

        def through(width):
            padded = np.zeros((1, width), np.int32)
            padded[0, :n] = prompt
            logits, cache = prefill(jnp.asarray(padded))
            return (np.asarray(logits[0, n - 1]),
                    np.asarray(cache["k"][:, used]),
                    np.asarray(cache["v"][:, used]))

        own, widest = through(rung), through(LADDER[-1])
        np.testing.assert_allclose(own[0], widest[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(own[1], widest[1], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(own[2], widest[2], rtol=1e-5, atol=1e-5)
        # and the ring's own program on this rung answers from them
        padded = np.zeros((1, rung), np.int32)
        padded[0, :n] = prompt
        tbl = np.full((2, m), TRASH_BLOCK, np.int32)
        tbl[0, :len(used)] = used
        first, _ = ring.cold_insert(rung, 0, tbl, [], jnp.asarray(padded),
                                    n, 0.0, 0)
        assert int(first) == int(np.argmax(widest[0]))
        np.testing.assert_allclose(
            np.asarray(ring.cache["k"][:, used]), widest[1],
            rtol=1e-5, atol=1e-5)


class TestReadyBeforeReady:
    def test_entry_point_build_leaves_nothing_to_compile(self, setup):
        cfg, params = setup
        prompts = {b: _prompt(cfg, b - 5, seed=b).tolist() for b in LADDER}
        again = _prompt(cfg, LADDER[0] - 9, seed=7).tolist()
        srv = serve.make_server(
            "127.0.0.1", 0, params, cfg, continuous=True, slots=2,
            max_len=MAX_LEN, chunk_tokens=4, paged=True, block_size=BS)
        b = srv.generator.batcher
        try:
            assert b.buckets == LADDER
            serve.ready_ring(b, prewarm=False)
            assert b.prewarmed.is_set()
            with compile_events() as seen:
                # the first request compiles the decode step (this ring
                # runs no prewarm), and no insert
                b.submit(again, max_new_tokens=3).result(timeout=300)
                assert "jit(step)" in {name for _, name in seen}
                assert "jit(insert)" not in {name for _, name in seen}
                del seen[:]
                # then a first prompt on every rung: nothing at all
                for rung, p in prompts.items():
                    out = b.submit(p, max_new_tokens=3).result(timeout=300)
                    assert len(out) == len(p) + 3
                assert seen == []
            assert b.stats["prefill_calls_by_bucket"] == {
                LADDER[0]: 2, **{r: 1 for r in LADDER[1:]}}
        finally:
            srv.generator.close()
            srv.server_close()

    def test_a_ring_built_directly_compiles_what_it_is_sent(self, setup):
        cfg, params = setup
        b = ContinuousBatcher(params, cfg, slots=2, max_len=64,
                              chunk_tokens=4, paged=True, block_size=8,
                              prefill_buckets=(16, 64))
        try:
            b.submit(_prompt(cfg, 9, seed=3).tolist(),
                     max_new_tokens=3).result(timeout=300)
            ins = b.executor.inserts            # still the lazy jits
            assert (ins[16]._cache_size(), ins[64]._cache_size()) == (1, 0)
        finally:
            b.close()

    def test_swap_to_another_tree_goes_back_to_the_jits(self, setup):
        cfg, params = setup
        ex = RingExecutor(params, cfg, slots=1, max_len=64, chunk_tokens=1,
                          paged=True, block_size=8)
        jits = dict(ex.inserts)
        ex.compile_inserts()
        assert all(ex.inserts[b] is not jits[b] for b in jits)
        ex.swap_weights(jax.tree.map(lambda x: x + 0, params))
        assert all(ex.inserts[b] is not jits[b] for b in jits)   # same tree
        ex.swap_weights(jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), params))
        assert ex.inserts == jits
