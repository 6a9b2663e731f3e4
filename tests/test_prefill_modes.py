"""Scheduler/executor split + prefill modes (ISSUE 6).

The serving ring's host half (infer/scheduler.py) and device half
(infer/executor.py) replaced the monolithic batcher; on top sit three
admission prefill paths — ``inline`` (the original one-dispatch
prefill), ``chunked`` (Sarathi-style slices interleaved into ring
iterations), ``disagg`` (DistServe-style: cold prompts prefill on a
separate executor thread + pool, handed off block-granular).  The
contract this file pins:

- greedy output BIT-IDENTICAL to decode.generate in every mode (the
  inline ring is the oracle, as in PR 3/4);
- the request lifecycle — admission order, deadline expiry, cancel,
  drain, watchdog rebuild — behaves identically across the three
  modes (parameterized);
- a chaos run under ``disagg`` keeps exactly-once resolution and the
  pool partition invariant across the handoff;
- the off-thread compile prewarm removes the first-long-prompt
  compile cliff (the lazy `_bucket_for`/insert-compile regression).
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer.chaos import ChaosEvent, ChaosInjector
from paddle_operator_tpu.infer.resilience import (
    LaneQuarantined,
    RetriableError,
    RingResilience,
    ShuttingDown,
)
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import make_model

MAX_LEN = 64
BS = 8
MODES = ("inline", "chunked", "disagg")


@pytest.fixture(scope="module")
def setup():
    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _prompt(cfg, s, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (s,), 0, cfg.vocab_size,
        dtype=jnp.int32)).tolist()


def _ref(cfg, params, prompt, new):
    return np.asarray(D.generate(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=new, max_len=MAX_LEN)[0]).tolist()


def _batcher(cfg, params, mode="inline", **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (8, 16, 32, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousBatcher(params, cfg, prefill_mode=mode, **kw)


class TestParity:
    """Greedy bit-identity: every mode must emit decode.generate's
    exact stream — short prompts (one slice), slice-boundary prompts,
    and long multi-slice prompts, concurrently."""

    # the inline param re-proves what test_paged already pins — full
    # runs only; tier-1 keeps the two NEW prefill paths
    # ISSUE 9 budget: all three paged parities live in the slow tier —
    # the dryrun serve-disagg line pins chunked+disagg bit-identity at
    # tp=1/tp=2, spec off/on, every run
    @pytest.mark.parametrize("mode", [
        pytest.param("inline", marks=pytest.mark.slow),
        pytest.param("chunked", marks=pytest.mark.slow),
        pytest.param("disagg", marks=pytest.mark.slow)])
    def test_greedy_parity_paged(self, setup, mode):
        cfg, params = setup
        # 5 < one slice; 16 = exactly two slices (and block-aligned);
        # 33 = five slices with a ragged tail crossing a block boundary
        lens = (5, 16, 33)
        refs = [_ref(cfg, params, _prompt(cfg, s, seed=10 + i), 8)
                for i, s in enumerate(lens)]
        b = _batcher(cfg, params, mode)
        try:
            hs = [b.submit(_prompt(cfg, s, seed=10 + i),
                           max_new_tokens=8)
                  for i, s in enumerate(lens)]
            got = [h.result(timeout=300) for h in hs]
            assert got == refs
            b.pool.check_invariant()
            if mode == "disagg":
                assert b.stats["disagg_prefills"] > 0
            if mode == "chunked":
                assert b.stats["chunked_prefill_tokens"] > 0
        finally:
            b.close()

    @pytest.mark.slow   # ISSUE 9 budget: contiguous chunked parity —
    # the serve-disagg gate pins the paged chunked leg every run
    def test_greedy_parity_chunked_contiguous(self, setup):
        """Chunked prefill on the CONTIGUOUS ring (paged off): the
        staging-lane slice path splices bit-identically."""
        cfg, params = setup
        lens = (5, 16, 33)
        refs = [_ref(cfg, params, _prompt(cfg, s, seed=20 + i), 8)
                for i, s in enumerate(lens)]
        b = _batcher(cfg, params, "chunked", paged=False)
        try:
            hs = [b.submit(_prompt(cfg, s, seed=20 + i),
                           max_new_tokens=8)
                  for i, s in enumerate(lens)]
            assert [h.result(timeout=300) for h in hs] == refs
            assert b.stats["chunked_prefill_tokens"] > 0
        finally:
            b.close()

    def test_disagg_rejects_contiguous_ring(self, setup):
        cfg, params = setup
        from paddle_operator_tpu.infer.executor import RingExecutor

        with pytest.raises(ValueError, match="paged"):
            RingExecutor(params, cfg, slots=1, max_len=MAX_LEN,
                         chunk_tokens=4, prefill_mode="disagg",
                         paged=False)
        with pytest.raises(ValueError, match="prefill_mode"):
            _batcher(cfg, params, "bogus")


class TestLifecycle:
    """The request lifecycle must not care which prefill path admitted
    the lane — one parameterized suite, three modes."""

    @pytest.mark.parametrize("mode", MODES)
    def test_admission_order_fifo(self, setup, mode):
        """slots=1: queued requests decode strictly in submission
        order, whatever the prefill path."""
        cfg, params = setup
        b = _batcher(cfg, params, mode, slots=1)
        order = []
        try:
            hs = [b.submit(_prompt(cfg, 12, seed=30 + i),
                           max_new_tokens=4)
                  for i in range(3)]
            done = []
            for i, h in enumerate(hs):
                threading.Thread(
                    target=lambda i=i, h=h: (h.result(timeout=300),
                                             order.append(i)),
                    daemon=True).start()
                done.append(h)
            for h in done:
                h.result(timeout=300)
            time.sleep(0.2)                   # let the appends land
            assert order == [0, 1, 2]
        finally:
            b.close()

    @pytest.mark.parametrize("mode", MODES)
    def test_deadline_expiry_partial(self, setup, mode):
        """A resident lane past its deadline retires at the chunk
        boundary with a partial, its blocks verifiably returned."""
        cfg, params = setup
        b = _batcher(cfg, params, mode, chunk_tokens=2)
        try:
            p = _prompt(cfg, 10, seed=40)
            h = b.submit(p, max_new_tokens=40, deadline_s=0.4)
            out = h.result(timeout=300)
            assert h.deadline_exceeded
            assert out[:len(p)] == p          # prompt + some prefix
            assert len(out) < len(p) + 40
            assert b.stats["deadline_exceeded"] == 1
            b.pool.check_invariant()
            # the freed lane serves the next request normally
            p2 = _prompt(cfg, 6, seed=41)
            assert b.submit(p2, max_new_tokens=4).result(
                timeout=300) == _ref(cfg, params, p2, 4)
        finally:
            b.close()

    @pytest.mark.parametrize("mode", MODES)
    def test_cancel_mid_generation(self, setup, mode):
        cfg, params = setup
        b = _batcher(cfg, params, mode, chunk_tokens=2)
        try:
            p = _prompt(cfg, 10, seed=50)
            ref = _ref(cfg, params, p, 30)
            h = b.submit(p, max_new_tokens=30, stream=True)
            it = h.stream(timeout=120)
            got = [next(it) for _ in range(3)]
            h.cancel()
            out = h.result(timeout=300)
            assert out == ref[:len(out)]      # a clean prefix
            assert out[len(p):len(p) + 3] == got
            b.pool.check_invariant()
        finally:
            b.close()

    @pytest.mark.parametrize("mode", ("chunked", "disagg"))
    def test_cancel_mid_prefill_leaks_no_prior_tokens(self, setup, mode):
        """Regression: the lane's host token mirror is reset at
        ADMISSION, not at activation — a lane cancelled (or expired)
        while still prefilling resolves with its own prompt and a clean
        prefix of its own continuation, never with tokens the lane's
        PREVIOUS occupant generated."""
        cfg, params = setup
        b = _batcher(cfg, params, mode, slots=1)
        try:
            pa = _prompt(cfg, 6, seed=80)
            # A decodes to completion on slot 0, leaving its tokens in
            # the slot's host mirror
            assert b.submit(pa, max_new_tokens=6).result(
                timeout=300) == _ref(cfg, params, pa, 6)
            pb = _prompt(cfg, 33, seed=81)     # multi-slice / cold
            refb = _ref(cfg, params, pb, 8)
            h = b.submit(pb, max_new_tokens=8)
            h.cancel()          # races the slices / the executor handoff
            out = h.result(timeout=300)
            assert out[:len(pb)] == pb
            assert out == refb[:len(out)]      # clean prefix, no A leak
            b.pool.check_invariant()
        finally:
            b.close()

    @pytest.mark.parametrize("mode", MODES)
    def test_drain_finishes_residents(self, setup, mode):
        """drain(): residents (including lanes still PREFILLING at the
        drain edge) finish, new work is refused, blocks return."""
        cfg, params = setup
        b = _batcher(cfg, params, mode)
        p = _prompt(cfg, 20, seed=60)
        ref = _ref(cfg, params, p, 6)
        hs = [b.submit(_prompt(cfg, 20, seed=60), max_new_tokens=6)
              for _ in range(2)]
        # both must be RESIDENT before the drain edge — still-queued
        # requests shed with ShuttingDown by design, and this test is
        # about the resident (including mid-prefill) guarantee
        deadline = time.monotonic() + 60
        while b.stats["admitted"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b.stats["admitted"] == 2
        b.drain(budget_s=60.0)
        for h in hs:
            assert h.result(timeout=10) == ref
        with pytest.raises((ShuttingDown, RuntimeError)):
            b.submit(p, max_new_tokens=2)
        assert b.pool.blocks_free() + b.pool.blocks_cached() \
            == b.pool.num_blocks

    @pytest.mark.parametrize("mode", MODES)
    def test_watchdog_rebuild_then_identical_output(self, setup, mode):
        """A ring-level dispatch fault fails residents retriably and
        self-heals; the rebuilt ring serves bit-identically — with the
        prefill bookkeeping (slices in flight, disagg handoffs) reset
        alongside the device state."""
        cfg, params = setup
        b = _batcher(cfg, params, mode, resilience=RingResilience(
            watchdog=False, max_restarts=3, backoff_base_s=0.05))
        try:
            p = _prompt(cfg, 12, seed=70)
            ref = _ref(cfg, params, p, 8)
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == ref
            inj = ChaosInjector("").install(b)
            nxt = inj.dispatches
            inj.events[nxt] = [ChaosEvent("dispatch_fail", nxt)]
            with pytest.raises(RetriableError):
                b.submit(p, max_new_tokens=8).result(timeout=120)
            assert b.stats["watchdog_restarts"] == 1
            assert b.healthy
            assert not b._prefilling and not b._disagg_waiting
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == ref
            b.pool.check_invariant()
        finally:
            b.close()


class TestDisaggSpecifics:
    def test_prefix_hit_skips_the_prefill_executor(self, setup):
        """A radix prefix HIT admits inline through the suffix insert —
        only uncached suffix tokens are ever prefilled anywhere, and
        the prefill executor never sees the request."""
        cfg, params = setup
        b = _batcher(cfg, params, "disagg")
        try:
            p = _prompt(cfg, 20, seed=80)     # 2 full blocks + tail 4
            ref = _ref(cfg, params, p, 4)
            assert b.submit(p, max_new_tokens=4).result(
                timeout=300) == ref
            assert b.stats["disagg_prefills"] == 1
            cold_tokens = b.stats["prefill_tokens"]
            assert b.submit(p, max_new_tokens=4).result(
                timeout=300) == ref
            assert b.stats["disagg_prefills"] == 1     # no second trip
            suffix = b.stats["prefill_tokens"] - cold_tokens
            assert 0 < suffix < len(p)        # only the uncached tail
            assert b.pool.hit_rate() > 0
            b.pool.check_invariant()
        finally:
            b.close()

    def test_handoff_dropped_for_cancelled_request(self, setup):
        """A request cancelled while its prompt is away on the prefill
        executor: the lane retires, the late result is dropped at
        handoff, no blocks leak."""
        cfg, params = setup
        b = _batcher(cfg, params, "disagg")
        try:
            # stall the executor queue behind a real job so the cancel
            # lands while the victim is still queued/prefilling
            hs = [b.submit(_prompt(cfg, 33, seed=90 + i),
                           max_new_tokens=2) for i in range(2)]
            victim = b.submit(_prompt(cfg, 33, seed=95),
                              max_new_tokens=8)
            victim.cancel()
            out = victim.result(timeout=300)
            assert len(out) <= 33 + 8
            for h in hs:
                h.result(timeout=300)
            pexec = b.executor.prefill_exec
            deadline = time.monotonic() + 30
            while ((not pexec.jobs.empty() or not pexec.results.empty()
                    or b._disagg_waiting)
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            time.sleep(0.1)                   # let late handoffs drain
            b.pool.check_invariant()
            assert sum(r is not None for r in b.lane) == 0
        finally:
            b.close()

    # ~6s; exactly-once + pool-invariant under chaos disagg is pinned
    # by the dryrun serve-chaos gate, so this twin rides -m slow
    @pytest.mark.slow
    def test_chaos_disagg_exactly_once_and_pool_invariant(self, setup):
        """The PR 5 chaos bars under SERVE_PREFILL=disagg: a seeded
        dispatch failure + NaN lane + client drop + drain in one ring
        lifetime — every request resolves exactly one way, the pool
        partition holds across every recovery AND the disagg handoff,
        survivors bit-identical."""
        cfg, params = setup
        new = 8
        prompts = [_prompt(cfg, 13, seed=100 + i) for i in range(4)]
        refs = [_ref(cfg, params, p, new) for p in prompts]

        def resolve(handle):
            try:
                return "ok", handle.result(timeout=300)
            except LaneQuarantined as e:
                return "quarantined", e
            except (ShuttingDown, RetriableError) as e:
                return "retriable", e

        b = _batcher(cfg, params, "disagg", block_size=16,
                     prefill_buckets=(16, MAX_LEN),
                     resilience=RingResilience(watchdog=False,
                                               nan_check=True,
                                               max_restarts=4,
                                               backoff_base_s=0.05))
        outcomes = {"ok": 0, "retriable": 0, "quarantined": 0}
        survivors_ok = True
        try:
            kind, out = resolve(b.submit(prompts[0], max_new_tokens=new))
            assert kind == "ok" and out == refs[0]
            outcomes["ok"] += 1
            inj = ChaosInjector("", seed=7).install(b)

            # dispatch failure with a disagg admission in flight
            nxt = inj.dispatches
            inj.events[nxt] = [ChaosEvent("dispatch_fail", nxt)]
            hs = [b.submit(p, max_new_tokens=new) for p in prompts[:2]]
            kinds = []
            for h, ref in zip(hs, refs[:2]):
                kind, out = resolve(h)
                outcomes[kind] += 1
                kinds.append(kind)
                assert kind in ("retriable", "ok")
                if kind == "ok":
                    survivors_ok &= (out == ref)
            assert b.stats["watchdog_restarts"] == 1
            b.pool.check_invariant()

            # NaN lane: exactly one quarantined, the other bit-identical
            nxt = inj.dispatches
            inj.events[nxt] = [ChaosEvent("nan_lane", nxt, 0)]
            hs = [b.submit(p, max_new_tokens=new) for p in prompts[:2]]
            got = [resolve(h) for h in hs]
            assert sorted(k for k, _ in got) == ["ok", "quarantined"]
            for (kind, out), ref in zip(got, refs[:2]):
                outcomes[kind] += 1
                if kind == "ok":
                    survivors_ok &= (out == ref)
            b.pool.check_invariant()

            # client drop, then drain with queued work
            nxt = inj.dispatches
            inj.events[nxt + 1] = [ChaosEvent("client_drop", nxt + 1)]
            kind, out = resolve(b.submit(prompts[2], max_new_tokens=new))
            assert kind == "ok" and out == refs[2][:len(out)]
            outcomes["ok"] += 1
            hs = [b.submit(p, max_new_tokens=new) for p in prompts]
            b.drain(budget_s=60.0)
            for h, ref in zip(hs, refs):
                kind, out = resolve(h)
                outcomes[kind] += 1
                if kind == "ok":
                    survivors_ok &= (out == ref[:len(out)])
            b.pool.check_invariant()
            assert survivors_ok
            # exactly once: every submit above is accounted for
            assert sum(outcomes.values()) == 1 + 2 + 2 + 1 + len(prompts)
        finally:
            b.close()


class TestPrewarm:
    """The lazy-compile regression (ISSUE 6 satellite): per-bucket
    inserts used to compile on the FIRST prompt that needed them,
    charging one request a full XLA compile.  ``prewarm=True``
    (serve.py default, SERVE_PREWARM=0 opts out) compiles them
    off-thread at construction."""

    # prewarm compiles EVERY bucket program up front — that is the
    # point, and also ~30s of tier-1 wall per mode, so the whole
    # check rides the slow tier (ISSUE 9 budget note: the fleet tests
    # took the fast-tier headroom; prewarm has no cheap variant — its
    # cost IS the compiles it front-loads)
    @pytest.mark.parametrize("mode", [
        pytest.param("inline", marks=pytest.mark.slow),
        pytest.param("chunked", marks=pytest.mark.slow)])
    def test_first_long_prompt_hits_warm_caches(self, setup, mode):
        cfg, params = setup
        b = _batcher(cfg, params, mode, prewarm=True)
        try:
            assert b.prewarmed.wait(timeout=600)
            ex = b.executor
            # every admission insert AND the resident step are compiled
            # before any request arrives...
            warm = {bk: ins._cache_size()
                    for bk, ins in ex.inserts.items()}
            assert all(n == 1 for n in warm.values()), warm
            assert ex.step._cache_size() == 1
            # ...so the first LONG prompt adds no compile: the jit
            # cache sizes stay put (a cold bucket would bump its insert
            # to a second entry only on signature drift — a fresh one
            # compiles 0 -> 1; either way a delta here is the cliff)
            p = _prompt(cfg, 33, seed=110)    # largest bucket, cold
            t0 = time.monotonic()
            out = b.submit(p, max_new_tokens=4).result(timeout=300)
            ttft_window = time.monotonic() - t0
            assert out == _ref(cfg, params, p, 4)
            after = {bk: ins._cache_size()
                     for bk, ins in ex.inserts.items()}
            assert after == warm, (warm, after)
            if mode == "chunked":
                assert all(p._cache_size() == 1
                           for p in ex._chunk_progs.values())
                assert all(p._cache_size() == 1
                           for p in ex._suffix_inserts.values())
            # belt + suspenders: the request turned around in request
            # time, not compile time (tiny model; generous CI bound)
            assert ttft_window < 60
        finally:
            b.close()

    def test_prewarm_opt_out_stays_lazy(self, setup):
        cfg, params = setup
        b = _batcher(cfg, params, "inline", prewarm=False)
        try:
            assert b.prewarmed.is_set()       # no thread to wait on
            assert all(ins._cache_size() == 0
                       for ins in b.executor.inserts.values())
        finally:
            b.close()


class TestServingStatusPrefill:
    @pytest.mark.parametrize("mode", MODES)
    def test_status_reports_mode_and_share(self, setup, mode):
        cfg, params = setup
        b = _batcher(cfg, params, mode)
        try:
            p = _prompt(cfg, 20, seed=120)
            b.submit(p, max_new_tokens=4).result(timeout=300)
            st = b.serving_status()
            assert st["prefillMode"] == mode
            assert st["prefillQueueDepth"] == 0
            share = st["chunkedPrefillTokenShare"]
            if mode == "chunked":
                assert share == 1.0           # every prefill token sliced
            else:
                assert share == 0.0
        finally:
            b.close()
