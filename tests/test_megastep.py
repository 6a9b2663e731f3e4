"""Device-resident megastep (ISSUE 11): N ring iterations fused into
ONE compiled dispatch via the plan-driven executor.

The contract this file pins:

- N-step greedy output BIT-IDENTICAL to the 1-step oracle — the fused
  program's on-device continuation (eos, token budget, deadline-tick
  step budget) makes exactly the decisions the host makes between two
  1-step dispatches (fast bf16 tp=1 legs here; the full prefill-mode x
  spec x kv-quant matrix is behind ``-m slow`` with its invariant
  carried every run by the dryrun ``serve-megastep`` line);
- the N=1 plan replayer dispatches THE legacy compiled program (the
  seam pacing/chaos wrappers install on), so the default ring is
  byte-identical to the pre-refactor dispatch path;
- a lane frozen mid-megastep by its step budget resumes
  bit-identically (the paged trash-redirect + frozen-pos invariants);
- deadlines expire at megastep boundaries with the partial delivered;
- preemption quiesces by consuming the in-flight megastep before the
  spill, and the victim's resumed stream stays bit-identical;
- a chaos run (dispatch_fail + nan_lane) through the wrapped plan
  replayer keeps exactly-once resolution and the pool invariant.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import qos as QOS
from paddle_operator_tpu.infer.chaos import ChaosInjector
from paddle_operator_tpu.infer.resilience import RingResilience
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import Llama, make_model

MAX_LEN = 64
BS = 8


@pytest.fixture(scope="module")
def setup():
    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


@pytest.fixture(scope="module")
def draft(setup):
    cfg, _ = setup
    dcfg = cfg.draft()
    dparams = Llama(dcfg).init(jax.random.PRNGKey(1),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    return dcfg, dparams


def _prompt(cfg, s, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (s,), 0, cfg.vocab_size,
        dtype=jnp.int32)).tolist()


def _batcher(cfg, params, megastep=4, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    return ContinuousBatcher(params, cfg, megastep=megastep, **kw)


def _run(cfg, params, prompts, megastep, new=10, eos=None, **kw):
    b = _batcher(cfg, params, megastep=megastep, **kw)
    try:
        hs = [b.submit(p, max_new_tokens=new, eos_token=eos)
              for p in prompts]
        outs = [h.result(timeout=300) for h in hs]
        if b.pool is not None:
            b.pool.check_invariant()
        return outs, dict(b.stats)
    finally:
        b.close()


def _throttle_replay(b, delay=0.03):
    """Pace the plan replayer (the ONE resident dispatch seam) so
    boundary-timing tests have a multi-dispatch window at any host
    speed — the megastep-era analogue of the old ``b._step`` pacing."""
    real = b.executor.replay
    gate = threading.Event()
    gate.set()

    def slow(plan):
        gate.wait(timeout=120)
        time.sleep(delay)
        return real(plan)

    b.executor.replay = slow
    return gate


# ---------------------------------------------------------------------------
# Bit-identity: the fused program vs the 1-step oracle (fast tp=1 legs)
# ---------------------------------------------------------------------------


class TestParity:
    def test_paged_megastep_bit_identical(self, setup):
        """N=4 fused dispatches emit the 1-step oracle's exact greedy
        stream — mixed prompt lengths, budgets that end mid-megastep,
        a second wave reusing freed lanes."""
        cfg, params = setup
        prompts = [_prompt(cfg, n, seed=50 + n) for n in (13, 33, 7)]
        ref, s1 = _run(cfg, params, prompts, 1)
        got, s4 = _run(cfg, params, prompts, 4)
        assert got == ref
        # the point of the fusion: strictly fewer host dispatches
        assert s4["chunks"] < s1["chunks"]

    def test_contiguous_megastep_bit_identical(self, setup):
        cfg, params = setup
        prompts = [_prompt(cfg, n, seed=70 + n) for n in (5, 21)]
        ref, _ = _run(cfg, params, prompts, 1, paged=False)
        got, _ = _run(cfg, params, prompts, 4, paged=False)
        assert got == ref

    def test_mid_megastep_eos(self, setup):
        """An eos landing inside a fused iteration truncates exactly
        like the oracle's chunk-boundary walk: nothing after eos
        reaches the result, the lane frees, the stream matches."""
        cfg, params = setup
        p = _prompt(cfg, 9, seed=3)
        base, _ = _run(cfg, params, [p], 1, new=12)
        eos = base[0][len(p) + 5]      # fires mid-second-megastep
        ref, _ = _run(cfg, params, [p], 1, new=12, eos=int(eos))
        got, _ = _run(cfg, params, [p], 4, new=12, eos=int(eos))
        assert got == ref
        assert got[0][-1] == eos and len(got[0]) < len(p) + 12

    def test_megastep_serving_status_gauges(self, setup):
        cfg, params = setup
        b = _batcher(cfg, params, megastep=4)
        try:
            b.submit(_prompt(cfg, 8), max_new_tokens=8).result(timeout=300)
            st = b.serving_status()
            assert st["megastepN"] == 4
            assert 0 < st["dispatchesPerToken"] <= 1.0
        finally:
            b.close()


class TestPlanReplayer:
    def test_n1_dispatches_the_legacy_program(self, setup):
        """The N=1 replay goes through ``self.step`` — the exact seam
        the pacing/chaos wrappers install on — so the default ring is
        the byte-identical pre-refactor dispatch path."""
        cfg, params = setup
        b = _batcher(cfg, params, megastep=1)
        calls = []
        real = b._step

        def spy(*a):
            calls.append(len(a))
            return real(*a)

        b._step = spy
        try:
            b.submit(_prompt(cfg, 8), max_new_tokens=8).result(timeout=300)
            assert calls, "replay did not route through executor.step"
        finally:
            b.close()

    def test_megastep_zero_rejected(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="megastep"):
            ContinuousBatcher(params, cfg, slots=1, max_len=32,
                              chunk_tokens=2, prefill_buckets=(16, 32),
                              megastep=0)

    def test_step_budget_freeze_resumes_bit_identical(self, setup):
        """The deadline-tick path: a huge per-iteration estimate forces
        every lane's step budget to 1-of-4 fused iterations, so lanes
        FREEZE mid-megastep every dispatch and resume in the next —
        the stream must still be the oracle's, bit for bit (frozen-pos
        restore + trash-redirect exactness)."""
        cfg, params = setup
        prompts = [_prompt(cfg, n, seed=90 + n) for n in (11, 26)]
        ref, _ = _run(cfg, params, prompts, 1, new=12)
        b = _batcher(cfg, params, megastep=4)
        b._step_s_est = 100.0          # => steps budget 1 per dispatch
        try:
            hs = [b.submit(p, max_new_tokens=12, deadline_s=3000.0)
                  for p in prompts]
            got = [h.result(timeout=300) for h in hs]
            assert not any(h.deadline_exceeded for h in hs)
            b.pool.check_invariant()
        finally:
            b.close()
        assert got == ref


# ---------------------------------------------------------------------------
# Lifecycle at megastep boundaries
# ---------------------------------------------------------------------------


class TestLifecycle:
    def test_deadline_expires_at_boundary_with_partial(self, setup):
        cfg, params = setup
        b = _batcher(cfg, params, megastep=4, slots=1)
        _throttle_replay(b, delay=0.08)
        try:
            p = _prompt(cfg, 8)
            h = b.submit(p, max_new_tokens=40, deadline_s=0.3)
            out = h.result(timeout=300)
            assert h.deadline_exceeded
            assert len(p) <= len(out) < len(p) + 40
            assert b.stats["deadline_exceeded"] == 1
            b.pool.check_invariant()
            # the freed lane serves the next request normally
            ref, _ = _run(cfg, params, [p], 1, new=4)
            assert b.submit(p, max_new_tokens=4).result(timeout=300) \
                == ref[0]
        finally:
            b.close()

    def test_preemption_quiesces_inflight_megastep(self, setup):
        """A p0 arrival against a full N=4 ring: the scheduler drains
        the in-flight megastep(s) to the TRUE boundary, spills the
        victim, serves p0, and the victim's resumed stream is
        bit-identical to an unpreempted run."""
        cfg, params = setup
        p_long = _prompt(cfg, 9, seed=5)
        p_hot = _prompt(cfg, 6, seed=6)
        ref, _ = _run(cfg, params, [p_long], 1, new=40)
        b = _batcher(cfg, params, megastep=4, slots=1,
                     qos=QOS.QoSConfig(priorities=2, preempt=True))
        _throttle_replay(b, delay=0.05)
        try:
            victim = b.submit(p_long, max_new_tokens=40)
            deadline = time.monotonic() + 30
            while b.stats["admitted"] == 0:
                assert time.monotonic() < deadline
                time.sleep(0.001)
            hot = b.submit(p_hot, max_new_tokens=4, priority=0)
            hot_out = hot.result(timeout=300)
            victim_out = victim.result(timeout=300)
            assert b.stats["preempted_lanes"] >= 1
            assert b.stats["restored_lanes"] >= 1
            assert victim_out == ref[0]
            href, _ = _run(cfg, params, [p_hot], 1, new=4)
            assert hot_out == href[0]
            b.pool.check_invariant()
        finally:
            b.close()

    def test_chaos_through_the_plan_replayer(self, setup):
        """dispatch_fail + nan_lane fired THROUGH the wrapped replayer
        on an N=4 ring: every request resolves exactly once (a result
        or a typed error, never a hang), the pool invariant holds, and
        the healed ring still serves the oracle stream."""
        cfg, params = setup
        b = _batcher(cfg, params, megastep=4, slots=2,
                     resilience=RingResilience(
                         watchdog=False, nan_check=True,
                         backoff_base_s=0.01, backoff_max_s=0.05))
        # N=4 megasteps make dispatches scarce: 40-token budgets keep
        # the ring alive past dispatch 4 so both events actually fire
        inj = ChaosInjector("nan_lane@2,dispatch_fail@4", seed=7).install(b)
        try:
            prompts = [_prompt(cfg, n, seed=30 + n) for n in (8, 12, 10)]
            hs = [b.submit(p, max_new_tokens=40) for p in prompts]
            resolved = 0
            for h in hs:
                try:
                    h.result(timeout=300)
                    resolved += 1
                except Exception:
                    resolved += 1        # typed failure IS a resolution
            assert resolved == len(hs)
            assert {k for k, _ in inj.fired} == {"nan_lane",
                                                 "dispatch_fail"}
            b.pool.check_invariant()
            # post-heal: the ring serves the exact oracle stream again
            ref, _ = _run(cfg, params, [prompts[0]], 1, new=6)
            assert b.submit(prompts[0],
                            max_new_tokens=6).result(timeout=300) == ref[0]
            b.pool.check_invariant()
        finally:
            b.close()


# ---------------------------------------------------------------------------
# The full matrix (slow; the dryrun serve-megastep line carries the
# fast invariant every run)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestFullMatrix:
    @pytest.mark.parametrize("mode", ("inline", "chunked", "disagg"))
    @pytest.mark.parametrize("spec", (0, 3))
    @pytest.mark.parametrize("kv_quant", ("none", "int8"))
    def test_matrix_tp1(self, setup, draft, mode, spec, kv_quant):
        cfg, params = setup
        dcfg, dparams = draft
        kw = dict(prefill_mode=mode, prefill_chunk=8)
        if spec:
            kw.update(draft_params=dparams, draft_cfg=dcfg, spec_k=spec)
        if kv_quant != "none":
            kw.update(kv_quant=kv_quant)
        prompts = [_prompt(cfg, n, seed=50 + n) for n in (13, 33)]
        ref, _ = _run(cfg, params, prompts, 1, new=8, **kw)
        got, _ = _run(cfg, params, prompts, 4, new=8, **kw)
        assert got == ref, f"{mode}/spec={spec}/{kv_quant} diverged"

    def test_matrix_tp2(self, setup):
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        cfg, params = setup
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        try:
            mesh = make_serving_mesh(2)
        except (RuntimeError, NotImplementedError) as e:
            pytest.skip(f"no tp=2 mesh: {e}")
        prompts = [_prompt(cfg, n, seed=50 + n) for n in (13, 33)]
        ref, _ = _run(cfg, params, prompts, 1, new=8, mesh=mesh)
        got, _ = _run(cfg, params, prompts, 4, new=8, mesh=mesh)
        assert got == ref
