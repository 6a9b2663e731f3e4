"""Multi-tenant QoS (ISSUE 10, infer/qos.py): priority classes with
class-then-FIFO admission, preemptive lane spill with BIT-IDENTICAL
resume (the ISSUE 8 spill/restore primitive driven by the scheduler),
per-class queue bounds, anti-thrash budgets, parked-lane lifecycle
(deadline/cancel), and many-adapter LoRA serving — mixed-adapter
batches equal to single-adapter runs, base traffic byte-identical to
the adapterless ring, and the radix prefix cache namespaced per
adapter load.

Heavyweight matrices (spec x quant x tp spill, adapter x tp) ride
``-m slow``; the dryrun ``serve-qos`` line pins their invariants every
run (the PR 9 tier-1 budget pattern).
"""

import queue as _queue
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import qos as QOS
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


def _paged_batcher(cfg, params, **kw):
    kw.setdefault("slots", 1)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    kw.setdefault("num_blocks", 16)
    return ContinuousBatcher(params, cfg, **kw)


def _throttle(b, delay=0.03, spec=False):
    """Slow the resident step AND return a pause gate: tests clear the
    gate to freeze the ring at its next dispatch, submit against the
    frozen resident state (a submit can take arbitrarily long on a
    contended host — timing windows flake), then set it to resume.
    Deterministic preemption setup at any machine speed."""
    real = b._spec_step if spec else b._step
    gate = threading.Event()
    gate.set()

    def slow(*a, **k):
        gate.wait(timeout=120)
        time.sleep(delay)
        return real(*a, **k)

    if spec:
        b._spec_step = slow
    else:
        b._step = slow
    return gate


def _wait_admitted(b, n0, timeout=30.0):
    deadline = time.monotonic() + timeout
    while b.stats["admitted"] == n0:
        assert time.monotonic() < deadline, "admission never happened"
        time.sleep(0.001)


def _completion_times(handles):
    """monotonic completion stamp per handle, captured by watchers."""
    times = [None] * len(handles)

    def watch(i, h):
        h.done.wait(timeout=300)
        times[i] = time.monotonic()

    ts = [threading.Thread(target=watch, args=(i, h))
          for i, h in enumerate(handles)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    assert all(x is not None for x in times)
    return times


# ---------------------------------------------------------------------------
# Units: queue, budget, config, registry
# ---------------------------------------------------------------------------


class TestUnits:
    def test_multi_class_queue_orders_class_then_fifo(self):
        q = QOS.MultiClassQueue(3)
        q.put_nowait("b1", 1)
        q.put_nowait("c2", 2)
        q.put_nowait("b2", 1)
        q.put_nowait("a1", 0)
        assert q.peek_class() == 0
        assert [q.get_nowait() for _ in range(4)] == \
            ["a1", "b1", "b2", "c2"]
        with pytest.raises(_queue.Empty):
            q.get_nowait()
        assert q.peek_class() is None

    def test_multi_class_queue_per_class_bound(self):
        """The bound is PER CLASS: a flooded batch class rejects its
        own overflow while the express class keeps admitting."""
        q = QOS.MultiClassQueue(2, maxsize=2)
        q.put_nowait("x", 1)
        q.put_nowait("y", 1)
        assert q.full(1) and not q.full(0)
        with pytest.raises(_queue.Full):
            q.put_nowait("z", 1)
        q.put_nowait("urgent", 0)          # still admits
        assert q.qsize_by_class() == [1, 2]

    def test_multi_class_queue_rejects_bad_class(self):
        q = QOS.MultiClassQueue(2)
        with pytest.raises(ValueError):
            q.put_nowait("x", 2)

    def test_preemption_budget_window(self):
        now = [0.0]
        bud = QOS.PreemptionBudget(2, 10.0, clock=lambda: now[0])
        assert bud.ok()
        bud.spend()
        bud.spend()
        assert not bud.ok()                 # window pinned
        now[0] = 10.1                       # window rolls
        assert bud.ok()

    def test_qos_config_defaults_least_urgent(self):
        cfg = QOS.QoSConfig(priorities=3)
        assert cfg.default_priority == 2
        with pytest.raises(ValueError):
            QOS.QoSConfig(priorities=0)
        with pytest.raises(ValueError):
            QOS.QoSConfig(priorities=2, default_priority=5)

    def test_adapter_registry_lifecycle(self, setup):
        _, cfg, _ = setup
        reg = QOS.AdapterRegistry(cfg, capacity=2, rank=4)
        i1 = reg.load("a", seed=1)
        i2 = reg.load("b", seed=2)
        assert {i1, i2} == {1, 2} and len(reg) == 2
        with pytest.raises(ValueError, match="pool full"):
            reg.load("c")
        with pytest.raises(ValueError, match="unknown adapter"):
            reg.resolve("zzz")
        ns_before = reg.ns_of(i1)
        with pytest.raises(ValueError, match="resident"):
            reg.evict("a", in_use={i1})
        reg.evict("a")
        assert reg.load("a2", seed=3) == i1       # slot reused...
        assert reg.ns_of(i1) != ns_before          # ...namespace fresh
        assert reg.ns_of(0) == 0                   # base = legacy chain

    def test_adapter_registry_zero_slot_is_zero(self, setup):
        _, cfg, _ = setup
        reg = QOS.AdapterRegistry(cfg, capacity=1, rank=2)
        reg.load("x", seed=5)
        arr = reg.arrays()
        for proj in QOS.LORA_PROJS:
            assert not np.asarray(arr[proj]["a"][:, 0]).any()
            assert np.asarray(arr[proj]["a"][:, 1]).any()


# ---------------------------------------------------------------------------
# Priority scheduling + preemption on the live ring
# ---------------------------------------------------------------------------


class TestPriorityScheduling:
    def test_priority_zero_jumps_the_queue(self, setup):
        """slots=1, preemption OFF: the p0 request still overtakes
        earlier-queued lower classes at admission (class-then-FIFO)."""
        _, cfg, params = setup
        b = _paged_batcher(cfg, params,
                           qos=QOS.QoSConfig(preempt=False))
        try:
            p = _prompt(cfg, 9, seed=3)
            b.submit(p, max_new_tokens=8).result(timeout=300)  # warm
            gate = _throttle(b)
            n0 = b.stats["admitted"]
            h_a = b.submit(p, max_new_tokens=12)
            _wait_admitted(b, n0)
            gate.clear()            # freeze the ring while we queue
            h_b = b.submit(_prompt(cfg, 7, seed=4), max_new_tokens=4)
            h_c = b.submit(_prompt(cfg, 7, seed=5), max_new_tokens=4,
                           priority=0)
            gate.set()
            times = _completion_times([h_a, h_b, h_c])
            assert times[2] < times[1], \
                "priority-0 did not overtake the earlier priority-1"
            assert b.stats["preempted_lanes"] == 0
        finally:
            b.close()

    def test_preemption_resumes_bit_identical(self, setup):
        """The tentpole invariant: a p0 arrival preempts the resident
        p1 lane (spill -> retire -> blocks freed -> re-admit), the p0
        finishes while the victim is parked, and the victim's final
        stream is BIT-IDENTICAL to its unpreempted oracle."""
        _, cfg, params = setup
        b = _paged_batcher(cfg, params)
        try:
            p_long = _prompt(cfg, 9, seed=3)
            ref = b.submit(p_long, max_new_tokens=40).result(timeout=300)
            gate = _throttle(b, delay=0.03)
            n0 = b.stats["admitted"]
            h_long = b.submit(p_long, max_new_tokens=40)
            _wait_admitted(b, n0)
            gate.clear()            # freeze: p0 must find a full ring
            h_p0 = b.submit(_prompt(cfg, 7, seed=5), max_new_tokens=4,
                            priority=0)
            gate.set()
            times = _completion_times([h_long, h_p0])
            assert h_long.result(timeout=5) == ref, \
                "preempted lane resumed on a different stream"
            assert times[1] < times[0], "p0 waited for the p1 lane"
            assert b.stats["preempted_lanes"] >= 1
            assert b.stats["restored_lanes"] >= 1
            b.pool.check_invariant()
            st = b.serving_status()
            assert st["preemptedLanes"] == b.stats["preempted_lanes"]
            assert st["parkedLanes"] == 0
            assert len(st["priorityQueueDepth"]) == 2
        finally:
            b.close()

    @pytest.mark.slow   # PreemptionBudget unit + serve-qos line pin this
    def test_preempt_budget_zero_disables_spill(self, setup):
        _, cfg, params = setup
        b = _paged_batcher(cfg, params,
                           qos=QOS.QoSConfig(preempt_budget=0))
        try:
            p = _prompt(cfg, 9, seed=3)
            b.submit(p, max_new_tokens=8).result(timeout=300)
            gate = _throttle(b)
            n0 = b.stats["admitted"]
            h_long = b.submit(p, max_new_tokens=16)
            _wait_admitted(b, n0)
            gate.clear()
            h_p0 = b.submit(_prompt(cfg, 7, seed=5), max_new_tokens=4,
                            priority=0)
            gate.set()
            h_p0.result(timeout=300)
            h_long.result(timeout=300)
            assert b.stats["preempted_lanes"] == 0
        finally:
            b.close()

    def test_parked_lane_deadline_resolves_partial(self, setup):
        """A parked victim whose deadline expires resolves with the
        tokens it had at the spill boundary — the same 504-style
        partial a resident gets — WITHOUT waiting for a free lane (the
        parked sweep fires while the preemptor still decodes)."""
        _, cfg, params = setup
        b = _paged_batcher(cfg, params)
        try:
            p = _prompt(cfg, 9, seed=3)
            b.submit(p, max_new_tokens=8).result(timeout=300)
            gate = _throttle(b, delay=0.05)
            n0 = b.stats["admitted"]
            h_long = b.submit(p, max_new_tokens=40, deadline_s=60.0)
            _wait_admitted(b, n0)
            gate.clear()
            h0 = b.submit(_prompt(cfg, 7, seed=5), max_new_tokens=24,
                          priority=0)
            gate.set()
            deadline = time.monotonic() + 30
            while not b.stats["preempted_lanes"]:
                assert time.monotonic() < deadline, "no preemption"
                time.sleep(0.002)
            # expire the PARKED request now — the sweep must resolve it
            # while the p0 lane is still busy, not at restore time
            h_long.deadline = time.monotonic() - 0.001
            times = _completion_times([h_long, h0])
            assert h_long.deadline_exceeded
            out = h_long.result(timeout=5)
            assert out[:len(p)] == [int(t) for t in p]
            assert times[0] < times[1], \
                "parked expiry waited for the p0 lane to free"
            h0.result(timeout=5)
            b.pool.check_invariant()
        finally:
            b.close()

    def test_parked_lane_cancel_resolves_partial(self, setup):
        _, cfg, params = setup
        b = _paged_batcher(cfg, params)
        try:
            p = _prompt(cfg, 9, seed=3)
            b.submit(p, max_new_tokens=8).result(timeout=300)
            gate = _throttle(b, delay=0.05)
            n0 = b.stats["admitted"]
            h_long = b.submit(p, max_new_tokens=40)
            _wait_admitted(b, n0)
            gate.clear()
            h0 = b.submit(_prompt(cfg, 7, seed=5), max_new_tokens=12,
                          priority=0)
            gate.set()
            # cancel the victim while (likely) parked — either way it
            # must resolve with a prompt-prefixed partial, not hang
            deadline = time.monotonic() + 30
            while not b.stats["preempted_lanes"]:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            h_long.cancel()
            out = h_long.result(timeout=300)
            assert out[:len(p)] == [int(t) for t in p]
            h0.result(timeout=300)
            b.pool.check_invariant()
        finally:
            b.close()

    def test_per_class_queue_bound(self, setup):
        """max_queue bounds each class separately: a full batch class
        rejects its overflow while priority 0 still admits."""
        _, cfg, params = setup
        from paddle_operator_tpu.infer.scheduler import QueueFull

        b = _paged_batcher(cfg, params, max_queue=1, queue_timeout=0.15)
        try:
            p = _prompt(cfg, 9, seed=3)
            b.submit(p, max_new_tokens=8).result(timeout=300)
            gate = _throttle(b)
            n0 = b.stats["admitted"]
            h = [b.submit(p, max_new_tokens=40)]
            _wait_admitted(b, n0)
            gate.clear()            # freeze so the queue cannot drain
            h.append(b.submit(p, max_new_tokens=4))   # fills class 1
            with pytest.raises(QueueFull):
                b.submit(p, max_new_tokens=4)         # class-1 overflow
            h.append(b.submit(p, max_new_tokens=4, priority=0))
            gate.set()
            for x in h:
                x.result(timeout=300)
        finally:
            b.close()

    def test_priority_validation(self, setup):
        _, cfg, params = setup
        b = _paged_batcher(cfg, params)
        try:
            with pytest.raises(ValueError, match="priority 7 outside"):
                b.submit([1, 2], max_new_tokens=2, priority=7)
        finally:
            b.close()


# ---------------------------------------------------------------------------
# Many-adapter serving
# ---------------------------------------------------------------------------


class TestAdapters:
    @pytest.fixture(scope="class")
    def rings(self, setup):
        """One plain ring (the byte-identity oracle) and one
        adapter-carrying ring with the same shape."""
        _, cfg, params = setup
        reg = QOS.AdapterRegistry(cfg, capacity=3, rank=4)
        reg.load("x", seed=7)
        reg.load("y", seed=9)
        plain = ContinuousBatcher(params, cfg, slots=2, max_len=MAX_LEN,
                                  chunk_tokens=4,
                                  prefill_buckets=(16, MAX_LEN))
        adapt = ContinuousBatcher(params, cfg, slots=2, max_len=MAX_LEN,
                                  chunk_tokens=4,
                                  prefill_buckets=(16, MAX_LEN),
                                  adapters=reg)
        yield plain, adapt, reg
        plain.close()
        adapt.close()

    def test_base_traffic_byte_identical(self, setup, rings):
        """Acceptance pin: SERVE_ADAPTERS set but a request using NO
        adapter decodes byte-identically to the adapterless ring (the
        zero adapter slot contributes exact-zero deltas)."""
        _, cfg, _ = setup
        plain, adapt, _ = rings
        p = _prompt(cfg, 10)
        ref = plain.submit(p, max_new_tokens=8).result(timeout=300)
        got = adapt.submit(p, max_new_tokens=8).result(timeout=300)
        assert got == ref

    def test_mixed_batch_equals_single_adapter_runs(self, setup, rings):
        """Acceptance pin: N-adapter mixed-batch outputs == the
        per-adapter single runs exactly (lane math is independent; the
        batched gather serves every lane its own delta)."""
        _, cfg, _ = setup
        _, adapt, _ = rings
        p = _prompt(cfg, 10)
        solo_x = adapt.submit(p, max_new_tokens=8,
                              adapter="x").result(timeout=300)
        solo_y = adapt.submit(p, max_new_tokens=8,
                              adapter="y").result(timeout=300)
        solo_base = adapt.submit(p, max_new_tokens=8).result(timeout=300)
        assert solo_x != solo_base and solo_y != solo_base \
            and solo_x != solo_y, "adapters did not change the stream"
        hx = adapt.submit(p, max_new_tokens=8, adapter="x")
        hy = adapt.submit(p, max_new_tokens=8, adapter="y")
        hb = adapt.submit(p, max_new_tokens=8)
        assert hx.result(timeout=300) == solo_x
        assert hy.result(timeout=300) == solo_y
        assert hb.result(timeout=300) == solo_base

    def test_unknown_adapter_rejected(self, rings):
        _, adapt, _ = rings
        with pytest.raises(ValueError, match="unknown adapter"):
            adapt.submit([1, 2, 3], max_new_tokens=2, adapter="nope")

    def test_adapter_without_registry_rejected(self, rings):
        plain, _, _ = rings
        with pytest.raises(ValueError, match="no adapter registry"):
            plain.submit([1, 2, 3], max_new_tokens=2, adapter="x")

    def test_spec_ring_refuses_adapters(self, setup):
        _, cfg, params = setup
        reg = QOS.AdapterRegistry(cfg, capacity=1, rank=2)
        dcfg = cfg.draft()
        dparams = Llama(dcfg).init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
        with pytest.raises(ValueError, match="speculative"):
            ContinuousBatcher(params, cfg, slots=1, max_len=MAX_LEN,
                              chunk_tokens=4,
                              prefill_buckets=(16, MAX_LEN),
                              draft_params=dparams, draft_cfg=dcfg,
                              spec_k=2, adapters=reg)

    def test_status_reports_adapters(self, rings):
        _, adapt, _ = rings
        st = adapt.serving_status()
        assert st["activeAdapters"] == 2
        assert st["adapterNames"] == ["x", "y"]


class TestAdapterPrefixNamespace:
    def test_no_cross_adapter_prefix_hits(self, setup):
        """An adapter's KV differs from the base model's for the SAME
        tokens (wk/wv carry the delta), so the radix cache must never
        serve one tenant's prefix to another: chains are namespaced by
        the adapter's load generation, including across evict+reload
        of the same registry slot."""
        _, cfg, params = setup
        reg = QOS.AdapterRegistry(cfg, capacity=2, rank=4)
        reg.load("x", seed=7)
        b = _paged_batcher(cfg, params, adapters=reg, num_blocks=32)
        try:
            p = _prompt(cfg, 2 * BS + 3)    # two full cacheable blocks
            b.submit(p, max_new_tokens=2).result(timeout=300)
            hit0 = b.pool.stats["prefix_hit_tokens"]
            # adapter admit of the SAME tokens: no cross-namespace hit
            b.submit(p, max_new_tokens=2,
                     adapter="x").result(timeout=300)
            assert b.pool.stats["prefix_hit_tokens"] == hit0
            # within-adapter reuse works
            b.submit(p, max_new_tokens=2,
                     adapter="x").result(timeout=300)
            hit1 = b.pool.stats["prefix_hit_tokens"]
            assert hit1 > hit0
            # evict + reload the name: fresh namespace, the dead
            # adapter's cached chain is unreachable
            reg.evict("x")
            reg.load("x", seed=11)
            b.submit(p, max_new_tokens=2,
                     adapter="x").result(timeout=300)
            assert b.pool.stats["prefix_hit_tokens"] == hit1
            b.pool.check_invariant()
        finally:
            b.close()


# ---------------------------------------------------------------------------
# Heavyweight matrices: spec/quant preempt-spill parity (dryrun
# serve-qos pins the fast invariants every run)
# ---------------------------------------------------------------------------


class TestSpillMatrixSlow:
    @pytest.mark.slow
    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_preempt_under_spec_bit_identical(self, setup, kv_quant):
        """Preemption mid-speculation: the spill captures the DRAFT
        lane + positions too, so the resumed spec stream (propose /
        verify / rollback history and all) is bit-identical to the
        uninterrupted oracle — bf16 and quantized pool alike (int8
        additionally spills the lane's staging tail mid-block)."""
        _, cfg, params = setup
        dcfg = cfg.draft()
        dparams = Llama(dcfg).init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
        b = _paged_batcher(
            cfg, params, draft_params=dparams, draft_cfg=dcfg,
            spec_k=3, kv_quant=kv_quant, prefix_cache=False)
        try:
            p = _prompt(cfg, 9, seed=3)
            ref = b.submit(p, max_new_tokens=24).result(timeout=600)
            gate = _throttle(b, delay=0.03, spec=True)
            n0 = b.stats["admitted"]
            h_long = b.submit(p, max_new_tokens=24)
            _wait_admitted(b, n0)
            gate.clear()
            h0 = b.submit(_prompt(cfg, 7, seed=5), max_new_tokens=4,
                          priority=0)
            gate.set()
            h0.result(timeout=600)
            assert h_long.result(timeout=600) == ref
            assert b.stats["preempted_lanes"] >= 1
            b.pool.check_invariant()
        finally:
            b.close()

    @pytest.mark.slow
    def test_preempt_int8_mid_staging_tail(self, setup):
        """A lane spilled with its write frontier MID-BLOCK under
        SERVE_KV_QUANT=int8: the bf16 staging tail crosses the spill
        byte-exactly, so the eventual block-completion quantize commits
        the same tile the uninterrupted run commits."""
        _, cfg, params = setup
        b = _paged_batcher(cfg, params, kv_quant="int8")
        try:
            # prompt NOT a block multiple -> live tail at admission;
            # chunk 4 with bs 8 keeps the frontier mid-block at odd
            # chunk boundaries, where the preemption will land
            p = _prompt(cfg, 9, seed=3)
            ref = b.submit(p, max_new_tokens=24).result(timeout=600)
            gate = _throttle(b, delay=0.03)
            n0 = b.stats["admitted"]
            h_long = b.submit(p, max_new_tokens=24)
            _wait_admitted(b, n0)
            gate.clear()
            h0 = b.submit(_prompt(cfg, 7, seed=5), max_new_tokens=4,
                          priority=0)
            gate.set()
            h0.result(timeout=600)
            assert h_long.result(timeout=600) == ref
            assert b.stats["preempted_lanes"] >= 1
            b.pool.check_invariant()
        finally:
            b.close()

    @pytest.mark.slow
    def test_preempt_tp2_bit_identical(self, setup):
        """Preempt-spill-restore under a tp=2 serving mesh: the spill
        reads sharded pool bytes through host gathers and the restore
        re-uploads through the sharded promote scatter — the resumed
        stream must still match the unpreempted tp=2 oracle."""
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        _, cfg, params = setup
        mesh = make_serving_mesh(2)
        b = _paged_batcher(cfg, params, mesh=mesh)
        try:
            p = _prompt(cfg, 9, seed=3)
            ref = b.submit(p, max_new_tokens=24).result(timeout=600)
            gate = _throttle(b, delay=0.03)
            n0 = b.stats["admitted"]
            h_long = b.submit(p, max_new_tokens=24)
            _wait_admitted(b, n0)
            gate.clear()
            h0 = b.submit(_prompt(cfg, 7, seed=5), max_new_tokens=4,
                          priority=0)
            gate.set()
            h0.result(timeout=600)
            assert h_long.result(timeout=600) == ref
            assert b.stats["preempted_lanes"] >= 1
            b.pool.check_invariant()
        finally:
            b.close()

    @pytest.mark.slow
    def test_adapter_parity_tp2(self, setup):
        """Mixed-adapter parity under a tp=2 serving mesh: the LoRA
        delta einsums ride GSPMD off replicated adapter arrays, and
        sharded streams match the single-device ones."""
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        _, cfg, params = setup
        reg = QOS.AdapterRegistry(cfg, capacity=2, rank=4)
        reg.load("x", seed=7)
        p = None
        b1 = ContinuousBatcher(params, cfg, slots=2, max_len=MAX_LEN,
                               chunk_tokens=4,
                               prefill_buckets=(16, MAX_LEN),
                               adapters=reg)
        try:
            p = _prompt(cfg, 10)
            ref_x = b1.submit(p, max_new_tokens=8,
                              adapter="x").result(timeout=600)
            ref_b = b1.submit(p, max_new_tokens=8).result(timeout=600)
        finally:
            b1.close()
        mesh = make_serving_mesh(2)
        b2 = ContinuousBatcher(params, cfg, slots=2, max_len=MAX_LEN,
                               chunk_tokens=4,
                               prefill_buckets=(16, MAX_LEN),
                               adapters=reg, mesh=mesh)
        try:
            hx = b2.submit(p, max_new_tokens=8, adapter="x")
            hb = b2.submit(p, max_new_tokens=8)
            assert hx.result(timeout=600) == ref_x
            assert hb.result(timeout=600) == ref_b
        finally:
            b2.close()
