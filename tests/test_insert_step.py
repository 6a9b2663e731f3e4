"""An insert carries one decode step (ISSUE 33): the paged LLaMA ring's
whole-prompt insert advances every live lane by a token on the weight read
it already pays for.

The program against the parent's two in sequence — the whole-prompt insert
written out here from ``paged.paged_prefill`` as the parent had it, then a
one-token ``make_paged_chunk_step`` — on a tiny float32 ring; the scheduler
against the same requests served one at a time.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import make_model

LANES, BS, MAX_LEN, W = 4, 8, 64, 16
M = MAX_LEN // BS
# the tolerance tests/test_paged.py holds the paged kernel to against the
# einsum: a wider product may round another way
TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def tiny():
    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)


def _padded(cfg, n, seed, width=W):
    out = np.zeros((1, width), np.int32)
    out[0, :n] = _prompt(cfg, n, seed)
    return jnp.asarray(out)


def _parent_insert(cfg, top_k=None, top_p=None):
    """The whole-prompt insert as the parent commit had it."""
    def insert(params, cache, table_row, tok, temp, keys, prompt,
               prompt_len, slot, temp_val, seed):
        logits, new_cache = PG.paged_prefill(params, cfg, prompt, cache,
                                             table_row, block_size=BS)
        new_cache["pos"] = new_cache["pos"].at[slot].set(prompt_len)
        key = jax.random.PRNGKey(seed)
        first = D._sample_tokens(
            logits[0, prompt_len - 1][None],
            jnp.reshape(temp_val, (1,)).astype(jnp.float32), key[None],
            jnp.reshape(prompt_len - 1, (1,)), top_k, top_p)[0]
        return (new_cache, tok.at[slot].set(first),
                temp.at[slot].set(temp_val), keys.at[slot].set(key), first)

    return jax.jit(insert)


def _ring(cfg, params, lens, temps, insert):
    """A ring whose lanes 0..len(lens)-1 hold prompts of `lens` tokens,
    admitted one by one with nobody riding."""
    cache = PG.init_paged_cache(cfg, LANES, LANES * M + 1, BS)
    table = jnp.asarray(1 + np.arange(LANES * M).reshape(LANES, M),
                        jnp.int32)
    tok = jnp.zeros((LANES,), jnp.int32)
    temp = jnp.zeros((LANES,), jnp.float32)
    keys = jnp.zeros((LANES, 2), jnp.uint32)
    for slot, (n, t) in enumerate(zip(lens, temps)):
        cache, tok, temp, keys, _ = insert(
            params, cache, table[slot], tok, temp, keys,
            _padded(cfg, n, 100 + slot), n, slot, t, 11 + slot)
    return cache, table, tok, temp, keys


CASES = {
    # lanes' contexts, which of them ride, the new prompt's length
    "all-ride": ((13, 9, 16), (True, True, True), 11),
    "lanes-masked": ((13, 9, 16), (True, False, True), 11),
    "nobody-rides": ((13, 9, 16), (False, False, False), 11),
    # a lane whose next row opens a block (16 % 8 == 0) and one whose next
    # row closes one (15 % 8 == 7)
    "block-boundary": ((16, 15, 8), (True, True, True), 5),
    "prompt-fills-bucket": ((13, 9, 16), (True, True, False), W),
    "prompt-bucket-less-one": ((13, 9, 16), (True, True, True), W - 1),
}


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_insert_equals_the_parents_insert_then_step(tiny, case, sampled):
    cfg, params = tiny
    lens, rides, n = CASES[case]
    temps = (0.8, 0.0, 1.3) if sampled else (0.0, 0.0, 0.0)
    temp_new = 0.7 if sampled else 0.0
    kw = dict(top_k=5) if sampled else {}
    parent = _parent_insert(cfg, **kw)
    cache, table, tok, temp, keys = _ring(cfg, params, lens, temps, parent)
    slot = LANES - 1
    active = jnp.asarray(list(rides) + [False])
    prompt = _padded(cfg, n, 7)

    # the parent's two programs in sequence
    c, t, tp, k, first_p = parent(params, cache, table[slot], tok, temp,
                                  keys, prompt, n, slot, temp_new, 5)
    step = PG.make_paged_chunk_step(cfg, 1, **kw)
    c, t, toks_p = step(params, c, jnp.where(active[:, None], table, 0), t,
                        tp, k, active)

    ins = PG.make_paged_prefill_insert(cfg, W, BS, **kw)
    nc, ntok, ntemp, nkeys, first, toks = ins(
        params, cache, table, tok, temp, keys, active, prompt, n, slot,
        temp_new, 5)

    assert int(first) == int(first_p)
    # the step's row: a lane that sat out keeps its token (as the step's
    # own mask has it); the inserted slot's entry is the step's business
    assert np.asarray(toks)[0, :slot].tolist() == \
        np.asarray(toks_p)[0, :slot].tolist()
    assert np.asarray(ntok).tolist() == np.asarray(t).tolist()
    np.testing.assert_array_equal(np.asarray(ntemp), np.asarray(tp))
    np.testing.assert_array_equal(np.asarray(nkeys), np.asarray(k))
    # positions: riders one further, the slot at its prompt's length; a
    # live lane that sat out KEEPS its position (the step zeroes it)
    want = [p + 1 if r else p for p, r in zip(lens, rides)] + [n]
    assert np.asarray(nc["pos"]).tolist() == want
    assert [int(x) for x, r in zip(np.asarray(c["pos"]), rides) if r] \
        == [w for w, r in zip(want, rides) if r]
    # the pool, on every live block (block 0 is the trash block)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(nc[name])[:, 1:],
                                   np.asarray(c[name])[:, 1:], **TOL)


def test_insert_through_the_decode_kernel_equals_the_einsum(tiny):
    """The lanes' attention through ``kernel_attend`` over the call's work
    list (interpret mode): the same tokens as the einsum's, the pool
    within the kernel's tolerance."""
    _, cfg_x = make_model("tiny", dtype=jnp.float32)
    _, cfg_k = make_model("tiny", dtype=jnp.float32,
                          decode_attn="pallas-interpret")
    params = tiny[1]
    active = jnp.asarray([True, False, True, False])

    def run(cfg):
        cache, table, tok, temp, keys = _ring(
            cfg, params, (13, 9, 16), (0.0,) * 3, _parent_insert(cfg))
        out = PG.make_paged_prefill_insert(cfg, W, BS)(
            params, cache, table, tok, temp, keys, active,
            _padded(cfg, 11, 7), 11, LANES - 1, 0.0, 5)
        return out

    x, k = run(cfg_x), run(cfg_k)
    assert int(x[4]) == int(k[4])
    assert np.asarray(x[5]).tolist() == np.asarray(k[5]).tolist()
    assert np.asarray(x[0]["pos"]).tolist() == np.asarray(k[0]["pos"]).tolist()
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(x[0][name])[:, 1:],
                                   np.asarray(k[0][name])[:, 1:],
                                   rtol=1e-4, atol=1e-4)


def test_check_finite_adds_the_steps_verdict(tiny):
    cfg, params = tiny
    cache, table, tok, temp, keys = _ring(cfg, params, (13, 9), (0.0, 0.0),
                                          _parent_insert(cfg))
    # poison lane 1's first block: its logits go non-finite, lane 0's not
    cache["k"] = cache["k"].at[:, int(table[1, 0])].set(jnp.nan)
    out = PG.make_paged_prefill_insert(cfg, W, BS, check_finite=True)(
        params, cache, table, tok, temp, keys,
        jnp.asarray([True, True, False, False]), _padded(cfg, 11, 7), 11,
        LANES - 1, 0.0, 5)
    assert len(out) == 7
    assert np.asarray(out[6]).tolist() == [True, False, True, True]


@pytest.mark.parametrize("n", [11, W - 1, W])
def test_a_rung_too_wide_to_carry_is_the_parents_insert(tiny, monkeypatch, n):
    """Above ``_STEP_MAX_BUCKET`` the insert stands alone, with the head at
    the prompt's last real token (no ``[W, vocab]`` logits): the parent's
    five outputs, sampled the same, whatever the lane mask says."""
    cfg, params = tiny
    monkeypatch.setattr(PG, "_STEP_MAX_BUCKET", W // 2)
    parent = _parent_insert(cfg, top_k=5)
    cache, table, tok, temp, keys = _ring(cfg, params, (13, 9, 16),
                                          (0.8, 0.0, 1.3), parent)
    slot, prompt = LANES - 1, _padded(cfg, n, 7)
    want = parent(params, cache, table[slot], tok, temp, keys, prompt, n,
                  slot, 0.7, 5)
    got = PG.make_paged_prefill_insert(cfg, W, BS, top_k=5)(
        params, cache, table, tok, temp, keys, jnp.ones((LANES,), bool),
        prompt, n, slot, 0.7, 5)
    assert len(got) == 5 and int(got[4]) == int(want[4])
    for a, b in zip(jax.tree.leaves(got[:4]), jax.tree.leaves(want[:4])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kw", [dict(quant=True), dict(lora=True)],
                         ids=["int8-pool", "adapter"])
def test_other_pools_keep_the_parents_program(tiny, kw):
    """The int8 pool's and an adapter ring's inserts return no ``toks``:
    five outputs, every other lane untouched."""
    from paddle_operator_tpu.infer import qos as QOS

    cfg, params = tiny
    quant = kw.get("quant", False)
    cache = PG.init_paged_cache(cfg, LANES, LANES * M + 1, BS,
                                quant="int8" if quant else "none")
    table = jnp.asarray(1 + np.arange(LANES * M).reshape(LANES, M),
                        jnp.int32)
    tail = ()
    if kw.get("lora"):
        reg = QOS.AdapterRegistry(cfg, rank=2, capacity=2)
        reg.load("a", seed=1)
        tail = (reg.arrays(), jnp.full((1,), 1, jnp.int32))
    assert PG.insert_carries_step(W) and not PG.insert_carries_step(
        W, None, quant, bool(tail))
    out = PG.make_paged_prefill_insert(cfg, W, BS, quant=quant)(
        params, cache, table, jnp.full((LANES,), 9, jnp.int32),
        jnp.zeros((LANES,), jnp.float32), jnp.zeros((LANES, 2), jnp.uint32),
        jnp.ones((LANES,), bool), _padded(cfg, 11, 7), 11, 2, 0.0, 5, *tail)
    assert len(out) == 5
    assert np.asarray(out[0]["pos"]).tolist() == [0, 0, 11, 0]
    assert [int(t) for i, t in enumerate(np.asarray(out[1])) if i != 2] \
        == [9, 9, 9]


# ---------------------------------------------------------------------------
# The scheduler: the carried step's tokens are delivered in device order
# ---------------------------------------------------------------------------


def _batcher(cfg, params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, 32, MAX_LEN))
    kw.setdefault("paged", True)
    kw.setdefault("block_size", BS)
    kw.setdefault("prefix_cache", False)
    return ContinuousBatcher(params, cfg, **kw)


class _Gate:
    """Holds the ring's thread inside its next dispatch, so that requests
    submitted meanwhile are admitted in ONE pass of the loop — each later
    insert then carries the earlier lanes' step."""

    def __init__(self, b):
        self.b, self.real = b, b.executor.replay
        self.closed, self.inside = threading.Event(), threading.Event()
        self.open = threading.Event()
        b.executor.replay = self

    def __call__(self, plan):
        if self.closed.is_set() and not self.open.is_set():
            self.inside.set()
            self.open.wait(timeout=60)
        return self.real(plan)

    def hold(self):
        self.closed.set()
        assert self.inside.wait(timeout=60)

    def release(self):
        self.open.set()


def _alone(cfg, params, reqs):
    """Each request's answer on a ring that serves it alone."""
    b = _batcher(cfg, params, slots=1)
    try:
        return [b.submit(p, **kw).result(timeout=300) for p, kw in reqs]
    finally:
        b.close()


def _held_ring(cfg, params, **kw):
    """A ring with one long request decoding in lane 0 and its thread
    held in a dispatch."""
    b = _batcher(cfg, params, **kw)
    gate = _Gate(b)
    warm = (_prompt(cfg, 9, 50).tolist(), dict(max_new_tokens=40))
    h = b.submit(warm[0], stream=True, **warm[1])
    it = h.stream(timeout=300)
    got = [next(it)]
    gate.hold()
    return b, gate, warm, h, it, got


def test_streams_are_the_same_requests_served_alone(tiny):
    cfg, params = tiny
    reqs = [(_prompt(cfg, n, 60 + i).tolist(),
             dict(max_new_tokens=m, temperature=t, seed=3 + i))
            for i, (n, m, t) in enumerate(
                [(5, 9, 0.0), (20, 12, 0.9), (14, 7, 0.0)])]
    b, gate, warm, h, it, got = _held_ring(cfg, params)
    try:
        hs = [b.submit(p, stream=True, **kw) for p, kw in reqs]
        gate.release()
        streams = [list(x.stream(timeout=300)) for x in hs]
        outs = [x.result(timeout=300) for x in hs]
        got += list(it)
        warm_out = h.result(timeout=300)
        st = b.serving_status()
    finally:
        b.close()
    want = _alone(cfg, params, [warm] + reqs)
    assert [warm_out] + outs == want
    # in order: what a stream yielded is the answer's tail
    assert warm_out[len(warm[0]):] == got
    for (p, _), s, o in zip(reqs, streams, outs):
        assert o[len(p):] == s
    # three admissions in one pass: the second carried lanes 0 and 1, the
    # third lanes 0, 1 and 2 (the first, lane 0; the warm request's found
    # the ring idle, and a step nobody rides is not counted)
    assert st["insertStepsTotal"] == 3
    assert st["insertStepLanesTotal"] == 1 + 2 + 3
    assert st["decodeStepsTotal"] == (4 * st["dispatchesTotal"]
                                      + st["insertStepsTotal"])


@pytest.mark.parametrize("how", ["max_new", "eos"])
def test_lane_finished_by_the_carried_token_leaves_once(tiny, how):
    """Lane 1's second token is the one insert 2 carries: its budget
    (or its EOS) ends there, it is evicted once and emits nothing after."""
    cfg, params = tiny
    p1, p2 = _prompt(cfg, 6, 71).tolist(), _prompt(cfg, 12, 72).tolist()
    ref = _alone(cfg, params, [(p1, dict(max_new_tokens=8))])[0]
    kw1 = (dict(max_new_tokens=2) if how == "max_new"
           else dict(max_new_tokens=8, eos_token=ref[len(p1) + 1]))
    b, gate, warm, h, it, got = _held_ring(cfg, params)
    try:
        evicted = b.stats["evicted"]
        h1 = b.submit(p1, stream=True, **kw1)
        h2 = b.submit(p2, max_new_tokens=6)
        gate.release()
        s1 = list(h1.stream(timeout=300))
        o1, o2 = h1.result(timeout=300), h2.result(timeout=300)
        warm_out = h.result(timeout=300)
        st = b.serving_status()
        assert b.stats["evicted"] - evicted == 3
    finally:
        b.close()
    assert o1 == ref[:len(p1) + 2] and s1 == o1[len(p1):]
    want = _alone(cfg, params, [warm, (p2, dict(max_new_tokens=6))])
    assert [warm_out, o2] == want          # two admissions, both deliver
    assert st["insertStepLanesTotal"] == 1 + 2


def test_lane_without_a_block_sits_the_step_out(tiny):
    """The pool cannot grow lane 0 while the insert asks: it does not
    ride, it is not failed, and its answer is the one it gives alone."""
    cfg, params = tiny
    p1 = _prompt(cfg, 6, 81).tolist()
    b, gate, warm, h, it, got = _held_ring(cfg, params)
    try:
        riders, ensure = b._insert_riders, b.pool.ensure
        asking = []

        def starved(slot, need):
            if asking and slot == 0:
                raise PG.NoFreeBlocks("test: no block for lane 0")
            return ensure(slot, need)

        def ask(slot):
            asking.append(slot)
            try:
                return riders(slot)
            finally:
                asking.pop()

        b.pool.ensure, b._insert_riders = starved, ask
        h1 = b.submit(p1, max_new_tokens=6)
        gate.release()
        o1 = h1.result(timeout=300)
        warm_out = h.result(timeout=300)
        st = b.serving_status()
        b.pool.check_invariant()
    finally:
        b.close()
    assert [warm_out, o1] == _alone(
        cfg, params, [warm, (p1, dict(max_new_tokens=6))])
    assert st["insertStepsTotal"] == st["insertStepLanesTotal"] == 0
    assert st["decodeStepsTotal"] == 4 * st["dispatchesTotal"]


def test_a_ring_mixes_rungs_that_carry_a_step_and_rungs_that_do_not(
        tiny, monkeypatch):
    """The benchmark's ring: the narrow rungs carry a step, the wide ones
    keep the insert alone (``_STEP_MAX_BUCKET``).  Here only the 16 rung
    carries: a 20-token prompt takes the 32 rung's plain insert beside
    lanes mid-answer, which stand still for it and answer as alone."""
    cfg, params = tiny
    monkeypatch.setattr(PG, "_STEP_MAX_BUCKET", 16)
    reqs = [(_prompt(cfg, n, 40 + i).tolist(), dict(max_new_tokens=m))
            for i, (n, m) in enumerate([(20, 9), (7, 8), (27, 6), (12, 7)])]
    b, gate, warm, h, it, got = _held_ring(cfg, params, slots=5)
    try:
        assert b.executor.insert_steps == {16}
        hs = [b.submit(p, stream=True, **kw) for p, kw in reqs]
        gate.release()
        streams = [list(x.stream(timeout=300)) for x in hs]
        outs = [x.result(timeout=300) for x in hs]
        got += list(it)
        warm_out = h.result(timeout=300)
        st = b.serving_status()
    finally:
        b.close()
    assert [warm_out] + outs == _alone(cfg, params, [warm] + reqs)
    assert warm_out[len(warm[0]):] == got
    for (p, _), s, o in zip(reqs, streams, outs):
        assert o[len(p):] == s
    # one pass admits 20, 7, 27, 12: the 16 rung's two inserts carry
    # lanes 0-1 and lanes 0-3, the 32 rung's two carry nobody
    assert st["prefillCallsByBucket"] == {"16": 3, "32": 2}
    assert st["insertStepsTotal"] == 2
    assert st["insertStepLanesTotal"] == 2 + 4
    assert st["decodeStepsTotal"] == (4 * st["dispatchesTotal"]
                                      + st["insertStepsTotal"])


@pytest.mark.parametrize("mode", ["int8", "contiguous"])
def test_rings_whose_insert_carries_no_step_are_served_as_before(tiny, mode):
    cfg, params = tiny
    kw = (dict(kv_quant="int8") if mode == "int8" else dict(paged=False))
    reqs = [(_prompt(cfg, n, 90 + n).tolist(), dict(max_new_tokens=6))
            for n in (5, 12)]
    b = _batcher(cfg, params, **kw)
    try:
        outs = [x.result(timeout=300)
                for x in [b.submit(p, **k) for p, k in reqs]]
        st = b.serving_status()
    finally:
        b.close()
    assert st["insertStepsTotal"] == st["insertStepLanesTotal"] == 0
    assert st["decodeStepsTotal"] == 4 * st["dispatchesTotal"]
    assert all(len(o) == len(p) + 6 for o, (p, _) in zip(outs, reqs))
