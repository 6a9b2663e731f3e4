"""Speculative decoding (infer/speculative.py) pinned against
decode.generate: greedy draft-propose + chunked-verify must be
TOKEN-IDENTICAL to plain autoregressive decoding — the acceptance rule
only ever commits tokens the target itself argmaxes, so any divergence
is a bug, not rounding.  Covers the issue's edge cases: all-reject and
all-accept rounds, EOS landing mid-speculated-block, per-slot divergent
accept lengths in the continuous-batching ring, vocab mismatch, and
the submit-queue backpressure satellite.
"""

import queue
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher, QueueFull
from paddle_operator_tpu.infer.speculative import (
    check_draft_compat,
    speculative_generate,
)
from paddle_operator_tpu.models.llama import Llama, make_model

MAX_LEN = 64


@pytest.fixture(scope="module")
def setup():
    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    dcfg = cfg.draft()
    dparams = Llama(dcfg).init(jax.random.PRNGKey(1),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params, dcfg, dparams


def _prompt(cfg, s, seed=1, batch=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, s), 0,
                              cfg.vocab_size, dtype=jnp.int32)


class TestDraftConfig:
    def test_draft_shares_vocab_and_rope_at_same_head_dim(self, setup):
        cfg, _, dcfg, _ = setup
        assert dcfg.vocab_size == cfg.vocab_size
        assert dcfg.max_seq_len == cfg.max_seq_len
        assert dcfg.head_dim == cfg.head_dim
        assert dcfg.n_layers < cfg.n_layers or cfg.n_layers == 1
        assert dcfg.dim < cfg.dim
        assert dcfg.n_heads % dcfg.n_kv_heads == 0

    def test_draft_overrides(self, setup):
        cfg, _, _, _ = setup
        d = cfg.draft(n_layers=2)
        assert d.n_layers == 2 and d.vocab_size == cfg.vocab_size

    def test_vocab_mismatch_raises_clear_error(self, setup):
        cfg, params, dcfg, dparams = setup
        import dataclasses

        bad = dataclasses.replace(dcfg, vocab_size=cfg.vocab_size + 1)
        with pytest.raises(ValueError, match="vocab mismatch"):
            check_draft_compat(cfg, bad)
        with pytest.raises(ValueError, match="vocab mismatch"):
            speculative_generate(params, dparams, cfg, bad,
                                 _prompt(cfg, 5), max_new_tokens=2,
                                 max_len=MAX_LEN)


class TestGreedyParity:
    @pytest.mark.slow   # pinned by dryrun serve-spec (tier-1 budget, ISSUE 10)
    def test_greedy_token_identical_to_generate(self, setup):
        """The core exactness claim, across K and batch: a random-init
        draft rejects nearly everything, yet the output must equal
        autoregressive generate token for token."""
        cfg, params, dcfg, dparams = setup
        for batch, k in ((1, 2), (2, 3), (2, 8)):
            p = _prompt(cfg, 9, seed=7, batch=batch)
            ref = D.generate(params, cfg, p, max_new_tokens=12,
                             max_len=MAX_LEN)
            out = speculative_generate(params, dparams, cfg, dcfg, p,
                                       max_new_tokens=12, spec_k=k,
                                       max_len=MAX_LEN)
            assert jnp.array_equal(ref, out), f"batch={batch} k={k}"

    def test_all_accept_rounds_self_draft(self, setup):
        """Draft == target: every round accepts all K drafts + bonus
        (accept_rate 1.0), and the output still equals generate."""
        cfg, params, _, _ = setup
        p = _prompt(cfg, 9, seed=7, batch=2)
        ref = D.generate(params, cfg, p, max_new_tokens=12,
                         max_len=MAX_LEN)
        out, stats = speculative_generate(
            params, params, cfg, cfg, p, max_new_tokens=12, spec_k=4,
            max_len=MAX_LEN, return_stats=True)
        assert stats["accept_rate"] == 1.0
        # full acceptance commits K+1 tokens per round
        assert stats["rounds"] == -(-(12 - 1) // 5)
        assert jnp.array_equal(ref, out)

    def test_all_reject_rounds_still_exact(self, setup):
        """Random-init tiny draft vs target: acceptance ~1/vocab — every
        round commits exactly ONE token (the target's correction), and
        the result is still exact."""
        cfg, params, dcfg, dparams = setup
        p = _prompt(cfg, 9, seed=3)
        ref = D.generate(params, cfg, p, max_new_tokens=10,
                         max_len=MAX_LEN)
        out, stats = speculative_generate(
            params, dparams, cfg, dcfg, p, max_new_tokens=10, spec_k=3,
            max_len=MAX_LEN, return_stats=True)
        assert jnp.array_equal(ref, out)
        assert stats["accept_rate"] < 0.5          # random agreement only
        assert stats["rounds"] >= 5                # ~1 token per round

    def test_eos_mid_speculated_block(self, setup):
        """EOS landing inside a speculated block: nothing after it leaks
        into the result, and the tail pads with eos exactly like
        generate's static-shape semantics."""
        cfg, params, dcfg, dparams = setup
        p = _prompt(cfg, 7, seed=3)
        ref = np.asarray(D.generate(params, cfg, p, max_new_tokens=12,
                                    max_len=MAX_LEN)[0]).tolist()
        eos = ref[7 + 6]                 # a token greedy decode emits
        want = D.generate(params, cfg, p, max_new_tokens=12,
                          max_len=MAX_LEN, eos_token=eos)
        # all-accept draft maximizes block length past the eos position
        out = speculative_generate(params, params, cfg, cfg, p,
                                   max_new_tokens=12, spec_k=8,
                                   max_len=MAX_LEN, eos_token=eos)
        assert jnp.array_equal(want, out)
        got = np.asarray(out[0]).tolist()
        cut = got.index(eos, 7)
        assert all(t == eos for t in got[cut:])    # nothing after eos

    def test_max_new_one_and_capacity_validation(self, setup):
        cfg, params, dcfg, dparams = setup
        p = _prompt(cfg, 5, seed=2)
        ref = D.generate(params, cfg, p, max_new_tokens=1, max_len=MAX_LEN)
        out = speculative_generate(params, dparams, cfg, dcfg, p,
                                   max_new_tokens=1, spec_k=4,
                                   max_len=MAX_LEN)
        assert jnp.array_equal(ref, out)
        with pytest.raises(ValueError, match="exceeds the cache"):
            speculative_generate(params, dparams, cfg, dcfg, p,
                                 max_new_tokens=MAX_LEN, spec_k=4,
                                 max_len=MAX_LEN)
        with pytest.raises(ValueError, match="spec_k"):
            speculative_generate(params, dparams, cfg, dcfg, p,
                                 max_new_tokens=2, spec_k=0,
                                 max_len=MAX_LEN)


class TestSampled:
    def test_sampled_deterministic_per_key_and_in_vocab(self, setup):
        cfg, params, dcfg, dparams = setup
        p = _prompt(cfg, 6, seed=4)
        kw = dict(max_new_tokens=8, spec_k=3, temperature=0.8,
                  max_len=MAX_LEN)
        a = speculative_generate(params, dparams, cfg, dcfg, p,
                                 key=jax.random.PRNGKey(5), **kw)
        b = speculative_generate(params, dparams, cfg, dcfg, p,
                                 key=jax.random.PRNGKey(5), **kw)
        c = speculative_generate(params, dparams, cfg, dcfg, p,
                                 key=jax.random.PRNGKey(6), **kw)
        assert jnp.array_equal(a, b)
        assert not jnp.array_equal(a, c)   # overwhelmingly likely
        assert 0 <= int(a.min()) and int(a.max()) < cfg.vocab_size

    def test_sampled_self_draft_accepts_everything(self, setup):
        """p == q makes min(1, p/q) = 1: rejection sampling must accept
        every draft when draft and target are the same model."""
        cfg, params, _, _ = setup
        p = _prompt(cfg, 6, seed=4)
        _, stats = speculative_generate(
            params, params, cfg, cfg, p, max_new_tokens=10, spec_k=4,
            temperature=0.7, key=jax.random.PRNGKey(8), max_len=MAX_LEN,
            return_stats=True)
        assert stats["accept_rate"] == 1.0


class TestSpeculativeRing:
    """Per-slot variable accept-length advance inside ContinuousBatcher:
    lanes accept divergent prefix lengths every round, and every emitted
    sequence must still equal decode.generate's."""

    def _ring(self, cfg, params, dcfg, dparams, **kw):
        kw.setdefault("slots", 2)
        kw.setdefault("max_len", MAX_LEN)
        kw.setdefault("chunk_tokens", 4)
        kw.setdefault("prefill_buckets", (16, MAX_LEN))
        return ContinuousBatcher(params, cfg, draft_params=dparams,
                                 draft_cfg=dcfg, spec_k=3, **kw)

    @pytest.mark.slow   # pinned by dryrun serve-spec (tier-1 budget, ISSUE 10)
    def test_ragged_lanes_divergent_accepts_match_generate(self, setup):
        cfg, params, dcfg, dparams = setup
        b = self._ring(cfg, params, dcfg, dparams)
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
            assert b.stats["spec_drafted"] > 0
            assert all(r.accept_rate is not None for r in reqs)
        finally:
            b.close()

    @pytest.mark.slow   # pinned by dryrun serve-spec (tier-1 budget, ISSUE 10)
    def test_mixed_accept_lengths_in_one_wave(self, setup):
        """One lane rides a SELF-draft-agreeing request while another
        diverges: submit the same ring a prompt whose draft is the
        target (impossible per-request — so approximate by checking the
        per-request accept rates differ across requests with different
        prompts, proving per-slot advance is independent)."""
        cfg, params, _, _ = setup
        # self-draft ring: acceptance 1.0 for every lane
        b = self._ring(cfg, params, cfg, params)
        try:
            prompts = [_prompt(cfg, n, seed=30 + i)
                       for i, n in enumerate([5, 9])]
            reqs = [b.submit(np.asarray(p[0]), max_new_tokens=8)
                    for p in prompts]
            for p, r in zip(prompts, reqs):
                ref = D.generate(params, cfg, p, max_new_tokens=8,
                                 max_len=MAX_LEN)
                assert r.result(timeout=300) == np.asarray(ref[0]).tolist()
                assert r.accept_rate == 1.0
        finally:
            b.close()

    def test_eos_in_ring_spec_block(self, setup):
        cfg, params, _, _ = setup
        p = _prompt(cfg, 7, seed=3)
        ref = np.asarray(D.generate(params, cfg, p, max_new_tokens=12,
                                    max_len=MAX_LEN)[0]).tolist()
        eos = ref[7 + 6]
        want = ref[:ref.index(eos, 7) + 1]
        b = self._ring(cfg, params, cfg, params)   # all-accept blocks
        try:
            out = b.submit(np.asarray(p[0]), max_new_tokens=12,
                           eos_token=eos).result(timeout=300)
            assert out == want                     # no tokens after eos
        finally:
            b.close()

    def test_spec_capacity_bound(self, setup):
        cfg, params, dcfg, dparams = setup
        b = self._ring(cfg, params, dcfg, dparams)
        try:
            # prompt + max_new + spec_k - 1 > max_len must be rejected
            with pytest.raises(ValueError, match="speculative headroom"):
                b.submit(list(range(1, 60)), max_new_tokens=4)
            # inside the bound it serves
            out = b.submit(list(range(1, 50)),
                           max_new_tokens=4).result(timeout=300)
            assert len(out) == 49 + 4
        finally:
            b.close()

    def test_spec_requires_draft(self, setup):
        cfg, params, _, _ = setup
        with pytest.raises(ValueError, match="draft_params"):
            ContinuousBatcher(params, cfg, slots=1, max_len=MAX_LEN,
                              spec_k=2)


class TestShardedSpeculative:
    @pytest.mark.slow      # dryrun serve-spec pins the tp=2 parity
    def test_tp2_speculative_matches_single_device(self, setup):
        """The tentpole's sharding claim: the draft's single-token steps
        and the chunked verify ride the same tp mesh, tokens unchanged."""
        from paddle_operator_tpu.parallel.mesh import make_serving_mesh

        _, params, _, dparams = setup
        _, cfg = make_model("tiny", dtype=jnp.float32,
                            decode_attn="pallas-interpret")
        dcfg = cfg.draft()
        mesh = make_serving_mesh(2)
        p = _prompt(cfg, 9, seed=7, batch=2)
        ref = D.generate(params, cfg, p, max_new_tokens=10,
                         max_len=MAX_LEN)
        out = speculative_generate(
            D.shard_params_for_serving(params, cfg, mesh),
            D.shard_params_for_serving(dparams, dcfg, mesh),
            cfg, dcfg, p, max_new_tokens=10, spec_k=3, max_len=MAX_LEN,
            mesh=mesh)
        assert jnp.array_equal(ref, out)


class TestBackpressure:
    def test_bounded_queue_rejects_on_saturation(self, setup):
        """max_queue: saturation raises QueueFull after the put timeout
        instead of growing the pending queue without limit, and the ring
        keeps serving the admitted requests."""
        cfg, params, _, _ = setup
        b = ContinuousBatcher(params, cfg, slots=1, max_len=MAX_LEN,
                              chunk_tokens=2, prefill_buckets=(16, MAX_LEN),
                              max_queue=1, queue_timeout=0.2)
        orig = b._step

        def paced(*a):
            time.sleep(0.05)
            return orig(*a)

        b._step = paced
        try:
            admitted = [b.submit([1, 2, 3], max_new_tokens=24)]
            # fill the single queue slot + the lane, then saturate
            seen_full = False
            backlog = []
            for i in range(6):
                try:
                    backlog.append(b.submit([4, 5, 6], max_new_tokens=24))
                except QueueFull:
                    seen_full = True
                    break
            assert seen_full, "saturation never rejected"
            assert b.stats["rejected_queue_full"] >= 1
            # everything actually admitted still completes correctly
            ref = D.generate(params, cfg,
                             jnp.asarray([[1, 2, 3]], jnp.int32),
                             max_new_tokens=24, max_len=MAX_LEN)
            assert admitted[0].result(timeout=300) == \
                np.asarray(ref[0]).tolist()
            for r in backlog:
                r.result(timeout=300)
        finally:
            b.close()

    def test_unbounded_default_never_rejects(self, setup):
        cfg, params, _, _ = setup
        b = ContinuousBatcher(params, cfg, slots=1, max_len=MAX_LEN,
                              chunk_tokens=2,
                              prefill_buckets=(16, MAX_LEN))
        try:
            reqs = [b.submit([1, 2], max_new_tokens=2) for _ in range(8)]
            for r in reqs:
                r.result(timeout=300)
            assert b.stats["rejected_queue_full"] == 0
        finally:
            b.close()
