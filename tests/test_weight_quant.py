"""Serving-side weight quantization (ISSUE 16, infer/quant.py +
SERVE_WEIGHT_QUANT / SERVE_DRAFT_QUANT): int8 (int4 stretch) matmul
kernels with per-output-channel f32 scale planes riding the params
dispatch operand, dequant fused at the matmul sites (decode._mm).

Quality is a LOGIT BOUND against the bf16 op sequence (the pinned
oracle, same discipline as test_kvquant); bit-level parity is claimed
MODE-vs-MODE: every admission path — cold, prefix hit, chunked, spec,
megastep, LoRA — dispatches the SAME quantized tree, so their outputs
must be IDENTICAL to each other (quant-vs-bf16 token equality is not
claimed: quantization legitimately flips an argmax whose logit gap is
below the quantization error).  bf16 stays the default and nothing here
touches its behavior; the fast legs are bf16/tp1-budget tiny-model
runs, the quant×spec×tp matrix rides ``-m slow`` with its invariants
pinned every run by the dryrun serve-wquant line."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import quant as Q
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import Llama, make_model

MAX_LEN = 64


@pytest.fixture(scope="module")
def setup():
    model, cfg = make_model("tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, cfg, params


@pytest.fixture(scope="module")
def qparams(setup):
    _, cfg, params = setup
    return Q.quantize_params(params, cfg, skip=Q.SERVING_SKIP)


def _prompt(cfg, s, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (s,), 0, cfg.vocab_size,
        dtype=jnp.int32))


def _batcher(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("chunk_tokens", 4)
    kw.setdefault("prefill_buckets", (16, 32, MAX_LEN))
    return ContinuousBatcher(params, cfg, **kw)


def _leaves_by_path(params):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = {}
    for path, leaf in flat:
        out["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)] = leaf
    return out


class TestQuantizeParams:
    """The quantize-at-load satellite: roundtrip bit-stability,
    skip-list coverage, and the shape/byte arithmetic the gauges and
    bench accounting build on.  No ring, no compile — pure tree math."""

    @pytest.mark.parametrize("mode", ["int8", "int4"])
    def test_roundtrip_bit_stable(self, mode):
        """quantize -> dequantize -> quantize is a FIXED POINT: the
        absmax element maps to ±qmax exactly, jnp.round is
        round-half-even, so the recomputed scale and every code
        reproduce — a process restarted from a dequantized snapshot
        serves identical logits."""
        w = jax.random.normal(jax.random.PRNGKey(3), (32, 16),
                              jnp.float32)
        l1 = Q.quantize_leaf(w, mode)
        deq = Q.dequantize_leaf(l1, jnp.float32)
        l2 = Q.quantize_leaf(deq, mode)
        assert (np.asarray(l1["q"]) == np.asarray(l2["q"])).all()
        assert (np.asarray(l1["s"]) == np.asarray(l2["s"])).all()
        # and the dequantized values themselves are a fixed point
        deq2 = Q.dequantize_leaf(l2, jnp.float32)
        assert (np.asarray(deq) == np.asarray(deq2)).all()

    def test_all_zero_channel_gets_unit_scale(self):
        w = jnp.zeros((8, 4))
        leaf = Q.quantize_leaf(w)
        assert (np.asarray(leaf["s"]) == 1.0).all()   # never divide by 0
        assert (np.asarray(leaf["q"]) == 0).all()

    @pytest.mark.parametrize("mode,qmax", [("int8", 127.0),
                                           ("int4", 7.0)])
    def test_quantization_error_bounded(self, mode, qmax):
        """Per-element error <= scale/2 (round-half-even over the code
        grid) — the arithmetic behind the logit bound."""
        w = jax.random.normal(jax.random.PRNGKey(4), (64, 32),
                              jnp.float32)
        leaf = Q.quantize_leaf(w, mode)
        err = np.abs(np.asarray(Q.dequantize_leaf(leaf, jnp.float32))
                     - np.asarray(w))
        bound = np.asarray(leaf["s"]) / 2 + 1e-7
        assert (err <= bound).all()

    def test_bf16_checkpoint_quantizes_like_f32(self):
        """Quantize-at-load sees the SERVING dtype (bf16): the f32
        scale/round math inside quantize_leaf keeps codes within one
        step of the f32-tree codes, and scales stay f32 planes."""
        w = jax.random.normal(jax.random.PRNGKey(5), (32, 16),
                              jnp.float32)
        lo = Q.quantize_leaf(w)
        lb = Q.quantize_leaf(w.astype(jnp.bfloat16))
        assert lb["s"].dtype == jnp.float32
        assert np.abs(np.asarray(lo["q"], np.int32)
                      - np.asarray(lb["q"], np.int32)).max() <= 2

    def test_serving_skip_list_coverage(self, setup, qparams):
        """Every targeted matmul kernel is a codes+scales dict; every
        embedding / lm_head / norm leaf survives untouched (bf16-path
        float, no new checkpoint format)."""
        _, cfg, params = setup
        orig = _leaves_by_path(params)
        got = _leaves_by_path(qparams)
        n_q = 0
        for path, leaf in orig.items():
            if any(s in path for s in Q.SERVING_SKIP):
                assert (np.asarray(got[path]) == np.asarray(leaf)).all(), \
                    f"skip-listed leaf {path} was modified"
            elif Q._TARGETS.search(path):
                assert got[path + "/q"].dtype == jnp.int8, path
                assert got[path + "/s"].dtype == jnp.float32, path
                n_q += 1
        # stacked-layer tree: one leaf per projection site covering
        # every layer — 4 attention + 3 MLP kernels
        assert n_q == 7

    def test_legacy_call_still_quantizes_lm_head(self, setup):
        """The no-kwargs form keeps the original target set (lm_head
        included) — bench comparability and the test_decode pin."""
        _, _, params = setup
        legacy = Q.quantize_params(params)
        assert legacy["lm_head"]["kernel"]["q"].dtype == jnp.int8

    def test_unknown_mode_rejected(self, setup):
        _, cfg, params = setup
        with pytest.raises(ValueError, match="int3"):
            Q.quantize_params(params, cfg, mode="int3")

    def test_mode_detection(self, setup, qparams):
        _, cfg, params = setup
        assert Q.weight_quant_mode(params) == "none"
        assert Q.weight_quant_mode(qparams) == "int8"
        i4 = Q.quantize_params(params, cfg, mode="int4",
                               skip=Q.SERVING_SKIP)
        assert Q.weight_quant_mode(i4) == "int4"

    def test_param_bytes_shrink(self, setup, qparams):
        """The gauge/bench arithmetic: int8 codes + f32 scale planes
        cost less than the bf16 tree they replace, and the serving
        tree's total respects the tiny model's embedding-heavy shape
        (the 7B-shape ratio is pinned by bench's hbm accounting)."""
        _, cfg, params = setup
        bf16 = Q.param_bytes(Q.serving_params(params, jnp.bfloat16))
        q8 = Q.param_bytes(Q.serving_params(qparams, jnp.bfloat16))
        assert 0 < q8 < bf16
        # per-kernel: 1 byte/param + scales vs 2 bytes/param
        w = params["layers"]["attn"]["wq"]["kernel"]
        kq = Q.param_bytes({"k": Q.quantize_leaf(w)})
        kb = Q.param_bytes({"k": w.astype(jnp.bfloat16)})
        assert kq < 0.6 * kb


class TestLogitBound:
    # Pinned tolerance for the tiny f32 model, same scale as the
    # kvquant bound: measured max per-step logit delta is ~0.01-0.05
    # at these shapes; 0.15 gives ~3x headroom without ever passing a
    # broken dequant (a dropped scale plane shows up as O(1)-O(100)
    # deltas).  The dryrun serve-wquant line pins the same bound
    # end-to-end at tp=1 and tp=2.
    TOL = 0.15

    def test_prefill_and_decode_logits_within_bound(self, setup,
                                                    qparams):
        """Per-step logits of the int8-weight forward against the bf16
        op sequence on identical token streams (the oracle's greedy
        choice drives both) — prefill position plus enough decode
        steps to exercise attention and MLP projections repeatedly."""
        _, cfg, params = setup
        prompt = jnp.asarray([_prompt(cfg, 19, seed=5)], jnp.int32)
        lo, co = D.prefill(params, cfg, prompt, MAX_LEN)
        lq, cq = D.prefill(qparams, cfg, prompt, MAX_LEN)
        worst = np.abs(np.asarray(lq) - np.asarray(lo)).max()
        assert worst <= self.TOL, f"prefill logit delta {worst}"
        step_o = D.make_decode_fn(cfg)
        step_q = D.make_decode_fn(cfg)
        tok = jnp.asarray(np.asarray(lo).argmax(-1), jnp.int32)
        for _ in range(16):
            lo, co = step_o(params, tok, co)
            lq, cq = step_q(qparams, tok, cq)
            d = np.abs(np.asarray(lq) - np.asarray(lo)).max()
            worst = max(worst, d)
            assert worst <= self.TOL, f"decode logit delta {worst}"
            tok = jnp.asarray(np.asarray(lo).argmax(-1), jnp.int32)
        assert worst > 0                 # int8 is not magically exact

    @pytest.mark.slow   # 870s budget: the int4 stretch is not a
    # tier-1 quality claim; the int8 bound above is the pinned oracle
    def test_int4_bound_is_looser_but_finite(self, setup, qparams):
        """The int4 stretch: coarser grid, larger — but still small —
        logit error; pinned only as finite and ordered vs int8 (int4
        is draft-model territory, not a target-quality claim)."""
        _, cfg, params = setup
        i4 = Q.quantize_params(params, cfg, mode="int4",
                               skip=Q.SERVING_SKIP)
        prompt = jnp.asarray([_prompt(cfg, 19, seed=5)], jnp.int32)
        lo, _ = D.prefill(params, cfg, prompt, MAX_LEN)
        l8, _ = D.prefill(qparams, cfg, prompt, MAX_LEN)
        l4, _ = D.prefill(i4, cfg, prompt, MAX_LEN)
        d8 = np.abs(np.asarray(l8) - np.asarray(lo)).max()
        d4 = np.abs(np.asarray(l4) - np.asarray(lo)).max()
        assert 0 < d8 <= d4 < 3.0


class TestQuantRing:
    def test_quantized_ring_serves_and_reports(self, setup, qparams):
        """Fast tp1 leg: a continuous ring over the quantized tree
        admits, decodes, and reports the weight-quant status block
        (weightQuantMode detected from leaf dtypes, paramBytes below
        the bf16 tree's) — the deeper path-identity matrix rides
        ``-m slow`` and the dryrun serve-wquant line."""
        _, cfg, params = setup
        b = _batcher(cfg, qparams)
        try:
            p = _prompt(cfg, 11, seed=6)
            out = b.submit(p, max_new_tokens=6).result(timeout=300)
            assert len(out) == 11 + 6
            st = b.serving_status()
            assert st["weightQuantMode"] == "int8"
            assert st["draftQuantMode"] == "none"
            assert 0 < st["paramBytes"] < Q.param_bytes(params)
        finally:
            b.close()

    @pytest.mark.slow   # 870s budget: pinned EVERY run by the dryrun
    # serve-wquant line's bf16-default-byte-identical leg
    def test_bf16_default_unchanged(self, setup):
        """bf16 stays the default and the oracle: an unquantized ring
        reports mode "none" and matches decode.generate exactly (the
        pre-PR contract, byte-for-byte — also pinned by the dryrun
        serve-wquant bf16 leg)."""
        _, cfg, params = setup
        b = _batcher(cfg, params)
        try:
            p = _prompt(cfg, 11, seed=7)
            want = np.asarray(D.generate(
                params, cfg, jnp.asarray([p], jnp.int32),
                max_new_tokens=6, max_len=MAX_LEN)[0]).tolist()
            assert b.submit(p, max_new_tokens=6).result(
                timeout=300) == want
            assert b.serving_status()["weightQuantMode"] == "none"
        finally:
            b.close()


class TestQuantCompositionSlow:
    """MODE-vs-MODE identity: every admission path dispatches the same
    int8 tree through decode._mm, so outputs must match the inline
    int8 ring bit-for-bit.  Each leg also rides the dryrun
    serve-wquant line; here they are regression pins with fixed
    seeds."""

    def _inline_ref(self, cfg, qparams, p, new=8):
        b = _batcher(cfg, qparams)
        try:
            return b.submit(p, max_new_tokens=new).result(timeout=300)
        finally:
            b.close()

    @pytest.mark.slow
    def test_paged_cold_and_prefix_hit_identical(self, setup, qparams):
        """Paged + radix reuse over quantized weights: the cold
        admission and the full-prefix-hit follower (suffix insert)
        produce identical streams — and match the contiguous inline
        ring (same params operand, same sampling rule)."""
        _, cfg, params = setup
        b = _batcher(cfg, qparams, paged=True, block_size=8)
        try:
            p = _prompt(cfg, 16, seed=8)
            ref = self._inline_ref(cfg, qparams, p)
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == ref, "cold paged int8 diverged"
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == ref, "int8 prefix hit diverged"
            assert b.pool.hit_rate() > 0
            b.pool.check_invariant()
        finally:
            b.close()

    @pytest.mark.slow
    def test_chunked_prefill_identical(self, setup, qparams):
        _, cfg, params = setup
        b = _batcher(cfg, qparams, prefill_mode="chunked",
                     prefill_chunk=8)
        try:
            for seed, n in ((9, 13), (10, 33)):
                p = _prompt(cfg, n, seed=seed)
                assert b.submit(p, max_new_tokens=8).result(
                    timeout=300) == self._inline_ref(
                        cfg, qparams, p), "chunked int8 diverged"
        finally:
            b.close()

    @pytest.mark.slow
    def test_megastep8_identical(self, setup, qparams):
        """The megastep N=8 leg: 8 fused ring iterations per dispatch
        over the quantized tree — byte-identical to single-step (the
        ISSUE 11 invariant carries over because megastep scans the
        same step function over the same params operand)."""
        _, cfg, params = setup
        b = _batcher(cfg, qparams, megastep=8)
        try:
            p = _prompt(cfg, 13, seed=11)
            assert b.submit(p, max_new_tokens=8).result(
                timeout=300) == self._inline_ref(
                    cfg, qparams, p), "megastep int8 diverged"
        finally:
            b.close()

    @pytest.mark.slow
    def test_speculative_target_quant_identical(self, setup, qparams):
        """Spec decode with a QUANTIZED TARGET (bf16 draft): the
        exact-greedy verify rule reads the same quantized logits the
        non-speculative ring emits, so the committed stream is
        identical regardless of what the draft proposes."""
        _, cfg, params = setup
        dcfg = cfg.draft()
        dparams = Llama(dcfg).init(
            jax.random.PRNGKey(1),
            jnp.zeros((1, 8), jnp.int32))["params"]
        b = _batcher(cfg, qparams, draft_params=dparams,
                     draft_cfg=dcfg, spec_k=3)
        try:
            for seed, n in ((12, 13), (13, 33)):
                p = _prompt(cfg, n, seed=seed)
                assert b.submit(p, max_new_tokens=8).result(
                    timeout=300) == self._inline_ref(
                        cfg, qparams, p), "spec int8-target diverged"
        finally:
            b.close()

    @pytest.mark.slow
    @pytest.mark.parametrize("dmode", ["int8", "int4"])
    def test_quantized_draft_accept_rate_sanity(self, setup, qparams,
                                                dmode):
        """SERVE_DRAFT_QUANT's contract: with draft == target (the
        perfect-draft construction, accept rate 1.0 in bf16),
        quantizing ONLY the draft still proposes mostly-accepted
        tokens — drift shows up as accept rate, never as wrong output
        (the committed stream stays identical to non-spec)."""
        _, cfg, params = setup
        dq = Q.quantize_params(params, cfg, mode=dmode,
                               skip=Q.SERVING_SKIP)
        b = _batcher(cfg, params, draft_params=dq, draft_cfg=cfg,
                     spec_k=3)
        try:
            p = _prompt(cfg, 13, seed=14)
            ref = self._inline_ref(cfg, params, p, new=16)
            assert b.submit(p, max_new_tokens=16).result(
                timeout=300) == ref, "quantized draft changed OUTPUT"
            st = b.serving_status()
            assert st["draftQuantMode"] == dmode
            assert st["acceptRate"] > 0.25, \
                f"{dmode} draft accept rate collapsed: {st['acceptRate']}"
        finally:
            b.close()

    @pytest.mark.slow
    def test_lora_on_quantized_base_parity(self, setup, qparams):
        """LoRA adapters stay bf16 deltas gathered AGAINST the
        quantized base (qos.lora_qkv adds to projection outputs after
        _mm): base traffic through an adapter-carrying quantized ring
        is byte-identical to the adapterless quantized ring (zero
        slot = exact-zero deltas), and a real adapter still changes
        the stream."""
        from paddle_operator_tpu.infer import qos as QOS

        _, cfg, params = setup
        reg = QOS.AdapterRegistry(cfg, capacity=2, rank=4)
        reg.load("x", seed=7)
        b = _batcher(cfg, qparams, adapters=reg)
        try:
            p = _prompt(cfg, 10, seed=15)
            ref = self._inline_ref(cfg, qparams, p)
            base = b.submit(p, max_new_tokens=8).result(timeout=300)
            assert base == ref, "base traffic on adapter ring diverged"
            lora = b.submit(p, max_new_tokens=8,
                            adapter="x").result(timeout=300)
            assert lora != base, "adapter did not change the stream"
        finally:
            b.close()

    @pytest.mark.slow
    @pytest.mark.parametrize("tp", [1, 2])
    @pytest.mark.parametrize("spec", [False, True])
    def test_quant_spec_tp_matrix(self, setup, qparams, tp, spec):
        """The quant×spec×tp matrix: generate() over the quantized
        tree at tp=1/tp=2, spec on/off — tp legs must match tp=1
        exactly (same math, head-sharded; scale planes replicate via
        shard_params_for_serving), spec legs must match non-spec."""
        _, cfg, params = setup
        prompt = jnp.asarray([_prompt(cfg, 13, seed=16)], jnp.int32)
        want = np.asarray(D.generate(
            qparams, cfg, prompt, max_new_tokens=8,
            max_len=MAX_LEN)[0]).tolist()
        mesh = None
        tree = qparams
        if tp == 2:
            from paddle_operator_tpu.parallel.mesh import (
                make_serving_mesh,
            )

            try:
                mesh = make_serving_mesh(2, devices=jax.devices())
            except (RuntimeError, ValueError) as e:
                pytest.skip(f"no tp=2 mesh here: {e}")
            tree = D.shard_params_for_serving(qparams, cfg, mesh)
        if spec:
            b = _batcher(cfg, tree, mesh=mesh, draft_params=qparams,
                         draft_cfg=cfg, spec_k=3)
            try:
                got = b.submit(np.asarray(prompt[0]),
                               max_new_tokens=8).result(timeout=300)
            finally:
                b.close()
        else:
            got = np.asarray(D.generate(
                tree, cfg, prompt, max_new_tokens=8, max_len=MAX_LEN,
                mesh=mesh)[0]).tolist()
        assert got == want, f"tp={tp} spec={spec} diverged"
