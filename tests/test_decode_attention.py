"""Pallas single-query decode attention (ops/decode_attention.py) pinned
against the XLA einsum path: the kernel reads only the filled cache
prefix, so these tests sweep ragged fill lengths, block sizes, GQA/MHA
ratios, and then run the full generate()/ring paths with the kernel
swapped in (interpret mode on CPU; compiled on TPU by bench.py).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.models.llama import make_model
from paddle_operator_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
    sharded_decode_attention,
)
from paddle_operator_tpu.parallel.mesh import make_serving_mesh


def _rand(shape, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), jnp.float32)


class TestKernelEquivalence:
    @pytest.mark.parametrize("lens", [[5, 64, 17, 33], [1, 1, 1, 1],
                                      [0, 10, 64, 3], [64, 64, 64, 64]])
    @pytest.mark.parametrize("block_k", [16, 64])
    def test_ragged_lengths(self, lens, block_k):
        B, S, HQ, HKV, DH = 4, 64, 8, 4, 32
        q = _rand((B, HQ, DH), 1)
        k = _rand((B, HKV, S, DH), 2)
        v = _rand((B, HKV, S, DH), 3)
        L = jnp.asarray(lens, jnp.int32)
        ref = decode_attention_reference(q, k, v, L)
        got = decode_attention(q, k, v, L, block_k=block_k, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_mha_no_grouping(self):
        B, S, H, DH = 2, 32, 4, 16
        q = _rand((B, H, DH), 4)
        k = _rand((B, H, S, DH), 5)
        v = _rand((B, H, S, DH), 6)
        L = jnp.asarray([7, 32], jnp.int32)
        ref = decode_attention_reference(q, k, v, L)
        got = decode_attention(q, k, v, L, block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_result_independent_of_block_size(self):
        B, S, HQ, HKV, DH = 2, 64, 4, 2, 16
        q, k, v = _rand((B, HQ, DH), 7), _rand((B, HKV, S, DH), 8), \
            _rand((B, HKV, S, DH), 9)
        L = jnp.asarray([3, 50], jnp.int32)
        outs = [np.asarray(decode_attention(q, k, v, L, block_k=bk,
                                            interpret=True))
                for bk in (8, 16, 32, 64)]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=2e-5, atol=2e-5)

    def test_odd_cache_length_shrinks_block(self):
        # S=48 not divisible by 256: the wrapper must shrink the block
        B, S, HQ, HKV, DH = 1, 48, 2, 2, 8
        q, k, v = _rand((B, HQ, DH)), _rand((B, HKV, S, DH), 1), \
            _rand((B, HKV, S, DH), 2)
        L = jnp.asarray([29], jnp.int32)
        ref = decode_attention_reference(q, k, v, L)
        got = decode_attention(q, k, v, L, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestShardedKernel:
    """The kernel TP-sharded under shard_map (the tentpole): per-shard
    block contraction over local GQA groups + the wo psum must equal
    the unsharded kernel + full wo matmul, and the full generate()
    must be TOKEN-IDENTICAL across mesh sizes."""

    @pytest.mark.parametrize("tp", [2, 4])
    def test_sharded_attention_plus_wo_matches_reference(self, tp):
        B, S, HQ, HKV, DH, E = 4, 64, 8, 4, 32, 24
        q = _rand((B, HQ, DH), 1)
        k = _rand((B, HKV, S, DH), 2)
        v = _rand((B, HKV, S, DH), 3)
        wo = _rand((HQ * DH, E), 4)
        L = jnp.asarray([5, 64, 0, 17], jnp.int32)
        mesh = make_serving_mesh(tp)
        got = sharded_decode_attention(mesh, q, k, v, L, wo,
                                       interpret=True)
        ref = decode_attention_reference(q, k, v, L).reshape(B, -1) @ wo
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_sharded_stacked_layer_select(self):
        """The stacked [L, B, Hkv, S, D] cache with the layer index
        steering the block index map — the decode scan's calling
        convention — through the sharded wrapper."""
        B, S, HQ, HKV, DH, E, LN = 2, 32, 4, 2, 16, 12, 3
        q = _rand((B, HQ, DH), 5)
        ks = _rand((LN, B, HKV, S, DH), 6)
        vs = _rand((LN, B, HKV, S, DH), 7)
        wo = _rand((HQ * DH, E), 8)
        L = jnp.asarray([9, 30], jnp.int32)
        mesh = make_serving_mesh(2)
        for lay in range(LN):
            got = sharded_decode_attention(
                mesh, q, ks, vs, L, wo,
                layer=jnp.asarray(lay, jnp.int32), interpret=True)
            ref = decode_attention_reference(
                q, ks[lay], vs[lay], L).reshape(B, -1) @ wo
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"layer {lay}")

    def test_indivisible_heads_rejected(self):
        B, S, HQ, HKV, DH = 2, 32, 4, 2, 16
        q, k, v = _rand((B, HQ, DH)), _rand((B, HKV, S, DH), 1), \
            _rand((B, HKV, S, DH), 2)
        wo = _rand((HQ * DH, 8), 3)
        with pytest.raises(ValueError, match="not divisible"):
            sharded_decode_attention(make_serving_mesh(4), q, k, v,
                                     jnp.asarray([3, 5], jnp.int32), wo,
                                     interpret=True)

    # ~6s; tp-sharded generate token identity is pinned by the dryrun
    # serve-decode gate, so this twin rides -m slow
    @pytest.mark.slow
    def test_generate_tp_sharded_token_identical(self):
        """Acceptance bar: sharded-vs-single-device token match for the
        pallas decode kernel through the full generate() path (tp=2
        mesh, seeded prompts) — and the GSPMD einsum fallback for a tp
        that cannot split the kv heads."""
        model, cfg = make_model("tiny", dtype=jnp.float32,
                                decode_attn="pallas-interpret")
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 9), 0,
                                    cfg.vocab_size, dtype=jnp.int32)
        ref = D.generate(params, cfg, prompt, max_new_tokens=8,
                         max_len=64)
        mesh = make_serving_mesh(2)           # kernel path (hkv=2 % 2)
        got = D.generate(D.shard_params_for_serving(params, cfg, mesh),
                         cfg, prompt, max_new_tokens=8, max_len=64,
                         mesh=mesh)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        mesh4 = make_serving_mesh(4)          # einsum fallback (hkv=2 % 4)
        got4 = D.generate(D.shard_params_for_serving(params, cfg, mesh4),
                          cfg, prompt, max_new_tokens=8, max_len=64,
                          mesh=mesh4)
        np.testing.assert_array_equal(np.asarray(got4), np.asarray(ref))

    def test_generate_tp_sharded_int8_weights(self):
        """Weight-only-int8 params through the sharded kernel: the wo
        {"q","s"} dict crosses the shard_map boundary row-sharded with
        replicated per-output-channel scales."""
        from paddle_operator_tpu.infer.quant import quantize_params

        model, cfg = make_model("tiny", dtype=jnp.float32,
                                decode_attn="pallas-interpret")
        params = quantize_params(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 7), 0,
                                    cfg.vocab_size, dtype=jnp.int32)
        ref = D.generate(params, cfg, prompt, max_new_tokens=6,
                         max_len=64)
        mesh = make_serving_mesh(2)
        got = D.generate(D.shard_params_for_serving(params, cfg, mesh),
                         cfg, prompt, max_new_tokens=6, max_len=64,
                         mesh=mesh)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


class TestGenerateWithKernel:
    def test_generate_matches_xla_path(self):
        """Full generate(): scalar-position decode through the kernel
        must reproduce the einsum path token for token."""
        model, cfg_x = make_model("tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        _, cfg_p = make_model("tiny", dtype=jnp.float32,
                              decode_attn="pallas-interpret")
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                    cfg_x.vocab_size, dtype=jnp.int32)
        ref = D.generate(params, cfg_x, prompt, max_new_tokens=8,
                         max_len=64)
        got = D.generate(params, cfg_p, prompt, max_new_tokens=8,
                         max_len=64)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_ring_step_matches_xla_path(self):
        """The continuous-batching ring with the kernel: ragged lane
        positions through the pallas path."""
        from paddle_operator_tpu.infer.decode import init_ring_cache
        from paddle_operator_tpu.infer.executor import make_prefill_insert

        model, cfg_x = make_model("tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        _, cfg_p = make_model("tiny", dtype=jnp.float32,
                              decode_attn="pallas-interpret")

        def run(cfg):
            cache = init_ring_cache(cfg, 2, 32)
            insert = make_prefill_insert(cfg, 16)
            tok = jnp.zeros((2,), jnp.int32)
            temp = jnp.zeros((2,), jnp.float32)
            keys = jnp.zeros((2, 2), jnp.uint32)
            for slot, n in enumerate((5, 11)):
                p = jax.random.randint(jax.random.PRNGKey(slot), (1, 16),
                                       0, cfg.vocab_size, dtype=jnp.int32)
                cache, tok, temp, keys, _f = insert(
                    params, cache, tok, temp, keys, p, n, slot, 0.0, 0)
            tok = jnp.asarray([3, 7], jnp.int32)
            out, _ = D.cached_step(cfg, params, tok,
                                   D.ContiguousView(cfg, cache))
            return np.asarray(out)

        np.testing.assert_allclose(run(cfg_p), run(cfg_x),
                                   rtol=1e-4, atol=1e-4)
