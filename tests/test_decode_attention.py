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
    CELL_FIRST,
    CELL_LAST,
    decode_attention,
    decode_attention_reference,
    decode_cells,
    paged_decode_attention,
    sharded_decode_attention,
)
from paddle_operator_tpu.parallel.mesh import make_serving_mesh
from tests.rect_paged_attention import rect_paged_decode_attention


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


def _cells_by_loop(table, lengths, block, starts=None):
    """:func:`decode_cells` as a plain loop: lane after lane, a column a
    block that holds positions of ``[start, length)``; a lane with none
    keeps one column, whose pool id repeats its neighbour's."""
    cols = []
    for b, n in enumerate(lengths):
        end = -(-n // block)
        lo = 0 if starts is None else min(starts[b] // block, end)
        blocks = list(range(lo, end))
        for j in blocks or [lo]:
            flags = ((CELL_FIRST if j == (blocks or [lo])[0] else 0)
                     | (CELL_LAST if j == (blocks or [lo])[-1] else 0))
            col = [b, table[b][j] if blocks else None, j, flags, n]
            cols.append(col + ([] if starts is None else [starts[b]]))
    real = [c[1] for c in cols if c[1] is not None]
    last = real[0] if real else 0       # no lane holds anything: block 0
    for c in cols:
        c[1] = last = last if c[1] is None else c[1]
    return np.asarray(cols, np.int64).T


BLOCK, M = 256, 16           # the serving cells' block and blocks a lane
LENGTHS = {
    "edges": [0, 1, 255, 256, 257, 4096],
    "all-masked": [0, 0, 0, 0],
    "all-full": [4096, 4096, 4096],
    "leading-and-trailing-empty": [0, 0, 700, 0, 300, 0],
    "one-lane": [513],
}


class TestWorkList:
    """The decode kernel's grid as a list (``decode_cells``) against a
    plain Python loop."""

    @pytest.mark.parametrize("name", sorted(LENGTHS))
    @pytest.mark.parametrize("window", [None, 0, 256, 300, 2048, 10 ** 6])
    def test_against_the_loop(self, name, window):
        """Windows: none; one whose start falls at 0 for every lane
        (``10 ** 6``), on a block's edge (256 under a length of 512 or
        a multiple), mid-block (300), and past every length's start (0:
        an empty range, which keeps a lane's one cell)."""
        lengths = np.asarray(LENGTHS[name], np.int32)
        b = len(lengths)
        table = 1 + np.random.default_rng(b).permutation(b * M).reshape(b, M)
        starts = (None if window is None
                  else np.maximum(lengths - window, 0))
        got = decode_cells(jnp.asarray(table, jnp.int32),
                           jnp.asarray(lengths), BLOCK,
                           None if starts is None else jnp.asarray(starts))
        want = _cells_by_loop(table.tolist(), lengths.tolist(), BLOCK,
                              None if starts is None else starts.tolist())
        n = int(got.n)
        assert n == want.shape[1]
        assert got.rows.shape == (5 + (window is not None), b * M)
        assert got.rows.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got.rows)[:, :n], want)

    def test_block_edge_start_drops_the_block_before_it(self):
        table = jnp.arange(1, 1 + M, dtype=jnp.int32)[None]
        got = decode_cells(table, jnp.asarray([600]), BLOCK,
                           jnp.asarray([256]))
        assert int(got.n) == 2
        assert np.asarray(got.rows)[:3, :2].tolist() == [[0, 0], [2, 3],
                                                         [1, 2]]


def _pool_case(lengths, seed=0, b_hkv_hq_d=(2, 4, 32), bs=16, m=4,
               layers=2):
    hkv, hq, d = b_hkv_hq_d
    b = len(lengths)
    rng = np.random.default_rng(seed)
    n = b * m + 1
    table = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    return (_rand((b, hq, d), seed + 1), _rand((layers, n, hkv, bs, d),
                                               seed + 2),
            _rand((layers, n, hkv, bs, d), seed + 3), table,
            jnp.asarray(lengths, jnp.int32))


def _lane_view(pool, table, li):
    """Layer ``li`` of the pool as contiguous lanes [B, Hkv, M*bs, D]."""
    _, _, hkv, bs, d = pool.shape
    b, m = table.shape
    v = np.asarray(pool)[li][np.asarray(table).reshape(-1)]
    return jnp.asarray(v.reshape(b, m, hkv, bs, d).transpose(0, 2, 1, 3, 4)
                       .reshape(b, hkv, m * bs, d))


class TestListKernel:
    """The paged kernel over the work list (interpret mode): against the
    einsum, and bit for bit against the rectangular grid it replaced
    (tests/rect_paged_attention.py)."""

    @pytest.mark.parametrize("lengths", [[0, 1, 15, 16, 17, 64],
                                         [64, 64, 64], [0, 0, 0],
                                         [0, 33, 0, 0, 5]])
    def test_against_reference_and_rectangle(self, lengths):
        q, kp, vp, table, lens = _pool_case(lengths)
        li = jnp.int32(1)
        got = paged_decode_attention(q, kp, vp, table, lens, layer=li,
                                     interpret=True)
        ref = decode_attention_reference(
            q, _lane_view(kp, table, 1), _lane_view(vp, table, 1), lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        rect = rect_paged_decode_attention(q, kp, vp, table, lens, layer=li)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(rect))
        assert not np.asarray(got)[np.asarray(lengths) == 0].any()

    @pytest.mark.parametrize("window", [1, 16, 20, 40, 10 ** 6])
    def test_window_against_rectangle(self, window):
        """Starts at 0, on a block's edge and mid-block."""
        q, kp, vp, table, lens = _pool_case([0, 1, 16, 36, 57, 64], seed=4)
        starts = jnp.maximum(lens - window, 0)
        got = paged_decode_attention(q, kp, vp, table, lens,
                                     layer=jnp.int32(0), starts=starts,
                                     interpret=True)
        rect = rect_paged_decode_attention(q, kp, vp, table, lens,
                                           layer=jnp.int32(0), starts=starts)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(rect))

    def test_prebuilt_list_and_masked_lanes(self):
        """A list built once serves every layer's call; a lane handed in
        at length 0 (masked out of the step) reads nothing, answers
        zeros, and leaves the live lanes' answers what they were."""
        q, kp, vp, table, lens = _pool_case([9, 40, 17, 64], seed=7)
        mask = jnp.asarray([True, False, True, False])
        cells = decode_cells(table, jnp.where(mask, lens, 0), kp.shape[3])
        assert int(cells.n) == 1 + 1 + 2 + 1
        for li in range(2):
            full = paged_decode_attention(q, kp, vp, table, lens,
                                          layer=jnp.int32(li),
                                          interpret=True)
            got = paged_decode_attention(q, kp, vp, table, lens,
                                         layer=jnp.int32(li), cells=cells,
                                         interpret=True)
            np.testing.assert_array_equal(np.asarray(got)[[0, 2]],
                                          np.asarray(full)[[0, 2]])
            assert not np.asarray(got)[[1, 3]].any()


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
