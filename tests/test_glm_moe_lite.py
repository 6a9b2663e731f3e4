"""The ``glm4_moe_lite`` architecture (``models/glm_moe_lite.py`` through the
expert stack, ``infer/afmoe_serve.py``) against its plain reference
(``benchmark/reference/glm_moe_lite_ref.py``), on the CPU in float32 at tiny
widths but the real structure, no two sizes alike: 4 heads, nope 24, rope 8,
values 16, query rank 40, latent 48, hidden 64, 1 dense + 2 expert layers,
8 experts top-2 with a shared expert.

Tolerance: logits agree to 1e-4 of the reference's largest logit.  Both
sides compute in float32 (``highest``); what differs is the order of the
sums (the absorbed form against the expanded one, a grouped product against
every expert over every token, a cache against a full forward), a few 1e-6
relative, so 1e-4 has two orders of room and any missing term is orders
above it.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import glm_moe_lite as H
from benchmark.harness import weights as W
from benchmark.reference import glm_moe_lite_ref as R
from paddle_operator_tpu.infer import afmoe_serve as AF
from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.models import afmoe as A
from paddle_operator_tpu.models import glm_moe_lite as M
from paddle_operator_tpu.ops import decode_attention as K

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG_FILE = os.path.join(ROOT, "benchmark", "tests", "configs",
                        "tiny-glm-serve.json")
RTOL = 1e-4
BLOCK, MAX_LEN, BUCKET = 8, 64, 24


@pytest.fixture(scope="module", autouse=True)
def _highest():
    # both sides in true float32 on whatever backend runs this
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    cfgj = json.load(open(CFG_FILE))
    cfg = H.config(cfgj, MAX_LEN)
    key = W.root_key(2 ** 31 + 7)
    params = jax.jit(lambda k: H.make_tree(k, M.param_shapes(cfg), cfg))(key)
    return cfgj, cfg, key, params


def ids(seed, n):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def rel(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.abs(np.asarray(want)).max())


def ref_logits(cfgj, key, seq):
    return np.asarray(R.forward(cfgj, key, jnp.asarray(seq, jnp.int32)))


def test_tiny_preset_is_registered_typed_and_unswappable():
    from paddle_operator_tpu.models.llama import CONFIGS, LlamaConfig

    cfg = CONFIGS["glm-lite-tiny"]
    assert isinstance(cfg, M.GlmMoeLiteConfig)
    assert not isinstance(cfg, LlamaConfig)
    assert AF.is_expert_stack(cfg) and AF.stack_of(cfg) is M
    assert AF.stack_of(CONFIGS["afmoe-tiny"]) is A
    assert not AF.is_expert_stack(CONFIGS["tiny-f32"])
    sizes = [cfg.dim, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
             cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.head_dim, cfg.n_heads]
    assert len(set(sizes)) == len(sizes)
    assert cfg.cache_buffers() == {"c": (1, 48), "pe": (1, 8)}
    assert cfg.cache_row == 56
    # the published sizes: 576 values, 1,152 bytes a token a layer
    assert M.GlmMoeLiteConfig().cache_row == 576


def test_trainer_refuses_the_architecture():
    from paddle_operator_tpu.models.llama import make_model

    with pytest.raises(ValueError, match="served only"):
        make_model("glm-lite-tiny")


def test_seeded_tree_cuts_kv_b_into_the_programs_two_halves(model):
    """The program holds ``kv_b_proj`` as every head's ``k_nope`` columns
    and every head's value columns; the reference holds the published
    matrix, head by head ``[k_nope | v]``."""
    cfgj, cfg, key, params = model
    h, nope, v = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    for l, (tree, at) in enumerate([("dense_layers", 0), ("moe_layers", 0),
                                    ("moe_layers", 1)]):
        whole = np.asarray(R.layer_weights(cfgj, key, l)["attn/kv_b/kernel"]
                           ).reshape(cfg.kv_lora_rank, h, nope + v)
        attn = params[tree]["attn"]
        # (to a float32 rounding: one side makes the leaf under jit)
        np.testing.assert_allclose(
            np.asarray(attn["kv_b_k"]["kernel"][at]),
            whole[..., :nope].reshape(cfg.kv_lora_rank, h * nope), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(attn["kv_b_v"]["kernel"][at]),
            whole[..., nope:].reshape(cfg.kv_lora_rank, h * v), rtol=1e-6)


# ---------------------------------------------------------------------------
# generate: prefill (expanded), then cached decoding (absorbed)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_prompt", [1, 3, 8, 13])
def test_generate_matches_the_reference(model, n_prompt):
    cfgj, cfg, key, params = model
    prompt = ids(n_prompt, n_prompt)
    logits, cache = D.prefill(params, cfg, jnp.asarray(prompt[None]), MAX_LEN)
    assert set(cache) == {"c", "pe", "pos"}
    assert cache["c"].shape == (3, 1, 1, MAX_LEN, 48)
    seq, got = list(prompt), [np.asarray(logits[0])]
    for _ in range(9):
        seq.append(int(got[-1].argmax()))
        lg, cache = D.decode_step(params, cfg,
                                  jnp.asarray(seq[-1:], jnp.int32), cache)
        got.append(np.asarray(lg[0]))
    want = ref_logits(cfgj, key, seq)
    for i, g in enumerate(got[:-1]):
        assert rel(g, want[n_prompt - 1 + i]) < RTOL, i
    out = D.generate(params, cfg, jnp.asarray(prompt[None]),
                     max_new_tokens=9, max_len=MAX_LEN)
    assert list(np.asarray(out[0])) == seq


def test_a_multi_token_forward_continues_a_cache(model):
    """The oracle's path: rows appended to a cache that already holds
    some attend the whole of it, expanded."""
    cfgj, cfg, key, params = model
    seq = ids(3, 17)
    cache = D.init_cache(cfg, 1, MAX_LEN)
    _, cache, _ = AF.forward(cfg, params, jnp.asarray(seq[None, :7]), cache)
    logits, cache, _ = AF.forward(cfg, params, jnp.asarray(seq[None, 7:]),
                                  cache)
    assert int(cache["pos"]) == 17
    assert rel(logits[0], ref_logits(cfgj, key, seq)[7:]) < RTOL


# ---------------------------------------------------------------------------
# absorbed = expanded
# ---------------------------------------------------------------------------


def test_absorbed_attention_equals_expanded_attention(model):
    """One layer's decode step both ways over the same cached rows: the
    query folded through ``Wuk`` against the latent and the latent
    attended then unfolded through ``Wuv``, against keys and values
    expanded for every head — equal to float32 rounding."""
    _, cfg, _, params = model
    lp = M.layer_at(params["moe_layers"], 1)
    rng = np.random.RandomState(0)
    b, s = 3, 29
    x_ctx = jnp.asarray(rng.randn(b, s, cfg.dim), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    tables = M.rope_tables(cfg)
    _, _, c, pe = M.attn_inputs(cfg, lp, x_ctx, *tables, pos)
    lengths = jnp.asarray([29, 1, 12], jnp.int32)
    x = jnp.asarray(rng.randn(b, 1, cfg.dim), jnp.float32)
    q_nope, q_pe, _, _ = M.attn_inputs(cfg, lp, x, *tables,
                                       (lengths - 1)[:, None])
    o_lat = K.latent_decode_attention_reference(
        M.absorb_query(cfg, lp, q_nope)[:, 0], q_pe[:, 0], c[:, :, 0],
        pe[:, :, 0], lengths, cfg.head_dim ** -0.5)
    absorbed = M.absorb_output(cfg, lp, o_lat[:, None])
    k, v = M.expand(cfg, lp, c[:, :, 0], pe[:, :, 0])
    expanded = M.attend_expanded(
        cfg, jnp.concatenate([q_nope, q_pe], -1), k, v,
        (lengths - 1)[:, None])
    assert absorbed.shape == expanded.shape == (b, 1, cfg.n_heads
                                                * cfg.v_head_dim)
    assert rel(absorbed, expanded) < 1e-5


def test_rotation_pairs_neighbours_and_leaves_scores_as_the_reference(model):
    """The program leaves the rotated halves apart, the reference leaves
    each pair in place: the same numbers in another order, so every
    q . k is the same."""
    _, cfg, _, _ = model
    rng = np.random.RandomState(2)
    s, r = 11, cfg.qk_rope_head_dim
    x = rng.randn(1, s, 3, r).astype(np.float32)
    got = np.asarray(M.rope(jnp.asarray(x), *M.rope_tables(cfg),
                            jnp.arange(s)[None]))[0]
    want = np.asarray(R.rope(jnp.asarray(x[0]), cfg.rope_theta))
    np.testing.assert_allclose(got[..., :r // 2], want[..., 0::2], atol=1e-5)
    np.testing.assert_allclose(got[..., r // 2:], want[..., 1::2], atol=1e-5)


# ---------------------------------------------------------------------------
# the latent view
# ---------------------------------------------------------------------------


def ring_state(cfg, slots):
    m = MAX_LEN // BLOCK
    cache = PG.init_paged_cache(cfg, slots, slots * m + 1, BLOCK)
    table = jnp.asarray(1 + np.arange(slots * m).reshape(slots, m), jnp.int32)
    return (cache, table, jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.float32),
            jnp.zeros((slots, 2), jnp.uint32))


def test_latent_pool_is_sized_from_the_views_row(model):
    _, cfg, _, _ = model
    cache, table, *_ = ring_state(cfg, 2)
    n = 2 * (MAX_LEN // BLOCK) + 1
    assert cache["c"].shape == (3, n, 1, BLOCK, 48)
    assert cache["pe"].shape == (3, n, 1, 8, BLOCK)      # transposed
    assert set(cache) == {"c", "pe", "pos", "moe_pf"}
    assert PG.cache_row_bytes(cache) == 56 * 4           # float32 here
    assert isinstance(PG.paged_view(cfg, cache, table), PG.LatentPagedView)
    # at the published sizes in bf16: 1,152 bytes, where K and V a head
    # would be 20 x (256 + 256) x 2
    big = jax.eval_shape(lambda: PG.init_paged_cache(
        M.GlmMoeLiteConfig(n_layers=2), 1, 3, 256))
    assert PG.cache_row_bytes(big) == 1152
    # a K/V pool reads the same counter
    kv = jax.eval_shape(lambda: PG.init_paged_cache(
        dataclasses.replace(A.CONFIGS["afmoe-tiny"]), 2, 5, 8))
    assert PG.cache_row_bytes(kv) == 2 * 2 * 32 * 4


def test_latent_view_write_scatter_and_lanes_round_trip(model):
    """A prompt's blocks through the insert's scatter, then single rows
    through the step's write, read back as contiguous lanes: every row is
    where its position says, the rotated keys through their transposed
    buffer."""
    _, cfg, _, _ = model
    cache, table, *_ = ring_state(cfg, 2)
    rng = np.random.RandomState(4)
    layers, bucket = cfg.n_layers, 16
    lane = {"c": jnp.asarray(rng.randn(layers, 1, 1, bucket, 48), jnp.float32),
            "pe": jnp.asarray(rng.randn(layers, 1, 1, bucket, 8), jnp.float32)}
    cache = dict(cache, **PG.LatentPagedView.scatter_prompt(
        cache, lane, table[1], BLOCK))
    cache["pos"] = jnp.asarray([0, 13], jnp.int32)
    view = PG.paged_view(cfg, cache, table)
    view.enter(1)
    assert view.step and not view.kernel
    c_new = jnp.asarray(rng.randn(2, 1, 1, 48), jnp.float32)
    pe_new = jnp.asarray(rng.randn(2, 1, 1, 8), jnp.float32)
    li = jnp.int32(2)
    bufs = view.write(view.buffers(), li, c_new, pe_new)
    lat, pe = view.lanes(bufs, li)
    assert lat.shape == (2, 1, MAX_LEN, 48) and pe.shape == (2, 1, MAX_LEN, 8)
    want_c = np.asarray(lane["c"][2, 0, 0]).copy()
    want_pe = np.asarray(lane["pe"][2, 0, 0]).copy()
    want_c[13], want_pe[13] = np.asarray(c_new[1, 0, 0]), np.asarray(
        pe_new[1, 0, 0])
    np.testing.assert_array_equal(np.asarray(lat[1, 0, :bucket]), want_c)
    np.testing.assert_array_equal(np.asarray(pe[1, 0, :bucket]), want_pe)
    np.testing.assert_array_equal(np.asarray(lat[0, 0, 0]),
                                  np.asarray(c_new[0, 0, 0]))
    np.testing.assert_array_equal(np.asarray(pe[0, 0, 0]),
                                  np.asarray(pe_new[0, 0, 0]))
    # the other layers are untouched by the step's write
    other = view.lanes(bufs, jnp.int32(1))[0]
    np.testing.assert_array_equal(np.asarray(other[1, 0, :bucket]),
                                  np.asarray(lane["c"][1, 0, 0]))
    end = view.end(bufs, 1)
    assert set(end) == {"c", "pe", "pos"}
    assert list(np.asarray(end["pos"])) == [1, 14]


@pytest.mark.parametrize("kw", [{"limit": jnp.zeros((2,), jnp.int32)},
                                {"aligned": True}])
def test_latent_view_refuses_what_it_is_not_written_for(model, kw):
    _, cfg, _, _ = model
    cache, table, *_ = ring_state(cfg, 2)
    with pytest.raises(ValueError, match="decode step and the whole-prompt"):
        PG.paged_view(cfg, cache, table, **kw)


def test_latent_view_refuses_a_multi_row_write(model):
    _, cfg, _, _ = model
    cache, table, *_ = ring_state(cfg, 2)
    view = PG.paged_view(cfg, cache, table)
    view.enter(3)
    with pytest.raises(ValueError, match="decode step and the whole-prompt"):
        view.write(view.buffers(), jnp.int32(0), jnp.zeros((2, 3, 1, 48)),
                   jnp.zeros((2, 3, 1, 8)))


# ---------------------------------------------------------------------------
# the latent kernel (interpret mode) against its einsum twin
# ---------------------------------------------------------------------------


LISTS = {
    "a_masked_lane": ([37, 0, 200, 130], None),
    "a_lane_of_one_block": ([64, 1, 63, 65], None),
    "a_full_lane": ([384, 384, 5, 129], None),
    "lanes_out_of_the_step": ([37, 300, 200, 384], [True, False, True, False]),
}


@pytest.mark.parametrize("case", sorted(LISTS))
def test_latent_kernel_against_its_einsum_twin(case):
    """``interpret`` mode at a latent of 128 and a rope part of 64, block
    64, 5 heads (padded to a tile inside): ragged work lists through a
    shuffled table, stacked pools and a layer's index."""
    lengths, mask = LISTS[case]
    b, m, bs, layers, h, c, r = 4, 6, 64, 2, 5, 128, 64
    rng = np.random.RandomState(7)
    c_pool = jnp.asarray(rng.randn(layers, b * m + 1, 1, bs, c), jnp.float32)
    pe_pool = jnp.asarray(rng.randn(layers, b * m + 1, 1, r, bs), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    q_lat = jnp.asarray(rng.randn(b, h, c), jnp.float32)
    q_pe = jnp.asarray(rng.randn(b, h, r), jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if mask is not None:
        lengths = jnp.where(jnp.asarray(mask), lengths, 0)
    cfg = M.GlmMoeLiteConfig(n_layers=layers, kv_lora_rank=c,
                             qk_rope_head_dim=r, n_heads=h,
                             dtype=jnp.float32)
    view = PG.LatentPagedView(
        cfg, {"c": c_pool, "pe": pe_pool,
              "pos": jnp.maximum(lengths - 1, 0)}, table)
    for li in range(layers):
        got = K.latent_paged_decode_attention(
            q_lat, q_pe, c_pool, pe_pool, table, lengths, scale=0.07,
            layer=jnp.int32(li), interpret=True)
        lat, pe = view.lanes((c_pool, pe_pool), jnp.int32(li))
        want = K.latent_decode_attention_reference(
            q_lat, q_pe, lat[:, 0], pe[:, 0], lengths, 0.07)
        assert got.shape == (b, h, c)
        assert rel(got, want) < RTOL
        for lane in np.flatnonzero(np.asarray(lengths) == 0):
            assert not np.asarray(got)[lane].any()
    # the list built once outside, and an unstacked pool
    cells = K.decode_cells(table, lengths, bs)
    again = K.latent_paged_decode_attention(
        q_lat, q_pe, c_pool[1], pe_pool[1], table, scale=0.07, cells=cells,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_latent_kernel_refuses_what_it_is_not_written_for():
    q_lat, q_pe = jnp.zeros((2, 4, 48)), jnp.zeros((2, 4, 8))
    table, lengths = jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="multiple of 128"):   # on the chip
        K.latent_paged_decode_attention(
            q_lat, q_pe, jnp.zeros((3, 1, 8, 48)), jnp.zeros((3, 1, 8, 8)),
            table, lengths, scale=1.0)
    with pytest.raises(ValueError, match="written for pools"):  # K/V heads
        K.latent_paged_decode_attention(
            q_lat, q_pe, jnp.zeros((3, 2, 8, 48)), jnp.zeros((3, 1, 8, 8)),
            table, lengths, scale=1.0, interpret=True)
    with pytest.raises(ValueError, match="written for pools"):  # not transposed
        K.latent_paged_decode_attention(
            q_lat, q_pe, jnp.zeros((3, 1, 8, 48)), jnp.zeros((3, 1, 16, 8)),
            table, lengths, scale=1.0, interpret=True)


# ---------------------------------------------------------------------------
# the paged ring's insert and step
# ---------------------------------------------------------------------------


def insert_prompts(cfg, params, prompts):
    cache, table, tok, temp, keys = ring_state(cfg, len(prompts))
    insert = AF.make_paged_prefill_insert(cfg, BUCKET, BLOCK)
    for slot, p in enumerate(prompts):
        padded = np.zeros((1, BUCKET), np.int32)
        padded[0, :len(p)] = p
        cache, tok, temp, keys, _ = insert(
            params, cache, table[slot], tok, temp, keys,
            jnp.asarray(padded), len(p), slot, 0.0, 0)
    return cache, table, tok, temp, keys


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_ring_matches_the_reference(model, impl):
    """Prefill through the insert program (expanded), then decoding
    through the ring's forward over the latent pool (absorbed: the einsum
    twin, and the kernel interpreted), lanes at different lengths that
    cross block edges."""
    cfgj, cfg, key, params = model
    cfg = dataclasses.replace(cfg, decode_attn=impl)
    prompts = [ids(11, 3), ids(12, 7), ids(13, 21)]
    cache, table, tok, _, _ = insert_prompts(cfg, params, prompts)
    assert list(np.asarray(cache["pos"])) == [3, 7, 21]
    seqs = [list(p) + [int(t)] for p, t in zip(prompts, np.asarray(tok))]
    active = jnp.ones((3,), bool)
    fwd = jax.jit(lambda c, t: AF.paged_ring_forward(cfg, params, t, c,
                                                    table, active))
    got = [[] for _ in prompts]
    for _ in range(12):
        logits, cache, _, _ = fwd(cache, tok)
        tok = logits.argmax(-1).astype(jnp.int32)
        for b in range(3):
            got[b].append(np.asarray(logits[b]))
            seqs[b].append(int(tok[b]))
    for b, p in enumerate(prompts):
        want = ref_logits(cfgj, key, seqs[b])
        assert seqs[b][len(p)] == int(want[len(p) - 1].argmax())
        for i, g in enumerate(got[b]):
            assert rel(g, want[len(p) + i]) < RTOL, (b, i)


def test_ring_step_with_a_masked_lane_through_the_kernel(model):
    """The chunk step with the kernel (interpret mode) and one lane out of
    the step: the live lanes' tokens are the einsum step's, every lane's
    logits stay finite and the counters count the live lanes alone."""
    _, cfg, _, params = model
    prompts = [ids(41, 5), ids(42, 19), ids(43, 11)]
    active = jnp.asarray([True, False, True])
    outs = []
    for impl in ("xla", "pallas-interpret"):
        c = dataclasses.replace(cfg, decode_attn=impl)
        cache, table, tok, temp, keys = insert_prompts(c, params, prompts)
        _, _, toks, ok, moe = AF.make_paged_chunk_step(
            c, 6, check_finite=True)(params, cache, table, tok, temp, keys,
                                     active)
        assert bool(np.asarray(ok).all())
        outs.append(np.asarray(toks)[:, [0, 2]])
        load, touched, prefill = AF.split_moe(c, np.asarray(moe))
        assert load.sum() == 2 * 6 * c.top_k * c.n_moe_layers
        assert prefill.sum() == sum(map(len, prompts)) * c.top_k \
            * c.n_moe_layers
    np.testing.assert_array_equal(outs[0], outs[1])


def test_ring_serves_what_generate_answers_and_reports_its_row(model):
    """The scheduler's path end to end: requests through the continuous
    batcher answer generate's greedy tokens; the routing counters are
    sized by this configuration's experts; ``cacheRowBytes`` is the
    latent row's on /statusz and /metrics."""
    from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
    from paddle_operator_tpu.utils.observability import serving_gauges

    _, cfg, _, params = model
    ring = ContinuousBatcher(params, cfg, slots=2, max_len=MAX_LEN,
                             chunk_tokens=4, paged=True, block_size=BLOCK,
                             prefix_cache=False)
    try:
        prompts = [ids(31, 4), ids(32, 13), ids(33, 21)]
        handles = [ring.submit(p, max_new_tokens=10) for p in prompts]
        rows = [h.result(timeout=120) for h in handles]
        for p, row in zip(prompts, rows):
            want = D.generate(params, cfg, jnp.asarray(p[None]),
                              max_new_tokens=10, max_len=MAX_LEN)
            assert list(row) == list(np.asarray(want[0]))
        st = ring.serving_status()
    finally:
        ring.close()
    assert st["cacheRowBytes"] == 56 * 4
    assert set(st["prefillAttnByBucket"].values()) == {"einsum"}
    assert len(st["moeExpertLoadTotal"]) == cfg.n_experts == 8
    assert st["moeLayerStepsTotal"] == st["decodeStepsTotal"] \
        * cfg.n_moe_layers
    assert st["moeAssignmentsTotal"] == (st["decodeLaneStepsTotal"]
                                         * cfg.top_k * cfg.n_moe_layers)
    assert st["decodeCellsLive"] > 0
    assert st["decodeCellsGrid"] >= st["decodeCellsLive"]
    gauges = serving_gauges(st, "j")
    assert gauges['tpujob_serve_cache_row_bytes{job="j"}'] == 56 * 4


def test_a_llama_ring_reports_k_and_v_as_its_row():
    from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
    from paddle_operator_tpu.models.llama import CONFIGS, Llama

    cfg = CONFIGS["tiny-f32"]
    params = Llama(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    for paged in (True, False):
        ring = ContinuousBatcher(params, cfg, slots=2, max_len=32,
                                 chunk_tokens=2, paged=paged, block_size=8)
        try:
            st = ring.serving_status()
        finally:
            ring.close()
        assert st["cacheRowBytes"] == 2 * cfg.n_kv_heads * cfg.head_dim * 4


# ---------------------------------------------------------------------------
# routing, and the insert's choice of attention
# ---------------------------------------------------------------------------


def test_routing_equals_the_references_selection_and_weights(model):
    """``models/afmoe.py route`` at this configuration's numbers (top-2 of
    8 here, scale 1.8, normalised) against the reference's ``noaux_tc``
    rule on the same weights: the correction bias moves the selection and
    never the weights."""
    cfgj, cfg, key, params = model
    w = R.layer_weights(cfgj, key, 1)
    mp = M.layer_at(params["moe_layers"]["moe"], 0)
    h = jnp.asarray(np.random.RandomState(1).randn(40, cfg.dim), jnp.float32)
    idx, wt = A.route(cfg, mp, h)
    ridx, rwt = R.routing(cfgj, w, h)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(wt), np.asarray(rwt), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(wt).sum(-1),
                               cfg.route_scale, rtol=1e-5)
    # the bias is a seeded leaf: selection by score + bias differs from
    # selection by score for some token
    plain, _ = A.route(cfg, dict(mp, expert_bias=jnp.zeros(cfg.n_experts)), h)
    assert (np.asarray(plain) != np.asarray(idx)).any()


def test_insert_attends_by_the_llama_inserts_rule(monkeypatch):
    """On a TPU the published widths (20 heads of 256, values 256) go
    through the flash kernel from 1024 positions up, the einsum below;
    nothing does on the CPU, nor a block whose values are not as wide as
    its keys, nor ``afmoe``'s."""
    big = M.GlmMoeLiteConfig(n_layers=2)
    rungs = [512, 1024, 2048, 4096, 6144, 8192]
    assert {AF.prefill_attn_impl(big, b) for b in rungs} == {"einsum"}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [AF.prefill_attn_impl(big, b) for b in rungs] == \
        ["einsum"] + ["flash"] * 5
    narrow = dataclasses.replace(big, v_head_dim=128)
    assert AF.prefill_attn_impl(narrow, 2048) == "einsum"
    assert AF.prefill_attn_impl(A.AfmoeConfig(), 2048) == "einsum"


# ---------------------------------------------------------------------------
# every mode the expert stack refuses, in one sentence
# ---------------------------------------------------------------------------


MODES = ["SERVE_PAGED=0", "SERVE_TP>1", "SERVE_SPEC_K>0",
         "SERVE_KV_QUANT=int8", "SERVE_PREFILL=chunked", "SERVE_ADAPTERS",
         "SERVE_MEGASTEP>1", "SERVE_PREFIX_CACHE=1", "QUANTIZE=int8",
         "SERVE_WEIGHT_QUANT"]


@pytest.mark.parametrize("preset", ["glm-lite-tiny", "afmoe-tiny"])
def test_refuse_modes_names_every_refused_mode(preset):
    from paddle_operator_tpu.models.llama import CONFIGS

    cfg = CONFIGS[preset]
    AF.refuse_modes(cfg, {m: False for m in MODES})           # nothing on
    AF.refuse_modes(CONFIGS["tiny-f32"], {m: True for m in MODES})  # LLaMA
    for mode in MODES:
        with pytest.raises(ValueError) as e:
            AF.refuse_modes(cfg, {m: m == mode for m in MODES})
        assert mode in str(e.value) and type(cfg).__name__ in str(e.value)
        assert "paged continuous ring at tp 1 with a bf16 pool" in str(e.value)
    with pytest.raises(ValueError) as e:
        AF.refuse_modes(cfg, {m: True for m in MODES})
    assert all(m in str(e.value) for m in MODES)


@pytest.mark.parametrize("kw", [
    {"paged": False}, {"prefix_cache": True}, {"kv_quant": "int8"},
    {"prefill_mode": "chunked"}, {"megastep": 2}])
def test_the_executor_refuses_the_modes_it_is_handed(model, kw):
    from paddle_operator_tpu.infer.executor import RingExecutor

    _, cfg, _, params = model
    args = dict(slots=2, max_len=MAX_LEN, chunk_tokens=4, paged=True,
                block_size=BLOCK, prefix_cache=False)
    args.update(kw)
    with pytest.raises(ValueError, match="not written for"):
        RingExecutor(params, cfg, **args)
