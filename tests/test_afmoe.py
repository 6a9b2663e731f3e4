"""The ``afmoe`` architecture (``models/afmoe.py``, ``infer/afmoe_serve.py``)
against its plain reference (``benchmark/reference/afmoe_ref.py``), on the
CPU in float32 at tiny widths but the real structure: 1 dense + 4 expert
layers, sliding x4 / full, window 8, 8 experts top-2 with a shared expert,
head width 32 != hidden / heads = 16.

Tolerance: logits agree to 1e-4 of the reference's largest logit.  Both
sides compute in float32 (``highest``); what differs is the order of the
sums (a grouped product over sorted assignments against every expert over
every token, a cache against a full forward), a few 1e-6 relative, so 1e-4
has two orders of room and any missing term is orders above it.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import afmoe as H
from benchmark.harness import weights as W
from benchmark.reference import afmoe_ref as R
from paddle_operator_tpu.infer import afmoe_serve as AF
from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.models import afmoe as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG_FILE = os.path.join(ROOT, "benchmark", "tests", "configs",
                        "tiny-afmoe-serve.json")
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
    params = jax.jit(lambda k: H.make_tree(k, M.param_shapes(cfg),
                                           cfg.n_dense_layers))(key)
    return cfgj, cfg, key, params


def ids(seed, n):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def rel(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.abs(np.asarray(want)).max())


def ref_logits(cfgj, key, seq):
    return np.asarray(R.forward(cfgj, key, jnp.asarray(seq, jnp.int32)))


def test_tiny_preset_is_registered_and_typed():
    from paddle_operator_tpu.models.llama import CONFIGS, LlamaConfig

    assert isinstance(CONFIGS["afmoe-tiny"], M.AfmoeConfig)
    assert not isinstance(CONFIGS["afmoe-tiny"], LlamaConfig)
    cfg = CONFIGS["afmoe-tiny"]
    assert cfg.head_dim != cfg.dim // cfg.n_heads
    assert cfg.layer_types == (M.SLIDING,) * 4 + (M.FULL,)
    assert cfg.windows() == (8, 8, 8, 8, cfg.max_seq_len + 1)
    assert M.AfmoeConfig().layer_types[:8] == (
        (M.SLIDING,) * 3 + (M.FULL,)) * 2


# ---------------------------------------------------------------------------
# generate: prefill, then cached decoding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_prompt", [3, 8, 13])
def test_generate_matches_the_reference(model, n_prompt):
    """Contexts that end below, at and beyond the window of 8."""
    cfgj, cfg, key, params = model
    prompt = ids(n_prompt, n_prompt)
    logits, cache = D.prefill(params, cfg, jnp.asarray(prompt[None]), MAX_LEN)
    seq, got = list(prompt), [np.asarray(logits[0])]
    for _ in range(9):
        seq.append(int(got[-1].argmax()))
        lg, cache = D.decode_step(params, cfg,
                                  jnp.asarray(seq[-1:], jnp.int32), cache)
        got.append(np.asarray(lg[0]))
    want = ref_logits(cfgj, key, seq)
    for i, g in enumerate(got[:-1]):
        assert rel(g, want[n_prompt - 1 + i]) < RTOL, i
    # and the entry point the batch server calls
    out = D.generate(params, cfg, jnp.asarray(prompt[None]),
                     max_new_tokens=9, max_len=MAX_LEN)
    assert list(np.asarray(out[0])) == seq


# ---------------------------------------------------------------------------
# the paged ring's insert and step
# ---------------------------------------------------------------------------


def ring_state(cfg, slots):
    m = MAX_LEN // BLOCK
    cache = PG.init_paged_cache(cfg, slots, slots * m + 1, BLOCK)
    table = jnp.asarray(1 + np.arange(slots * m).reshape(slots, m), jnp.int32)
    return (cache, table, jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.float32),
            jnp.zeros((slots, 2), jnp.uint32))


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


def test_paged_ring_matches_the_reference(model):
    """Prefill through the insert program, then decoding through the
    ring's forward, lanes at different lengths: contexts that end below
    (3 -> 7), at and beyond the window (7 -> 19, crossing the block edges
    at 8 and 16) and well beyond it (21 -> 33, crossing 24 and 32)."""
    cfgj, cfg, key, params = model
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
        # the insert's first token is the reference's at the prompt's end
        assert seqs[b][len(p)] == int(want[len(p) - 1].argmax())
        for i, g in enumerate(got[b]):
            assert rel(g, want[len(p) + i]) < RTOL, (b, i)


def reference_routing(cfgj, key, seq):
    """Per expert layer, the experts each position selected, ``[S, k]``."""
    x = R.embed(cfgj, R.top_weight(cfgj, key, "tok_embed/embedding"),
                jnp.asarray(seq, jnp.int32))
    out = []
    for l in range(cfgj["num_hidden_layers"]):
        x, idx = R.layer(cfgj, R.layer_weights(cfgj, key, l), x, l,
                         with_routing=True)
        if idx is not None:
            out.append(np.asarray(idx))
    return out


def test_counters_against_a_host_recount(model):
    """The step's routing counters (decode load by expert and experts
    touched over live lanes, the inserts' load since the last dispatch)
    against the reference's routing of the same tokens, recounted here."""
    cfgj, cfg, key, params = model
    chunk, e = 3, cfg.n_experts
    prompts = [ids(21, 5), ids(22, 11), ids(23, 9)]
    cache, table, tok, temp, keys = insert_prompts(cfg, params, prompts)
    active = np.array([True, False, True])
    step = AF.make_paged_chunk_step(cfg, chunk)
    first = np.asarray(tok)
    cache, tok, toks, moe = step(params, cache, table, tok, temp, keys,
                                 jnp.asarray(active))
    load, touched, prefill = AF.split_moe(cfg, np.asarray(moe))
    want_load, want_touched, want_prefill = (np.zeros(e, int), 0,
                                             np.zeros(e, int))
    routed = []
    for b, p in enumerate(prompts):
        seq = list(p) + [int(first[b])] + [int(t) for t in
                                           np.asarray(toks)[:, b]]
        routed.append(reference_routing(cfgj, key, seq))
        for idx in routed[-1]:
            want_prefill += np.bincount(idx[:len(p)].ravel(), minlength=e)
    for layer in range(cfg.n_moe_layers):
        for tick in range(chunk):
            hit = np.zeros(e, int)
            for b, p in enumerate(prompts):
                if active[b]:
                    hit += np.bincount(routed[b][layer][len(p) + tick],
                                       minlength=e)
            want_load += hit
            want_touched += int((hit > 0).sum())
    assert list(prefill) == list(want_prefill)
    assert prefill.sum() == sum(map(len, prompts)) * cfg.top_k \
        * cfg.n_moe_layers
    assert list(load) == list(want_load)
    assert load.sum() == 2 * chunk * cfg.top_k * cfg.n_moe_layers
    assert touched == want_touched
    # read out once: the next dispatch reports no prefill
    _, _, _, moe2 = step(params, cache, table, tok, temp, keys,
                         jnp.asarray(active))
    assert AF.split_moe(cfg, np.asarray(moe2))[2].sum() == 0


def test_ring_serves_what_generate_answers_and_counts_it(model):
    """The scheduler's path end to end: requests through the continuous
    batcher answer generate's greedy tokens, and the routing counters
    arrive on serving_status with the dispatch's tokens."""
    from paddle_operator_tpu.infer.scheduler import ContinuousBatcher

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
    steps = st["decodeStepsTotal"]
    assert st["moeLayerStepsTotal"] == steps * cfg.n_moe_layers
    assert st["moeAssignmentsTotal"] == (st["decodeLaneStepsTotal"]
                                         * cfg.top_k * cfg.n_moe_layers)
    assert sum(st["moeExpertLoadTotal"]) == st["moeAssignmentsTotal"]
    assert len(st["moeExpertLoadTotal"]) == cfg.n_experts
    assert 0 < st["moeExpertsTouchedTotal"] <= st["moeAssignmentsTotal"]
    assert st["moePrefillAssignmentsTotal"] == (
        sum(map(len, prompts)) * cfg.top_k * cfg.n_moe_layers)
    # and on /metrics, as tpujob_serve_*_total
    from paddle_operator_tpu.utils.observability import serving_gauges

    gauges = serving_gauges(st, "j")
    assert gauges['tpujob_serve_moe_assignments_total{job="j"}'] == \
        st["moeAssignmentsTotal"]
    assert gauges['tpujob_serve_moe_experts_touched_total{job="j"}'] == \
        st["moeExpertsTouchedTotal"]
    assert sum(v for k, v in gauges.items() if k.startswith(
        "tpujob_serve_moe_expert_load_total{")) == st["moeAssignmentsTotal"]
    # a LLaMA ring's block has none of them
    assert not [k for k in serving_gauges({"dispatchesTotal": 1}, "j")
                if "moe" in k]


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def _loop_experts(cfg, ep, h, idx, w):
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j])
            g = h[t] @ ep["w1"][e]
            y = (g / (1 + np.exp(-g)) * (h[t] @ ep["w3"][e])) @ ep["w2"][e]
            out[t] += w[t, j] * y
    return out


ROUTINGS = {
    "all_to_one_expert": lambda t, e, k: np.tile([3, 3], (t, 1))[:, :k],
    "an_expert_with_none": lambda t, e, k: np.stack(
        [np.arange(t) % (e - 1) + 1, (np.arange(t) + 2) % (e - 1) + 1], 1),
    "every_expert_evenly": lambda t, e, k: np.stack(
        [np.arange(t) % e, (np.arange(t) + 1) % e], 1),
    "skewed": lambda t, e, k: np.stack(
        [np.zeros(t, int), np.where(np.arange(t) < t - 2, 1,
                                    np.arange(t) % e)], 1),
}


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_grouped_product_against_a_per_token_loop(model, case):
    """Dropless under any skew: every assignment is computed, whatever
    the experts' loads."""
    _, cfg, _, params = model
    t = 19
    layer = 1
    stack = params["moe_layers"]["moe"]["experts"]
    ep = jax.tree.map(lambda x: np.asarray(x[layer], np.float64), stack)
    h = np.random.RandomState(5).randn(t, cfg.dim)
    idx = ROUTINGS[case](t, cfg.n_experts, cfg.top_k).astype(np.int32)
    w = np.random.RandomState(6).rand(t, cfg.top_k)
    got = M.expert_ffn(cfg, stack, jnp.int32(layer),
                       jnp.asarray(h, jnp.float32), jnp.asarray(idx),
                       jnp.asarray(w, jnp.float32))
    assert rel(got, _loop_experts(cfg, ep, h, idx, w)) < RTOL


def test_routing_selects_by_biased_and_weights_by_unbiased_scores(model):
    """Ties in the scores go to the lower expert on both sides; the bias
    moves the selection and never the weights."""
    cfgj, cfg, _, params = model
    mp = M.layer_at(params["moe_layers"]["moe"], 0)
    mp = dict(mp, router={"kernel": jnp.zeros_like(mp["router"]["kernel"])})
    h = jnp.asarray(np.random.RandomState(1).randn(6, cfg.dim), jnp.float32)
    idx, w = M.route(cfg, dict(mp, expert_bias=jnp.zeros(cfg.n_experts)), h)
    assert np.asarray(idx).tolist() == [[0, 1]] * 6       # all scores tie
    bias = jnp.zeros(cfg.n_experts).at[5].set(0.3).at[2].set(0.1)
    idx, w = M.route(cfg, dict(mp, expert_bias=bias), h)
    assert np.asarray(idx).tolist() == [[5, 2]] * 6
    # sigmoid(0) = 0.5 each: normalised halves times route_scale
    np.testing.assert_allclose(np.asarray(w), cfg.route_scale / 2, rtol=1e-6)
    rw = {"moe/router/kernel": jnp.zeros((cfg.dim, cfg.n_experts)),
          "moe/expert_bias": bias}
    ridx, rsel = R.routing(cfgj, rw, h)
    assert np.asarray(ridx).tolist() == np.asarray(idx).tolist()
    np.testing.assert_allclose(np.asarray(rsel), np.asarray(w), rtol=1e-6)


# ---------------------------------------------------------------------------
# the decode kernel with a window
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [40, 300, 10 ** 6])
def test_windowed_paged_kernel_against_the_einsum(window):
    """``pallas-interpret`` at head width 128, block 64: lanes whose window
    starts inside the first block, skips whole blocks, or never cuts."""
    from paddle_operator_tpu.ops.decode_attention import (
        paged_decode_attention,
    )

    cfg = M.AfmoeConfig(vocab_size=64, dim=256, n_layers=2, n_dense_layers=1,
                        n_heads=4, n_kv_heads=2, head_dim=128, n_experts=8,
                        top_k=2, layer_types=(M.SLIDING, M.FULL),
                        max_seq_len=512, dtype=jnp.float32,
                        param_dtype=jnp.float32)
    b, m, bs, layers = 3, 6, 64, 2
    rng = np.random.RandomState(0)
    pool_k = jnp.asarray(rng.randn(layers, b * m + 1, 2, bs, 128), jnp.float32)
    pool_v = jnp.asarray(rng.randn(layers, b * m + 1, 2, bs, 128), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    q = jnp.asarray(rng.randn(b, 4, 128), jnp.float32)
    lengths = jnp.asarray([37, 200, 384], jnp.int32)
    li = jnp.int32(1)
    got = paged_decode_attention(
        q, pool_k, pool_v, table, lengths, layer=li,
        starts=jnp.maximum(lengths - window, 0), interpret=True)
    view = PG.PagedView(cfg, {"k": pool_k, "v": pool_v, "pos": lengths - 1},
                        table)
    want = M.attend(cfg, q[:, None], *view.lanes((pool_k, pool_v), li),
                    (lengths - 1)[:, None], window)
    assert rel(got.reshape(b, -1), want[:, 0]) < RTOL
    if window >= 384:       # no cut: the windowless kernel's answer
        plain = paged_decode_attention(q, pool_k, pool_v, table, lengths,
                                       layer=li, interpret=True)
        assert rel(got, plain) < 1e-6


@pytest.mark.parametrize("window", [1, 64, 100, 300, 10 ** 6])
def test_windowed_list_against_the_rectangular_grid(window):
    """The list-driven kernel answers bit for bit what the rectangular
    grid it replaced answers (tests/rect_paged_attention.py): windows
    that start at 0, on a block's edge and mid-block; an empty lane."""
    from paddle_operator_tpu.ops.decode_attention import (
        decode_cells,
        paged_decode_attention,
    )
    from tests.rect_paged_attention import rect_paged_decode_attention

    b, m, bs, layers = 4, 6, 64, 2
    rng = np.random.RandomState(1)
    pool_k = jnp.asarray(rng.randn(layers, b * m + 1, 2, bs, 128), jnp.float32)
    pool_v = jnp.asarray(rng.randn(layers, b * m + 1, 2, bs, 128), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    q = jnp.asarray(rng.randn(b, 4, 128), jnp.float32)
    lengths = jnp.asarray([37, 0, 200, 384], jnp.int32)
    starts = jnp.maximum(lengths - window, 0)
    cells = decode_cells(table, lengths, bs, starts)
    lo, end = np.asarray(starts) // bs, -(-np.asarray(lengths) // bs)
    assert int(cells.n) == int(np.maximum(end - lo, 1).sum())
    for li in range(layers):
        got = paged_decode_attention(q, pool_k, pool_v, table, lengths,
                                     layer=jnp.int32(li), cells=cells,
                                     interpret=True)
        want = rect_paged_decode_attention(q, pool_k, pool_v, table, lengths,
                                           layer=jnp.int32(li), starts=starts)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[1].any()


def test_ring_step_with_a_masked_lane_through_the_kernel():
    """The chunk step with the kernel (interpret mode) and one lane out
    of the step: the live lanes' tokens are the einsum step's, and every
    lane's logits stay finite (``check_finite``)."""
    cfg = M.AfmoeConfig(vocab_size=64, dim=64, n_layers=3, n_dense_layers=1,
                        n_heads=2, n_kv_heads=1, head_dim=128, ffn_dim=64,
                        moe_ffn_dim=32, n_experts=4, top_k=2,
                        sliding_window=8, layer_types=(M.SLIDING, M.SLIDING,
                                                       M.FULL),
                        max_seq_len=64, dtype=jnp.float32,
                        param_dtype=jnp.float32, decode_attn="xla")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [ids(41, 5), ids(42, 19), ids(43, 11)]
    active = jnp.asarray([True, False, True])
    outs = []
    for impl in ("xla", "pallas-interpret"):
        c = dataclasses.replace(cfg, decode_attn=impl)
        cache, table, tok, temp, keys = insert_prompts(c, params, prompts)
        _, _, toks, ok, _ = AF.make_paged_chunk_step(
            c, 3, check_finite=True)(params, cache, table, tok, temp, keys,
                                     active)
        assert np.asarray(ok).all()
        outs.append(np.asarray(toks))
    assert outs[0][:, [0, 2]].tolist() == outs[1][:, [0, 2]].tolist()


def test_ring_forward_through_the_windowed_kernel():
    """The ring's forward with the kernel (interpret mode) agrees with its
    einsum path at a head width the kernel takes."""
    cfg = M.AfmoeConfig(vocab_size=64, dim=64, n_layers=3, n_dense_layers=1,
                        n_heads=2, n_kv_heads=1, head_dim=128, ffn_dim=64,
                        moe_ffn_dim=32, n_experts=4, top_k=2,
                        sliding_window=8, layer_types=(M.SLIDING, M.SLIDING,
                                                       M.FULL),
                        max_seq_len=64, dtype=jnp.float32,
                        param_dtype=jnp.float32, decode_attn="xla")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [ids(41, 5), ids(42, 19)]
    outs = []
    for impl in ("xla", "pallas-interpret"):
        c = dataclasses.replace(cfg, decode_attn=impl)
        cache, table, tok, _, _ = insert_prompts(c, params, prompts)
        logits, _, _, _ = AF.paged_ring_forward(
            c, params, tok, cache, table, jnp.ones((2,), bool))
        outs.append(np.asarray(logits))
    assert rel(outs[1], outs[0]) < RTOL


# ---------------------------------------------------------------------------
# every other mode refuses the architecture, in one sentence
# ---------------------------------------------------------------------------


REFUSED = {
    "SERVE_PAGED=0": dict(paged=False),
    "SERVE_SPEC_K>0": dict(spec_k=2, draft_params={}, draft_cfg=object()),
    "SERVE_KV_QUANT=int8": dict(kv_quant="int8"),
    "SERVE_PREFILL=chunked": dict(prefill_mode="chunked"),
    "SERVE_PREFILL=disagg": dict(prefill_mode="disagg"),
    "SERVE_ADAPTERS": dict(adapters=object()),
    "SERVE_MEGASTEP>1": dict(megastep=2),
    "SERVE_PREFIX_CACHE=1": dict(prefix_cache=True),
}


@pytest.mark.parametrize("mode", sorted(REFUSED))
def test_ring_modes_refuse_the_architecture(model, mode):
    from paddle_operator_tpu.infer.executor import RingExecutor

    _, cfg, _, params = model
    kw = dict(slots=2, max_len=MAX_LEN, chunk_tokens=2, paged=True,
              block_size=BLOCK, prefix_cache=False)
    kw.update(REFUSED[mode])
    with pytest.raises(ValueError, match="not written for: .*"
                       + mode.replace(">", ".").replace("=", ".")):
        RingExecutor(params, cfg, **kw)


def test_tensor_parallel_quantizers_and_trainer_refuse(model, monkeypatch):
    from paddle_operator_tpu.infer import serve
    from paddle_operator_tpu.models.llama import make_model
    from paddle_operator_tpu.parallel.mesh import make_serving_mesh

    _, cfg, _, params = model
    mesh = make_serving_mesh(2)
    with pytest.raises(ValueError, match="SERVE_TP>1"):
        serve.load_serving_params(cfg, None, mesh=mesh)
    with pytest.raises(ValueError, match="SERVE_TP>1"):
        D.prefill(params, cfg, jnp.zeros((1, 4), jnp.int32), MAX_LEN,
                  mesh=mesh)
    with pytest.raises(ValueError, match="served only"):
        make_model("afmoe-tiny")
    # the entry point, before anything is loaded
    for env in ({"QUANTIZE": "int8"}, {"SERVE_WEIGHT_QUANT": "int8"},
                {"SERVE_TP": "2"}, {"SERVE_SPEC_K": "2"}):
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        monkeypatch.setenv("MODEL_PRESET", "afmoe-tiny")
        with pytest.raises(ValueError, match="not written for"):
            serve.main()
        for k in env:
            monkeypatch.delenv(k)
    # a LLaMA preset is never refused anything here
    from paddle_operator_tpu.models.llama import CONFIGS

    AF.refuse_modes(CONFIGS["tiny"], {"SERVE_PAGED=0": True})


def test_smoke_initialiser_serves(model):
    """``load_serving_params`` reaches the architecture by the preset's
    type: the tree ``param_shapes`` describes, routing bias zero."""
    from paddle_operator_tpu.infer import serve

    _, cfg, _, _ = model
    params, resumed = serve.load_serving_params(cfg, None)
    assert resumed is False
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), M.param_shapes(cfg))
    assert float(jnp.abs(params["moe_layers"]["moe"]["expert_bias"]).max()) \
        == 0.0


# ---------------------------------------------------------------------------
# planted faults: each pushes the logit gap past the tiny cell's limit
# ---------------------------------------------------------------------------


def served_gap(cfgj, cfg, key, params):
    """What the cell's check reads: how far the served (greedy) tokens'
    reference logits lie below the reference's best, on average over 160
    positions, over contexts well beyond the window."""
    n, new = 14, 40
    prompts = np.stack([ids(51 + i, n) for i in range(4)])
    out = np.asarray(D.generate(params, cfg, jnp.asarray(prompts),
                                max_new_tokens=new, max_len=MAX_LEN))
    want = np.asarray(jax.jit(jax.vmap(
        lambda seq: R.forward(cfgj, key, seq)))(jnp.asarray(out[:, :-1])))
    rows = want[:, n - 1:]                                  # [4, new, V]
    served = np.take_along_axis(rows, out[:, n:, None], -1)[..., 0]
    return float((rows.max(-1) - served).mean())


def _no_shared(monkeypatch):
    real = M.swiglu
    monkeypatch.setattr(M, "swiglu", lambda x, w, dtype: (
        jnp.zeros_like(x) if w["w1"]["kernel"].shape[-1] == 32
        else real(x, w, dtype)))


def _rope_everywhere(monkeypatch):
    monkeypatch.setattr(M.AfmoeConfig, "ropes",
                        lambda self: (True,) * self.n_layers)


def _no_window(monkeypatch):
    monkeypatch.setattr(M.AfmoeConfig, "windows",
                        lambda self: (self.max_seq_len + 1,) * self.n_layers)


def _bias_in_weights(monkeypatch):
    def route(cfg, mp, h):
        s = jax.nn.sigmoid(h @ mp["router"]["kernel"]) + mp["expert_bias"]
        w, idx = jax.lax.top_k(s, cfg.top_k)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * cfg.route_scale

    monkeypatch.setattr(M, "route", route)


FAULTS = {"shared_expert_left_out": _no_shared,
          "rope_on_the_full_layer": _rope_everywhere,
          "window_ignored": _no_window,
          "bias_used_in_the_weights": _bias_in_weights}


def test_sound_program_is_within_the_tiny_cells_limit(model):
    cfgj, cfg, key, params = model
    assert served_gap(cfgj, cfg, key, params) <= \
        cfgj["check"]["logit_gap_mean"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_passes_the_tiny_cells_limit(model, monkeypatch, fault):
    cfgj, cfg, key, params = model
    FAULTS[fault](monkeypatch)
    assert served_gap(cfgj, cfg, key, params) > \
        cfgj["check"]["logit_gap_mean"]
