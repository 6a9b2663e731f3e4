"""The benchmark's side of the ``afmoe`` configuration (ISSUE 28) without
the chip: the counts (``harness/opsbytes_afmoe.py``), the five readers on
hand-made records (a value between two scrapes; nothing — never a raise,
never a 0 made up — where the program has no such counter, as the parent
has not), the sample the comparison judges, the fp8 control, and what
``BENCHMARK.json`` may and may not list the new cells under."""

import copy
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import common as C
from benchmark.harness import opsbytes_afmoe as O
from benchmark.harness import serve_afmoe_window as SW
from benchmark.harness import weights as W
from benchmark.metrics import (
    moe_expert_load_max_over_mean,
    moe_experts_touched_pct,
    moe_gmm_roofline,
    serve_moe_mfu_pct,
    swa_decode_attn_roofline,
)
from benchmark.reference import afmoe_ref as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "trinity-mini-serve-5l.json")
TINY = os.path.join(ROOT, "benchmark", "tests", "configs",
                    "tiny-afmoe-serve.json")
CELL = "trinity5l.closed16-deep"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    return json.load(open(CONFIG))


def test_counts_are_the_issues(cfg):
    """ISSUE 28's reckoning: attention 27.26 M with the gate, a dense layer
    65.0 M, an expert layer 84.1 M active of 839.1 M, the head 410.0 M."""
    assert O.attention_params(cfg) == 27_262_976
    assert O.expert_params(cfg) == 6_291_456
    dense = O.attention_params(cfg) + 3 * 2048 * 6144
    moe = O.attention_params(cfg) + 2048 * 128 + 9 * O.expert_params(cfg)
    assert round(dense / 1e6, 1) == 65.0 and round(moe / 1e6, 1) == 84.1
    assert O.active_matmul_params(cfg) == dense + 4 * moe + 2048 * 200192
    assert O.serve_flops_per_token(cfg) == 2.0 * O.active_matmul_params(cfg)
    # a layer-step of 128 assignments over 82 experts: the touched
    # experts' matrices once, and that is nearly all of its bytes
    need = O.grouped_products_layer_step(cfg, 128, 82)
    weights = 82 * O.expert_params(cfg) * 2
    assert weights < need["bytes"] < 1.01 * weights
    assert need["flops"] == 128 * 2 * O.expert_params(cfg)
    # a sliding layer's bytes stop at the window; the full layer's do not
    short = O.decode_attention_step(cfg, [100])
    deep = O.decode_attention_step(cfg, [3000])
    per_token = 2 * 4 * 128 * 2
    assert short == {"calls": 5, "bytes": 5 * 100 * per_token,
                     "flops": 5 * 100 * 2 * 2 * 32 * 128}
    assert deep["bytes"] == (4 * 2048 + 3000) * per_token


def kernel(name, shape, module="jit_step(1)", ns=50_000):
    return {"name": name, "shape": shape, "module": module, "start_ns": 0,
            "ns": ns}


def record(cfg):
    e = cfg["num_experts"]
    a = {"moeLayerStepsTotal": 1000, "moeAssignmentsTotal": 100_000,
         "moeExpertsTouchedTotal": 70_000,
         "moeExpertLoadTotal": [100] * e, "decodeStepsTotal": 250}
    b = {"moeLayerStepsTotal": 1000 + 400, "moeAssignmentsTotal":
         100_000 + 400 * 128, "moeExpertsTouchedTotal": 70_000 + 400 * 80,
         "moeExpertLoadTotal": [100 + 400] * (e - 1) + [100 + 1200],
         "decodeStepsTotal": 350}
    kernels = ([kernel("closed_call.7", "bf16[16,32,128]", ns=20_000)] * 5
               + [kernel("gmm.3", "bf16[128,1024]", ns=600_000)] * 8
               + [kernel("gmm.5", "f32[128,2048]", ns=600_000)] * 4
               + [kernel("gmm.3", "bf16[8192,1024]", "jit_insert(2)")] * 3)
    req = types.SimpleNamespace(prompt=[1] * 100, token_times=[10.5, 11.0],
                                tokens=[5, 6])
    return {
        "cell": {"config": cfg, "chips": 1},
        "window": {"t_open": 10.0, "t_close": 61.0, "seconds": 51.0,
                   "traced": (56.0, 60.0)},
        "requests": [req], "device": {"kind": "TPU v5 lite"},
        "metrics_open": {"statusz": a}, "metrics_close": {"statusz": b},
        "statusz_traced": (a, b),
        "lane_samples": [{"t": 57.0, "lanePos": [100, 3000] + [0] * 14}],
        "trace": {"kernels": kernels},
    }


def test_readers_on_a_hand_made_record(cfg):
    rec = record(cfg)
    assert moe_experts_touched_pct.read(rec) == pytest.approx(
        100.0 * 80 / 128)
    load = [400] * 127 + [1200]
    assert moe_expert_load_max_over_mean.read(rec) == pytest.approx(
        1200 * 128 / sum(load))
    # 12 grouped products = 4 layer-steps of 128 assignments, 80 experts
    need = O.grouped_products_layer_step(cfg, 128, 80)
    assert moe_gmm_roofline.read(rec) == pytest.approx(
        100.0 * 4 * need["bytes"] / 819e9 / (12 * 600_000e-9))
    step = O.decode_attention_step(cfg, [100, 3000] + [0] * 14)
    assert swa_decode_attn_roofline.read(rec) == pytest.approx(
        100.0 * step["bytes"] / 819e9 / (5 * 20_000e-9))
    # one prompt of 100 tokens and two answer tokens inside the window
    assert serve_moe_mfu_pct.read(rec) == pytest.approx(
        100.0 * O.serve_flops_per_token(cfg) * 102 / 51.0 / 197e12)
    for reader in (moe_gmm_roofline, swa_decode_attn_roofline,
                   moe_experts_touched_pct):
        assert 0 < reader.read(rec) <= 100


def test_attention_calls_are_told_from_grouped_products(cfg):
    assert O.is_attention_call(cfg, kernel("attn.kernel.7", "bf16[16,32,128]"))
    assert O.is_attention_call(cfg, kernel("closed_call.12", "bf16[16,32,128]"))
    assert not O.is_attention_call(cfg, kernel("ffn.experts.2", "f32[128,2048]"))
    assert not O.is_attention_call(cfg, kernel("closed_call.9", "bf16[128,1024]"))


def test_readers_find_nothing_on_a_parent_without_the_counters(cfg):
    """A program without the routing counters, a trace without kernels, a
    dense configuration: None, and no raise."""
    rec = record(cfg)
    bare = copy.deepcopy(rec)
    for side in ("metrics_open", "metrics_close"):
        bare[side]["statusz"] = {"decodeStepsTotal": 1}
    bare["statusz_traced"] = None
    bare["trace"] = {"kernels": []}
    bare["lane_samples"] = []
    for reader in (moe_gmm_roofline, swa_decode_attn_roofline,
                   moe_experts_touched_pct, moe_expert_load_max_over_mean):
        assert reader.read(bare) is None
    bare["metrics_open"], bare["metrics_close"] = {}, {}
    assert moe_experts_touched_pct.read(bare) is None
    assert moe_expert_load_max_over_mean.read(bare) is None
    dense = copy.deepcopy(rec)
    dense["cell"]["config"] = {"hidden_size": 4096, "serve": {}}
    assert serve_moe_mfu_pct.read(dense) is None
    assert swa_decode_attn_roofline.read(dense) is None
    # counters that did not move
    still = copy.deepcopy(rec)
    still["metrics_close"] = still["metrics_open"]
    still["statusz_traced"] = (still["statusz_traced"][0],) * 2
    assert moe_experts_touched_pct.read(still) is None
    assert moe_expert_load_max_over_mean.read(still) is None
    assert moe_gmm_roofline.read(still) is None


def fake(n_prompt, n_answer):
    return types.SimpleNamespace(prompt=[0] * n_prompt, tokens=[0] * n_answer)


def test_sample_holds_long_contexts_and_every_rung(cfg):
    finished = ([fake(100, 50), fake(200, 700), fake(300, 100), fake(400, 30),
                 fake(600, 100), fake(900, 500), fake(1500, 200),
                 fake(2000, 400), fake(2040, 300), fake(2500, 100),
                 fake(3000, 50), fake(2200, 20), fake(1900, 500)])
    size = lambda r: len(r.prompt) + len(r.tokens)
    for seed in (1, 2 ** 31 + 5):
        sample = SW.sample_for_check(finished, seed, cfg)
        long = [r for r in sample if size(r) > 2304]
        assert len(long) == 3 and sample[:3] == long
        rungs = {SW.rung_of(len(r.prompt), cfg["serve"]["rungs"])
                 for r in sample}
        assert rungs == {256, 512, 1024, 2048, 3072}
        assert {SW.rung_of(len(r.prompt), cfg["serve"]["rungs"])
                for r in long} == {2048, 3072}
        assert all(size(r) <= 1024 for r in sample[3:])
    with pytest.raises(C.BenchError, match="pass 2304 tokens"):
        SW.sample_for_check(finished[:9], 1, cfg)


def test_fp8_control_fails_the_tiny_limit():
    """The reference computed in the nearest precision below the one the
    configuration states reads past the tiny cell's limit: the comparison
    is tight enough to tell them apart."""
    tiny = json.load(open(TINY))
    key = W.root_key(11)
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 256, 40), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(R.forward(tiny, key, ids))
        low = np.asarray(R.forward(tiny, key, ids, "fp8"))
    picked = low.argmax(-1)
    gap = (ref.max(-1) - ref[np.arange(len(ids)), picked]).mean()
    assert gap > tiny["check"]["logit_gap_mean"]


def test_configuration_states_the_catalogs_row_and_its_cut(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Trinity-Mini")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity-mini-serve-5l")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types"]
    for k, v in row["config"].items():
        if k not in entry["reduced"]:
            assert cfg[k] == v, k
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert set(cfg["assumed"]) >= {"sandwich_norm", "attention_gate",
                                   "qk_norm", "rope", "routing",
                                   "torch_dtype", "initializer_range",
                                   "window_layers_cache"}


def test_benchmark_lists_the_new_cell_where_the_issue_says():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1
    # ISSUE 28's second cell was taken out again: too unsteady to be
    # admitted (PERF.md section 7), so this PR adds the one cell
    # (PR 32 appended its own cell: tests/test_glm_moe_lite_benchmark.py)
    glm = "glm47.closed16-longprompt"
    assert set(cells) == {"serve16l.closed16", "train6l.dense-2k",
                          "serve16l.open-bursty", CELL, glm}
    lists = {m["name"]: m.get("workloads", []) for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in ("serve_tokens_per_s", "ttft_p95_ms.closed",
                 "tpot_p95_ms.closed", "sched_queue_wait_ms.closed",
                 "exec_dispatches_per_token", "prefill_share_pct.closed",
                 "kv_pool_live_pct", "device_idle_pct.serve",
                 "prefill_pad_pct.closed", "decode_lanes_live_pct.closed",
                 "ring_idle_pct.closed", "sched_host_ms_per_dispatch.closed"):
        assert lists[name] == ["serve16l.closed16", CELL, glm], name
    for name in ("serve_mfu_pct", "decode_attn_roofline"):
        assert lists[name] == ["serve16l.closed16"], name
    for name in ("serve_moe_mfu_pct", "moe_gmm_roofline",
                 "swa_decode_attn_roofline", "moe_experts_touched_pct",
                 "moe_expert_load_max_over_mean"):
        # the one reader of the five that holds none of Trinity's keys
        # reads the other expert configuration's cell too
        want = [CELL, glm] if name == "moe_expert_load_max_over_mean" \
            else [CELL]
        assert lists[name] == want, name
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
    deep = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                       "closed16-deep.json")))
    base = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                       "closed16.json")))
    differ = {k for k in deep if deep[k] != base.get(k)}
    assert differ == {"why", "traffic_seed", "requests_per_caller"}
    assert deep["requests_per_caller"] == 192
