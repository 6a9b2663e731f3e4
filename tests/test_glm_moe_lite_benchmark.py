"""The benchmark's side of the ``glm4_moe_lite`` configuration (ISSUE 32)
without the chip: the counts (``harness/opsbytes_glm_moe_lite.py``) against
hand arithmetic, the new readers on hand-made records (a value between two
scrapes; nothing — never a raise, never a 0 made up — where there is
nothing to read, as on a parent without the architecture), the sample the
comparison judges, the fp8 control, and what ``BENCHMARK.json`` lists the
new cell under."""

import copy
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import common as C
from benchmark.harness import opsbytes_glm_moe_lite as O
from benchmark.harness import serve_afmoe_window as AW
from benchmark.harness import weights as W
from benchmark.metrics import (
    mla_decode_attn_roofline,
    mla_insert_attn_roofline,
    mla_moe_experts_touched_pct,
    mla_moe_gmm_roofline,
    moe_expert_load_max_over_mean,
    serve_mla_moe_mfu_pct,
)
from benchmark.reference import glm_moe_lite_ref as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "glm-4.7-flash-serve.json")
TINY = os.path.join(ROOT, "benchmark", "tests", "configs",
                    "tiny-glm-serve.json")
CELL = "glm47.closed16-longprompt"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("mla_decode_attn_roofline", "mla_insert_attn_roofline",
       "serve_mla_moe_mfu_pct", "mla_moe_gmm_roofline",
       "mla_moe_experts_touched_pct")


@pytest.fixture(scope="module")
def cfg():
    return json.load(open(CONFIG))


def test_counts_are_the_issues(cfg):
    """ISSUE 32's reckoning by hand: the five projections 21.76 M, a dense
    layer 84.67 M, an expert layer 635.3 M of which 69.08 M meet a token,
    the head 317.2 M; 1,152 bytes a cached token a layer."""
    q_a, q_b = 2048 * 768, 768 * 20 * 256
    kv_a, kv_b, o = 2048 * 576, 512 * 20 * 448, 20 * 256 * 2048
    assert O.attention_params(cfg) == q_a + q_b + kv_a + kv_b + o == 21_757_952
    assert O.dense_layer_params(cfg) == 21_757_952 + 3 * 2048 * 10240 \
        == 84_672_512
    assert O.expert_params(cfg) == 3 * 2048 * 1536 == 9_437_184
    assert O.expert_layer_params(cfg) == (21_757_952 + 2048 * 64
                                          + 65 * 9_437_184) == 635_305_984
    assert O.cache_row_bytes(cfg) == 1152
    active = 21_757_952 + 2048 * 64 + 5 * 9_437_184
    assert round(active / 1e6, 2) == 69.07
    assert O.active_matmul_params(cfg) == (84_672_512 + 7 * active
                                           + 2048 * 154880)
    assert round(O.active_matmul_params(cfg) / 1e6) == 885
    assert O.serve_flops_per_token(cfg) == 2.0 * O.active_matmul_params(cfg)
    # the whole of what the chip holds: 5.17 B parameters, 10.33 GB
    held = (O.dense_layer_params(cfg) + 7 * O.expert_layer_params(cfg)
            + 2 * 2048 * 154880)
    assert round(held * 2 / 1e9, 2) == 10.33
    # a layer-step of 64 assignments over 41 experts: the touched experts'
    # matrices once, and that is nearly all of its bytes
    need = O.grouped_products_layer_step(cfg, 64, 41)
    weights = 41 * O.expert_params(cfg) * 2
    assert weights < need["bytes"] < 1.01 * weights
    assert need["flops"] == 64 * 2 * O.expert_params(cfg)
    # the latent kernel: a lane's rows once for all 20 heads, 38
    # operations a byte
    step = O.latent_decode_step(cfg, [3000, 100, 0])
    assert step["calls"] == 8
    assert step["bytes"] == 8 * (3100 * 1152 + 2 * 20 * (1024 + 64) * 2)
    assert step["flops"] == 8 * 3100 * 2 * 20 * (576 + 512)
    assert 36 < step["flops"] / step["bytes"] < 38
    # the insert's flash call: heads x 512 x n^2, the causal half
    call = O.insert_attention_layer(cfg, 2000, 2000 ** 2)
    assert call["flops"] == 20 * 512 * 2000 ** 2
    assert call["bytes"] == 2000 * 20 * 1024 * 2


def kernel(name, shape, module="jit_step(1)", ns=50_000):
    return {"name": name, "shape": shape, "module": module, "start_ns": 0,
            "ns": ns}


def request(n_prompt, first, tokens=(5, 6)):
    return types.SimpleNamespace(prompt=[1] * n_prompt,
                                 token_times=[first, first + 0.5],
                                 tokens=list(tokens))


def record(cfg):
    e = cfg["n_routed_experts"]
    a = {"moeLayerStepsTotal": 1000, "moeAssignmentsTotal": 100_000,
         "moeExpertsTouchedTotal": 70_000,
         "moeExpertLoadTotal": [100] * e, "decodeStepsTotal": 250}
    b = {"moeLayerStepsTotal": 1000 + 700, "moeAssignmentsTotal":
         100_000 + 700 * 64, "moeExpertsTouchedTotal": 70_000 + 700 * 40,
         "moeExpertLoadTotal": [100 + 600] * (e - 1) + [100 + 1800],
         "decodeStepsTotal": 350}
    kernels = ([kernel("closed_call.7", "bf16[16,32,512]", ns=150_000)] * 8
               + [kernel("gmm.3", "bf16[64,1536]", ns=400_000)] * 14
               + [kernel("gmm.5", "f32[64,2048]", ns=400_000)] * 7
               + [kernel("attn.kernel.3", "bf16[1,20,2048,256]",
                         "jit_insert(2)", ns=900_000)] * 8
               + [kernel("closed_call.5", "bf16[1,20,4096,256]",
                         "jit_insert(3)", ns=3_000_000)] * 8
               + [kernel("gmm.9", "bf16[8192,1536]", "jit_insert(2)")] * 3)
    return {
        "cell": {"config": cfg, "chips": 1},
        "window": {"t_open": 10.0, "t_close": 61.0, "seconds": 51.0,
                   "traced": (56.0, 60.0)},
        "requests": [request(1500, 10.5), request(2000, 20.0),
                     request(3000, 30.0), request(4000, 70.0),
                     request(300, 12.0)],
        "device": {"kind": "TPU v5 lite"},
        "metrics_open": {"statusz": a}, "metrics_close": {"statusz": b},
        "statusz_traced": (a, b),
        "lane_samples": [{"t": 57.0, "lanePos": [100, 3000] + [0] * 14},
                         {"t": 5.0, "lanePos": [9000] * 16}],
        "trace": {"kernels": kernels},
    }


def test_readers_on_a_hand_made_record(cfg):
    rec = record(cfg)
    assert mla_moe_experts_touched_pct.read(rec) == pytest.approx(
        100.0 * 40 / 64)
    load = [600] * 63 + [1800]
    assert moe_expert_load_max_over_mean.read(rec) == pytest.approx(
        1800 * 64 / sum(load))
    # 21 grouped products = 7 layer-steps of 64 assignments, 40 experts
    need = O.grouped_products_layer_step(cfg, 64, 40)
    assert mla_moe_gmm_roofline.read(rec) == pytest.approx(
        100.0 * 7 * need["bytes"] / 819e9 / (21 * 400_000e-9))
    # eight latent calls = one step over the traced sample's lanes (the
    # sample outside the traced seconds is not read)
    step = O.latent_decode_step(cfg, [100, 3000] + [0] * 14)
    assert mla_decode_attn_roofline.read(rec) == pytest.approx(
        100.0 * step["bytes"] / 819e9 / (8 * 150_000e-9))
    # the 2048 rung's calls count the window's prompts on that rung (1500
    # and 2000: mean n^2), the 4096 rung's the one of 3000; the prompt
    # whose first token fell outside the window counts nowhere
    ops = 8 * 20 * 512 * ((1500 ** 2 + 2000 ** 2) / 2 + 3000 ** 2)
    assert mla_insert_attn_roofline.read(rec) == pytest.approx(
        100.0 * ops / 197e12 / (8 * 900_000e-9 + 8 * 3_000_000e-9))
    # four prompts and their two answer tokens each inside the window
    tokens = 1500 + 2000 + 3000 + 300 + 8
    assert serve_mla_moe_mfu_pct.read(rec) == pytest.approx(
        100.0 * O.serve_flops_per_token(cfg) * tokens / 51.0 / 197e12)
    for reader in (mla_moe_gmm_roofline, mla_decode_attn_roofline,
                   mla_insert_attn_roofline, mla_moe_experts_touched_pct):
        assert 0 < reader.read(rec) <= 100


def test_attention_calls_are_told_from_grouped_products():
    assert O.is_attention_call(kernel("attn.kernel.7", "bf16[16,32,512]"))
    assert O.is_attention_call(kernel("closed_call.12", "bf16[16,32,512]"))
    assert O.is_attention_call(kernel("closed_call.4", "bf16[1,20,2048,256]"))
    assert not O.is_attention_call(kernel("ffn.experts.2", "f32[64,2048]"))
    assert not O.is_attention_call(kernel("closed_call.9", "bf16[64,1536]"))


def test_readers_find_nothing_where_there_is_nothing_to_read(cfg):
    """A program without the routing counters, a trace without kernels, a
    run whose inserts all sat on the einsum rung, another architecture's
    configuration: None, and no raise."""
    rec = record(cfg)
    bare = copy.deepcopy(rec)
    for side in ("metrics_open", "metrics_close"):
        bare[side]["statusz"] = {"decodeStepsTotal": 1}
    bare["statusz_traced"] = None
    bare["trace"] = {"kernels": []}
    bare["lane_samples"] = []
    for reader in (mla_moe_gmm_roofline, mla_decode_attn_roofline,
                   mla_insert_attn_roofline, mla_moe_experts_touched_pct,
                   moe_expert_load_max_over_mean):
        assert reader.read(bare) is None
    bare["trace"] = None
    assert mla_insert_attn_roofline.read(bare) is None
    # inserts only of a rung no prompt of the window used
    odd = copy.deepcopy(rec)
    odd["trace"]["kernels"] = [kernel("attn.kernel.3", "bf16[1,20,8192,256]",
                                      "jit_insert(9)")] * 8
    assert mla_insert_attn_roofline.read(odd) is None
    # Trinity's configuration, a dense one: these readers are not theirs
    for other in ({"num_experts": 128, "hidden_size": 2048, "serve": {}},
                  {"hidden_size": 4096, "serve": {}}):
        alien = copy.deepcopy(rec)
        alien["cell"]["config"] = other
        for reader in (mla_moe_gmm_roofline, mla_decode_attn_roofline,
                       mla_insert_attn_roofline, serve_mla_moe_mfu_pct,
                       mla_moe_experts_touched_pct):
            assert reader.read(alien) is None
    # counters that did not move
    still = copy.deepcopy(rec)
    still["metrics_close"] = still["metrics_open"]
    still["statusz_traced"] = (still["statusz_traced"][0],) * 2
    assert mla_moe_experts_touched_pct.read(still) is None
    assert mla_moe_gmm_roofline.read(still) is None


def fake(n_prompt, n_answer):
    return types.SimpleNamespace(prompt=[0] * n_prompt, tokens=[0] * n_answer)


def test_sample_holds_two_long_contexts_and_every_other_rung(cfg):
    """Two requests past 4,096 tokens of context, the widest rungs first
    (checked at 8,192: the 6144 or 8192 rung, the flash insert and a decode
    over 17 or more blocks are all seen), and one on each other rung."""
    finished = ([fake(300, 50), fake(400, 200), fake(900, 100),
                 fake(1500, 60), fake(2000, 90), fake(3000, 100),
                 fake(3900, 150), fake(4090, 100), fake(5000, 40),
                 fake(6000, 120), fake(7000, 200), fake(7600, 16)])
    size = lambda r: len(r.prompt) + len(r.tokens)
    rungs = cfg["serve"]["rungs"]
    for seed in (1, 2 ** 31 + 5):
        sample = AW.sample_for_check(finished, seed, cfg)
        long = [r for r in sample if size(r) > 4096]
        assert len(long) == 2 and sample[:2] == long
        assert {AW.rung_of(len(r.prompt), rungs) for r in long} == {6144,
                                                                    8192}
        assert all(size(r) > 16 * 256 for r in long)     # 17 blocks or more
        assert {AW.rung_of(len(r.prompt), rungs) for r in sample} == set(rungs)
        assert all(size(r) <= 4096 for r in sample[2:])
        assert len(sample) == 6
    with pytest.raises(C.BenchError, match="pass 4096 tokens"):
        AW.sample_for_check(finished[:8], 1, cfg)


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


def test_reference_stands_apart_from_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "glm_moe_lite_ref.py")).read()
    assert "paddle_operator_tpu" not in src.split('"""', 2)[2]
    assert "absorb" not in src.split('"""', 2)[2]


def test_configuration_states_the_catalogs_row_and_its_cut(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "GLM-4.7-Flash")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "glm-4.7-flash-serve")
    assert entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/glm-4.7-flash-serve.json"
    assert entry["reduced"] == ["num_hidden_layers",
                                "num_nextn_predict_layers"]
    for k, v in row["config"].items():
        if k not in entry["reduced"]:
            assert cfg[k] == v, k
    assert cfg["num_hidden_layers"] == 8
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["published"] == {"num_hidden_layers": 47,
                                "num_nextn_predict_layers": 1}
    assert "eight layers" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"rope_interleave", "softmax_scale",
                                   "multi_token_prediction", "torch_dtype",
                                   "initializer_range", "weights", "routing"}
    s = cfg["serve"]
    assert (s["lanes"], s["block"], s["max_len"], s["chunk"]) == (16, 256,
                                                                  8192, 8)
    assert s["rungs"] == [512, 1024, 2048, 4096, 6144, 8192]
    assert s["env"] == {"SERVE_PREFIX_CACHE": 0}
    # one warm prompt a rung
    assert sorted(AW.rung_of(n, s["rungs"]) for n in s["warm_prompts"]) \
        == s["rungs"]
    assert cfg["kind"] == "serve_glm_moe_lite"
    for name in ("serve_glm_moe_lite_window", "serve_glm_moe_lite_child"):
        assert os.path.exists(os.path.join(ROOT, "benchmark", "harness",
                                           name + ".py"))


def test_benchmark_lists_the_new_cell_where_the_issue_says():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert list(cells)[-1] == CELL and len(cells) == 5
    assert cells[CELL] == {**cells[CELL], "config": "glm-4.7-flash-serve",
                           "traffic": "closed16-longprompt", "chips": 1}
    assert bench["configs"][-1]["name"] == "glm-4.7-flash-serve"
    assert len(bench["configs"]) == 4
    lists = {m["name"]: m.get("workloads", []) for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in ("serve_tokens_per_s", "ttft_p95_ms.closed",
                 "tpot_p95_ms.closed", "sched_queue_wait_ms.closed",
                 "exec_dispatches_per_token", "prefill_share_pct.closed",
                 "kv_pool_live_pct", "device_idle_pct.serve",
                 "prefill_pad_pct.closed", "decode_lanes_live_pct.closed",
                 "ring_idle_pct.closed", "sched_host_ms_per_dispatch.closed",
                 "moe_expert_load_max_over_mean"):
        assert lists[name][-1] == CELL, name
    # readers that hold another configuration's keys are left alone
    for name in ("serve_mfu_pct", "decode_attn_roofline", "serve_moe_mfu_pct",
                 "moe_gmm_roofline", "swa_decode_attn_roofline",
                 "moe_experts_touched_pct", "train_tokens_per_s",
                 "ttft_p95_ms", "tpot_p95_ms"):
        assert CELL not in lists[name], name
    assert "workloads" not in next(m for m in bench["end_to_end"]
                                   if m["name"] == "setup_s")
    new = [m for m in bench["per_layer"] if m["name"] in NEW]
    # ... appended together, in this order (later PRs append behind them)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 5] == list(NEW) and at == 30
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py"))
    assert {m["name"]: m["unit"] for m in new if "roofline" in m["name"]} \
        == {n: "%" for n in NEW if "roofline" in n}
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                      "closed16-longprompt.json")))
    assert {k: mix[k] for k in mix if k != "why"} == {
        "kind": "serve", "loop": "closed", "traffic_seed": 20261004,
        "callers": 16, "requests_per_caller": 192,
        "prompt_tokens": {"dist": "lognormal", "median": 2048, "sigma": 0.8,
                          "min": 256, "max": 7680},
        "answer_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.5,
                          "min": 16, "max": 256},
        "shared_prefix": None, "think_ms": 25, "stagger_ms": 40}
    # every context fits the ring
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] <= 8192
