"""The benchmark's reader of EVERY decode-kernel call
(``benchmark/metrics/decode_attn_all_roofline.py``, ISSUE 33) on hand-made
records: the lanes' contexts of the device's traced seconds only (the
accepted ``decode_attn_roofline`` also reads the samples taken while the
profiler wrote its file), the carried step's calls inside ``jit_insert``
added with the share of the lanes that rode, the flash kernel's calls there
left out, and nothing where there is nothing to read."""

import json
import os

import pytest

from benchmark.harness import common as C
from benchmark.metrics import decode_attn_all_roofline as ALL
from benchmark.metrics import decode_attn_roofline as STEP

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DECODE, FLASH = "bf16[16,32,128]", "bf16[1,32,2048,128]"


def call(module, shape=DECODE, ns=70_000, name="attn.kernel.7"):
    return {"module": module, "name": name, "shape": shape, "ns": ns,
            "start_ns": 0}


def rec(kernels, close=None):
    cell = C.load_cell("serve16l.closed16")
    a = {"decodeStepsTotal": 100, "decodeLaneStepsTotal": 1500,
         "insertStepsTotal": 10, "insertStepLanesTotal": 140}
    return {
        "cell": cell, "device": {"kind": "TPU v5 lite"},
        # the profiler took 40 s to stop and write: `traced` ends there
        "window": {"t_open": 100.0, "t_close": 151.0,
                   "traced": (146.0, 190.0)},
        "trace": {"kernels": kernels, "window_s": 4.0},
        # samples before the trace and while the profiler wrote its file
        # are not of the traced seconds
        "lane_samples": [{"t": 120.0, "lanePos": [4000] * 16}]
        + [{"t": 146.5 + i, "lanePos": [700 + 10 * i] * 16}
           for i in range(3)]
        + [{"t": 151.0 + i, "lanePos": [900] * 16} for i in range(30)],
        "metrics_open": {"statusz": a},
        "metrics_close": {"statusz": a if close is None
                          else {k: a[k] + v for k, v in close.items()}},
    }


STEPS = [call("jit_step") for _ in range(320)]
# ... the copies and layout calls a trace also holds take no time
NOISE = [call("jit_step", "s32[8,16]", 0, "custom-call.6"),
         call("jit_insert", "bf16[16,1,8,256,128]", 3, "custom-call.3")]


def test_contexts_are_those_of_the_devices_traced_seconds():
    r = rec(STEPS + NOISE + [call("jit_insert", FLASH, 900_000)] * 16)
    # 16 lanes at 710 tokens: 4,096 B a token a call at 819 GB/s, 70 us
    want = 100 * (16 * 710 * 4096 / 819e9) / 70e-6
    assert ALL.read(r) == pytest.approx(want)
    # the accepted reader over `traced` as the harness sets it: 3 samples
    # at 700-720 and 30 at 900, of which the trace holds none
    assert STEP.read(r) == pytest.approx(want * (3 * 710 + 30 * 900) / 33 / 710)
    # ... and the two agree where `traced` is the device's span
    r["window"]["traced"] = (146.0, 150.0)
    assert ALL.read(r) == pytest.approx(STEP.read(r))


def test_carried_calls_count_with_the_share_of_lanes_that_rode():
    moved = {"decodeStepsTotal": 3400, "decodeLaneStepsTotal": 51000,
             "insertStepsTotal": 280, "insertStepLanesTotal": 3920}
    assert ALL.ride_share(rec([], moved)) == pytest.approx(14.0 / 15.0)
    carried = [call("jit_insert", ns=63_000, name="attn.kernel.8")] * 32
    r = rec(STEPS + carried + NOISE + [call("jit_insert", FLASH, 900_000)],
            moved)
    # the step's calls alone, scaled by bytes and by time
    alone = ALL.read(rec(STEPS))
    want = alone * (320 + 32 * 14 / 15) / 320 \
        * (320 * 70_000) / (320 * 70_000 + 32 * 63_000)
    assert ALL.read(r) == pytest.approx(want)
    # inserts that advanced nobody: their calls cost time and carry no bytes
    idle = dict(moved, insertStepsTotal=0, insertStepLanesTotal=0)
    assert ALL.read(rec(STEPS + carried, idle)) == pytest.approx(
        alone * (320 * 70_000) / (320 * 70_000 + 32 * 63_000))


def test_nothing_to_read():
    assert ALL.read(rec(NOISE)) is None                  # no kernel call
    r = rec(STEPS)
    r["lane_samples"] = r["lane_samples"][:1] + r["lane_samples"][4:]
    assert ALL.read(r) is None                           # none while traced
    r = rec(STEPS)
    r["trace"] = None                                    # an untraced run
    assert ALL.read(r) is None


def test_entry_of_record():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine, old = (by_name["decode_attn_all_roofline"],
                 by_name["decode_attn_roofline"])
    assert bench["per_layer"][-1] is mine
    assert {k: v for k, v in mine.items() if k != "name"} == \
        {k: v for k, v in old.items() if k != "name"}
