"""The benchmark's readers of the ring's raw counters and phase table
(``benchmark/metrics/{prefill_pad_pct,decode_lanes_live_pct,ring_idle_pct,
sched_host_ms_per_dispatch}.py``, ISSUE 26) on hand-made records: the value
between two ``/statusz`` scrapes, and nothing (never a raise, never a 0 made
up) where a counter is absent, as on a parent without it, or did not move."""

import copy
import json
import os

import pytest

from benchmark import run as R
from benchmark.metrics import (
    decode_lanes_live_pct,
    prefill_pad_pct,
    ring_idle_pct,
    sched_host_ms_per_dispatch,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OPEN = {
    "tokensTotal": 1000, "dispatchesTotal": 100, "decodeStepsTotal": 800,
    "decodeLaneStepsTotal": 12000, "prefillCallsTotal": 10,
    "prefillTokensTotal": 6000, "prefillBucketTokensTotal": 18000,
    "prefillCallsByBucket": {"256": 4, "512": 2, "4096": 4},
    "phaseSeconds": {
        "sched.housekeeping": 0.10, "sched.admit": 0.05, "pool.admit": 0.01,
        "exec.insert": 0.04, "sched.plan": 0.02, "exec.dispatch": 0.10,
        "sched.consume_wait": 9.00, "sched.consume": 0.08,
        "sched.idle.no_work": 5.00, "sched.idle.prefill_pending": 0.10},
    "phaseCounts": {"exec.dispatch": 100},
}
CLOSE = {
    "tokensTotal": 30000, "dispatchesTotal": 600, "decodeStepsTotal": 4800,
    "decodeLaneStepsTotal": 12000 + 4000 * 14,      # 14 of 16 lanes live
    "prefillCallsTotal": 210, "prefillTokensTotal": 6000 + 120000,
    "prefillBucketTokensTotal": 18000 + 360000,     # two thirds padding
    "prefillCallsByBucket": {"256": 80, "512": 50, "4096": 80},
    "phaseSeconds": {
        "sched.housekeeping": 0.60, "sched.admit": 0.55, "pool.admit": 0.11,
        "exec.insert": 0.44, "sched.plan": 0.22, "exec.dispatch": 0.60,
        "sched.consume_wait": 53.00, "sched.consume": 0.58,
        "sched.idle.no_work": 7.50, "sched.idle.prefill_pending": 0.10},
    "phaseCounts": {"exec.dispatch": 600},
}


def rec(a=OPEN, b=CLOSE):
    return {
        "cell": {"config": {"serve": {"lanes": 16}}},
        "window": {"t_open": 100.0, "t_close": 151.0},
        "metrics_open": {} if a is None else {"statusz": copy.deepcopy(a)},
        "metrics_close": {} if b is None else {"statusz": copy.deepcopy(b)},
    }


def without(d, *keys):
    return {k: v for k, v in d.items() if k not in keys}


class TestValues:
    def test_prefill_pad_pct(self):
        assert prefill_pad_pct.read(rec(), "closed") == \
            pytest.approx(100.0 * (1 - 120000 / 360000))

    def test_decode_lanes_live_pct(self):
        assert decode_lanes_live_pct.read(rec(), "open") == \
            pytest.approx(100.0 * 14 / 16)

    def test_ring_idle_pct_over_the_threads_own_seconds(self):
        # 2.5 s of waiting for work out of the 49.2 s of self seconds the
        # thread's phases gained between the scrapes: its own clock, so a
        # scrape that ran late cannot push the share over 100
        a, b = OPEN["phaseSeconds"], CLOSE["phaseSeconds"]
        gained = sum(b.values()) - sum(a.values())
        assert gained == pytest.approx(49.2)
        assert ring_idle_pct.read(rec(), "open") == \
            pytest.approx(100.0 * 2.5 / gained)

    def test_ring_that_never_waited_reads_zero(self):
        b = copy.deepcopy(CLOSE)
        b["phaseSeconds"]["sched.idle.no_work"] = 5.00
        assert ring_idle_pct.read(rec(b=b), "closed") == 0.0

    def test_sched_host_ms_per_dispatch_leaves_the_waits_out(self):
        a, b = OPEN["phaseSeconds"], CLOSE["phaseSeconds"]
        host = sum(b[k] - a[k] for k in b
                   if not k.startswith("sched.idle.")
                   and k != "sched.consume_wait")
        assert host == pytest.approx(2.7)
        assert sched_host_ms_per_dispatch.read(rec(), "closed") == \
            pytest.approx(1e3 * 2.7 / 500)


READERS = [prefill_pad_pct, decode_lanes_live_pct, ring_idle_pct,
           sched_host_ms_per_dispatch]
NEW_KEYS = ("dispatchesTotal", "decodeStepsTotal", "decodeLaneStepsTotal",
            "prefillCallsTotal", "prefillTokensTotal",
            "prefillBucketTokensTotal", "prefillCallsByBucket",
            "phaseSeconds", "phaseCounts")


@pytest.mark.parametrize("reader", READERS,
                         ids=[r.__name__.rsplit(".", 1)[-1] for r in READERS])
class TestNothingToRead:
    def test_no_statusz_at_an_edge(self, reader):
        assert reader.read(rec(a=None), "closed") is None
        assert reader.read(rec(b=None), "closed") is None

    def test_a_parent_without_the_counters(self, reader):
        old = without(OPEN, *NEW_KEYS), without(CLOSE, *NEW_KEYS)
        assert reader.read(rec(*old), "open") is None

    def test_a_counter_that_did_not_move(self, reader):
        assert reader.read(rec(b=OPEN), "closed") is None


def test_entries_of_record_name_layer_cells_and_readers():
    """Each of the eight entries has its reader, reads a program counter,
    names a layer the benchmark already has and the end-to-end metric of its
    own cell; the traced line of a serving cell carries them through
    ``run.read_metrics``."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    mine = [m for m in bench["per_layer"]
            if m["name"].split(".")[0] in {r.__name__.rsplit(".", 1)[-1]
                                           for r in READERS}]
    assert len(mine) == 8
    layers = {m["layer"] for m in bench["per_layer"] if m not in mine}
    for m in mine:
        variant = m["name"].split(".")[1]
        cell = {"closed": "serve16l.closed16",
                "open": "serve16l.open-bursty"}[variant]
        # the cell of record first; cells later PRs appended behind it
        # (ISSUE 28's two closed cells) report the same end-to-end metric
        assert m["workloads"][0] == cell
        assert variant == "closed" or m["workloads"] == [cell]
        assert m["source"] == "program_counter" and m["layer"] in layers
        assert all(c in e2e[m["moves"]]["workloads"] for c in m["workloads"])
    line = R.read_metrics(rec(), [m for m in mine
                                  if m["name"].endswith(".closed")])
    assert sorted(line) == ["decode_lanes_live_pct.closed",
                            "prefill_pad_pct.closed",
                            "ring_idle_pct.closed",
                            "sched_host_ms_per_dispatch.closed"]
    assert R.read_metrics(rec(a=None), mine) == {}


# ---------------------------------------------------------------------------
# benchmark/tools/scopes.py: the pure half, on a hand-made trace
# ---------------------------------------------------------------------------


class TestScopesTool:
    @pytest.mark.parametrize("op_name,scope,which", [
        ("jit(step)/while/body/attn.qkv/dot_general", "attn.qkv", "forward"),
        ("jit(step)/while/body/closed_call/attn.kernel/pallas_call",
         "attn.kernel", "forward"),
        ("jit(step_fn)/jvp(Llama)/layers/attn/attn.rope/mul", "attn.rope",
         "forward"),
        ("jit(step_fn)/transpose(jvp(Llama))/layers/ffn/mlp/w1/dot_general",
         "ffn", "backward"),
        ("jit(step_fn)/transpose(jvp(Llama))/layers/rematted_computation/"
         "norm/rsqrt", "norm", "recompute"),
        ("jit(step_fn)/jvp(loss)/reduce_sum", "loss", "forward"),
        ("jit(step_fn)/opt_update/mul", "opt_update", "forward"),
        ("jit(step)/while/body/add", "_unscoped_", "forward"),
        # a scope's name inside a longer word is not the scope
        ("jit(step)/normalize/formatting", "_unscoped_", "forward"),
        ("", "_unscoped_", "forward"),
    ])
    def test_scope_and_pass_of_an_op_name(self, op_name, scope, which):
        from benchmark.tools import scopes

        assert scopes.scope_of(op_name) == scope
        assert scopes.pass_of(op_name) == which

    @staticmethod
    def trace(with_stats):
        def ev(text, start, dur, op_name):
            return (text, float(start), float(dur),
                    {"hlo_op": text.split(" ")[0], "tf_op": op_name}
                    if with_stats else {"hlo_op": text.split(" ")[0]})

        loop = "%while.1 = (s32[], bf16[16,4096]) while(%t)"
        events = [
            ev(loop, 100, 800, "jit(step)/while"),
            ev("%fusion.191 = bf16[16,14336]{1,0} fusion(%a)", 100, 300,
               "jit(step)/while/body/ffn/dot_general"),
            ev("%closed_call.12 = bf16[16,32,128]{2,1,0} custom-call(%q)",
               400, 200, "jit(step)/while/body/attn.kernel/pallas_call"),
            ev("%bitcast_add_fusion.5 = bf16[16,1,4096]{2,1,0} fusion(%b)",
               600, 300, "jit(step)/while/body/attn.out/add"),
            ev("%fusion.140 = f32[4096,8,4]{2,1,0} fusion(%c)", 1000, 500,
               "jit(insert)/while/body/attn.kernel/reduce_max"),
        ]
        modules = [("jit_step(11)", 100.0, 900.0),
                   ("jit_insert(22)", 1000.0, 1500.0)]
        return events, modules

    def test_by_scope_reads_op_name_from_the_stat_that_carries_it(self):
        from benchmark.tools import scopes

        out = scopes.by_scope(*self.trace(True), {}, top=5)
        assert out["op_name_stat"] == "tf_op"
        assert out["without_op_name"] == 0
        step = out["programs"]["jit_step(11)"]
        assert step["calls"] == 1 and step["seconds"] == pytest.approx(8e-7)
        # the loop encloses its operations and has no time of its own
        assert step["scopes"] == {"ffn": pytest.approx(3e-7),
                                  "attn.kernel": pytest.approx(2e-7),
                                  "attn.out": pytest.approx(3e-7)}
        assert step["ops"][0][0] == "fusion.191 bf16[16,14336]"
        assert [r[3] for r in step["ops"]] == ["ffn", "attn.out",
                                               "attn.kernel"]
        insert = out["programs"]["jit_insert(22)"]
        assert insert["scopes"] == {"attn.kernel": pytest.approx(5e-7)}

    def test_by_scope_falls_back_to_the_dumped_hlo_text(self, tmp_path):
        from benchmark.tools import scopes

        (tmp_path / "module_0003.jit_step.cl_1.after_optimizations.txt"
         ).write_text(
            'HloModule jit_step\n'
            '  %fusion.191 = bf16[16,14336]{1,0} fusion(%a), kind=kOutput, '
            'metadata={op_name="jit(step)/while/body/ffn/dot_general" '
            'source_file="x.py"}\n'
            '  ROOT %bitcast_add_fusion.5 = bf16[16,1,4096]{2,1,0} '
            'fusion(%b), metadata={op_name="jit(step)/while/body/attn.out/'
            'add"}\n')
        (tmp_path / "module_0003.jit_step.cl_1.before_optimizations.txt"
         ).write_text('%fusion.191 = x, metadata={op_name="wrong"}\n')
        # a second program of the same name numbers its instructions alike
        (tmp_path / "module_0004.jit_step.cl_2.after_optimizations.txt"
         ).write_text(
            '  %fusion.191 = bf16[16,512]{1,0} fusion(%a), kind=kOutput, '
            'metadata={op_name="jit(step)/while/body/attn.qkv/dot_general"}\n')
        names = scopes.hlo_op_names(str(tmp_path))
        assert names == {"jit_step": {
            ("fusion.191", "bf16[16,14336]"):
                "jit(step)/while/body/ffn/dot_general",
            ("bitcast_add_fusion.5", "bf16[16,1,4096]"):
                "jit(step)/while/body/attn.out/add",
            ("fusion.191", "bf16[16,512]"):
                "jit(step)/while/body/attn.qkv/dot_general"}}
        out = scopes.by_scope(*self.trace(False), names, top=5)
        assert out["op_name_stat"].startswith("none")
        step = out["programs"]["jit_step(11)"]["scopes"]
        assert step["ffn"] == pytest.approx(3e-7)
        assert step["attn.out"] == pytest.approx(3e-7)
        assert step["_unscoped_"] == pytest.approx(2e-7)    # closed_call.12
        assert out["without_op_name"] == 2
