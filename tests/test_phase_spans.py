"""The phase-span layer (utils/tracing.py phase / PhaseTable / Tiling), the
scheduler's tiling and raw counters, the profiler hook-up, and the named
scopes (ISSUE 26).  All on the CPU, tiny sizes."""

import glob
import hashlib
import os
import re
import subprocess
import sys
import threading
import time

import pytest

from paddle_operator_tpu.utils import tracing as TR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def table():
    """A table of this test's own, bound to the calling thread."""
    t = TR.PhaseTable()
    TR.use_table(t)
    yield t
    TR.use_table(None)


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------


class TestPhaseTable:
    def test_nesting_self_against_inclusive(self, table):
        with TR.phase("outer") as outer:
            time.sleep(0.02)
            with TR.phase("inner", n=1) as inner:
                time.sleep(0.03)
            with TR.phase("inner"):
                time.sleep(0.01)
        incl, own = table.seconds(), table.self_seconds()
        assert table.counts() == {"inner": 2, "outer": 1}
        assert incl["outer"] == pytest.approx(outer.t1 - outer.t0)
        assert incl["inner"] >= 0.04 and inner.t1 - inner.t0 >= 0.03
        # self = inclusive less what the children cover; leaves keep all
        assert own["inner"] == pytest.approx(incl["inner"])
        assert own["outer"] == pytest.approx(incl["outer"] - incl["inner"])
        assert 0.02 <= own["outer"] < incl["outer"]
        # self seconds add up to the time spent inside any phase
        assert sum(own.values()) == pytest.approx(incl["outer"])

    def test_phase_closes_on_an_exception(self, table):
        with pytest.raises(ValueError):
            with TR.phase("outer"):
                with TR.phase("inner"):
                    raise ValueError("x")
        assert table.counts() == {"inner": 1, "outer": 1}
        with TR.phase("after"):         # the stack is clean again
            pass
        assert table.self_seconds()["after"] == \
            pytest.approx(table.seconds()["after"])

    def test_two_threads_keep_their_own_stacks(self, table):
        other = TR.PhaseTable()
        inside = threading.Event()
        release = threading.Event()

        def worker():
            TR.use_table(other)
            with TR.phase("w.outer"):
                with TR.phase("w.inner"):
                    inside.set()
                    release.wait(5)

        t = threading.Thread(target=worker)
        with TR.phase("main.outer"):
            t.start()
            assert inside.wait(5)
            # the worker's open phases are not this thread's children
            with TR.phase("main.inner"):
                time.sleep(0.01)
            release.set()
            t.join(5)
        assert table.counts() == {"main.inner": 1, "main.outer": 1}
        assert other.counts() == {"w.inner": 1, "w.outer": 1}
        own = table.self_seconds()
        assert own["main.outer"] == pytest.approx(
            table.seconds()["main.outer"] - own["main.inner"])

    def test_unbound_thread_records_into_the_process_table(self):
        name = f"test.unbound.{os.getpid()}.{time.monotonic_ns()}"
        done = []

        def worker():
            with TR.phase(name):
                pass
            done.append(1)

        t = threading.Thread(target=worker)
        t.start()
        t.join(5)
        assert done and TR.PHASES.counts()[name] == 1

    def test_tiling_lays_phases_end_to_end(self, table):
        tile = TR.Tiling()
        a = tile.to("a")
        time.sleep(0.01)
        b = tile.to("b", k=1)
        with TR.phase("b.child"):
            time.sleep(0.01)
        c = tile.to("a")
        tile.close()
        assert a.t1 == b.t0 and b.t1 == c.t0     # one clock reading
        assert table.counts() == {"a": 2, "b": 1, "b.child": 1}
        assert sum(table.self_seconds().values()) == \
            pytest.approx(c.t1 - a.t0)
        tile.close()                             # idempotent

    def test_annotator_sees_every_phase_with_its_attrs(self, table):
        seen = []

        class Ann:
            def __init__(self, name, **attrs):
                self.rec = (name, attrs)

            def __enter__(self):
                seen.append(("enter",) + self.rec)

            def __exit__(self, *exc):
                seen.append(("exit",) + self.rec)

        TR.set_annotator(Ann)
        try:
            with TR.phase("x", bucket=256):
                with TR.phase("y"):
                    pass
        finally:
            TR.set_annotator(None)
        with TR.phase("z"):                      # hook out again
            pass
        assert seen == [("enter", "x", {"bucket": 256}),
                        ("enter", "y", {}), ("exit", "y", {}),
                        ("exit", "x", {"bucket": 256})]


def test_tracing_and_router_import_without_jax():
    """The router and the controller import utils.tracing: with no
    annotator installed nothing of it may pull jax in."""
    code = ("import sys\n"
            "import paddle_operator_tpu.utils.tracing as TR\n"
            "import paddle_operator_tpu.router.router\n"
            "with TR.phase('a'):\n"
            "    pass\n"
            "assert TR.PHASES.counts() == {'a': 1}\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


# ---------------------------------------------------------------------------
# the ring: tiling, counters, the profiler
# ---------------------------------------------------------------------------

BLOCK, CHUNK, BUCKETS = 8, 4, (16, 64)


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models.llama import make_model

    model, cfg = make_model("tiny-f32")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return params, cfg


def _ring(tiny, **kw):
    from paddle_operator_tpu.infer.scheduler import ContinuousBatcher

    params, cfg = tiny
    return ContinuousBatcher(params, cfg, slots=2, max_len=64,
                             chunk_tokens=CHUNK, prefill_buckets=BUCKETS,
                             paged=True, block_size=BLOCK, **kw)


def _prompt(n, base=1):
    return [(base + 7 * i) % 250 + 1 for i in range(n)]


def _delta(a, b):
    return {k: b.get(k, 0) - a.get(k, 0) for k in b}


class TestRingPhases:
    def test_self_seconds_tile_the_loops_wall_time(self, tiny):
        b = _ring(tiny)
        try:
            b.submit(_prompt(5), max_new_tokens=6).result(timeout=300)
            st0, t0 = b.serving_status(), time.monotonic()
            reqs = [b.submit(_prompt(n, base=n), max_new_tokens=9)
                    for n in (5, 20, 33)]
            for r in reqs:
                r.result(timeout=300)
            time.sleep(1.0)
            st1, t1 = b.serving_status(), time.monotonic()
        finally:
            b.close()
        wall = t1 - t0
        spent = _delta(st0["phaseSeconds"], st1["phaseSeconds"])
        # a phase still open at a snapshot is at most one 0.1 s idle wait
        assert 0.95 * wall - 0.1 <= sum(spent.values()) <= 1.05 * wall + 0.1
        counts = _delta(st0["phaseCounts"], st1["phaseCounts"])
        assert counts["sched.admit"] == counts["pool.admit"] == \
            counts["exec.insert"] == 3
        n = st1["dispatchesTotal"] - st0["dispatchesTotal"]
        assert n > 0
        # a phase counts when it ends: one may be open at either snapshot
        for name in ("sched.plan", "exec.dispatch"):
            assert abs(counts[name] - n) <= 1, (name, counts[name], n)
        # an insert that carried a step for a live lane queues a one-step
        # result of its own, consumed like a dispatch's (ISSUE 33)
        # (the first of the three finds the ring idle: no lane rides, so
        # its program's step counts for nothing)
        rode = st1["insertStepsTotal"] - st0["insertStepsTotal"]
        assert rode <= 2
        for name in ("sched.consume_wait", "sched.consume"):
            assert n - 1 <= counts[name] <= n + rode + 1, (
                name, counts[name], n)
        assert spent["sched.idle.no_work"] >= 0.7        # the sleep
        # the same names go out as Prometheus series
        from paddle_operator_tpu.utils.observability import serving_gauges

        g = serving_gauges(st1, "ns/j")
        assert g['tpujob_serve_phase_seconds_total'
                 '{job="ns/j",phase="exec.dispatch"}'] == \
            st1["phaseSeconds"]["exec.dispatch"]
        assert g['tpujob_serve_dispatches_total{job="ns/j"}'] == \
            st1["dispatchesTotal"]

    def test_idle_grows_only_while_nothing_is_resident(self, tiny):
        b = _ring(tiny)
        idle = "sched.idle.no_work"
        try:
            b.submit(_prompt(5), max_new_tokens=6).result(timeout=300)
            a = b.serving_status()
            time.sleep(0.5)
            c = b.serving_status()
            # nothing queued, nothing resident: the ring waits
            assert c["phaseSeconds"][idle] - a["phaseSeconds"][idle] >= 0.3
            req = b.submit(_prompt(9, base=3), max_new_tokens=48,
                           stream=True)
            it = req.stream(timeout=300)
            next(it)
            d = b.serving_status()
            got = 1
            while got < 40:
                next(it)
                got += 1
            e = b.serving_status()
            for _ in it:
                pass
        finally:
            b.close()
        # 39 tokens arrived between d and e, so lanes were decoding: the
        # ring never entered the idle phase
        assert e["phaseCounts"][idle] == d["phaseCounts"][idle]
        assert e["phaseSeconds"][idle] == d["phaseSeconds"][idle]
        assert e["dispatchesTotal"] - d["dispatchesTotal"] >= 5

    def test_prefill_counters_cold_and_prefix_hit(self, tiny):
        b = _ring(tiny)
        try:
            cold = [_prompt(5), _prompt(20, base=9), _prompt(33, base=40)]
            outs = [b.submit(p, max_new_tokens=5).result(timeout=300)
                    for p in cold]
            st_cold = b.serving_status()
            # the 33-token prompt again: 4 whole blocks are cached, a
            # one-token suffix is prefilled through the 8-wide bucket
            again = b.submit(cold[2], max_new_tokens=5).result(timeout=300)
            st = b.serving_status()
            sb = b.executor.suffix_bucket(1)
        finally:
            b.close()
        assert again == outs[2]
        assert st_cold["prefillCallsTotal"] == 3
        assert st_cold["prefillTokensTotal"] == 5 + 20 + 33
        assert st_cold["prefillBucketTokensTotal"] == 16 + 64 + 64
        assert st_cold["prefillCallsByBucket"] == {"16": 1, "64": 2}
        assert st["prefillCallsTotal"] == 4
        assert st["prefillTokensTotal"] == 5 + 20 + 33 + 1
        assert st["prefillBucketTokensTotal"] == 16 + 64 + 64 + sb
        assert st["prefillCallsByBucket"][str(sb)] == 1
        # decode: every dispatch runs CHUNK iterations for the lanes
        # live in its plan; an insert's carried step counts only where a
        # lane rode it, and here the requests come one at a time; tokens
        # beyond each request's first come out of decode iterations
        assert st["insertStepsTotal"] == st["insertStepLanesTotal"] == 0
        assert st["decodeStepsTotal"] == CHUNK * st["dispatchesTotal"]
        decoded = st["tokensTotal"] - 4
        assert st["decodeStepsTotal"] <= st["decodeLaneStepsTotal"] \
            <= 2 * st["decodeStepsTotal"]
        assert st["decodeLaneStepsTotal"] >= decoded == 4 * 4

    def test_chunked_slices_count_their_own_width(self, tiny):
        b = _ring(tiny, prefill_mode="chunked", prefill_chunk=8)
        try:
            b.submit(_prompt(20), max_new_tokens=3).result(timeout=300)
            st = b.serving_status()
        finally:
            b.close()
        assert st["prefillCallsByBucket"] == {"8": 3}
        assert st["prefillTokensTotal"] == 20
        assert st["prefillBucketTokensTotal"] == 24
        assert st["phaseCounts"]["sched.prefill_slice"] == 3

    def test_streams_identical_and_profiler_sees_the_phases(self, tiny,
                                                            tmp_path):
        """The annotator changes nothing that is served; with it in and
        the profiler on (Python tracer off, as the benchmark traces) the
        ring's phases are events of the xplane's host plane."""
        import jax
        from jax.profiler import ProfileData

        prompts = [_prompt(5), _prompt(20, base=9), _prompt(33, base=40)]

        def serve():
            b = _ring(tiny)
            try:
                reqs = [b.submit(p, max_new_tokens=7) for p in prompts]
                return [r.result(timeout=300) for r in reqs]
            finally:
                b.close()

        plain = serve()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        TR.set_annotator(jax.profiler.TraceAnnotation)
        try:
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                annotated = serve()
            finally:
                jax.profiler.stop_trace()
        finally:
            TR.set_annotator(None)
        assert annotated == plain
        paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        assert paths
        names = set()
        for plane in ProfileData.from_file(paths[-1]).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    names.update(ev.name for ev in line.events)
        assert {"sched.admit", "exec.dispatch", "sched.consume_wait",
                "exec.insert", "sched.plan"} <= names


# ---------------------------------------------------------------------------
# named scopes
# ---------------------------------------------------------------------------


def _scopes_in(lowered):
    """Scope names in the op names of a lowered (never a compiled)
    program's debug information; under autodiff a scope reads
    ``jvp(loss)`` and ``transpose(jvp(loss))``."""
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r'[/"(]((?:attn\.)?[a-z_]+)(?=[/")])', text))


class TestNamedScopes:
    def test_decode_step_and_insert(self, tiny):
        import jax.numpy as jnp

        from paddle_operator_tpu.infer import executor as X

        params, cfg = tiny
        cache = X.init_ring_cache(cfg, 2, 32)
        tok = jnp.zeros((2,), jnp.int32)
        temp = jnp.zeros((2,), jnp.float32)
        keys = jnp.zeros((2, 2), jnp.uint32)
        step = X.make_chunk_step(cfg, 2)
        got = _scopes_in(step.lower(params, cache, tok, temp, keys,
                                    jnp.ones((2,), bool)))
        want = {"embed", "norm", "attn.qkv", "attn.rope", "cache_write",
                "attn.kernel", "attn.out", "ffn", "lm_head", "sample"}
        assert want <= got, want - got
        insert = X.make_prefill_insert(cfg, 16, 32)
        got = _scopes_in(insert.lower(
            params, cache, tok, temp, keys, jnp.zeros((1, 16), jnp.int32),
            5, 0, 0.0, 0))
        assert want <= got, want - got

    def test_paged_step(self, tiny):
        import jax.numpy as jnp

        from paddle_operator_tpu.infer import paged as PG

        params, cfg = tiny
        cache = PG.init_paged_cache(cfg, 2, 9, BLOCK)
        step = PG.make_paged_chunk_step(cfg, 2)
        got = _scopes_in(step.lower(
            params, cache, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.float32),
            jnp.zeros((2, 2), jnp.uint32), jnp.ones((2,), bool)))
        want = {"embed", "norm", "attn.qkv", "attn.rope", "cache_write",
                "attn.kernel", "attn.out", "ffn", "lm_head", "sample"}
        assert want <= got, want - got

    def test_train_step(self):
        import jax
        import jax.numpy as jnp

        from paddle_operator_tpu.models.llama import (
            make_model,
            partition_patterns,
        )
        from paddle_operator_tpu.parallel.mesh import make_mesh
        from paddle_operator_tpu.train import trainer as T

        model, cfg = make_model("tiny", dtype=jnp.float32)
        mesh = make_mesh(devices=jax.devices()[:1])
        opt = T.make_optimizer(1e-3, moments="int8")
        example = (jnp.zeros((2, 8), jnp.int32),)
        shardings, _ = T.state_shardings(model, opt, mesh,
                                         partition_patterns(cfg), example)
        state = T.abstract_state(model, opt, mesh,
                                 partition_patterns(cfg), example)
        step = T.make_train_step(model, opt, mesh, shardings)
        got = _scopes_in(step.lower(
            state, {"tokens": jax.ShapeDtypeStruct((2, 9), jnp.int32)}))
        want = {"embed", "norm", "attn", "attn.qkv", "attn.rope",
                "attn.kernel", "attn.out", "ffn", "lm_head", "loss",
                "opt_update"}
        assert want <= got, want - got

    def test_scopes_do_not_change_the_compile_cache_key(self):
        """The trap this PR's scopes sit in, pinned: the persistent
        cache's key hashes the computation with its debug information
        stripped, so a program that differs from a cached one only by
        scopes HITS the cached executable and runs without the names.
        Good for set-up time; it means the names show only in a run
        with a fresh cache (benchmark/tools/scopes.py)."""
        import jax
        import jax.numpy as jnp
        from jax._src import cache_key

        def plain():
            def f(x):
                return jnp.tanh(x @ x) + 1.0
            return f

        def scoped():
            def f(x):
                with jax.named_scope("attn.kernel"):
                    y = x @ x
                with jax.named_scope("ffn"):
                    return jnp.tanh(y) + 1.0
            return f

        x = jnp.ones((8, 8), jnp.float32)

        def computation_hash(fn):
            lowered = jax.jit(fn).lower(x)
            h = hashlib.sha256()
            cache_key._hash_computation(h, lowered.compiler_ir(),
                                        cache_key.IgnoreCallbacks.NO)
            return h.hexdigest(), lowered.as_text(debug_info=True)

        (k0, t0), (k1, t1) = computation_hash(plain()), \
            computation_hash(scoped())
        assert "attn.kernel" in t1 and "attn.kernel" not in t0
        assert k0 == k1
        assert not jax.config.jax_compilation_cache_include_metadata_in_key
