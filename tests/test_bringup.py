"""Bring-up invariants (PR 21): what had to hold before the program could
run on the chip through its normal entry points, pinned on the CPU.

- a server start restores a TRAINING checkpoint without ever materialising
  optimizer state, whatever moment layout the job trained with;
- a restart restore never holds two copies of the state;
- ``chip_smoke.py``'s phases run end to end at ``tiny`` size (sizes handed
  to the phase functions here, not through an option of the script), and
  the script as a whole fails without ``"ok": true`` when it finds no TPU;
- the compile cache follows one rule; the native library builds on demand,
  atomically; an unknown device has no peak.
"""

import ctypes
import gc
import os
import subprocess
import sys
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_operator_tpu.api.types import MeshSpec
from paddle_operator_tpu.models.llama import make_model, partition_patterns
from paddle_operator_tpu.parallel.mesh import make_mesh
from paddle_operator_tpu.train import trainer as T
from paddle_operator_tpu.train.checkpoint import (
    CheckpointManager,
    resume_or_init,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def trained_checkpoint(path, moments):
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    model, cfg = make_model("tiny")
    opt = T.make_optimizer(1e-3, warmup_steps=1, decay_steps=10,
                           moments=moments)
    args = (model, opt, mesh, partition_patterns(cfg),
            (jnp.zeros((8, 16), jnp.int32),))
    shardings, _ = T.state_shardings(*args)
    state = T.create_state(*args)
    step = T.make_train_step(model, opt, mesh, shardings)
    state, _ = step(state, T.synthetic_batch(8, 17, cfg.vocab_size))
    ckpt = CheckpointManager(path, save_interval_steps=1)
    ckpt.save(1, state, force=True)
    ckpt.close()
    want = jax.tree.map(lambda x: np.asarray(x.astype(cfg.dtype), np.float32),
                        state.params)
    return cfg, args, want


@pytest.mark.parametrize("moments", ["f32", "int8"])
def test_server_start_restores_params_only(tmp_path, moments):
    """serve.py / prefill_serve.py start-up: a checkpoint trained with
    either moment layout restores (the server's old f32-moment template
    could not read an int8-moment tree), in the served dtype, and the only
    buffers that appear are the params."""
    from paddle_operator_tpu.infer.serve import load_serving_params

    path = str(tmp_path / "ckpt")
    cfg, _, want = trained_checkpoint(path, moments)

    def buffers():
        # distinct device buffers (a shard view shares its array's)
        gc.collect()
        return {x.unsafe_buffer_pointer() for a in jax.live_arrays()
                for x in (s.data for s in a.addressable_shards)}

    before = buffers()
    params, resumed = load_serving_params(cfg, CheckpointManager(path))
    assert resumed
    leaves = jax.tree.leaves(params)
    assert len(buffers() - before) == len(leaves)
    assert {x.dtype for x in leaves} == {jnp.dtype(cfg.dtype)}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), b), params, want)


def test_server_smoke_init_matches_cast_of_training_init():
    """No checkpoint: the server initialises what it serves, in the served
    dtype — the values of a training init cast afterwards (to one rounding
    of that dtype: the cast is fused into the init under jit)."""
    from paddle_operator_tpu.infer.quant import serving_params
    from paddle_operator_tpu.infer.serve import load_serving_params

    model, cfg = make_model("tiny")
    params, resumed = load_serving_params(cfg, None, seed=3)
    assert not resumed
    ref = serving_params(model.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"],
        cfg.dtype)
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype(cfg.dtype)}
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=2.0 ** -7), params, ref)


def test_restore_template_is_abstract(tmp_path):
    """resume_or_init without a `state_like` drops the fresh state before
    it restores; trainer.abstract_state never builds one."""
    path = str(tmp_path / "ckpt")
    _, args, _ = trained_checkpoint(path, "int8")
    like = T.abstract_state(*args)
    assert all(isinstance(x, jax.ShapeDtypeStruct)
               for x in jax.tree.leaves(like))

    ckpt = CheckpointManager(path)
    fresh = []

    def init():
        state = T.create_state(*args)
        fresh.append(weakref.ref(jax.tree.leaves(state.params)[0]))
        return state

    restore = ckpt.restore

    def restore_checked(state_like, step=None):
        gc.collect()
        assert fresh and fresh[0]() is None, "fresh state still alive"
        assert all(isinstance(x, jax.ShapeDtypeStruct)
                   for x in jax.tree.leaves(state_like))
        return restore(state_like, step=step)

    ckpt.restore = restore_checked
    state, resumed = resume_or_init(ckpt, init)
    assert resumed and int(state.step) == 1
    # and the abstract template restores the same state with no init at all
    state2, _ = resume_or_init(CheckpointManager(path), None, like)
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, state.params),
                 jax.tree.map(np.asarray, state2.params))


# ---------------------------------------------------------------------------
# chip_smoke.py rehearsal (CPU, tiny)
# ---------------------------------------------------------------------------

TINY_TRAIN = dict(preset="tiny", n_layers=2, batch=8, seq=64,
                  param_dtype="float32", moments="int8", checkpoint=True,
                  offload_layers=2, platform="cpu", flash=False)
# tiny-f32: XLA:CPU cannot run the bf16 presets through the decode scan
TINY_SERVE = dict(preset="tiny-f32", vocab=256, slots=2, block=8, max_len=64,
                  chunk=4, prompt_lens=(12, 20, 33), new_tokens=5,
                  platform="cpu", decode_attn="xla",
                  kernel="pallas-interpret")


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    """chip_smoke with its scratch in tmp_path and its children on ONE
    virtual CPU device (conftest gives this process eight)."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    yield chip_smoke
    chip_smoke.stop_all()


def test_smoke_train_phase(smoke):
    r = smoke.phase_train(TINY_TRAIN, mesh={})
    assert r["restore"]["resumed"] and r["restore"]["step"] == 5
    assert r["offload"]["opt_state_memory_kinds"] == ["pinned_host"]
    assert r["model"]["layers"] == 2 and len(r["loss"]) == 5


def test_smoke_serve_phases(smoke):
    served = {
        "serve": smoke.phase_serve(TINY_SERVE),
        "serve-int8": smoke.phase_serve(TINY_SERVE, name="serve-int8",
                                        kv_quant="int8", probe=1,
                                        traffic=False),
    }
    assert len(served["serve"]["answer"]) == TINY_SERVE["new_tokens"]
    r = smoke.phase_reference(TINY_SERVE, served)
    assert set(r["token_gaps"]) == {"serve", "serve-int8"}
    # in f32 the prefix-cache admission answers exactly as the cold one
    assert r["resubmit"] == {"serve": None}
    # a resubmission that parted from the first answer by more than a
    # near-tie is caught: make one up, off by one token id at position 2
    re = served["serve"]["resubmit"]
    re["again"] = re["first"][:2] + [(re["first"][2] + 1) % 256] + \
        re["first"][3:]
    with pytest.raises(smoke.PhaseFailed, match="near-tie"):
        smoke.phase_reference(TINY_SERVE, {"serve": served["serve"]})


def test_smoke_phase_fails_on_the_wrong_platform(smoke):
    with pytest.raises(smoke.PhaseFailed, match="exit code 3"):
        smoke.phase_train(dict(TINY_TRAIN, platform="tpu"), mesh={})


def test_smoke_script_fails_without_a_tpu():
    """As the driver runs it, in a sandbox with no accelerator."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "platform" in proc.stderr and "cpu" in proc.stderr


# ---------------------------------------------------------------------------
# one cache rule, one installation, built from git
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", [None, "somewhere/else"])
def test_compile_cache_rule(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR set: used, and nothing set in code; unset:
    the fixed directory inside the checkout."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = subprocess.run(
        [sys.executable, "-c",
         "from paddle_operator_tpu.utils.compile_cache import "
         "enable_compile_cache as e; import jax; "
         "print(e()); print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    want = str(tmp_path / env_dir) if env_dir \
        else os.path.join(ROOT, ".jax_cache")
    assert out.stdout.split() == [want, want]


def test_native_library_builds_atomically(tmp_path):
    """Six builders at once (the xdist workers of a fresh checkout): each
    sees a whole library or none, and what is left loads."""
    from paddle_operator_tpu.controller.hostport import _build_native_lib

    out = str(tmp_path / "build" / "libtpujob_native.so")
    results = []

    def build():
        ok = _build_native_lib(os.path.join(ROOT, "native"), out)
        if ok:
            ctypes.CDLL(out).hp_new       # a torn file would not load
        results.append(ok)

    threads = [threading.Thread(target=build) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert results == [True] * 6
    assert os.listdir(os.path.dirname(out)) == ["libtpujob_native.so"]


def test_unknown_device_has_no_peak():
    import bench

    class Dev:
        device_kind = "TPU v5 lite"

    assert bench.peak_flops_for(Dev()) == 197e12
    Dev.device_kind = "cpu"
    with pytest.raises(ValueError, match="no peak"):
        bench.peak_flops_for(Dev())


def test_dryrun_parent_decides_from_the_environment(monkeypatch):
    """__graft_entry__.dryrun_multichip re-execs onto virtual CPU devices
    unless the process is already held to them — decided without asking
    jax, which would take the chip."""
    import __graft_entry__ as g

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    assert g._cpu_devices_configured(8)
    assert not g._cpu_devices_configured(16)
    monkeypatch.delenv("JAX_PLATFORMS")
    assert not g._cpu_devices_configured(8)
