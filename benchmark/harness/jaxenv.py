"""Helpers for the children that import jax (never the runner)."""

from __future__ import annotations

import os

from benchmark.harness.common import ROOT


def enable_cache() -> str:
    """The program's one rule for the persistent compile cache
    (``utils/compile_cache.py``), restated so that the reference can follow
    it without importing the program: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else the fixed ``.jax_cache/`` in the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_report() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def count_compiles() -> dict:
    """Counts JAX's compile requests and persistent-cache hits from here on."""
    import jax

    seen = {"requests": 0, "hits": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            seen["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    return seen


def start_trace(trace_dir: str) -> None:
    """The profiler with the Python tracer off: with it on, stopping a 4 s
    trace of the serving ring took 20 s and stalled the server's threads."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def llama_config(cfg: dict, max_len: int, **extra):
    """The program's ``LlamaConfig`` for a published ``config.json``."""
    import jax.numpy as jnp

    from paddle_operator_tpu.models.llama import LlamaConfig

    dtype = jnp.dtype(cfg["torch_dtype"])
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"], max_seq_len=max_len,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype=dtype, param_dtype=dtype, **extra)


def peak_bytes() -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use") or 0
               for d in jax.devices())
