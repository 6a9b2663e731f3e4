#!/usr/bin/env python3
"""Builder's tool: compile the ``serve_glm_moe_lite`` cell's decode step
and its inserts for a DESCRIBED v5e, with no chip attached, and print
``memory_analysis()`` (sizes, never a time) — ``afmoe_aot.py`` for the
latent pool.

    JAX_PLATFORMS=cpu python3 benchmark/tools/glm_moe_lite_aot.py \
        benchmark/configs/glm-4.7-flash-serve.json [bucket ...]
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import glm_moe_lite as H
    from paddle_operator_tpu.infer import afmoe_serve as AF
    from paddle_operator_tpu.infer import paged as PG
    from paddle_operator_tpu.models import glm_moe_lite as M

    cfgj = json.load(open(sys.argv[1]))
    buckets = [int(b) for b in sys.argv[2:]] or [cfgj["serve"]["max_len"]]
    s = cfgj["serve"]
    # the program asks the backend which branch to take: here it is the
    # described chip's
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = dataclasses.replace(H.config(cfgj, s["max_len"]),
                              decode_attn="pallas")
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = on_chip(M.param_shapes(cfg))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print("parameters", n_params, "bytes",
          sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)))
    pool = PG.PagedCacheManager(s["lanes"], s["max_len"], s["block"], None,
                                prefix_cache=False)
    cache = on_chip(jax.eval_shape(lambda: PG.init_paged_cache(
        cfg, s["lanes"], pool.total, s["block"])))
    print("pool", {k: v.shape for k, v in cache.items()}, "cacheRowBytes",
          PG.cache_row_bytes(cache))
    lanes = s["lanes"]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    tok, temp = i32(lanes), jax.ShapeDtypeStruct((lanes,), jnp.float32,
                                                 sharding=one)
    keys = jax.ShapeDtypeStruct((lanes, 2), jnp.uint32, sharding=one)
    active = jax.ShapeDtypeStruct((lanes,), jnp.bool_, sharding=one)
    step = AF.make_paged_chunk_step(cfg, s["chunk"])
    compiled = step.lower(params, cache, i32(lanes, pool.max_blocks), tok,
                          temp, keys, active).compile()
    print("jit_step", compiled.memory_analysis(), flush=True)
    text = compiled.as_text()
    print("  custom calls:", text.count("tpu_custom_call"),
          "ragged-dot:", text.count("ragged-dot"),
          "copies of a pool-sized operand:",
          sum(1 for l in text.splitlines()
              if " copy(" in l and f"[{cfg.n_layers},{pool.total}," in l),
          flush=True)
    if os.environ.get("AOT_DUMP"):
        open(os.environ["AOT_DUMP"], "w").write(text)
    for b in buckets:
        insert = AF.make_paged_prefill_insert(cfg, b, s["block"])
        compiled = insert.lower(params, cache, i32(pool.max_blocks), tok, temp,
                                keys, i32(1, b), 1, 0, 0.0, 0).compile()
        print(f"jit_insert[{b}]", AF.prefill_attn_impl(cfg, b),
              compiled.memory_analysis(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
