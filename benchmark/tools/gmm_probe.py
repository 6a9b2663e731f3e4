#!/usr/bin/env python3
"""Builder's tool, run on the chip: the expert layer's grouped product at
the decode step's shape (16 lanes x 8 assignments, ~82 of 128 experts
touched, all four layers' experts stacked) and at an insert's, as
megablox's ``gmm`` under several tilings and as ``jax.lax.ragged_dot`` —
the readings behind ``models/afmoe.py GMM_TILING`` (PERF.md section 6).

    python3 benchmark/tools/gmm_probe.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

L, E, D, F = 4, 128, 2048, 1024


def bench(fn, *args, n=30):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> int:
    print(jax.devices()[0].device_kind, flush=True)
    key = jax.random.PRNGKey(0)
    w1 = (0.02 * jax.random.normal(key, (L * E, D, F), jnp.float32)
          ).astype(jnp.bfloat16)
    w2 = w1.reshape(L * E, F, D)
    for m, label in ((128, "decode"), (2048 * 8, "insert 2048")):
        rng = np.random.default_rng(1)
        idx = np.sort(rng.integers(0, E, m))
        sizes = np.bincount(idx, minlength=E).astype(np.int32)
        touched = int((sizes > 0).sum())
        full = np.zeros(L * E, np.int32)
        full[E:2 * E] = sizes                          # layer 1's groups
        x = jax.random.normal(key, (m, D), jnp.bfloat16)
        h = jax.random.normal(key, (m, F), jnp.bfloat16)
        need_up = touched * D * F * 2 / 819e9
        print(f"{label}: m={m} touched={touched} "
              f"byte floor of one matrix {need_up * 1e6:.0f} us", flush=True)
        for tiling in ((128, 1024, 1024), (128, 2048, 1024), (128, 512, 1024),
                       (128, 1024, 512), (128, 2048, 512), (256, 1024, 1024),
                       (512, 1024, 1024)):
            if tiling[0] > m:
                continue
            try:
                up = jax.jit(lambda x, w, s, t=tiling: gmm(
                    x, w, s, preferred_element_type=jnp.bfloat16, tiling=t))
                down = jax.jit(lambda h, w, s, t=tiling: gmm(
                    h, w, s, preferred_element_type=jnp.float32,
                    tiling=(t[0], min(t[1], F), t[2])))
                print(f"  gmm {tiling}: up {bench(up, x, w1, full) * 1e6:.0f} us"
                      f"  down {bench(down, h, w2, full) * 1e6:.0f} us",
                      flush=True)
            except Exception as e:          # a tiling the compiler refuses
                print(f"  gmm {tiling}: {type(e).__name__}: "
                      f"{str(e)[:120]}", flush=True)
        rd = jax.jit(lambda x, w, s: jax.lax.ragged_dot(
            x, w, s, preferred_element_type=jnp.bfloat16))
        try:
            print(f"  ragged_dot over one layer's [128, D, F] slice: "
                  f"{bench(rd, x, w1[E:2 * E], jnp.asarray(sizes)) * 1e6:.0f}"
                  " us", flush=True)
        except Exception as e:
            print(f"  ragged_dot: {type(e).__name__}: {str(e)[:200]}",
                  flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
