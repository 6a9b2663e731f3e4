"""Parameter-server–style sharded embedding tier.

The reference's PS mode is pure orchestration: it creates PS pods and hands
Paddle the endpoint list (``PADDLE_PSERVERS_IP_PORT_LIST``,
controllers/paddlejob_helper.go:146; process model docs/design-arch.md:5-12)
— the actual parameter server lives in Paddle.  The TPU-native equivalent of
"embedding tables too big for one accelerator, updated sparsely" is a table
**sharded across the mesh** with lookups as collectives over ICI:

- rows are range-sharded over a chosen axis (default the data axes, i.e.
  each data-parallel group stores a distinct vocab range — what the PS tier
  stored on CPU hosts in the reference deployment of Wide&Deep);
- lookup: every device gathers its local hits and ``psum`` completes the
  row (exactly one shard contributes per id);
- gradients flow through the same psum (transpose handled by autodiff), so
  updates land only on the owning shard — sparse-update semantics without a
  server process.

Used by models/wide_deep.py (BASELINE config 1).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def sharded_embedding_lookup(table_local: jax.Array, ids: jax.Array,
                             *, axis_name) -> jax.Array:
    """shard_map body: table_local [V_loc, D] (this shard's row range),
    ids [...] global int ids -> [..., D] rows.

    Out-of-range ids on a shard contribute zeros; psum over the axis
    assembles the full row from the single owning shard.
    """
    idx = jax.lax.axis_index(axis_name)
    v_loc = table_local.shape[0]
    lo = idx * v_loc
    local = ids - lo
    hit = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    rows = jnp.take(table_local, safe, axis=0)
    rows = jnp.where(hit[..., None], rows, 0)
    return jax.lax.psum(rows, axis_name)


def make_ps_embedding(mesh: Mesh, vocab: int, dim: int,
                      *, axis: str = "fsdp",
                      dtype=jnp.float32):
    """Build (init_fn, lookup_fn) for a PS-sharded embedding.

    init_fn(rng) -> sharded [V, D] table (rows over `axis`);
    lookup_fn(table, ids[B]) -> [B, D] via shard_map+psum.
    """
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)
    if vocab % axis_size:
        raise ValueError(f"vocab {vocab} not divisible by {axis}={axis_size}")

    table_sharding = NamedSharding(mesh, P(axis, None))

    def init_fn(rng):
        init = jax.jit(
            lambda r: jax.random.normal(r, (vocab, dim), dtype) * 0.02,
            out_shardings=table_sharding,
        )
        return init(rng)

    lookup = jax.shard_map(
        functools.partial(sharded_embedding_lookup, axis_name=axis),
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )
    return init_fn, lookup
