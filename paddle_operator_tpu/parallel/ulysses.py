"""Ulysses-style sequence parallelism — the all-to-all alternative to ring
attention over the ``cp`` mesh axis.

Two standard ways to distribute long-context attention (the reference has
neither — SURVEY.md §5 "long-context/sequence parallelism: absent"):

- **Ring** (parallel/ring_attention.py): Q stays sequence-sharded, K/V
  chunks rotate cp-1 neighbor hops; O(S/cp · S/cp) score tiles.
- **Ulysses** (this module): one ``all_to_all`` re-shards the activations
  from sequence-sharded [B, S/cp, H, D] to head-sharded [B, S, H/cp, D],
  each device runs ordinary FULL-sequence attention for its head subset
  (reusing ops.attention — the pallas flash kernel on TPU), and a second
  all_to_all re-shards back.  Communication is 2 all-to-alls of the
  activations regardless of sequence length, vs cp-1 K/V rotations for
  ring — cheaper when heads are plentiful and cp is small; ring wins when
  H/cp would drop below 1 or K/V are small (GQA).

Requires n_heads % cp == 0 and n_kv_heads % cp == 0 (heads must split
across the axis); callers fall back to ring otherwise.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from paddle_operator_tpu.ops.attention import attention


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      segment_ids: Optional[jax.Array] = None,
                      *, axis_name: str = "cp",
                      causal: bool = True, mesh=None) -> jax.Array:
    """Per-device body: local [B, S_loc, H, D] shards in, same shape out.
    Must run inside shard_map with `axis_name` bound.  segment_ids
    [B, S_loc] (packed sequences) are all-gathered to the full sequence —
    every device attends full-length for its head subset, so the mask is
    applied by ordinary attention (on `mesh`, see ops.attention)."""
    n = jax.lax.psum(1, axis_name)
    if n == 1:
        return attention(q, k, v, causal=causal, segment_ids=segment_ids,
                         mesh=mesh)
    # seq-sharded -> head-sharded: split heads (axis 2), gather seq (axis 1)
    qh = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    kh = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    seg_full = None
    if segment_ids is not None:
        seg_full = jax.lax.all_gather(segment_ids, axis_name, axis=1,
                                      tiled=True)
    out = attention(qh, kh, vh, causal=causal, segment_ids=seg_full,
                    mesh=mesh)                   # full-seq, H/cp heads
    # head-sharded -> seq-sharded: split seq, gather heads
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def make_ulysses_attention_fn(mesh: Mesh, *, causal: bool = True,
                              axis_name: str = "cp"):
    """shard_map-wrapped Ulysses attention: global [B, S, H, D] arrays with
    the sequence sharded over `axis_name`.  Partial-manual like
    make_ring_attention_fn — only ``cp`` is manual, so batch/head dims keep
    their dp/fsdp/tp shardings and the wrapper nests inside other manual
    regions (the pp pipeline body)."""
    from paddle_operator_tpu.parallel.mesh import resolve_shard_map_mesh

    seq_spec = P(None, axis_name)
    use_mesh, _ = resolve_shard_map_mesh(mesh)

    common = dict(mesh=use_mesh, out_specs=seq_spec,
                  axis_names=frozenset({axis_name}), check_vma=False)
    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal, mesh=mesh),
        in_specs=(seq_spec, seq_spec, seq_spec),
        **common,
    )
    fn_seg = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal, mesh=mesh),
        in_specs=(seq_spec, seq_spec, seq_spec, seq_spec),
        **common,
    )

    def call(q, k, v, segment_ids=None):
        if segment_ids is None:
            return fn(q, k, v)
        return fn_seg(q, k, v, segment_ids)

    return call
