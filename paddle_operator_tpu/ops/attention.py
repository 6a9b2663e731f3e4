"""Attention ops.

The single entry point :func:`attention` picks the implementation from the
backend and the shapes, before the call:

- TPU, shapes the kernel tiles: the pallas flash-attention kernel
  (ops/pallas_attention.py) — tiled online-softmax, O(S) memory,
  MXU-shaped blocks.
- elsewhere (CPU tests, dryrun, shapes that do not tile): a reference XLA
  implementation with f32 softmax accumulation.

Shapes follow the [batch, seq, heads, head_dim] convention throughout the
framework.  GQA is handled here (kv heads repeated to query heads) so model
code stays shape-oblivious.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] (GQA broadcast)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.repeat(x, n_rep, axis=2)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool = True,
                        segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """XLA reference implementation.  [B, S, H, D] x3 -> [B, S, H, D]."""
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5

    # [B, H, Sq, Sk] scores in f32 for numerical stability
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        scores = jnp.where(seg_mask[:, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_axes(mesh, n_heads: int, n_kv_heads: int):
    """``(mesh_or_None, manual_axes, batch_axes, head_axis)`` for running
    the flash kernel on `mesh`, or None where the heads cannot split over
    ``tp`` in whole GQA groups.  Batch rides (dp, fsdp) and heads ride tp
    — the shardings q/k/v already carry out of the projections.  The
    region is manual over EVERY axis not already manual around it (inside
    the pipeline's pp region the ambient mesh is inherited): with any
    axis left to GSPMD, even one of size 1, XLA refuses the kernel."""
    from paddle_operator_tpu.parallel.mesh import (
        DATA_AXES,
        resolve_shard_map_mesh,
    )

    use_mesh, sizes = resolve_shard_map_mesh(mesh)
    tp = sizes.get("tp", 1)
    if n_heads % tp or n_kv_heads % tp:
        return None
    manual = frozenset(sizes)
    if use_mesh is None:        # nested: the ambient mesh says what is taken
        manual -= set(jax.sharding.get_abstract_mesh().manual_axes)
    batch_axes = tuple(a for a in DATA_AXES if sizes.get(a, 1) > 1)
    return use_mesh, manual, batch_axes or None, "tp" if tp > 1 else None


def _block_kw(blocks) -> dict:
    """``blocks`` = ``(block_q, block_k)`` as the kernel's keywords; None
    leaves the kernel its defaults (the trainer's)."""
    return {} if blocks is None else dict(block_q=blocks[0],
                                          block_k=blocks[1])


def sharded_flash_attention(mesh, q: jax.Array, k: jax.Array, v: jax.Array,
                            *, causal: bool = True,
                            segment_ids: Optional[jax.Array] = None,
                            interpret: bool = False,
                            blocks=None) -> jax.Array:
    """The flash kernel on a multi-device mesh.  A Mosaic kernel cannot be
    partitioned by GSPMD ("wrap the call in a shard_map"), and attention
    needs no cross-shard term over batch or heads: each shard runs the
    kernel on its own batch rows and whole GQA groups."""
    from paddle_operator_tpu.ops.pallas_attention import flash_attention

    axes = _flash_axes(mesh, q.shape[2], k.shape[2])
    if axes is None:
        raise NotImplementedError(
            f"flash attention cannot split heads {q.shape[2]}/{k.shape[2]} "
            "over tp in whole GQA groups")
    use_mesh, manual, batch_axes, head_axis = axes
    spec = P(batch_axes, None, head_axis, None)
    args, in_specs = (q, k, v), (spec, spec, spec)
    if segment_ids is not None:
        args, in_specs = args + (segment_ids,), in_specs + (
            P(batch_axes, None),)

    def local(q, k, v, seg=None):
        return flash_attention(q, k, v, causal=causal, segment_ids=seg,
                               interpret=interpret, **_block_kw(blocks))

    return jax.shard_map(local, mesh=use_mesh, in_specs=in_specs,
                         out_specs=spec, axis_names=manual,
                         check_vma=False)(*args)


def picks_flash(q_shape, k_shape, segment_ids=None, mesh=None,
                blocks=None) -> bool:
    """What :func:`attention` decides when left to itself
    (``use_pallas=None``), from the backend and the ``[B, S, H, D]``
    shapes alone: the flash kernel on TPU wherever it tiles and, on a
    `mesh`, the heads split over tp.  For a caller whose own fallback is
    not the reference (infer/decode.py's whole-prompt prefill)."""
    from paddle_operator_tpu.ops.pallas_attention import flash_tiles

    return (jax.default_backend() == "tpu"
            and flash_tiles(q_shape, k_shape, segment_ids,
                            **_block_kw(blocks))
            and (mesh is None
                 or _flash_axes(mesh, q_shape[2], k_shape[2]) is not None))


@jax.named_scope("attn.kernel")
def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              *, causal: bool = True,
              segment_ids: Optional[jax.Array] = None,
              use_pallas: Optional[bool] = None,
              mesh=None, blocks=None) -> jax.Array:
    """Dispatching attention.  [B, S, H, D] inputs, head-count ratio = GQA.

    ``use_pallas=None`` decides before the call (:func:`picks_flash`):
    the flash kernel on TPU wherever it tiles
    (:func:`pallas_attention.flash_tiles`) and, on a `mesh`, the heads
    split over tp — the reference elsewhere.  ``use_pallas=True`` asks for
    the kernel: shapes it cannot take raise instead of quietly running
    the O(S^2) reference.  `mesh` is the job mesh the arrays are sharded
    over (None: one device).  ``blocks``: the kernel's
    ``(block_q, block_k)`` where the caller has measured its own (None:
    the kernel's defaults, the trainer's)."""
    from paddle_operator_tpu.ops.pallas_attention import flash_attention

    if use_pallas is None:
        use_pallas = picks_flash(q.shape, k.shape, segment_ids, mesh,
                                 blocks)
    if not use_pallas:
        return reference_attention(q, k, v, causal=causal,
                                   segment_ids=segment_ids)
    if mesh is not None:
        return sharded_flash_attention(mesh, q, k, v, causal=causal,
                                       segment_ids=segment_ids,
                                       blocks=blocks)
    return flash_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                           **_block_kw(blocks))
