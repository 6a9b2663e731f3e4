"""Ring attention — context/sequence parallelism over the ``cp`` mesh axis.

Long-context support is first-class here (the reference has none anywhere —
SURVEY.md §5 "long-context/sequence parallelism: absent"): the sequence is
sharded across the ``cp`` axis, Q stays resident, and K/V chunks rotate
around the ring via ``ppermute`` while each device accumulates its part of
the softmax online (same math as flash attention at chunk granularity).
Peak memory per device is O(S/cp · S/cp) for the score tile instead of
O(S²); communication is cp-1 neighbor hops riding ICI.

Causality at chunk granularity: with contiguous chunking, chunk j
contributes to chunk i fully when j < i, with a causal mask when j == i,
and not at all when j > i (the contribution is masked out; the rotation
is uniform so the program stays SPMD).

Use :func:`ring_attention` inside ``shard_map`` (see
:func:`make_ring_attention_fn` for the wrapped version).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _chunk_scores(q, k, *, scale):
    """[B, Sq, H, D] x [B, Sk, H, D] -> [B, H, Sq, Sk] f32 (GQA-aware)."""
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   ring_pos: Optional[jax.Array] = None,
                   segment_ids: Optional[jax.Array] = None,
                   *, axis_name: str = "cp",
                   causal: bool = True) -> jax.Array:
    """Per-device body: local [B, S_loc, H, D] shards, full attention over
    the distributed sequence.  Must run inside shard_map with `axis_name`
    bound.

    ring_pos: optional [1] int32 — this device's position on the ring
    (the local chunk of an axis-sharded iota).  When None it is read with
    ``jax.lax.axis_index``; passing it as data instead keeps the body legal
    in a *nested* manual region (axis_index's lowering re-binds every mesh
    axis, which MLIR rejects inside a parent manual computation — the pp
    pipeline body).

    segment_ids: optional [B, S_loc] int32 — packed-sequence ids; the
    local chunk rotates around the ring with K/V so every score tile can
    mask cross-document attention."""
    my = (jax.lax.axis_index(axis_name) if ring_pos is None
          else ring_pos[0])
    n = jax.lax.psum(1, axis_name)
    scale = q.shape[-1] ** -0.5
    b, s_loc, h, d = q.shape
    hkv = k.shape[2]
    n_rep = h // hkv
    has_seg = segment_ids is not None

    perm = [(i, (i + 1) % n) for i in range(n)]

    # accumulators (chunk-granular online softmax), [B, H, Sq, *]
    m0 = jnp.full((b, h, s_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)

    def body(carry, step):
        if has_seg:
            m, l, acc, k_cur, v_cur, seg_cur = carry
        else:
            m, l, acc, k_cur, v_cur = carry
        src = (my - step) % n          # which chunk k_cur/v_cur came from

        s = _chunk_scores(q, k_cur, scale=scale)      # [B, H, Sq, Sk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)
            diag_mask = rows >= cols
            # full when src < my; diagonal-causal when src == my; none after
            keep = jnp.where(src == my, diag_mask, src < my)
            s = jnp.where(keep[None, None], s, NEG_INF)
        if has_seg:
            seg_keep = (segment_ids[:, :, None]
                        == seg_cur[:, None, :])       # [B, Sq, Sk]
            s = jnp.where(seg_keep[:, None], s, NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)                        # [B, H, Sq, Sk]
        v_rep = jnp.repeat(v_cur, n_rep, axis=2) if n_rep > 1 else v_cur
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v_rep.dtype), v_rep,
                        preferred_element_type=jnp.float32)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + pv
        # rotate K/V (and segments) to the next device
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        out = (m_new, l_new, acc_new, k_nxt, v_nxt)
        if has_seg:
            out = out + (jax.lax.ppermute(seg_cur, axis_name, perm),)
        return out, None

    init = (m0, l0, acc0, k, v)
    if has_seg:
        init = init + (segment_ids,)
    carry, _ = jax.lax.scan(body, init, jnp.arange(n))
    _, l, acc = carry[0], carry[1], carry[2]
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).astype(q.dtype)                   # [B, H, Sq, D]
    return out.transpose(0, 2, 1, 3)                  # [B, Sq, H, D]


def make_ring_attention_fn(mesh: Mesh, *, causal: bool = True,
                           axis_name: str = "cp"):
    """shard_map-wrapped ring attention: global [B, S, H, D] arrays with the
    sequence sharded over `axis_name`.

    Partial-manual: ONLY ``cp`` is manual; batch/head dims stay auto so
    GSPMD keeps them on dp/fsdp/tp however the caller sharded them.  This
    also makes the wrapper nestable inside another manual region (the pp
    pipeline body, parallel/pipeline.py): when tracing already happens
    inside a shard_map, the context's abstract mesh is used instead of the
    concrete `mesh` (nested shard_map must inherit the ambient mesh).

    When the cp axis has size 1 this degrades to plain attention (the ring
    has one hop), so model code can call it unconditionally.
    """
    from paddle_operator_tpu.parallel.mesh import resolve_shard_map_mesh

    seq_spec = P(None, axis_name)
    use_mesh, sizes = resolve_shard_map_mesh(mesh)
    size = sizes.get(axis_name, 1)

    common = dict(mesh=use_mesh, out_specs=seq_spec,
                  axis_names=frozenset({axis_name}), check_vma=False)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          causal=causal),
        in_specs=(seq_spec, seq_spec, seq_spec, P(axis_name)),
        **common,
    )
    fn_seg = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          causal=causal),
        in_specs=(seq_spec, seq_spec, seq_spec, P(axis_name), seq_spec),
        **common,
    )

    def call(q, k, v, segment_ids=None):
        # ring position as data (see ring_attention docstring)
        pos = jnp.arange(size, dtype=jnp.int32)
        if segment_ids is None:
            return fn(q, k, v, pos)
        return fn_seg(q, k, v, pos, segment_ids)

    return call
