"""Flash attention — pallas TPU kernels (forward + backward).

Tiled online-softmax attention: O(S) memory, MXU-shaped blocks, f32
accumulators in VMEM scratch.  The full [S, S] score matrix never
materializes in HBM — on the bench config (B8 H16 S2048 f32 scores) the
reference XLA path moves ~2 GiB of score traffic per layer per direction;
this kernel keeps each (block_q × block_k) tile in VMEM.

Layout: kernels work on [B, H, S, D]; the public wrapper takes the
framework-wide [B, S, H, D] and GQA head ratios (kv-head blocks are indexed
with h // n_rep — no materialized repeat).

Backward follows the standard flash decomposition: the forward saves the
per-row logsumexp; `delta = rowsum(dO * O)` is precomputed in XLA; one
kernel walks k-blocks to produce dk/dv, another walks q-blocks for dq.

Causality is exploited at block granularity: fully-masked tiles are skipped
with `pl.when` (half the work), the diagonal gets an elementwise mask.

Serving reaches this kernel too: a whole-prompt prefill (infer/decode.py
_prefill_layer — the ring's inserts, ``generate``, the prefill pod)
attends over the prompt's own q, k, v through ops/attention.py
``attention``, forward only.  Its serving-side siblings live in
ops/decode_attention.py: the single-query filled-prefix kernel
(contiguous ring cache) and its PAGED variant, whose index map walks a
block table into a global KV pool (infer/paged.py) — same online-softmax
discipline as here, with the DMA skip driven by the fill length / table
instead of causality.  A forward that continues a cache (chunked slice,
suffix insert, speculative block) attends through the XLA einsum over
the cache.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

# Measured on v5e (fwd+bwd, seq 2048, head_dim 128, 16 and 32 heads):
# q512/k512 is ~11% faster than q256/k512 at dim-2048 LLaMA shapes and
# ~5% at dim-4096; q1024 ties q512 with twice the VMEM tile.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
# The forward ALONE (serving's whole-prompt prefill, infer/decode.py
# _prefill_layer) wants wider blocks than forward + backward do.
# Measured on v5e, causal, q [1, W, 32, 128] over 8 kv heads, ms a call
# at q512/k512 -> q1024/k1024: W 1024: 0.329 -> 0.251, 2048: 0.902 ->
# 0.590, 3072: 1.750 -> 1.058 (q512/k1024 1.175, q1024/k512 1.848: it is
# the key block that pays) — fewer, larger grid steps, though more of the
# diagonal tiles' work is masked.  q2048/k2048 does not fit VMEM.
FORWARD_BLOCK_Q = 1024
FORWARD_BLOCK_K = 1024



def _masked_scores(q, k, iq, ik, *, scale, causal, block_q, block_k,
                   seg_q=None, seg_k=None):
    """Block score tile [bq, bk] in f32 with the causal (and optional
    packed-sequence) mask applied — shared by the forward and both
    backward kernels."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    if seg_q is not None:
        # seg tiles arrive [8, block] (sublane-padded layout, see _seg3d);
        # row 0 carries the ids
        s = jnp.where(seg_q[0][:, None] == seg_k[0][None, :], s, NEG_INF)
    return s


def _seg_gate(live, seg_q, seg_k):
    """Block-execution gate: the causal skip AND (when packed) a dynamic
    id-range overlap test — disjoint q/k document ranges mean the whole
    tile is masked, so skip its matmuls entirely.  ``live`` may be a
    Python bool (causal=False) or a traced predicate.  Reductions run on
    the full 2-D [8, block] tiles (rows identical, see _seg3d) — Mosaic-
    layout-friendly, verified compiled on v5e."""
    if seg_q is None:
        return live
    overlap = ((jnp.min(seg_q) <= jnp.max(seg_k))
               & (jnp.max(seg_q) >= jnp.min(seg_k)))
    return jnp.logical_and(live, overlap)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale: float, causal: bool,
                block_q: int, block_k: int, has_seg: bool = False):
    if has_seg:
        seg_q_ref, seg_k_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    seg_q = seg_q_ref[0] if has_seg else None
    seg_k = seg_k_ref[0] if has_seg else None

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # block-level causal skip: block is live iff some q_row >= some k_col
    live = (not causal) or (iq * block_q + block_q - 1 >= ik * block_k)
    # segment skip: a tile whose q and k documents are disjoint is fully
    # masked — with contiguous packing this cuts attention work from S^2
    # to ~S x doc_len (min/max reductions cost nothing vs the matmul)
    gate = _seg_gate(live, seg_q, seg_k)

    @pl.when(gate)
    def _compute():
        # keep MXU inputs in their storage dtype (bf16 native rate);
        # accumulation is f32 via preferred_element_type.
        q = q_ref[0, 0]                              # [bq, D]
        k = k_ref[0, 0]                              # [bk, D]
        v = v_ref[0, 0]                              # [bk, D]
        s = _masked_scores(q, k, iq, ik, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           seg_q=seg_q, seg_k=seg_k)

        m_prev = m_ref[:, :1]                        # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)               # [bq, 1]
        p = jnp.exp(s - m_new)                       # [bq, bk]
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        # A row with no unmasked entry anywhere still has m == NEG_INF:
        # either every k-block was skipped by the block-level `live` gate
        # (then l == 0 too) or live blocks saw only NEG_INF scores (then
        # p = exp(0) = 1 accumulated l = block_k, and acc = sum(v) —
        # garbage).  NEG_INF is finite (-1e30), so without the clamp lse
        # would be ~NEG_INF and the backward kernels would compute
        # p = exp(s - lse) ≈ 1 per masked entry.  Emit o = 0 and lse = 0
        # for such rows so backward p = exp(NEG_INF - 0) = 0 (correct zero
        # gradient).  Unreachable for causal self-attention (each row
        # attends itself) but real with sq > sk or extra masking.
        masked_row = m_ref[:, :1] <= NEG_INF / 2
        l = jnp.where(l == 0.0, 1.0, l)
        o = acc_ref[:] / l
        o_ref[0, 0] = jnp.where(masked_row, 0.0, o).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(masked_row, 0.0,
                                  m_ref[:, :1] + jnp.log(l))


def _fwd(q, k, v, seg=None, *, scale, causal, block_q, block_k, n_rep,
         interpret=False):
    b, h, sq, d = q.shape
    _, hk, sk, _ = k.shape
    nq, nk = sq // block_q, sk // block_k
    grid = (b, h, nq, nk)
    has_seg = seg is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, has_seg=has_seg,
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, iq, ik, n_rep=n_rep: (b, h // n_rep, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, iq, ik, n_rep=n_rep: (b, h // n_rep, ik, 0)),
    ]
    args = [q, k, v]
    if has_seg:
        seg3 = _seg3d(seg)
        in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, h, iq, ik: (b, 0, iq)),
            pl.BlockSpec((1, 8, block_k), lambda b, h, iq, ik: (b, 0, ik)),
        ]
        args += [seg3, seg3]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        scratch_shapes=[
            _vmem((block_q, d)),
            _vmem((block_q, 128)),
            _vmem((block_q, 128)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    return o, lse


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _seg3d(seg):
    """[B, S] segment ids -> [B, 8, S]: Pallas TPU lowering needs the last
    two block dims divisible by (8, 128), so the ids are broadcast over a
    sublane dim (kernels read row 0).  ~8·S·4 bytes per row — noise."""
    b, s = seg.shape
    return jnp.broadcast_to(seg[:, None, :], (b, 8, s))


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale, causal, block_q, block_k,
                    has_seg: bool = False):
    if has_seg:
        seg_q_ref, seg_k_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    ik, iq = pl.program_id(2), pl.program_id(3)   # q innermost
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = (not causal) or (iq * block_q + block_q - 1 >= ik * block_k)
    seg_q = seg_q_ref[0] if has_seg else None
    seg_k = seg_k_ref[0] if has_seg else None
    gate = _seg_gate(live, seg_q, seg_k)

    @pl.when(gate)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                        # [bq, 1]
        delta = delta_ref[0, 0]                    # [bq, 1]

        s = _masked_scores(q, k, iq, ik, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           seg_q=seg_q, seg_k=seg_k)
        p = jnp.exp(s - lse)                       # [bq, bk]
        # dv += p^T @ dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # ds = p * (dO @ v^T - delta)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, scale, causal, block_q, block_k,
                   has_seg: bool = False):
    if has_seg:
        seg_q_ref, seg_k_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
    iq, ik = pl.program_id(2), pl.program_id(3)   # k innermost
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (not causal) or (iq * block_q + block_q - 1 >= ik * block_k)
    seg_q = seg_q_ref[0] if has_seg else None
    seg_k = seg_k_ref[0] if has_seg else None
    gate = _seg_gate(live, seg_q, seg_k)

    @pl.when(gate)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = _masked_scores(q, k, iq, ik, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           seg_q=seg_q, seg_k=seg_k)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrapper ([B, H, S, D] layout)
# ---------------------------------------------------------------------------


def _bwd_impl(q, k, v, seg, o, lse, do, *, causal, block_q, block_k,
              n_rep, interpret):
    b, h, sq, d = q.shape
    _, hk, sk, _ = k.shape
    scale = d ** -0.5
    has_seg = seg is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)        # [B, H, Sq, 1]

    nq, nk = sq // block_q, sk // block_k
    common = dict(scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, has_seg=has_seg)

    # GQA: walk query heads; kv blocks indexed h // n_rep.  dk/dv produced
    # per query head then reduced over the repeat groups below.
    dkv_in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, ik, iq: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, ik, iq, n_rep=n_rep: (b, h // n_rep, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, ik, iq, n_rep=n_rep: (b, h // n_rep, ik, 0)),
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, ik, iq: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
    ]
    seg3 = _seg3d(seg) if has_seg else None
    dkv_args = [q, k, v, do, lse, delta]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, h, ik, iq: (b, 0, iq)),
            pl.BlockSpec((1, 8, block_k), lambda b, h, ik, iq: (b, 0, ik)),
        ]
        dkv_args += [seg3, seg3]
    dkv_shape = [
        jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
        jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(b, h, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        scratch_shapes=[_vmem((block_k, d)), _vmem((block_k, d))],
        out_shape=dkv_shape,
        interpret=interpret,
    )(*dkv_args)

    dq_in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, iq, ik, n_rep=n_rep: (b, h // n_rep, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, iq, ik, n_rep=n_rep: (b, h // n_rep, ik, 0)),
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
    ]
    dq_args = [q, k, v, do, lse, delta]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, h, iq, ik: (b, 0, iq)),
            pl.BlockSpec((1, 8, block_k), lambda b, h, iq, ik: (b, 0, ik)),
        ]
        dq_args += [seg3, seg3]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b, h, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        scratch_shapes=[_vmem((block_q, d))],
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        interpret=interpret,
    )(*dq_args)

    if n_rep > 1:
        dk = dk.reshape(b, hk, n_rep, sk, d).sum(axis=2)
        dv = dv.reshape(b, hk, n_rep, sk, d).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, n_rep, interpret):
    o, _ = _fwd(q, k, v, scale=q.shape[-1] ** -0.5, causal=causal,
                block_q=block_q, block_k=block_k, n_rep=n_rep,
                interpret=interpret)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, n_rep, interpret):
    o, lse = _fwd(q, k, v, scale=q.shape[-1] ** -0.5, causal=causal,
                  block_q=block_q, block_k=block_k, n_rep=n_rep,
                  interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, n_rep, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, None, o, lse, do, causal=causal,
                     block_q=block_q, block_k=block_k, n_rep=n_rep,
                     interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Packed-sequence variant: segment_ids ride as a differentiable-position
# arg (int arrays take a None cotangent) so the bwd kernels see them.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_seg(q, k, v, seg, causal, block_q, block_k, n_rep, interpret):
    o, _ = _fwd(q, k, v, seg, scale=q.shape[-1] ** -0.5, causal=causal,
                block_q=block_q, block_k=block_k, n_rep=n_rep,
                interpret=interpret)
    return o


def _flash_seg_fwd(q, k, v, seg, causal, block_q, block_k, n_rep,
                   interpret):
    o, lse = _fwd(q, k, v, seg, scale=q.shape[-1] ** -0.5, causal=causal,
                  block_q=block_q, block_k=block_k, n_rep=n_rep,
                  interpret=interpret)
    return o, (q, k, v, seg, o, lse)


def _flash_seg_bwd(causal, block_q, block_k, n_rep, interpret, res, do):
    q, k, v, seg, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, seg, o, lse, do, causal=causal,
                           block_q=block_q, block_k=block_k, n_rep=n_rep,
                           interpret=interpret)
    return dq, dk, dv, None


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


# ---------------------------------------------------------------------------
# Public API ([B, S, H, D] layout, GQA-aware)
# ---------------------------------------------------------------------------


def flash_tiles(q_shape, k_shape, segment_ids=None,
                block_q: int = DEFAULT_BLOCK_Q,
                block_k: int = DEFAULT_BLOCK_K) -> bool:
    """Whether the kernel can tile these ``[B, S, H, D]`` shapes — the
    decision :func:`ops.attention.attention` makes before it calls."""
    b, s, _, d = q_shape
    sk = k_shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    if (s % block_q or sk % block_k or block_q % 128 or block_k % 128
            or d not in (64, 128, 256)):
        return False
    return segment_ids is None or (segment_ids.shape == (b, s) and s == sk)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True,
                    segment_ids: Optional[jax.Array] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """[B, S, H, D] flash attention, optionally with packed-sequence
    ``segment_ids`` [B, S] (cross-document scores masked in-kernel).
    Shapes that do not tile (:func:`flash_tiles`) raise
    NotImplementedError: a caller that may meet them asks first."""
    b, s, hq, d = q.shape
    sk = k.shape[1]
    if not flash_tiles(q.shape, k.shape, segment_ids, block_q, block_k):
        raise NotImplementedError(
            f"flash_attention cannot tile q{tuple(q.shape)} k{tuple(k.shape)}"
            f" segment_ids="
            f"{None if segment_ids is None else tuple(segment_ids.shape)}: "
            "needs seq lengths in multiples of a >=128 block, head_dim in "
            "(64, 128, 256), and segment_ids [B, S] with Sq == Sk")
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    n_rep = hq // k.shape[2]

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if segment_ids is not None:
        ot = _flash_seg(qt, kt, vt, segment_ids.astype(jnp.int32),
                        causal, block_q, block_k, n_rep, interpret)
    else:
        ot = _flash(qt, kt, vt, causal, block_q, block_k, n_rep, interpret)
    return ot.transpose(0, 2, 1, 3)
