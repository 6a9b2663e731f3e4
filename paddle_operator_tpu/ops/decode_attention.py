"""Single-query (decode) attention over the KV cache — pallas TPU kernel.

At serving contexts the decode hot loop is bound by reading the KV cache
from HBM, and an XLA einsum reads all ``max_len`` allocated positions
every step no matter how few are filled.  This kernel makes decode cost
proportional to the FILLED context:

- **Grid: a list of live cells (paged cache), a rectangle (contiguous
  cache).**  A call's work is the set of (lane, key-block) cells that
  hold attended positions.  :func:`paged_decode_attention` steps a
  ONE-dimensional grid over exactly those cells: :func:`decode_cells`
  lists them once a tick from the lanes' lengths, the block table and a
  layer's window — lane, pool block, block index, lane bounds,
  first/last-of-lane flags — the list rides in as a scalar-prefetch
  operand that the index maps read, and its length is the grid's
  run-time bound (a dynamic grid dimension).  A call therefore costs
  what its lanes hold: at the serving cells' fill, a fifth of the pool,
  82.5 us against the rectangle's 127.7 (16 lanes, 10,790 context
  tokens in 51 blocks of 1 MB, 8 kv heads; 65 us is 51 whole blocks at
  819 GB/s), 40.5 against 90.7 with 9 of the 16 lanes masked out of the
  step, and at full fill (16 x 4,096: the list IS the rectangle) 389.6
  against 388.2 — 1.5 us a 1 MB cell, 690 GB/s, 84 % of the chip's
  peak; with 4 kv heads a cell is 0.5 MB and costs 1.1 us, bound by
  the cell's compute and the grid step rather than its bytes (v5e,
  isolated differenced timing, PERF.md section 6, PR 31).  The
  rectangle ``(B, key-blocks)`` — cells past a lane's fill remapped to
  its last live block, so Mosaic skips their DMA, and their compute
  skipped, but each still a grid step of 0.3 us — is what the paged
  kernel ran before and what the contiguous cache's
  :func:`decode_attention` still runs (no benchmark cell reaches it).
  The same list driven by a loop inside the kernel (grid ``(B,)``,
  ``fori_loop`` over the lane's cells, two-slot manual DMA from the
  pool in ``pl.ANY``) measured slower at every fill: 97.0, 42.9 and
  471.5 us.
- **Head-major cache layout** ``[B, H_kv, S, D]`` (the decode caches
  are stored this way, infer/decode.py init_cache): each grid cell
  reads one CONTIGUOUS ``[hkv * block_k, D]`` tile; token-major would
  make Mosaic relayout every strided per-head slice.
- **Block-contraction matmuls, not per-head matvecs.**  Per-head dots
  of shape [n_rep, D] x [D, block_k] with n_rep 1-4 are matvecs that
  leave the MXU pipeline idle.  The cell contracts over the BLOCK
  dimension instead: its scores are ONE ``[hkv*bk, d] @ [d, hq]``
  matmul against every head's query (the cross-head products are masked
  off — MXU flops are free next to the HBM stream), and the output is
  ONE ``[hq, hkv*bk] @ [hkv*bk, d]`` matmul of the head-masked
  probabilities against the V tile, with the softmax bookkeeping kept
  in the transposed [hq, rows] layout (hq ~16-32 as the lane dim would
  waste most of every vreg), so that a 1 MB cell's compute hides under
  its DMA.
- **Online softmax** accumulation in f32 VMEM scratch, cache tiles read
  in storage dtype (bf16 native MXU rate); masking folds the causal/
  fill bound AND the head-match predicate into one -inf write.

**When int8 KV pays** (revised from the r4-era "why not" analysis,
which was right about the kernel and wrong about the system): at the
DMA roofline a 256-row bf16 block costs ~2.4us of HBM time against
~1.7us of cell compute — the pipeline hides compute under the DMA.
int8 codes halve the DMA to ~1.2us but add a dequantize pass
(int8->bf16 convert + scale multiply) over every cache element:
~0.55us per tensor per block on the 8x128 VPU, ~1.1us for K+V, pushing
cell compute to ~2.8us > the 1.2us DMA — on v5e the kernel flips from
bandwidth- to compute-bound and PER-STEP wall time grows ~17%.  That
per-kernel regression is real and bounded; what it buys is CAPACITY:
the paged pool (infer/paged.py) is the HBM ceiling on resident lanes
(``measure_paged_serving``/``measure_disagg_serving`` saturate on
``kv_blocks_free``, not compute), and int8 codes + one f32 scale per
(block, kv-head) cut pool bytes ~2x, so the same HBM holds ~2x the
lanes.  Under admission-bound load the AGGREGATE ring throughput
scales with resident lanes, not per-step latency: bench.py
``measure_quantized_pool`` measures 1.8x resident-lane capacity at
fixed pool bytes (codes + scale planes + the bf16 staging tails all
counted against the budget) buying ~2x aggregate tok/s (1.96-2.4x
across runs) on this box's admission-bound sweep (summary keys
``kvq_capacity_ratio``/``kvq_tok_s_ratio``), with the per-step cost
reported alongside
(``kvq_step_ms_ratio`` — 0.35-0.5x here, i.e. FASTER, but that is CPU
einsum physics where bf16 is emulated; on v5e budget the ~17% above).
So: enable ``SERVE_KV_QUANT=int8`` when deployments are
capacity-bound (queue depth high, ``kv_blocks_free`` pinned at 0);
keep the bf16 pool — the default and the parity oracle — when they
are latency-bound (spare blocks, TTFT-sensitive).  Weight-only int8
(infer/quant.py) is unaffected either way — weights feed large
matmuls where XLA folds the dequant into the MXU-bound weight stream.
The quantized-pool kernel variants below keep the dequant INSIDE the
cell (codes stream from HBM, scales ride the same index map, the
lane's bf16 staging tail substitutes for the one partial block), so
the capacity win never re-materializes a bf16 pool anywhere.

Equivalence is pinned against the XLA einsum path by
tests/test_decode_attention.py (interpret mode on CPU is exact).
Compiled on TPU, kernel and einsum logits agree only to MXU rounding
(~1e-2 on f32 standard-normal logits — both paths multiply in bf16 on
the MXU but round differently), so greedy generations may diverge at
near-tie argmax positions; that is cross-implementation fp behavior,
not an error.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 256


def _cell_softmax(qt, k2, v2, ik, length, scale, block_k, n_rep,
                  acc_ref, m_ref, l_ref, start=None):
    """One grid cell's score matmul + masked online-softmax update —
    the compute shared verbatim by the bf16 and the dequantizing
    kernels (factored, not changed: the bf16 op sequence is the one the
    parity tests pin).  ``start`` (sliding-window layers): the lane's
    first visible position; absent, the trace is the windowless one."""
    hq = qt.shape[1]
    rows = k2.shape[0]
    # every block row against EVERY query head in one MXU pass;
    # wrong-head products are masked below (flops are free next to
    # the 2MB HBM stream this cell must wait for anyway)
    s = jax.lax.dot_general(
        k2, qt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # [rows, hq]
    # softmax bookkeeping in the TRANSPOSED [hq, rows] layout: with
    # hq ~16, [rows, hq] ops fill 16/128 of each vreg's lanes and
    # the masked softmax became the cell's critical path (measured
    # ~225 GB/s); transposed, the same ops are 8x fewer vregs and
    # the kernel sits on the DMA roofline
    st = s.T                                              # [hq, rows]

    row_h = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 0) \
        // n_rep
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 1)
    pos = ik * block_k + col_iota % block_k
    live = (row_h == col_iota // block_k) & (pos < length)
    if start is not None:
        live = live & (pos >= start)
    st = jnp.where(live, st, NEG_INF)

    m_prev = m_ref[:, 0]                                  # [hq]
    m_new = jnp.maximum(m_prev, jnp.max(st, axis=1))
    corr = jnp.exp(m_prev - m_new)                        # [hq]
    p = jnp.exp(st - m_new[:, None])                      # [hq, rows]
    l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
    m_ref[:, 0] = m_new
    # [hq, rows] @ [rows, d]: zero cols outside each row's head
    # segment make this exact — one more MXU pass
    acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
        p.astype(v2.dtype), v2, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _init_softmax(acc_ref, m_ref, l_ref):
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _finish_softmax(o_ref, acc_ref, m_ref, l_ref):
    # a lane with nothing to attend (length 0: an idle or masked ring
    # lane): no cell computed, l == 0 — emit zeros rather than 0/0
    l = l_ref[:, 0]
    o = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)[:, None]
    o_ref[0] = jnp.where(m_ref[:, 0][:, None] <= NEG_INF / 2, 0.0,
                         o).astype(o_ref.dtype)


def _kernel(len_ref, *refs, scale: float, block_k: int, n_rep: int,
            stacked: bool):
    """The contiguous cache's body, on the rectangular grid ``(B,
    key-blocks)``: blocks at/after the fill boundary were index-remapped
    to the last live block (no new DMA) and their compute is skipped —
    but the grid still steps through them (the paged kernel's list,
    :func:`decode_cells`, is what removes those steps)."""
    b = pl.program_id(0)
    if stacked:       # extra scalar-prefetch ref (layer index, unused
        _lay, qt_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        k_ref, v_ref = k_ref.at[0], v_ref.at[0]   # in body; maps use it)
    else:
        qt_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    ik, nk = pl.program_id(1), pl.num_programs(1)
    length = len_ref[b]
    rows = k_ref.shape[1] * block_k

    @pl.when(ik == 0)
    def _init():
        _init_softmax(acc_ref, m_ref, l_ref)

    @pl.when(ik * block_k < length)
    def _compute():
        # the cell's whole K/V tile as one 2D matrix; rows are
        # (head-major) h*block_k + s — a pure leading-dim collapse of
        # the contiguous [hkv, block_k, d] window, no relayout
        k2 = k_ref[0].reshape(rows, -1)              # [hkv*bk, d]
        v2 = v_ref[0].reshape(rows, -1)
        _cell_softmax(qt_ref[0], k2, v2, ik, length, scale, block_k, n_rep,
                      acc_ref, m_ref, l_ref)

    @pl.when(ik == nk - 1)
    def _finish():
        _finish_softmax(o_ref, acc_ref, m_ref, l_ref)


@jax.named_scope("attn.kernel")
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, scale: Optional[float] = None,
                     layer: Optional[jax.Array] = None,
                     block_k: int = DEFAULT_BLOCK_K,
                     interpret: bool = False) -> jax.Array:
    """One query per head against the filled prefix of the KV cache.

    q: [B, Hq, D]; k_cache/v_cache: [B, Hkv, S, D] (head-major, the
    decode cache layout); lengths: [B] int32 — lane b attends cache
    cols [0, lengths[b]).  Returns [B, Hq, D].  Hq must be a multiple
    of Hkv (GQA); S a multiple of the (possibly shrunk) key block.

    ``layer``: when given (scalar int32), the caches are the FULL
    stacked [L, B, Hkv, S, D] buffers and the kernel reads layer
    ``layer`` via its index map.  This is how the decode layer loop
    must call it: slicing the layer out of the stack first makes the
    slice an operand of the pallas custom-call, which XLA must
    MATERIALIZE — a per-layer copy of the whole layer cache that
    measured +170us/layer (b8, S 512), erasing the kernel's win.  With
    the stack passed whole, pallas DMAs the blocks straight from the
    stacked HBM buffer and no copy exists."""
    b, hq, d = q.shape
    stacked = layer is not None
    _, hkv, s, _ = k_cache.shape[1:] if stacked else k_cache.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    if d % 128 and not interpret:
        # Mosaic tiles the last dim in 128-lane registers; a smaller
        # head_dim fails deep in the compiler with an alignment error.
        # LlamaConfig.resolved_decode_attn routes such configs to the
        # einsum — reaching here means the kernel was forced explicitly.
        raise ValueError(
            f"decode_attention requires head_dim % 128 == 0 on TPU "
            f"(got {d}); use decode_attn='xla' for this config")
    n_rep = hq // hkv
    while s % block_k:
        block_k //= 2
    nk = s // block_k
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    # queries pre-transposed to [B, d, Hq]: the kernel's score matmul
    # contracts d as the LHS lane dim — a host-side transpose of a tiny
    # tensor beats a per-cell relayout
    qt = q.transpose(0, 2, 1)
    lengths = lengths.astype(jnp.int32)

    def clamp(ik, lane_len):
        # last live block for this lane; repeat it for dead tail blocks
        # (repeated window => Mosaic skips the fetch)
        return jnp.minimum(ik, jnp.maximum(lane_len - 1, 0) // block_k)

    if stacked:
        lay = jnp.reshape(layer, (1,)).astype(jnp.int32)
        cache_spec = pl.BlockSpec(
            (1, 1, hkv, block_k, d),
            lambda b, ik, lens, lay: (lay[0], b, 0, clamp(ik, lens[b]), 0))
        q_spec = pl.BlockSpec((1, d, hq),
                              lambda b, ik, lens, lay: (b, 0, 0))
        out_spec = pl.BlockSpec((1, hq, d),
                                lambda b, ik, lens, lay: (b, 0, 0))
        num_prefetch, extra = 2, (lay,)
    else:
        cache_spec = pl.BlockSpec(
            (1, hkv, block_k, d),
            lambda b, ik, lens: (b, 0, clamp(ik, lens[b]), 0))
        q_spec = pl.BlockSpec((1, d, hq), lambda b, ik, lens: (b, 0, 0))
        out_spec = pl.BlockSpec((1, hq, d), lambda b, ik, lens: (b, 0, 0))
        num_prefetch, extra = 1, ()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=(b, nk),
        in_specs=[q_spec, cache_spec, cache_spec],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((hq, d), jnp.float32),        # acc
            pltpu.VMEM((hq, 128), jnp.float32),      # m (col 0 live)
            pltpu.VMEM((hq, 128), jnp.float32),      # l (col 0 live)
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k,
                          n_rep=n_rep, stacked=stacked),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        interpret=interpret,
    )(lengths, *extra, qt, k_cache, v_cache)
    return out


# The work list's rows: one column a live (lane, block) cell.
CELL_LANE, CELL_BLOCK, CELL_INDEX, CELL_FLAGS, CELL_LEN, CELL_START = range(6)
CELL_FIRST, CELL_LAST = 1, 2      # CELL_FLAGS bits: a lane's first/last cell


class DecodeCells(NamedTuple):
    """The paged kernel's grid as a list (:func:`decode_cells`): ``rows``
    [5, B*M] int32 (6 with a window: ``CELL_START``), of which the first
    ``n`` columns are the cells the grid steps over."""
    rows: jax.Array
    n: jax.Array


def decode_cells(block_table: jax.Array, lengths: jax.Array, block_k: int,
                 starts: Optional[jax.Array] = None) -> DecodeCells:
    """The (lane, block) cells a decode call has to visit, lane after
    lane and block after block — what the kernel's one-dimensional grid
    steps over in place of the whole ``B x M`` rectangle.

    block_table [B, M] pool ids, lengths [B]: lane b attends positions
    ``[starts[b], lengths[b])`` (``starts`` absent: from 0), which lie in
    its blocks ``[starts[b] // block_k, ceil(lengths[b] / block_k))``.
    Each such block is one cell: its lane, its pool id (the table is
    read here, so the kernel needs none), its index in the lane (the
    mask's positions), the lane's bounds, and whether it opens or closes
    the lane (accumulators reset / output written).  A lane with nothing
    to attend (length 0: idle, or masked out of the step) still owns an
    output row, so it keeps ONE cell, which computes nothing and writes
    zeros; its pool id repeats the cell before it (the first real cell's,
    ahead of any), so no block is fetched for it.

    A few integer operations over ``B*M`` elements: lengths and table
    hold for every layer of a tick, so a caller with a layer loop builds
    the list once outside it (infer/paged.py ``PagedView.cells``)."""
    b, m = block_table.shape
    table = block_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    end = (lengths + block_k - 1) // block_k
    lo = (jnp.zeros_like(end) if starts is None
          else jnp.minimum(starts.astype(jnp.int32) // block_k, end))
    has = end > lo                                     # [B]: any cell
    n = jnp.maximum(end - lo, 1)
    # Written as masks and sums over [B, B], [C, B] and [C, B, M] so that
    # XLA fuses it into a few operations: a cumulative sum, a search and
    # gathers of this size would each cost a launch of their own.
    lanes = jnp.arange(b, dtype=jnp.int32)
    blocks = jnp.arange(m, dtype=jnp.int32)
    c = jnp.arange(b * m, dtype=jnp.int32)
    before = lanes[None, :] < lanes[:, None]           # [b, b']: b' < b
    off = jnp.sum(jnp.where(before, n[None, :], 0), axis=1)
    own = ((c[:, None] >= off[None, :])                # [C, B]: the cell's
           & (c[:, None] < (off + n)[None, :]))        # lane, one-hot

    def of_lane(x):
        return jnp.sum(jnp.where(own, x[None, :], 0), axis=1)

    def entry(at):      # [B]: table[b, at[b]] (0 where at[b] is outside)
        return jnp.sum(jnp.where(blocks[None, :] == at[:, None], table, 0),
                       axis=1)

    j = c - of_lane(off)                               # cell within lane
    index = of_lane(lo) + j
    block = jnp.sum(jnp.where(
        own[:, :, None] & (blocks[None, None, :] == index[:, None, None]),
        table[None], 0), axis=(1, 2))
    # a lane with no cell of its own repeats the last block of the
    # nearest lane before it that has one, else the first lane's first
    prev = jnp.max(jnp.where(before & has[None, :], lanes[None, :], -1),
                   axis=1)
    first = jnp.min(jnp.where(has, lanes, b))
    fill = jnp.where(
        prev >= 0,
        jnp.sum(jnp.where(lanes[None, :] == prev[:, None],
                          entry(end - 1)[None, :], 0), axis=1),
        jnp.sum(jnp.where(lanes == first, entry(lo), 0)))
    block = jnp.where(of_lane(has.astype(jnp.int32)) > 0, block,
                      of_lane(fill))
    flags = (jnp.where(j == 0, CELL_FIRST, 0)
             | jnp.where(j == of_lane(n) - 1, CELL_LAST, 0))
    rows = [of_lane(lanes), block, index, flags, of_lane(lengths)]
    if starts is not None:
        rows.append(of_lane(starts.astype(jnp.int32)))
    return DecodeCells(jnp.stack(rows), jnp.sum(n))


def _cells_kernel(cells_ref, *refs, scale: float, block_k: int, n_rep: int,
                  stacked: bool, quant: bool):
    """The paged kernel's body over grid step ``c`` of the work list:
    cell ``c``'s K/V tile is already the window (the index maps read the
    list), so the body takes the cell's place in its lane from the list
    too and is :func:`_kernel`'s compute — ``_cell_softmax`` over the
    same blocks of a lane in the same order, the accumulators reset on a
    lane's first cell and the output written on its last.

    ``quant``: the INT8 pool with the dequant fused into the cell
    (SERVE_KV_QUANT=int8, infer/paged.py): the K/V tiles stream from HBM
    as int8 codes (half the bytes of the bf16 kernel — the capacity
    story in the module header), the lane's per-(block, kv-head) f32
    scales sit in SMEM (gathered through the block table by the wrapper
    — a ``[1, hkv]`` VMEM window per pool block is a shape Mosaic's
    (8, 128) tiling refuses), and the lane's bf16 staging tail (the one
    partial write block, quantized only on completion) substitutes for
    the cell at the write frontier — so full blocks are read quantized
    and the in-progress block is read exact, matching the einsum
    fallback's view (infer/paged.py ``_gather_lane_view_quant``) element
    for element.  Either way the cell's tile lands in a compute-dtype
    VMEM scratch; compute after that is byte-for-byte
    :func:`_cell_softmax`."""
    if stacked:       # extra scalar-prefetch ref (layer index, unused
        refs = refs[1:]                           # in body; maps use it)
    if quant:
        (qt_ref, k_ref, v_ref, ks_ref, vs_ref, kt_ref, vt_ref,
         o_ref, acc_ref, m_ref, l_ref, kd_ref, vd_ref) = refs
    else:
        qt_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    if stacked:
        k_ref, v_ref = k_ref.at[0], v_ref.at[0]
        if quant:
            kt_ref, vt_ref = kt_ref.at[0], vt_ref.at[0]
    c = pl.program_id(0)
    ik, flags = cells_ref[CELL_INDEX, c], cells_ref[CELL_FLAGS, c]
    length = cells_ref[CELL_LEN, c]
    start = None
    if cells_ref.shape[0] > CELL_START:     # a sliding-window layer: the
        start = cells_ref[CELL_START, c]    # lane attends [start, length)
    hkv = k_ref.shape[1]
    rows = hkv * block_k

    @pl.when((flags & CELL_FIRST) != 0)
    def _init():
        _init_softmax(acc_ref, m_ref, l_ref)

    # every listed cell holds live positions but the one cell of a lane
    # with nothing to attend
    @pl.when(ik * block_k < length)
    def _compute():
        if not quant:
            # the cell's whole K/V tile as one 2D matrix; rows are
            # (head-major) h*block_k + s — a pure leading-dim collapse
            # of the contiguous [hkv, block_k, d] window, no relayout
            k2 = k_ref[0].reshape(rows, -1)              # [hkv*bk, d]
            v2 = v_ref[0].reshape(rows, -1)
        else:
            # the lane's write-frontier block: its rows live in the bf16
            # staging tail (quantize-on-completion), not the int8 pool
            wb = jnp.maximum(length - 1, 0) // block_k

            @pl.when(ik == wb)
            def _tail():
                kd_ref[...] = kt_ref[0].astype(kd_ref.dtype)
                vd_ref[...] = vt_ref[0].astype(vd_ref.dtype)

            @pl.when(ik != wb)
            def _dequant():
                # one scalar scale per head: a static unroll of
                # [block_k, d] tile x SMEM scalar multiplies
                for h in range(hkv):
                    kd_ref[h] = (k_ref[0, h].astype(jnp.float32)
                                 * ks_ref[0, ik, h]).astype(kd_ref.dtype)
                    vd_ref[h] = (v_ref[0, h].astype(jnp.float32)
                                 * vs_ref[0, ik, h]).astype(vd_ref.dtype)

            k2 = kd_ref[...].reshape(rows, -1)
            v2 = vd_ref[...].reshape(rows, -1)
        _cell_softmax(qt_ref[0], k2, v2, ik, length, scale, block_k, n_rep,
                      acc_ref, m_ref, l_ref, start=start)

    @pl.when((flags & CELL_LAST) != 0)
    def _finish():
        _finish_softmax(o_ref, acc_ref, m_ref, l_ref)


@jax.named_scope("attn.kernel")
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           lengths: Optional[jax.Array] = None, *,
                           scale: Optional[float] = None,
                           layer: Optional[jax.Array] = None,
                           interpret: bool = False,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           k_tail: Optional[jax.Array] = None,
                           v_tail: Optional[jax.Array] = None,
                           starts: Optional[jax.Array] = None,
                           cells: Optional[DecodeCells] = None
                           ) -> jax.Array:
    """:func:`decode_attention` over a PAGED cache: lane b's context
    lives in pool blocks ``block_table[b, 0..ceil(len_b/bs)-1]`` instead
    of one contiguous slab.

    q: [B, Hq, D]; k_pool/v_pool: [N, Hkv, bs, D] (or stacked
    [L, N, Hkv, bs, D] with ``layer``, the decode layer-scan layout);
    block_table: [B, M] int32 pool ids (lane-local block j of lane b is
    pool block ``block_table[b, j]``; entries past the lane's fill are
    ignored); lengths: [B] — lane b attends logical positions
    [0, lengths[b]).  Returns [B, Hq, D].

    The pool's block size IS the kernel's key block, and the grid is
    ONE dimension over the call's live cells (:func:`decode_cells`; its
    bound a run-time scalar), not the ``B x M`` rectangle: the K/V index
    map is ``c -> rows[CELL_BLOCK, c]``, the query's and the output's
    ``c -> rows[CELL_LANE, c]``, so a call costs what its lanes hold —
    at full fill the list is the whole rectangle.  The pipeline keeps
    prefetching the next cell's tile, across lane boundaries too.  The
    gather that the XLA fallback must materialize (infer/paged.py
    ``_gather_lane_view``) never exists here: blocks stream straight
    from their pool rows.

    ``cells``: the list already built from ``block_table``, the lengths
    and the starts (a layer loop builds it once a tick), in place of
    ``lengths`` and ``starts``; absent, it is built here from them.

    ``k_scale``/``v_scale``/``k_tail``/``v_tail`` (all four together)
    select the QUANTIZED-pool variant (SERVE_KV_QUANT=int8): pools are
    int8 codes, scales are f32 ``[N, Hkv]`` (or ``[L, N, Hkv]``
    stacked), and the tails are the per-lane bf16 staging blocks
    ``[lanes+1, Hkv, bs, D]`` (or stacked with L) whose row ``b``
    substitutes for lane b's one partial write block — its index map
    follows the cell's lane, so Mosaic fetches each lane's tail once and
    skips the repeat.  The scales are gathered through the block table
    here (``[B, M, Hkv]``, a few KB) and each lane's slab rides into
    SMEM, where the cell reads one scalar per head.  Dequant happens in
    the cell (:func:`_cells_kernel`); HBM streams half the bytes.

    ``starts`` [B] (sliding-window layers; bf16 pool only): lane b
    attends logical positions [starts[b], lengths[b]).  The list leaves
    out the blocks wholly before it and carries it for the mask, which
    drops the positions before it.  Absent, the traced program is the
    windowless one."""
    b, hq, d = q.shape
    quant = k_scale is not None
    if quant and (v_scale is None or k_tail is None or v_tail is None):
        raise ValueError("quantized paged attention needs k_scale, "
                         "v_scale, k_tail and v_tail together")
    stacked = layer is not None
    _, hkv, block_k, _ = k_pool.shape[1:] if stacked else k_pool.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    if d % 128 and not interpret:
        raise ValueError(
            f"paged_decode_attention requires head_dim % 128 == 0 on TPU "
            f"(got {d}); use decode_attn='xla' for this config")
    if cells is None:
        cells = decode_cells(block_table, lengths, block_k, starts)
    if quant and cells.rows.shape[0] > CELL_START:
        raise ValueError("a window over the int8 pool is not written "
                         "(the staging tail's substitution assumes the "
                         "whole prefix)")
    n_rep = hq // hkv
    nk = block_table.shape[1]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    qt = q.transpose(0, 2, 1)

    def lane_map(c, cells, *_):
        return (cells[CELL_LANE, c], 0, 0)

    if stacked:
        lay = jnp.reshape(layer, (1,)).astype(jnp.int32)
        cache_spec = pl.BlockSpec(
            (1, 1, hkv, block_k, d),
            lambda c, cells, lay: (lay[0], cells[CELL_BLOCK, c], 0, 0, 0))
        tail_spec = pl.BlockSpec(
            (1, 1, hkv, block_k, d),
            lambda c, cells, lay: (lay[0], cells[CELL_LANE, c], 0, 0, 0))
        extra = (lay,)
    else:
        cache_spec = pl.BlockSpec(
            (1, hkv, block_k, d),
            lambda c, cells: (cells[CELL_BLOCK, c], 0, 0, 0))
        tail_spec = pl.BlockSpec(
            (1, hkv, block_k, d),
            lambda c, cells: (cells[CELL_LANE, c], 0, 0, 0))
        extra = ()

    in_specs = [pl.BlockSpec((1, d, hq), lane_map), cache_spec, cache_spec]
    scratch_shapes = [
        pltpu.VMEM((hq, d), jnp.float32),        # acc
        pltpu.VMEM((hq, 128), jnp.float32),      # m (col 0 live)
        pltpu.VMEM((hq, 128), jnp.float32),      # l (col 0 live)
    ]
    quant_operands = ()
    compiler_params = None
    if quant:
        def lane_scales(plane):
            if stacked:
                plane = jax.lax.dynamic_index_in_dim(
                    plane, lay[0], 0, keepdims=False)
            return plane.astype(jnp.float32)[
                block_table.astype(jnp.int32)]              # [B, M, Hkv]

        # the cell's lane's whole [M, hkv] slab: fetched once a lane
        scale_spec = pl.BlockSpec((1, nk, hkv), lane_map,
                                  memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec, tail_spec, tail_spec]
        quant_operands = (lane_scales(k_scale), lane_scales(v_scale),
                          k_tail, v_tail)
        # the cell's dequantized (or tail-substituted) K/V tiles
        scratch_shapes += [pltpu.VMEM((hkv, block_k, d), q.dtype)] * 2
        # double-buffered code and tail windows plus the two scratch
        # tiles: 16 MiB at 32 kv heads x 256 rows x 128, which is the
        # whole default scoped-VMEM budget — ask for what the shapes need
        tile = hkv * block_k * d
        need = tile * (4 + 4 * k_tail.dtype.itemsize + 2 * q.dtype.itemsize)
        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=need + (16 << 20))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(extra),
        grid=(cells.n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, d), lane_map),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        functools.partial(_cells_kernel, scale=scale, block_k=block_k,
                          n_rep=n_rep, stacked=stacked, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(cells.rows, *extra, qt, k_pool, v_pool, *quant_operands)


def _latent_cells_kernel(cells_ref, *refs, scale: float, block_k: int,
                         stacked: bool):
    """:func:`_cells_kernel` for a LATENT pool: the cell's tiles are ONE
    block of cached rows — the latents ``[block_k, C]`` and the rotated
    keys, stored transposed, ``[R, block_k]`` — read once for every head:
    the scores are the heads' (absorbed) queries against both, the values
    the latents again.  Nothing is masked off by head: every product of
    the matmuls is wanted."""
    if stacked:
        refs = refs[1:]
    ql_ref, qp_ref, c_ref, pe_ref, o_ref, acc_ref, m_ref, l_ref = refs
    tile = (0, 0, 0) if stacked else (0, 0)  # the block's leading 1s
    c = pl.program_id(0)
    ik, flags = cells_ref[CELL_INDEX, c], cells_ref[CELL_FLAGS, c]
    length = cells_ref[CELL_LEN, c]

    @pl.when((flags & CELL_FIRST) != 0)
    def _init():
        _init_softmax(acc_ref, m_ref, l_ref)

    @pl.when(ik * block_k < length)
    def _compute():
        lat = c_ref[tile]                                    # [bk, C]
        s = (jax.lax.dot_general(ql_ref[0], lat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qp_ref[0], pe_ref[tile],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
             ) * scale                                       # [hq, bk]
        pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_new
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(lat.dtype), lat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [hq, C]

    @pl.when((flags & CELL_LAST) != 0)
    def _finish():
        _finish_softmax(o_ref, acc_ref, m_ref, l_ref)


@jax.named_scope("attn.kernel")
def latent_paged_decode_attention(q_lat: jax.Array, q_pe: jax.Array,
                                  c_pool: jax.Array, pe_pool: jax.Array,
                                  block_table: jax.Array,
                                  lengths: Optional[jax.Array] = None, *,
                                  scale: float,
                                  layer: Optional[jax.Array] = None,
                                  interpret: bool = False,
                                  cells: Optional[DecodeCells] = None
                                  ) -> jax.Array:
    """The decode step of LATENT (MLA) attention in its absorbed form,
    over a paged pool of cached rows: every head attends the SAME rows,
    which are key and value at once.

    q_lat: [B, Hq, C] — a head's query against the latent itself (the
    keys' up-projection folded into it, models/glm_moe_lite.py
    ``absorb_query``) — and q_pe: [B, Hq, R], its rotated part; c_pool:
    [N, 1, bs, C] the cached latents, pe_pool: [N, 1, R, bs] the one
    rotated key a token, TRANSPOSED (both stacked with a leading L under
    ``layer``; the singleton axis is where a K/V pool has its heads);
    block_table, lengths, cells: as :func:`paged_decode_attention`.
    Returns [B, Hq, C]: ``softmax((q_lat . c + q_pe . k_pe) * scale) @
    c`` — the caller's down-projection turns it into values.

    Why the rotated keys lie transposed: a ``[bs, 64]`` tile (or one
    ``[bs, 576]`` row of both) is not a whole number of 128-lane
    registers, so XLA pads it in HBM to 128 (640) columns or lays the
    pool out column-major and copies it for every call; ``[64, bs]`` is
    dense as it stands, the pool holds exactly (C + R) * 2 bytes a token,
    and the scores' second product needs no transpose.

    The grid is :func:`paged_decode_attention`'s work list of live
    (lane, block) cells.  A cell's tiles are fetched once for all heads:
    (C + R) * 2 bytes a cached token where expanded keys and values would
    be ``Hq * (K + V) * 2`` — 1,152 against 20,480 at GLM-4.7-Flash's
    sizes — and 2 * Hq * (2 C + R) operations, 38 a byte at 20 heads, so
    the call is bound by bytes.  The heads are padded to a whole tile of
    sublanes here (zero queries, dropped rows).

    Written for a bf16 pool at tp 1 and a latent of a multiple of 128
    columns; anything else is refused."""
    b, hq, c = q_lat.shape
    r = q_pe.shape[2]
    stacked = layer is not None
    block_k = c_pool.shape[-2]
    want_c, want_pe = (1, block_k, c), (1, r, block_k)
    if (tuple(c_pool.shape[-3:]) != want_c
            or tuple(pe_pool.shape[-3:]) != want_pe
            or c_pool.ndim != 4 + stacked or (c % 128 and not interpret)):
        raise ValueError(
            f"latent_paged_decode_attention is written for pools [.., 1, "
            f"bs, C] and [.., 1, R, bs] matching q_lat [B, H, C] and q_pe "
            f"[B, H, R] with C a multiple of 128 (got {tuple(c_pool.shape)}"
            f", {tuple(pe_pool.shape)}, {tuple(q_lat.shape)}, "
            f"{tuple(q_pe.shape)}); use decode_attn='xla' for this config")
    if cells is None:
        cells = decode_cells(block_table, lengths, block_k)
    hp = -(-hq // 16) * 16
    if hp != hq:
        pad = ((0, 0), (0, hp - hq), (0, 0))
        q_lat, q_pe = jnp.pad(q_lat, pad), jnp.pad(q_pe, pad)

    def lane_map(i, cells, *_):
        return (cells[CELL_LANE, i], 0, 0)

    if stacked:
        lay = jnp.reshape(layer, (1,)).astype(jnp.int32)
        pool_map = lambda i, cells, lay: (lay[0], cells[CELL_BLOCK, i],
                                          0, 0, 0)
        lead, extra = (1, 1), (lay,)
    else:
        pool_map = lambda i, cells: (cells[CELL_BLOCK, i], 0, 0, 0)
        lead, extra = (1,), ()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(extra),
        grid=(cells.n,),
        in_specs=[pl.BlockSpec((1, hp, c), lane_map),
                  pl.BlockSpec((1, hp, r), lane_map),
                  pl.BlockSpec(lead + want_c, pool_map),
                  pl.BlockSpec(lead + want_pe, pool_map)],
        out_specs=pl.BlockSpec((1, hp, c), lane_map),
        scratch_shapes=[
            pltpu.VMEM((hp, c), jnp.float32),             # acc
            pltpu.VMEM((hp, 128), jnp.float32),           # m (col 0 live)
            pltpu.VMEM((hp, 128), jnp.float32),           # l (col 0 live)
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_cells_kernel, scale=scale, block_k=block_k,
                          stacked=stacked),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, c), q_lat.dtype),
        interpret=interpret,
    )(cells.rows, *extra, q_lat, q_pe, c_pool, pe_pool)
    return out[:, :hq]


def latent_decode_attention_reference(q_lat: jax.Array, q_pe: jax.Array,
                                      lat: jax.Array, pe: jax.Array,
                                      lengths: jax.Array,
                                      scale: float) -> jax.Array:
    """:func:`latent_paged_decode_attention`'s einsum twin over
    CONTIGUOUS lanes — the CPU's path and what the kernel is pinned
    against: q_lat [B, Hq, C], q_pe [B, Hq, R], the lanes' cached rows
    lat [B, S, C] and pe [B, S, R] (a pool gathered through its table, or
    a contiguous cache), lane b attending rows ``[0, lengths[b])``.
    Returns [B, Hq, C]; zeros for a lane with nothing to attend, like the
    kernel."""
    scores = (jnp.einsum("bhc,bsc->bhs", q_lat, lat,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bsr->bhs", q_pe, pe,
                           preferred_element_type=jnp.float32)) * scale
    mask = (jnp.arange(lat.shape[1])[None, :] < lengths[:, None])[:, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    probs = jnp.where(mask, probs, 0.0)
    out = jnp.einsum("bhs,bsc->bhc", probs.astype(lat.dtype), lat,
                     preferred_element_type=jnp.float32)
    return out.astype(q_lat.dtype)


def sharded_paged_decode_attention(mesh, q: jax.Array, k_pool: jax.Array,
                                   v_pool: jax.Array,
                                   block_table: jax.Array,
                                   lengths: Optional[jax.Array], wo, *,
                                   layer: Optional[jax.Array] = None,
                                   axis_name: str = "tp",
                                   interpret: bool = False,
                                   compute_dtype=None,
                                   k_scale: Optional[jax.Array] = None,
                                   v_scale: Optional[jax.Array] = None,
                                   k_tail: Optional[jax.Array] = None,
                                   v_tail: Optional[jax.Array] = None,
                                   cells: Optional[DecodeCells] = None
                                   ) -> jax.Array:
    """:func:`sharded_decode_attention` for the paged pool: the pool
    shards over its kv-head axis exactly like the ring cache (block ids
    are position-like, replicated), so each shard runs the paged kernel
    on its own whole GQA groups and the wo psum completes the Megatron
    row-parallel projection — block table and work list replicate
    (``cells``, or ``lengths`` to build it from here).

    The quantized-pool operands (``k_scale``/``v_scale`` per-block
    scales, ``k_tail``/``v_tail`` per-lane staging blocks) shard over
    the SAME kv-head axis as their codes — every shard dequantizes
    purely locally, and the psum is unchanged."""
    from paddle_operator_tpu.parallel.mesh import resolve_shard_map_mesh
    from jax.sharding import PartitionSpec as P

    use_mesh, sizes = resolve_shard_map_mesh(mesh)
    tp = sizes.get(axis_name, 1)
    b, hq, d = q.shape
    hkv = k_pool.shape[2] if layer is not None else k_pool.shape[1]
    if hq % tp or hkv % tp:
        raise ValueError(
            f"Hq={hq}/Hkv={hkv} not divisible by {axis_name}={tp} — "
            "route this config to the einsum path")
    dtype = compute_dtype if compute_dtype is not None else q.dtype

    head_spec = P(None, axis_name, None)
    pool_spec = (P(None, None, axis_name, None, None)
                 if layer is not None else P(None, axis_name, None, None))
    scale_spec = (P(None, None, axis_name)
                  if layer is not None else P(None, axis_name))
    wo_spec = ({"q": P(axis_name, None), "s": P(None, None)}
               if isinstance(wo, dict) else P(axis_name, None))
    stacked = layer is not None
    quant = k_scale is not None

    def body(q, kc, vc, tbl, cells, wo, *rest):
        if quant:
            ks, vs, kt, vt = rest[:4]
            rest = rest[4:]
            qkw = {"k_scale": ks, "v_scale": vs,
                   "k_tail": kt, "v_tail": vt}
        else:
            qkw = {}
        out = paged_decode_attention(q, kc, vc, tbl, cells=cells,
                                     layer=rest[0] if stacked else None,
                                     interpret=interpret,
                                     **qkw)                 # [B, Hq/tp, D]
        with jax.named_scope("attn.out"):
            o = out.reshape(b, -1)
            if isinstance(wo, dict):
                o = (o @ wo["q"].astype(dtype)) * wo["s"][..., 0, :].astype(dtype)
            else:
                o = o @ wo.astype(dtype)
            return jax.lax.psum(o, axis_name)                   # [B, E]

    if cells is None:
        cells = decode_cells(block_table, lengths, k_pool.shape[-2])
    in_specs = (head_spec, pool_spec, pool_spec, P(),
                DecodeCells(P(), P()), wo_spec)
    args = (q, k_pool, v_pool, block_table.astype(jnp.int32), cells, wo)
    if quant:
        in_specs += (scale_spec, scale_spec, pool_spec, pool_spec)
        args += (k_scale, v_scale, k_tail, v_tail)
    if stacked:
        in_specs += (P(),)
        args += (layer,)
    fn = jax.shard_map(
        body, mesh=use_mesh,
        in_specs=in_specs,
        out_specs=P(None, None),
        axis_names=frozenset({axis_name}), check_vma=False)
    return fn(*args)


def sharded_decode_attention(mesh, q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, lengths: jax.Array,
                             wo, *, layer: Optional[jax.Array] = None,
                             axis_name: str = "tp",
                             interpret: bool = False,
                             compute_dtype=None) -> jax.Array:
    """Tensor-parallel decode attention + output projection in ONE
    manual region (the Megatron decomposition, serving-side).

    q [B, Hq, D] sharded over heads, caches sharded over the KV-head
    axis ([B, Hkv, S, D], or stacked [L, B, Hkv, S, D] with ``layer``),
    wo [Hq*D, E] row-sharded (raw kernel or the weight-only-int8
    {"q","s"} dict — the per-output-channel scale is constant along the
    contraction, so it commutes with the reduction).  Returns [B, E]
    replicated: each shard runs the block-contraction kernel on its own
    whole GQA groups (no cross-shard softmax terms exist — heads are
    independent), contracts its local head slab against its rows of wo,
    and a single psum over ``axis_name`` completes the projection.

    A pallas call cannot be GSPMD-partitioned (XLA would all-gather the
    sharded cache around the custom call), which is why the kernel must
    enter the mesh through shard_map while the surrounding einsums ride
    GSPMD.  Sharding is by WHOLE GQA groups: Hkv % tp must be 0 (then
    Hq = n_rep * Hkv splits with it) — LlamaConfig.decode_tp_compatible
    gates callers into the GSPMD einsum fallback otherwise."""
    from paddle_operator_tpu.parallel.mesh import resolve_shard_map_mesh
    from jax.sharding import PartitionSpec as P

    use_mesh, sizes = resolve_shard_map_mesh(mesh)
    tp = sizes.get(axis_name, 1)
    b, hq, d = q.shape
    hkv = k_cache.shape[2] if layer is not None else k_cache.shape[1]
    if hq % tp or hkv % tp:
        raise ValueError(
            f"Hq={hq}/Hkv={hkv} not divisible by {axis_name}={tp} — "
            "route this config to the einsum path")
    dtype = compute_dtype if compute_dtype is not None else q.dtype

    head_spec = P(None, axis_name, None)
    cache_spec = (P(None, None, axis_name, None, None)
                  if layer is not None else P(None, axis_name, None, None))
    wo_spec = ({"q": P(axis_name, None), "s": P(None, None)}
               if isinstance(wo, dict) else P(axis_name, None))
    stacked = layer is not None

    def body(q, kc, vc, lens, wo, *lay):
        out = decode_attention(q, kc, vc, lens,
                               layer=lay[0] if stacked else None,
                               interpret=interpret)      # [B, Hq/tp, D]
        with jax.named_scope("attn.out"):
            o = out.reshape(b, -1)
            if isinstance(wo, dict):
                o = (o @ wo["q"].astype(dtype)) * wo["s"][..., 0, :].astype(dtype)
            else:
                o = o @ wo.astype(dtype)
            return jax.lax.psum(o, axis_name)                # [B, E]

    fn = jax.shard_map(
        body, mesh=use_mesh,
        in_specs=(head_spec, cache_spec, cache_spec, P(), wo_spec)
        + ((P(),) if stacked else ()),
        out_specs=P(None, None),
        axis_names=frozenset({axis_name}), check_vma=False)
    args = (q, k_cache, v_cache, lengths.astype(jnp.int32), wo)
    if stacked:
        args += (layer,)
    return fn(*args)


def scatter_prefill_blocks(pool: jax.Array, rows: jax.Array,
                           table_row: jax.Array, block_size: int,
                           start_block: int = 0, axis: int = 3
                           ) -> jax.Array:
    """The prefill-WRITE path against the block pool: place a
    contiguous slab of freshly prefilled KV rows
    (``[L, 1, H, T, D]``, T a multiple of ``block_size``; with ``axis``
    4 a transposed slab ``[L, 1, H, D, T]`` into a pool of transposed
    blocks) into the pool
    as WHOLE-block writes at the lane's table entries, starting at
    lane-local block ``start_block``.

    Whole blocks on purpose: the per-row unroll the suffix insert uses
    (infer/paged.py ``_write_rows_paged``) costs O(rows)
    dynamic_update_slice ops — fine for a short divergent suffix,
    pathological for a 2k-token cold prefill.  Block-aligned prefill
    output (decode.paged_prefill, the chunked slices of a cold prompt)
    writes O(blocks) instead, and each write is exactly the pallas
    decode kernel's DMA unit (``paged_decode_attention`` streams these
    same [H, bs, D] tiles back out through its index map).  Pad rows
    past the real prompt scatter into whatever the table maps there —
    the trash block for unmapped entries, a future decode block
    otherwise, where every row is overwritten before it becomes
    attendable (the exactness-with-padding contract, block-granular).
    """
    t = rows.shape[axis]
    for j in range(t // block_size):
        blk = jax.lax.slice_in_dim(rows, j * block_size,
                                   (j + 1) * block_size, axis=axis)
        pool = jax.lax.dynamic_update_slice(
            pool, blk, (0, table_row[start_block + j], 0, 0, 0))
    return pool


def scatter_prefill_blocks_quant(pool: jax.Array, scales: jax.Array,
                                 rows: jax.Array, table_row: jax.Array,
                                 block_size: int, start_block: int = 0):
    """:func:`scatter_prefill_blocks` for the INT8 pool: each whole
    block quantizes ONCE on the way in — per-(layer, kv-head) absmax
    scale over the block's rows (infer/paged.py ``quantize_kv``), codes
    to the pool, scale to the scale plane, same table-driven write
    targets.  The prompt's partial last block is ALSO scattered (its
    pad rows make the scale garbage) but is never read quantized: the
    lane's bf16 staging tail serves every read of the write-frontier
    block until decode truly completes it, which requantizes it whole.
    Returns ``(pool', scales')``."""
    from paddle_operator_tpu.infer.paged import quantize_kv

    t = rows.shape[3]
    for j in range(t // block_size):
        blk = jax.lax.slice_in_dim(rows, j * block_size,
                                   (j + 1) * block_size, axis=3)
        codes, scale = quantize_kv(blk)       # [L,1,H,bs,D], [L,1,H]
        pool = jax.lax.dynamic_update_slice(
            pool, codes, (0, table_row[start_block + j], 0, 0, 0))
        scales = jax.lax.dynamic_update_slice(
            scales, scale, (0, table_row[start_block + j], 0))
    return pool, scales


def scatter_promote_blocks_quant(pool: jax.Array, scales: jax.Array,
                                 rows: jax.Array, scale_rows: jax.Array,
                                 table_row: jax.Array, block_size: int):
    """:func:`scatter_prefill_blocks` for PROMOTING already-quantized
    blocks back from the host tier (infer/paged.py HostCacheTier): the
    payload's int8 codes (``rows`` [L, 1, H, T, D], T a block multiple)
    and its per-block scale rows (``scale_rows`` [L, T//bs, H]) are
    copied VERBATIM to the pool at the reserved table entries — unlike
    ``scatter_prefill_blocks_quant`` there is no quantize on the way
    in, because a demoted block's scale was computed exactly once at
    its original completion and re-deriving it from dequantized rows
    would break the promote-is-a-byte-copy guarantee the host-hit
    bit-exactness rests on.  Returns ``(pool', scales')``."""
    t = rows.shape[3]
    for j in range(t // block_size):
        blk = jax.lax.slice_in_dim(rows, j * block_size,
                                   (j + 1) * block_size, axis=3)
        pool = jax.lax.dynamic_update_slice(
            pool, blk, (0, table_row[j], 0, 0, 0))
        scales = jax.lax.dynamic_update_slice(
            scales, jax.lax.slice_in_dim(scale_rows, j, j + 1, axis=1),
            (0, table_row[j], 0))
    return pool, scales


def decode_attention_reference(q: jax.Array, k_cache: jax.Array,
                               v_cache: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """XLA einsum ground truth (the decode._layer math, lifted out) —
    what the kernel is equivalence-pinned against.  Same head-major
    [B, Hkv, S, D] cache layout as the kernel."""
    b, hq, d = q.shape
    _, hkv, s, _ = k_cache.shape
    n_rep = hq // hkv
    qg = q.reshape(b, hkv, n_rep, d)
    scores = jnp.einsum("bhrd,bhsd->bhrs", qg, k_cache,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    mask = jnp.arange(s)[None, :] < lengths[:, None]          # [B, S]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked lanes (length 0): emit zeros like the kernel
    probs = jnp.where(mask[:, None, None, :], probs, 0.0)
    out = jnp.einsum("bhrs,bhsd->bhrd", probs.astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, d).astype(q.dtype)
