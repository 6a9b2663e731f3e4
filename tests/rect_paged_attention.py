"""The paged decode kernel as it was before ISSUE 31: the rectangular grid
``(B, M)`` whose cells past a lane's fill (or before its window) repeat a
live block and skip their compute.  Test-only: the list-driven kernel
(ops/decode_attention.py ``paged_decode_attention``) must answer a live
lane bit for bit as this does — the same blocks in the same order through
the same ``_cell_softmax``."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_operator_tpu.ops.decode_attention import (
    _cell_softmax,
    _finish_softmax,
    _init_softmax,
)


def _body(len_ref, tbl_ref, lay_ref, *refs, scale, block_k, n_rep,
          windowed, quant):
    del tbl_ref, lay_ref
    b, ik, nk = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    start = None
    if windowed:
        start, refs = refs[0][b], refs[1:]
    if quant:
        (qt_ref, k_ref, v_ref, ks_ref, vs_ref, kt_ref, vt_ref,
         o_ref, acc_ref, m_ref, l_ref, kd_ref, vd_ref) = refs
        kt_ref, vt_ref = kt_ref.at[0], vt_ref.at[0]
    else:
        qt_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    k_ref, v_ref = k_ref.at[0], v_ref.at[0]
    length = len_ref[b]
    hkv = k_ref.shape[1]
    rows = hkv * block_k

    @pl.when(ik == 0)
    def _init():
        _init_softmax(acc_ref, m_ref, l_ref)

    live_cell = ik * block_k < length
    if windowed:
        live_cell = live_cell & ((ik + 1) * block_k > start)

    @pl.when(live_cell)
    def _compute():
        if quant:
            wb = jnp.maximum(length - 1, 0) // block_k

            @pl.when(ik == wb)
            def _tail():
                kd_ref[...] = kt_ref[0].astype(kd_ref.dtype)
                vd_ref[...] = vt_ref[0].astype(vd_ref.dtype)

            @pl.when(ik != wb)
            def _dequant():
                for h in range(hkv):
                    kd_ref[h] = (k_ref[0, h].astype(jnp.float32)
                                 * ks_ref[0, ik, h]).astype(kd_ref.dtype)
                    vd_ref[h] = (v_ref[0, h].astype(jnp.float32)
                                 * vs_ref[0, ik, h]).astype(vd_ref.dtype)

            k2, v2 = kd_ref[...].reshape(rows, -1), \
                vd_ref[...].reshape(rows, -1)
        else:
            k2, v2 = k_ref[0].reshape(rows, -1), v_ref[0].reshape(rows, -1)
        _cell_softmax(qt_ref[0], k2, v2, ik, length, scale, block_k, n_rep,
                      acc_ref, m_ref, l_ref, start=start)

    @pl.when(ik == nk - 1)
    def _finish():
        _finish_softmax(o_ref, acc_ref, m_ref, l_ref)


def rect_paged_decode_attention(q, k_pool, v_pool, block_table, lengths, *,
                                layer, starts=None, k_scale=None,
                                v_scale=None, k_tail=None, v_tail=None):
    """Stacked pools ``[L, N, Hkv, bs, D]``, interpret mode."""
    b, hq, d = q.shape
    _, _, hkv, block_k, _ = k_pool.shape
    nk = block_table.shape[1]
    windowed, quant = starts is not None, k_scale is not None
    lengths = lengths.astype(jnp.int32)
    block_table = block_table.astype(jnp.int32)
    lay = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def blk(ik, lens, tbl, bb, first=None):
        live = jnp.minimum(ik, jnp.maximum(lens[bb] - 1, 0) // block_k)
        if first is not None:
            live = jnp.maximum(live, first[bb] // block_k)
        return tbl[bb, live]

    if windowed:
        cache_spec = pl.BlockSpec(
            (1, 1, hkv, block_k, d),
            lambda b, ik, lens, tbl, lay, first: (
                lay[0], blk(ik, lens, tbl, b, first), 0, 0, 0))
        extra = (lay, starts.astype(jnp.int32))
    else:
        cache_spec = pl.BlockSpec(
            (1, 1, hkv, block_k, d),
            lambda b, ik, lens, tbl, lay: (lay[0], blk(ik, lens, tbl, b),
                                           0, 0, 0))
        extra = (lay,)
    in_specs = [pl.BlockSpec((1, d, hq), lambda b, ik, *_: (b, 0, 0)),
                cache_spec, cache_spec]
    scratch = [pltpu.VMEM((hq, d), jnp.float32),
               pltpu.VMEM((hq, 128), jnp.float32),
               pltpu.VMEM((hq, 128), jnp.float32)]
    operands = ()
    if quant:
        def lane_scales(plane):
            plane = jax.lax.dynamic_index_in_dim(plane, lay[0], 0,
                                                 keepdims=False)
            return plane.astype(jnp.float32)[block_table]

        scale_spec = pl.BlockSpec((1, nk, hkv), lambda b, ik, *_: (b, 0, 0),
                                  memory_space=pltpu.SMEM)
        tail_spec = pl.BlockSpec(
            (1, 1, hkv, block_k, d),
            lambda b, ik, lens, tbl, lay: (lay[0], b, 0, 0, 0))
        in_specs += [scale_spec, scale_spec, tail_spec, tail_spec]
        operands = (lane_scales(k_scale), lane_scales(v_scale),
                    k_tail, v_tail)
        scratch += [pltpu.VMEM((hkv, block_k, d), q.dtype)] * 2
    return pl.pallas_call(
        functools.partial(_body, scale=1.0 / float(d) ** 0.5,
                          block_k=block_k, n_rep=hq // hkv,
                          windowed=windowed, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + windowed, grid=(b, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hq, d), lambda b, ik, *_: (b, 0, 0)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        interpret=True,
    )(lengths, block_table, *extra, q.transpose(0, 2, 1), k_pool, v_pool,
      *operands)
