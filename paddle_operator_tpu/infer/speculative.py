"""Speculative decoding: draft-model propose + chunked target verify.

Decode at low batch is memory-bandwidth-bound (BENCH_r05: HBM util
0.23-0.31 on the XLA path at batch 1-8) — every generated token streams
the full weight set for ONE matmul-vector's worth of compute.
Speculative decoding (Leviathan et al., "Fast Inference from
Transformers via Speculative Decoding"; Chen et al., "Accelerating
Large Language Model Decoding with Speculative Sampling") converts that
idle bandwidth into tokens: a small DRAFT model proposes K tokens
autoregressively (cheap — its weight stream is a fraction of the
target's), then the TARGET model scores all K+1 positions in ONE
chunked forward (the same weight stream a single decode step pays) and
accepts the longest prefix consistent with its own distribution.  Per
accepted token the target streams its weights 1/(a+1) times.

Design, in this codebase's terms:

- **Draft propose** rides the existing single-token ring step
  (infer/batcher.py ``_ring_forward`` — per-lane positions, pallas
  kernel on TPU) for K+1 ticks: the last tick's logits are discarded
  but its cache write appends d_K's KV, so ANY accept length can rewind
  without a gap (the standard "feed the last draft too" trick).
- **Chunked verify** is one multi-token forward at per-lane offsets
  (:func:`_multi_forward`) — the prefill math of infer/decode.py
  ``_layer`` generalized to a per-lane position vector, reusing the
  cache-append layout the ring path established.  XLA einsum attention:
  T = K+1 is a handful of rows, the weight stream dominates.
- **Acceptance**: exact greedy equality at temperature 0 (output is
  BIT-IDENTICAL to autoregressive ``decode.generate`` — pinned by
  tests and the dryrun ``serve-spec`` gate), and textbook rejection
  sampling (accept d_i with prob min(1, p/q); on rejection sample the
  normalized residual max(0, p-q)) for temperature > 0, which preserves
  the target distribution exactly in expectation.
- **Cache rollback is a write-index rewind, no copy**: rejected
  positions' K/V rows simply stay behind the rewound per-lane ``pos``;
  the causal/fill mask never attends past ``pos`` and later writes
  overwrite them — the same invariant idle ring lanes already rely on.
- **No divergent compiles**: one jitted round serves every accept
  pattern; per-lane accept lengths land in a ``pos`` vector, and the
  greedy/sampled rules are computed side by side and selected per lane
  by ``temp > 0`` (the ``_sample_tokens`` discipline).

Capacity: a round starting at position p writes verify rows p..p+K, so
callers must leave ``spec_k - 1`` positions of headroom past
prompt+max_new_tokens (speculative_generate grows its allocation;
ContinuousBatcher.submit enforces it against max_len).

Fault tolerance (infer/resilience.py): the spec round is just another
resident dispatch to the batcher's host loop, so request deadlines,
the dispatch watchdog, and ring self-healing all apply unchanged — a
heal rebuilds BOTH caches (target + draft) and re-admits queued work.
The one exception is ``nan_check``: the per-lane isfinite fold is a
chunk-step output the spec round does not produce, so the batcher
rejects the combination up front rather than silently not checking.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.models.llama import LlamaConfig, rope_frequencies


def check_draft_compat(cfg: LlamaConfig, draft_cfg: LlamaConfig) -> None:
    """The one hard compatibility invariant: only TOKEN IDS cross
    between draft and target, so they must share a tokenizer.  Raises a
    clear error on vocab mismatch (everything else — depth, width,
    head counts — may differ freely; ``LlamaConfig.draft()`` builds a
    compatible config)."""
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            f"draft/target vocab mismatch: draft vocab_size="
            f"{draft_cfg.vocab_size} vs target {cfg.vocab_size} — "
            "speculative decoding exchanges token ids between the two "
            "models, so they must share one tokenizer")


# ---------------------------------------------------------------------------
# Device side: multi-token verify forward at per-lane positions
# ---------------------------------------------------------------------------


@jax.named_scope("cache_write")
def _write_rows(cache_l: jax.Array, kv: jax.Array,
                pos: jax.Array) -> jax.Array:
    """[B, H, S, D] cache layer <- [B, H, T, D] new rows at per-lane
    start positions ``pos``.  Unrolled per lane (static slot count) for
    the same reason as batcher._write_lane_stacked: a vmapped update
    over ragged positions lowers to a scatter that copies the carry."""
    for lane in range(kv.shape[0]):
        cache_l = jax.lax.dynamic_update_slice(
            cache_l, kv[lane][None], (lane, 0, pos[lane], 0))
    return cache_l


def _proj_qkv(cfg: LlamaConfig, lp: Dict[str, Any], x: jax.Array,
              lora=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared multi-token projection block: norm -> q/k/v (+ the
    per-row LoRA delta when ``lora=(adp_l, aid)`` — qos.lora_qkv, the
    same rule every other projection site applies), reshaped to
    [B, T, H, D] pre-RoPE."""
    b, t, _ = x.shape
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = D._rms(x, lp["attn_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    q = D._mm(h, lp["attn"]["wq"]["kernel"], cfg.dtype)
    k = D._mm(h, lp["attn"]["wk"]["kernel"], cfg.dtype)
    v = D._mm(h, lp["attn"]["wv"]["kernel"], cfg.dtype)
    if lora is not None:
        from paddle_operator_tpu.infer.qos import lora_qkv

        q, k, v = lora_qkv(h, lora[0], lora[1], q, k, v, cfg.dtype)
    return (q.reshape(b, t, hq, d), k.reshape(b, t, hkv, d),
            v.reshape(b, t, hkv, d))


def _layer_multi(cfg: LlamaConfig, lp: Dict[str, Any], x: jax.Array,
                 cos: jax.Array, sin: jax.Array, k_cache: jax.Array,
                 v_cache: jax.Array, pos: jax.Array, lora=None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decoder layer over [B, T] new tokens starting at PER-LANE
    offsets ``pos`` [B] — decode._layer's math with the scalar position
    generalized to a vector (and batcher._layer_step's with one token
    generalized to T).  Row (b, j) sits at absolute position pos[b]+j
    and attends cache cols [0, pos[b]+j]."""
    b, t, _ = x.shape
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _proj_qkv(cfg, lp, x, lora)
    abs_pos = pos[:, None] + jnp.arange(t)[None, :]          # [B, T]
    cos_b = cos[abs_pos][:, :, None, :]                      # [B, T, 1, d/2]
    sin_b = sin[abs_pos][:, :, None, :]

    def rot(u):
        u1, u2 = jnp.split(u.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [u1 * cos_b - u2 * sin_b, u2 * cos_b + u1 * sin_b],
            axis=-1).astype(u.dtype)

    q, k = rot(q), rot(k)
    k_cache = _write_rows(k_cache, k.transpose(0, 2, 1, 3), pos)
    v_cache = _write_rows(v_cache, v.transpose(0, 2, 1, 3), pos)

    n_rep = hq // hkv
    s = k_cache.shape[2]
    qg = q.reshape(b, t, hkv, n_rep, d)
    scores = jnp.einsum("bthrd,bhsd->bthrs", qg, k_cache,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    mask = jnp.arange(s)[None, None, :] <= abs_pos[:, :, None]  # [B, T, S]
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bthrs,bhsd->bthrd", probs.astype(cfg.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    out = out.reshape(b, t, hq * d).astype(cfg.dtype)
    return D._finish_layer(cfg, lp, x, out), k_cache, v_cache


def _multi_forward(cfg: LlamaConfig, params: Dict[str, Any],
                   toks: jax.Array, cache: Dict[str, jax.Array],
                   mesh=None, head: bool = True, lora=None
                   ) -> Tuple[Optional[jax.Array], Dict[str, jax.Array]]:
    """[B, T] new tokens at per-lane cache['pos'] -> ([B, T, vocab]
    logits, advanced cache).  The chunked-verify forward: every einsum
    is the ring path's, so under a serving mesh the whole thing rides
    GSPMD off the param/cache shardings (T is a handful of rows — the
    pallas single-query kernel has nothing to win here).

    ``head=False`` skips the final norm + lm head and returns
    ``(None, cache)`` — an INTERMEDIATE chunked-prefill slice
    (executor.make_prefill_chunk) only appends KV, and head logits
    over a whole slice are the biggest tensor in the prefill path."""
    pos = cache["pos"]
    adp, aid = lora if lora is not None else (None, None)
    x = D._embed(cfg, params, toks)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)

    def body(x, layer_in):
        if adp is not None:
            lp, adp_l, k_c, v_c = layer_in
            lo = (adp_l, aid)
        else:
            lp, k_c, v_c = layer_in
            lo = None
        y, k_c, v_c = _layer_multi(cfg, lp, x, cos, sin, k_c, v_c, pos,
                                   lora=lo)
        return y, (k_c, v_c)

    xs = ((params["layers"], adp, cache["k"], cache["v"])
          if adp is not None
          else (params["layers"], cache["k"], cache["v"]))
    x, (k_new, v_new) = jax.lax.scan(body, x, xs)
    new_cache = {"k": k_new, "v": v_new, "pos": pos + toks.shape[1]}
    if not head:
        return None, new_cache
    logits = D._lm_head(cfg, params, x)
    return logits, new_cache


def _layer_multi_paged(cfg: LlamaConfig, lp: Dict[str, Any], x: jax.Array,
                       cos: jax.Array, sin: jax.Array, k_pool: jax.Array,
                       v_pool: jax.Array, li: jax.Array, table: jax.Array,
                       pos: jax.Array, limit: Optional[jax.Array],
                       lora=None, aligned: bool = False
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`_layer_multi` over the PAGED pool (infer/paged.py): new
    rows land in whatever pool block the lane's table maps for their
    absolute position (rows past ``limit`` route to the trash block —
    suffix-prefill pads), and the attention walks the table through the
    gathered lane view.  Same einsum/mask sequence as the contiguous
    verify, so greedy paged-vs-contiguous streams stay bit-identical.

    ``aligned=True`` (callers that guarantee block-aligned ``pos`` and
    a block-multiple row count — the N-lane prefill engine's slice
    programs): writes go whole-block (``_write_blocks_paged``) instead
    of per-row, collapsing the traced write-op count by
    ``block_size``x — at production slice widths the per-row unroll is
    pathological to compile, not just to run."""
    from paddle_operator_tpu.infer.paged import (
        _gather_lane_view,
        _write_blocks_paged,
        _write_rows_paged,
    )

    b, t, _ = x.shape
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _proj_qkv(cfg, lp, x, lora)
    abs_pos = pos[:, None] + jnp.arange(t)[None, :]          # [B, T]
    cos_b = cos[abs_pos][:, :, None, :]
    sin_b = sin[abs_pos][:, :, None, :]

    def rot(u):
        u1, u2 = jnp.split(u.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [u1 * cos_b - u2 * sin_b, u2 * cos_b + u1 * sin_b],
            axis=-1).astype(u.dtype)

    q, k = rot(q), rot(k)
    block_size = k_pool.shape[3]
    write = _write_blocks_paged if aligned else _write_rows_paged
    k_pool = write(k_pool, k.transpose(0, 2, 1, 3), li, table, pos,
                   block_size, limit)
    v_pool = write(v_pool, v.transpose(0, 2, 1, 3), li, table, pos,
                   block_size, limit)
    k_view = _gather_lane_view(k_pool, table, li)
    v_view = _gather_lane_view(v_pool, table, li)

    n_rep = hq // hkv
    s = k_view.shape[2]
    qg = q.reshape(b, t, hkv, n_rep, d)
    scores = jnp.einsum("bthrd,bhsd->bthrs", qg, k_view,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    mask = jnp.arange(s)[None, None, :] <= abs_pos[:, :, None]  # [B, T, S]
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bthrs,bhsd->bthrd", probs.astype(cfg.dtype),
                     v_view, preferred_element_type=jnp.float32)
    out = out.reshape(b, t, hq * d).astype(cfg.dtype)
    return D._finish_layer(cfg, lp, x, out), k_pool, v_pool


def _layer_multi_paged_quant(cfg: LlamaConfig, lp: Dict[str, Any],
                             x: jax.Array, cos: jax.Array, sin: jax.Array,
                             kc: jax.Array, vc: jax.Array, ks: jax.Array,
                             vs: jax.Array, kt: jax.Array, vt: jax.Array,
                             li: jax.Array, table: jax.Array,
                             pos: jax.Array, limit: Optional[jax.Array],
                             lane_mask: Optional[jax.Array], lora=None):
    """:func:`_layer_multi_paged` over the QUANTIZED pool
    (SERVE_KV_QUANT=int8): each new row accumulates EXACT in the lane's
    bf16 staging tail; a row completing its block quantizes the whole
    tail block into the int8 pool — codes + one scale, computed once
    from the full block (the reason the tail exists: per-token
    requantization would re-derive the scale T times and perturb
    already-written rows every step).  Rows that are pads (``p >=
    limit``) or belong to masked lanes (``lane_mask``) redirect to the
    TRASH tail row (index B) — a pad row writing the lane's real tail
    would clobber live rows when the pad span wraps the block.  The
    attention reads the dequantizing gather view: full blocks from the
    pool, the write-frontier block from the tail."""
    from paddle_operator_tpu.infer.paged import (
        _gather_lane_view_quant,
        quantize_kv,
    )

    b, t, _ = x.shape
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _proj_qkv(cfg, lp, x, lora)
    abs_pos = pos[:, None] + jnp.arange(t)[None, :]          # [B, T]
    cos_b = cos[abs_pos][:, :, None, :]
    sin_b = sin[abs_pos][:, :, None, :]

    def rot(u):
        u1, u2 = jnp.split(u.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [u1 * cos_b - u2 * sin_b, u2 * cos_b + u1 * sin_b],
            axis=-1).astype(u.dtype)

    q, k = rot(q), rot(k)
    bs = kc.shape[3]
    kh = k.transpose(0, 2, 1, 3)                             # [B, H, T, D]
    vh = v.transpose(0, 2, 1, 3)
    trash_row = kt.shape[1] - 1
    for lane in range(b):
        for j in range(t):
            p = pos[lane] + j
            real = None
            if limit is not None:
                real = p < limit[lane]
            if lane_mask is not None:
                real = (lane_mask[lane] if real is None
                        else real & lane_mask[lane])
            row = (lane if real is None
                   else jnp.where(real, lane, trash_row))
            kt = jax.lax.dynamic_update_slice(
                kt, kh[lane, :, j][None, None, :, None, :],
                (li, row, 0, p % bs, 0))
            vt = jax.lax.dynamic_update_slice(
                vt, vh[lane, :, j][None, None, :, None, :],
                (li, row, 0, p % bs, 0))
            complete = (p + 1) % bs == 0
            if real is not None:
                complete = complete & real
            dst = table[lane, p // bs]

            # block-completion commit behind a cond: only the
            # 1-in-bs completing row pays the two tile quantizes +
            # pool writes (same rationale as paged._write_token_quant)
            def _commit(st, row=row, dst=dst, kt=kt, vt=vt):
                kc, vc, ks, vs = st
                ktile = jax.lax.dynamic_slice(
                    kt, (li, row, 0, 0, 0), (1, 1, hkv, bs, d))
                kcodes, kscale = quantize_kv(ktile)
                kc = jax.lax.dynamic_update_slice(kc, kcodes,
                                                  (li, dst, 0, 0, 0))
                ks = jax.lax.dynamic_update_slice(ks, kscale,
                                                  (li, dst, 0))
                vtile = jax.lax.dynamic_slice(
                    vt, (li, row, 0, 0, 0), (1, 1, hkv, bs, d))
                vcodes, vscale = quantize_kv(vtile)
                vc = jax.lax.dynamic_update_slice(vc, vcodes,
                                                  (li, dst, 0, 0, 0))
                vs = jax.lax.dynamic_update_slice(vs, vscale,
                                                  (li, dst, 0))
                return kc, vc, ks, vs

            kc, vc, ks, vs = jax.lax.cond(complete, _commit,
                                          lambda st: st,
                                          (kc, vc, ks, vs))

    # per-lane write-frontier block: the last REAL row written (pads
    # never advance the tail), floor 0 for fully-masked lanes
    lim_eff = limit if limit is not None else pos + t
    wb = jnp.maximum(jnp.minimum(pos + t, lim_eff) - 1, 0) // bs
    k_view = _gather_lane_view_quant(kc, ks, kt, table, li, wb)
    v_view = _gather_lane_view_quant(vc, vs, vt, table, li, wb)

    n_rep = hq // hkv
    s = k_view.shape[2]
    qg = q.reshape(b, t, hkv, n_rep, d)
    scores = jnp.einsum("bthrd,bhsd->bthrs", qg, k_view,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    mask = jnp.arange(s)[None, None, :] <= abs_pos[:, :, None]  # [B, T, S]
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bthrs,bhsd->bthrd", probs.astype(cfg.dtype),
                     v_view, preferred_element_type=jnp.float32)
    out = out.reshape(b, t, hq * d).astype(cfg.dtype)
    return D._finish_layer(cfg, lp, x, out), kc, vc, ks, vs, kt, vt


def _multi_forward_paged(cfg: LlamaConfig, params: Dict[str, Any],
                         toks: jax.Array, cache: Dict[str, jax.Array],
                         table: jax.Array,
                         limit: Optional[jax.Array] = None,
                         mesh=None, head: bool = True,
                         quant: bool = False,
                         lane_mask: Optional[jax.Array] = None,
                         lora=None, aligned: bool = False
                         ) -> Tuple[Optional[jax.Array],
                                    Dict[str, jax.Array]]:
    """:func:`_multi_forward` with the target cache PAGED: the
    chunked-verify (and paged suffix-prefill) forward whose writes and
    attention walk the block table.  ``table`` [B, M] int32;
    ``limit`` [B] (optional) bounds real rows per lane — pads beyond it
    write to the trash block.  The pools ride the layer scan as carry
    (block ids are dynamic).  ``head=False``: KV append only, logits
    None (intermediate chunked-prefill slices,
    paged.make_paged_prefill_chunk).

    ``quant=True``: the cache is the int8 codes+scales+tails dict and
    the per-lane staging tails ride the carry too; ``lane_mask`` [B]
    (the spec round's ``active``) additionally redirects masked lanes'
    writes to the trash tail — their tail rows may be live prefill
    state (see :func:`_layer_multi_paged_quant`).

    ``aligned=True`` (bf16 only — the quant tail protocol is
    inherently per-row): block-aligned whole-block writes, see
    :func:`_layer_multi_paged`."""
    pos = cache["pos"]
    adp, aid = lora if lora is not None else (None, None)
    x = D._embed(cfg, params, toks)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    xs = ((params["layers"], adp, jnp.arange(cfg.n_layers))
          if adp is not None
          else (params["layers"], jnp.arange(cfg.n_layers)))

    def _unpack(layer_in):
        if adp is not None:
            lp, adp_l, li = layer_in
            return lp, li, (adp_l, aid)
        lp, li = layer_in
        return lp, li, None

    if quant:
        def body_q(carry, layer_in):
            x, kc, vc, ks, vs, kt, vt = carry
            lp, li, lo = _unpack(layer_in)
            y, kc, vc, ks, vs, kt, vt = _layer_multi_paged_quant(
                cfg, lp, x, cos, sin, kc, vc, ks, vs, kt, vt, li,
                table, pos, limit, lane_mask, lora=lo)
            return (y, kc, vc, ks, vs, kt, vt), ()

        (x, k_new, v_new, ks_new, vs_new, kt_new, vt_new), _ = \
            jax.lax.scan(
                body_q,
                (x, cache["k"], cache["v"], cache["ks"], cache["vs"],
                 cache["kt"], cache["vt"]), xs)
        new_cache = {"k": k_new, "v": v_new, "ks": ks_new, "vs": vs_new,
                     "kt": kt_new, "vt": vt_new,
                     "pos": pos + toks.shape[1]}
    else:
        def body(carry, layer_in):
            x, kc, vc = carry
            lp, li, lo = _unpack(layer_in)
            y, kc, vc = _layer_multi_paged(cfg, lp, x, cos, sin, kc, vc,
                                           li, table, pos, limit,
                                           lora=lo, aligned=aligned)
            return (y, kc, vc), ()

        (x, k_new, v_new), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]), xs)
        new_cache = {"k": k_new, "v": v_new, "pos": pos + toks.shape[1]}
    if not head:
        return None, new_cache
    logits = D._lm_head(cfg, params, x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# The speculative round: propose K, verify K+1, commit a+1, rewind
# ---------------------------------------------------------------------------


def make_spec_round_fn(cfg: LlamaConfig, dcfg: LlamaConfig, spec_k: int,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None, mesh=None,
                       paged: bool = False, quant: bool = False):
    """One jitted speculative round over ring-style caches (per-lane
    ``pos`` vectors), BOTH caches donated.

    ``round(params, dparams, tcache, dcache, tok [B], temp [B],
    keys [B,2], active [B]) -> (tcache', dcache', tok', committed
    [spec_k+1, B], n_commit [B])``

    ``tok`` is the per-lane carry token — committed but not yet in
    either cache.  ``committed[:n_commit[b], b]`` are lane b's newly
    committed tokens this round (accepted drafts then the
    correction/bonus token); inactive lanes freeze their output
    (n_commit 0, tok unchanged; their pos is zeroed — retired-lane
    hygiene) so the compiled program is one shape for every
    arrival/accept pattern.

    ``paged=True``: the TARGET cache is the paged block pool
    (infer/paged.py) — the round signature gains the block table after
    the caches (``round(params, dparams, tcache, dcache, table, ...)``)
    and the verify forward walks it (:func:`_multi_forward_paged`).
    The DRAFT cache stays a contiguous ring either way: its propose
    loop keeps the fast contiguous write path and pays no paging.

    ``quant=True`` (with ``paged``): the target pool is the int8
    codes+scales+tails dict.  The one spec-specific wrinkle is the
    ROLLBACK: the verify wrote K+1 rows through the staging tail, so a
    rewind that crosses back over a completed block boundary leaves the
    tail holding a NEWER block than the lane's write frontier — the
    round re-seeds such lanes' tails by dequantizing the frontier block
    from the pool (its rows below the rewound pos are exactly the
    committed ones; rows above sit behind the fill mask and are
    overwritten before they become attendable, the standard rollback
    invariant).  Lanes whose frontier block never completed keep their
    live tail untouched."""
    _round = _build_spec_round(cfg, dcfg, spec_k, top_k, top_p, mesh,
                               paged, quant)

    if paged:
        def round_fn(params, dparams, tcache, dcache, table, tok, temp,
                     keys, active):
            return _round(params, dparams, tcache, dcache, tok, temp,
                          keys, active, table)
    else:
        def round_fn(params, dparams, tcache, dcache, tok, temp, keys,
                     active):
            return _round(params, dparams, tcache, dcache, tok, temp,
                          keys, active, None)

    return jax.jit(round_fn, donate_argnums=(2, 3))


def _build_spec_round(cfg, dcfg, spec_k, top_k, top_p, mesh, paged,
                      quant):
    """The RAW (un-jitted) speculative round body behind
    :func:`make_spec_round_fn` — extracted so the megastep
    (:func:`make_spec_megastep`) can scan it N times inside one
    compiled program.  The op sequence is exactly what the jitted
    1-round program traced before the extraction; nothing about the
    round changed."""
    from paddle_operator_tpu.infer.executor import _ring_forward

    kk = spec_k

    def _round(params, dparams, tcache, dcache, tok, temp, keys, active,
               table):
        b = tok.shape[0]
        tpos0, dpos0 = tcache["pos"], dcache["pos"]
        # decoupled sampling streams: draft draws, acceptance uniforms
        # and residual draws must not reuse each other's bits
        dkeys = jax.vmap(lambda u: jax.random.fold_in(u, 1))(keys)
        akeys = jax.vmap(lambda u: jax.random.fold_in(u, 2))(keys)
        rkeys = jax.vmap(lambda u: jax.random.fold_in(u, 3))(keys)

        def draft_tick(carry, _):
            dc, tk = carry
            p0 = dc["pos"]
            logits, dc = _ring_forward(dcfg, dparams, tk, dc, mesh=mesh)
            greedy = logits.argmax(-1).astype(jnp.int32)
            filt = D._filter_logits(
                logits / jnp.maximum(temp, 1e-6)[:, None], top_k, top_p)
            qdist = jax.nn.softmax(filt, axis=-1)            # [B, V] f32
            sub = jax.vmap(jax.random.fold_in)(dkeys, p0)
            drawn = jax.vmap(
                lambda u, l: jax.random.categorical(u, l))(sub, filt)
            nxt = jnp.where(temp > 0, drawn.astype(jnp.int32), greedy)
            return (dc, nxt), (nxt, qdist)

        # K+1 ticks: K proposals, plus one extra feed whose logits are
        # discarded but whose cache write appends d_K's KV — the rewind
        # then has no gap at full acceptance (module docstring)
        (dcache2, _), (ds, qdists) = jax.lax.scan(
            draft_tick, (dcache, tok), None, length=kk + 1)
        drafts = ds[:kk].T                                   # [B, K]
        q = jnp.transpose(qdists[:kk], (1, 0, 2))            # [B, K, V]

        seq = jnp.concatenate([tok[:, None], drafts], axis=1)  # [B, K+1]
        if paged and quant:
            # quantized target pool: masked lanes' verify rows redirect
            # to the trash tail (their tail rows may be live prefill
            # state a resident dispatch must not clobber)
            tlogits, tcache2 = _multi_forward_paged(
                cfg, params, seq, tcache, table, mesh=mesh, quant=True,
                lane_mask=active)
        elif paged:
            # paged target: the verify forward walks the block table —
            # writes land in pool blocks, attention gathers the lane
            # view (or streams table-mapped blocks on the kernel path)
            tlogits, tcache2 = _multi_forward_paged(cfg, params, seq,
                                                    tcache, table,
                                                    mesh=mesh)
        else:
            tlogits, tcache2 = _multi_forward(cfg, params, seq, tcache,
                                              mesh=mesh)
        tgt = tlogits.argmax(-1).astype(jnp.int32)           # [B, K+1]

        # greedy rule: accept while the draft equals the target argmax
        accept_g = drafts == tgt[:, :kk]
        # sampled rule: accept d_i with prob min(1, p(d_i)/q(d_i))
        tfilt = D._filter_logits(
            tlogits / jnp.maximum(temp, 1e-6)[:, None, None], top_k, top_p)
        pdist = jax.nn.softmax(tfilt, axis=-1)               # [B, K+1, V]
        p_tok = jnp.take_along_axis(
            pdist[:, :kk], drafts[..., None], -1)[..., 0]    # [B, K]
        q_tok = jnp.take_along_axis(q, drafts[..., None], -1)[..., 0]
        sub_a = jax.vmap(jax.random.fold_in)(akeys, tpos0)
        u = jax.vmap(lambda s_: jax.random.uniform(s_, (kk,)))(sub_a)
        accept_s = u * q_tok < p_tok
        accept = jnp.where(temp[:, None] > 0, accept_s, accept_g)
        # longest accepted prefix per lane, 0..K
        a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

        # the token after the accepted prefix: at a < K the correction
        # (greedy: target argmax; sampled: the normalized residual
        # max(0, p - q)), at a == K the bonus from the target's K-th
        # distribution — the same gather covers both (q padded with 0)
        nxt_g = jnp.take_along_axis(tgt, a[:, None], 1)[:, 0]
        q_pad = jnp.concatenate([q, jnp.zeros_like(q[:, :1])], axis=1)
        pd_a = jnp.take_along_axis(pdist, a[:, None, None], 1)[:, 0]
        qd_a = jnp.take_along_axis(q_pad, a[:, None, None], 1)[:, 0]
        resid = jnp.clip(pd_a - qd_a, 0.0, None)
        rs = resid.sum(-1, keepdims=True)
        resid = jnp.where(rs > 0, resid, pd_a)   # numerically-empty residual
        sub_r = jax.vmap(jax.random.fold_in)(rkeys, tpos0)
        nxt_s = jax.vmap(
            lambda s_, r: jax.random.categorical(s_, jnp.log(r)))(
            sub_r, resid).astype(jnp.int32)
        nxt = jnp.where(temp > 0, nxt_s, nxt_g)

        n_commit = jnp.where(active, a + 1, 0)
        drafts_pad = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)  # [B, K+1]
        idx = jnp.arange(kk + 1)[None, :]
        committed = jnp.where(
            idx < a[:, None], drafts_pad,
            jnp.where(idx == a[:, None], nxt[:, None], 0))
        tok_out = jnp.where(active, nxt, tok)
        # ROLLBACK: monotone write-index rewind — both caches advanced
        # spec_k+1 rows, committed only a+1; rejected rows stay behind
        # pos, never attended, overwritten by later writes.  Inactive
        # (retired/free) lanes get their position ZEROED rather than
        # frozen: serving_status must never see a stale fill position,
        # and under paging their writes route to the trash block via
        # the zeroed table row regardless.
        tcache2["pos"] = jnp.where(active, tpos0 + a + 1, 0)
        dcache2["pos"] = jnp.where(active, dpos0 + a + 1, 0)
        if paged and quant:
            # tail resync across a block-crossing rewind (docstring):
            # re-seed the tail from the pool's frontier block for lanes
            # whose rewound write block was completed+quantized by the
            # verify; inactive lanes keep their (possibly live-prefill)
            # tails untouched
            from paddle_operator_tpu.infer.paged import dequantize_kv

            bs_q = tcache2["k"].shape[3]
            wb_after = (tpos0 + kk) // bs_q
            wb_new = tcache2["pos"] // bs_q
            need = active & (wb_new < wb_after)

            # behind a cond: a rewind crosses a completed block only
            # ~spec_k/block_size of rounds (and only on partial
            # accepts) — the two pool gathers + dequants + full-tail
            # rewrites must not tax every spec round
            def _resync(tails):
                kt, vt = tails
                blks = jnp.take_along_axis(table, wb_new[:, None],
                                           axis=1)[:, 0]       # [B]
                deqk = dequantize_kv(
                    jnp.take(tcache2["k"], blks, axis=1),
                    jnp.take(tcache2["ks"], blks, axis=1),
                    kt.dtype)                           # [L, B, H, bs, D]
                deqv = dequantize_kv(
                    jnp.take(tcache2["v"], blks, axis=1),
                    jnp.take(tcache2["vs"], blks, axis=1),
                    vt.dtype)
                sel = need[None, :, None, None, None]
                kt = kt.at[:, :b].set(jnp.where(sel, deqk, kt[:, :b]))
                vt = vt.at[:, :b].set(jnp.where(sel, deqv, vt[:, :b]))
                return kt, vt

            tcache2["kt"], tcache2["vt"] = jax.lax.cond(
                need.any(), _resync, lambda t: t,
                (tcache2["kt"], tcache2["vt"]))
        return tcache2, dcache2, tok_out, committed.T, n_commit

    return _round


def make_spec_megastep(cfg: LlamaConfig, dcfg: LlamaConfig, spec_k: int,
                       n_steps: int, top_k: Optional[int] = None,
                       top_p: Optional[float] = None, mesh=None,
                       paged: bool = False, quant: bool = False):
    """N fused SPECULATIVE rounds in one compiled dispatch (ISSUE 11):
    the raw round body (:func:`_build_spec_round`) scanned ``n_steps``
    times with the host's between-round decisions — eos inside a
    committed block, token budget, step budget — carried on device
    (executor._mega_advance over each round's committed tokens).  A
    lane that finishes mid-megastep free-runs masked: under paging its
    verify writes go through an effective table whose row is replaced
    by the trash block, its draft writes land past its frozen draft
    frontier (the rows a rollback already leaves there), and both
    positions are restored from the pre-round snapshot each boundary —
    so a lane frozen by its STEP budget resumes bit-identically later.

    ``mega(params, dparams, tcache, dcache[, table], tok, temp, keys,
    active, eos, left, steps) -> (tcache', dcache', tok',
    committed [n, K+1, B], raw [n, B], counts [n, B])``

    ``raw[r, b]`` is the round's device commit count (the oracle's
    acceptance-telemetry number; 0 for dead rounds), ``counts[r, b]``
    the rows of ``committed[r, :, b]`` the host consumes (eos/budget
    truncated — scheduler._consume's walk, precomputed)."""
    from paddle_operator_tpu.infer.executor import _mega_continue
    from paddle_operator_tpu.infer.paged import TRASH_BLOCK

    _round = _build_spec_round(cfg, dcfg, spec_k, top_k, top_p, mesh,
                               paged, quant)

    def _mega(params, dparams, tcache, dcache, tok, temp, keys, active,
              eos, left, steps, table):

        def outer(carry, _):
            tcache, dcache, tok, live, lleft, lsteps = carry
            tp0, dp0 = tcache["pos"], dcache["pos"]
            tbl_eff = (jnp.where(live[:, None], table, TRASH_BLOCK)
                       if paged else None)
            tcache, dcache, tok, committed, n_commit = _round(
                params, dparams, tcache, dcache, tok, temp, keys, live,
                tbl_eff)
            count, live2, left2, lsteps2 = _mega_continue(
                committed, n_commit, live, lleft, lsteps, eos)
            # frozen/dead lanes keep the positions their last consumed
            # token earned (the round zeroed them via the active mask)
            tcache["pos"] = jnp.where(live, tcache["pos"], tp0)
            dcache["pos"] = jnp.where(live, dcache["pos"], dp0)
            return ((tcache, dcache, tok, live2, left2, lsteps2),
                    (committed, n_commit, count))

        live0 = active & (left > 0) & (steps > 0)
        (tcache, dcache, tok, _, _, _), (committed, raws, counts) = \
            jax.lax.scan(outer, (tcache, dcache, tok, live0, left, steps),
                         None, length=n_steps)
        return tcache, dcache, tok, committed, raws, counts

    if paged:
        def mega(params, dparams, tcache, dcache, table, tok, temp,
                 keys, active, eos, left, steps):
            return _mega(params, dparams, tcache, dcache, tok, temp,
                         keys, active, eos, left, steps, table)
    else:
        def mega(params, dparams, tcache, dcache, tok, temp, keys,
                 active, eos, left, steps):
            return _mega(params, dparams, tcache, dcache, tok, temp,
                         keys, active, eos, left, steps, None)

    return jax.jit(mega, donate_argnums=(2, 3))


@functools.lru_cache(maxsize=16)
def _cached_round_fn(cfg, dcfg, spec_k, top_k, top_p, mesh):
    """Round programs keyed by (configs, K, filters, mesh) so repeated
    speculative_generate calls (bench sweeps, tests) reuse compiles."""
    return make_spec_round_fn(cfg, dcfg, spec_k, top_k, top_p, mesh=mesh)


@functools.lru_cache(maxsize=16)
def _cached_prefill(cfg, alloc_len, mesh):
    return jax.jit(lambda p, t: D.prefill(p, cfg, t, alloc_len, mesh=mesh))


# ---------------------------------------------------------------------------
# Host side: the standalone generate loop
# ---------------------------------------------------------------------------


def speculative_generate(params: Dict[str, Any],
                         draft_params: Dict[str, Any],
                         cfg: LlamaConfig, draft_cfg: LlamaConfig,
                         prompt: jax.Array, *, max_new_tokens: int,
                         spec_k: int = 4, temperature: float = 0.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         key: Optional[jax.Array] = None,
                         max_len: Optional[int] = None,
                         eos_token: Optional[int] = None, mesh=None,
                         return_stats: bool = False):
    """Speculative counterpart of decode.generate: prompt [B, S] ->
    [B, S + max_new_tokens].  At temperature 0 the output is exactly
    token-identical to ``decode.generate`` (greedy acceptance only ever
    commits tokens the target itself would have produced); at
    temperature > 0 rejection sampling preserves the target
    distribution (streams differ from generate's — distributional, not
    bitwise, equivalence).  Host-driven: rounds commit a data-dependent
    1..spec_k+1 tokens each, so the loop runs until every lane has its
    budget (lanes that finish early freeze via the active mask).

    ``mesh`` (make_serving_mesh): BOTH param trees must be laid out
    with decode.shard_params_for_serving; the draft's single-token
    steps and the chunked verify ride the same tp axis.

    ``return_stats``: also return {"accept_rate", "accepted",
    "drafted", "rounds", "spec_k"} — the serving acceptance telemetry.
    """
    check_draft_compat(cfg, draft_cfg)
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1 (got {spec_k})")
    b, s = prompt.shape
    cache_len = max_len or cfg.max_seq_len
    need = s + max_new_tokens
    if need > cache_len:
        raise ValueError(f"prompt ({s}) + max_new_tokens "
                         f"({max_new_tokens}) = {need} exceeds the cache "
                         f"({cache_len} positions)")
    # a verify round may write spec_k rows past the last committed
    # token; grow the allocation within the RoPE table and fail clearly
    # when it cannot fit
    alloc_len = min(cfg.max_seq_len, cache_len + spec_k)
    if need + spec_k - 1 > D.cache_alloc_len(alloc_len):
        raise ValueError(
            f"speculative decoding needs {spec_k - 1} positions of cache "
            f"headroom past prompt+max_new_tokens ({need}) but the RoPE "
            f"table caps the allocation at {alloc_len} "
            f"(cfg.max_seq_len={cfg.max_seq_len}); lower spec_k or "
            f"max_new_tokens")
    if alloc_len > draft_cfg.max_seq_len:
        raise ValueError(
            f"draft max_seq_len ({draft_cfg.max_seq_len}) is smaller than "
            f"the serving context ({alloc_len}); derive the draft with "
            f"cfg.draft() to inherit the target's RoPE table")
    if key is None:
        key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, b)

    logits, tc = _cached_prefill(cfg, alloc_len, mesh)(params, prompt)
    _, dc = _cached_prefill(draft_cfg, alloc_len, mesh)(draft_params,
                                                        prompt)
    # two distinct pos buffers: the round donates BOTH caches, and a
    # shared array would be donated twice
    tcache = {"k": tc["k"], "v": tc["v"],
              "pos": jnp.full((b,), s, jnp.int32)}
    dcache = {"k": dc["k"], "v": dc["v"],
              "pos": jnp.full((b,), s, jnp.int32)}

    temp_vec = jnp.full((b,), float(temperature), jnp.float32)
    if temperature <= 0:
        tok = logits.argmax(-1).astype(jnp.int32)
    else:
        filt = D._filter_logits(logits / temperature, top_k, top_p)
        tok = jax.vmap(lambda u, l: jax.random.categorical(u, l))(
            jax.vmap(lambda u: jax.random.fold_in(u, 0))(keys),
            filt).astype(jnp.int32)

    out = [[] for _ in range(b)]
    done = [False] * b
    first = np.asarray(tok)
    for i in range(b):
        t0 = int(first[i])
        out[i].append(t0)
        if eos_token is not None and t0 == eos_token:
            done[i] = True

    round_fn = _cached_round_fn(cfg, draft_cfg, spec_k, top_k, top_p, mesh)
    accepted = drafted = rounds = 0
    while True:
        act = [not done[i] and len(out[i]) < max_new_tokens
               for i in range(b)]
        if not any(act):
            break
        tcache, dcache, tok, committed, n_commit = round_fn(
            params, draft_params, tcache, dcache, tok, temp_vec, keys,
            jnp.asarray(act))
        committed = np.asarray(committed)             # [K+1, B]
        n_commit = np.asarray(n_commit)
        rounds += 1
        for i in range(b):
            if not act[i]:
                continue
            n = int(n_commit[i])
            drafted += spec_k
            accepted += n - 1
            for t in committed[:n, i]:
                if len(out[i]) >= max_new_tokens:
                    break
                out[i].append(int(t))
                if eos_token is not None and int(t) == eos_token:
                    done[i] = True
                    break

    # finished lanes keep emitting eos for their remaining positions —
    # decode.generate's static-shape eos semantics
    pad = eos_token if eos_token is not None else 0
    res = np.full((b, s + max_new_tokens), pad, np.int32)
    res[:, :s] = np.asarray(prompt)
    for i in range(b):
        res[i, s:s + len(out[i])] = out[i]
    tokens = jnp.asarray(res, prompt.dtype)
    if return_stats:
        stats = {
            "accept_rate": round(accepted / drafted, 4) if drafted else 0.0,
            "accepted": accepted, "drafted": drafted,
            "rounds": rounds, "spec_k": spec_k,
        }
        return tokens, stats
    return tokens
