"""Autoregressive KV-cache decoding for the LLaMA family.

The reference delegates ALL model execution to user containers; a complete
framework also needs the serving-shaped path.  TPU-native design:

- **Static shapes throughout**: the KV cache is a fixed-size ring of
  ``[L, B, H_kv, max_len, D]`` arrays and the generation loop is a
  ``lax.scan`` over ``max_new_tokens`` — one compile serves any
  prompt/continuation length ≤ max_len (no shape-polymorphic retraces).
- **Pure functions over the trained param tree**: decode consumes the
  exact pytree ``train/trainer.py`` optimizes (scanned ``layers`` layout),
  so a checkpoint restored by ``train/checkpoint.py`` serves directly.
  The layer math mirrors ``models/llama.py`` (RMSNorm → GQA attention
  with the split-halves RoPE → SwiGLU); equivalence is pinned by
  tests/test_decode.py, which asserts decode logits match the training
  forward position-for-position.
- Prefill processes the whole prompt in one pass (MXU-friendly [B, S]
  matmuls; attention through the flash kernel over the prompt's own
  q, k, v where it runs, else the einsum with a causal mask against the
  cache just written — ``prefill_attn_impl``); the step loop then
  decodes one token per scan tick with single-query attention over the
  cache.

MoE configs decode with exact no-drop top-1 routing (the training layer's
capacity buffer is a static-shape device whose drops are an
approximation; inference computes the conditional model directly).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_operator_tpu.models.llama import LlamaConfig, rope_frequencies


# ---------------------------------------------------------------------------
# Mesh-sharded serving (tensor parallel over heads/ffn/vocab)
# ---------------------------------------------------------------------------


def mesh_tp(mesh) -> int:
    """Size of the mesh's ``tp`` axis (1 for no mesh) — the one axis the
    serving path shards over (parallel/mesh.py make_serving_mesh)."""
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("tp", 1)


def shard_params_for_serving(params: Dict[str, Any], cfg: LlamaConfig,
                             mesh) -> Dict[str, Any]:
    """Lay the serving param tree onto ``mesh``: the training partition
    table (models/llama.py partition_patterns — heads/mlp/vocab → tp)
    applied with indivisible axes replicated, which covers weight-only
    int8 scale leaves whose contraction dim collapsed to 1.  Works on
    raw bf16/f32 trees and quantize_params output alike."""
    from paddle_operator_tpu.models.llama import partition_patterns
    from paddle_operator_tpu.parallel.sharding import tree_shardings

    return jax.device_put(
        params, tree_shardings(params, mesh, partition_patterns(cfg),
                               replicate_indivisible=True))


def _use_sharded_kernel(cfg: LlamaConfig, mesh, attn_impl: str) -> bool:
    """THE kernel-eligibility rule for tp>1 meshes, shared by
    :func:`_forward` and the cache views: the pallas kernel enters
    a sharded mesh only through shard_map (sharded_decode_attention)
    and only when whole GQA groups split; everything else serves
    through the GSPMD einsum path."""
    return (mesh is not None and mesh_tp(mesh) > 1
            and attn_impl != "xla"
            and cfg.decode_tp_compatible(mesh_tp(mesh)))


def resolve_decode_attn(cfg: LlamaConfig, mesh) -> Tuple[str, bool]:
    """``(attn_impl, use_sharded)`` as every decode forward on `mesh`
    resolves them — and as the server's start-up line reports them: the
    config's impl, except that a tp>1 mesh the kernel cannot split in
    whole GQA groups serves through the GSPMD einsum."""
    attn_impl = cfg.resolved_decode_attn()
    use_sharded = _use_sharded_kernel(cfg, mesh, attn_impl)
    if mesh_tp(mesh) > 1 and not use_sharded:
        attn_impl = "xla"
    return attn_impl, use_sharded


def decode_kernel_mode(cfg: LlamaConfig, mesh, step: bool
                       ) -> Tuple[bool, bool, bool]:
    """``(kernel, projects, interpret)`` for a cache view's attention
    (:class:`ContiguousView`, infer/paged.py): whether a forward that is
    (``step``) the decode step — one token a lane — attends through the
    decode kernel, whether that is the TP-sharded region which applies
    ``wo`` itself, and whether the kernel runs interpreted.  Every other
    forward attends through the einsum (:func:`_attend_cache`)."""
    attn_impl, sharded = resolve_decode_attn(cfg, mesh)
    kernel = step and attn_impl != "xla"
    return kernel, kernel and sharded, attn_impl == "pallas-interpret"


def alloc_kv_buffer(cfg: LlamaConfig, shape, mesh) -> jax.Array:
    """One KV cache buffer (decode scalar cache or ring cache — they
    differ only in the batch/lane dim), sharded over the kv-head axis
    when the serving mesh can split it: every cache shard lives with
    the wk/wv shard that fills it.  Indivisible kv heads leave the
    buffer replicated — the GSPMD einsum fallback handles it.  Callers
    allocate k and v separately: the jitted steps donate them as
    distinct buffers.  The zeros are born on their shards: staged whole
    on the first device and then re-laid, a pool sized to fill the mesh
    would not fit."""
    sharding = None
    if (mesh is not None and mesh_tp(mesh) > 1
            and cfg.n_kv_heads % mesh_tp(mesh) == 0):
        from paddle_operator_tpu.parallel.sharding import kv_cache_sharding

        sharding = kv_cache_sharding(mesh)
    return jnp.zeros(shape, cfg.dtype, device=sharding)


# Named scopes put a layer's name into every device operation's
# ``op_name``, one vocabulary wherever the block is written out (here,
# the cache views' writes and kernel calls in infer/paged.py,
# models/llama.py): embed, norm, attn.qkv, attn.rope, cache_write,
# attn.kernel, attn.out, ffn, lm_head, sample — and loss, opt_update in
# the train step.  They change no computation and no compile-cache key
# (the key strips debug information).


@jax.named_scope("norm")
def _rms(x: jax.Array, scale: jax.Array, eps: float, dtype) -> jax.Array:
    """models/llama.py RMSNorm math, f32 internals."""
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * scale.astype(jnp.float32)).astype(dtype)


def _mm(x: jax.Array, kernel_leaf, dtype) -> jax.Array:
    """x @ kernel for a raw or weight-only-int8 kernel leaf
    (infer/quant.py): the convert-then-dot form lets XLA fuse the
    dequant into the dot's weight stream (measured fastest — see the
    "what bounds int8" note in infer/quant.py; a hand-written pallas
    dequant-in-register kernel LOST to this lowering at model level).
    The per-output-channel scale applies after the matmul (valid because
    the scale is constant along the contraction dim)."""
    if isinstance(kernel_leaf, dict) and "q" in kernel_leaf:
        out = x @ kernel_leaf["q"].astype(dtype)
        return out * kernel_leaf["s"][..., 0, :].astype(dtype)
    return x @ kernel_leaf.astype(dtype)


@jax.named_scope("embed")
def _embed(cfg: LlamaConfig, params: Dict[str, Any],
           tokens: jax.Array) -> jax.Array:
    """Token ids (any shape) -> hidden states in the compute dtype."""
    return params["tok_embed"]["embedding"].astype(cfg.dtype)[tokens]


def _lm_head(cfg: LlamaConfig, params: Dict[str, Any],
             x: jax.Array) -> jax.Array:
    """Final norm + vocabulary projection -> f32 logits."""
    x = _rms(x, params["final_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    with jax.named_scope("lm_head"):
        return _mm(x, params["lm_head"]["kernel"],
                   cfg.dtype).astype(jnp.float32)


@jax.named_scope("attn.rope")
def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
          pos: jax.Array) -> jax.Array:
    """Split-halves RoPE at dynamic offset ``pos`` (mirrors
    models/llama.py apply_rope, which slices at a static offset)."""
    t = x.shape[1]
    cos = jax.lax.dynamic_slice_in_dim(cos, pos, t)[None, :, None, :]
    sin = jax.lax.dynamic_slice_in_dim(sin, pos, t)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("attn.rope")
def _rope_lanes(q: jax.Array, k: jax.Array, cos: jax.Array, sin: jax.Array,
                rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """:func:`_rope` of q and k ``[B, T, H, D]`` with every lane at its
    own position: ``rows`` is ``[B]`` for one token a lane, else
    ``[B, T]`` (the table slice is a plain gather ``cos[rows]``)."""
    if rows.ndim == 1:
        cos_b = cos[rows][:, None, None, :]          # [B, 1, 1, d/2]
        sin_b = sin[rows][:, None, None, :]
    else:
        cos_b = cos[rows][:, :, None, :]             # [B, T, 1, d/2]
        sin_b = sin[rows][:, :, None, :]

    def rot(u):
        u1, u2 = jnp.split(u.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [u1 * cos_b - u2 * sin_b, u2 * cos_b + u1 * sin_b],
            axis=-1).astype(u.dtype)

    return rot(q), rot(k)


def cache_alloc_len(max_len: int) -> int:
    """Allocation length for a KV cache of logical capacity ``max_len``:
    rounded up to a whole number of pallas key blocks
    (ops/decode_attention.py DEFAULT_BLOCK_K) so the kernel never has to
    shrink its block to divide an odd length — S=2240 would force
    64-wide blocks whose per-cell overhead measured 4x slower than
    256-wide.  Padding is dead weight only to the einsum path (it reads
    the full allocation), bounded at +255 positions — noise next to the
    weight stream at short caches and <12% of cache bytes beyond 2k.
    Lengths within one block stay exact (tiny test caches)."""
    from paddle_operator_tpu.ops.decode_attention import DEFAULT_BLOCK_K

    if max_len <= DEFAULT_BLOCK_K:
        return max_len
    return -(-max_len // DEFAULT_BLOCK_K) * DEFAULT_BLOCK_K


def init_cache(cfg: LlamaConfig, batch: int,
               max_len: Optional[int] = None,
               mesh=None) -> Dict[str, jax.Array]:
    """Fixed-size KV cache: k/v [L, B, H_kv, alloc, D] in compute
    dtype, plus the fill position (scalar int32).  Head-major layout:
    per-head rows are contiguous, which is what both the XLA attention
    einsums and the pallas decode kernel (ops/decode_attention.py) want
    as their DMA/contraction unit — token-major measured 0.64x on the
    kernel from per-head strided relayouts.  The allocation is
    block-aligned (:func:`cache_alloc_len`); positions past the LOGICAL
    ``max_len`` are never written or attended (the fill mask covers
    them), so the RoPE bound below checks the requested capacity, not
    the padded allocation.  max_len may not exceed cfg.max_seq_len:
    positions past the RoPE table would silently clamp (dynamic_slice
    semantics) and corrupt the rotary phases."""
    max_len = max_len or cfg.max_seq_len
    if max_len > cfg.max_seq_len:
        raise ValueError(f"cache max_len {max_len} exceeds the RoPE table "
                         f"(cfg.max_seq_len={cfg.max_seq_len})")
    alloc = cache_alloc_len(max_len)
    # K and V a kv head; an expert stack names its own buffers (a latent
    # cache holds one row for all heads: models/glm_moe_lite.py)
    buffers = (cfg.cache_buffers() if hasattr(cfg, "cache_buffers")
               else {"k": (cfg.n_kv_heads, cfg.head_dim),
                     "v": (cfg.n_kv_heads, cfg.head_dim)})
    cache = {name: alloc_kv_buffer(
        cfg, (cfg.n_layers, batch, heads, alloc, width), mesh)
        for name, (heads, width) in buffers.items()}
    cache["pos"] = jnp.zeros((), jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# The contiguous ring: one cache lane a request, a fill position a lane
# ---------------------------------------------------------------------------


def init_ring_cache(cfg: LlamaConfig, slots: int,
                    max_len: int, mesh=None) -> Dict[str, jax.Array]:
    """KV ring: like :func:`init_cache` (same head-major layout,
    block-aligned allocation, same kv-head tp sharding under a serving
    mesh) but with a per-lane fill position vector instead of one
    scalar."""
    if max_len > cfg.max_seq_len:
        raise ValueError(f"max_len {max_len} exceeds the RoPE table "
                         f"(cfg.max_seq_len={cfg.max_seq_len})")
    alloc = cache_alloc_len(max_len)
    shape = (cfg.n_layers, slots, cfg.n_kv_heads, alloc, cfg.head_dim)
    return {
        "k": alloc_kv_buffer(cfg, shape, mesh),
        "v": alloc_kv_buffer(cfg, shape, mesh),
        "pos": jnp.zeros((slots,), jnp.int32),
    }


@jax.named_scope("cache_write")
def _write_lane(cache_l: jax.Array, kv: jax.Array,
                pos: jax.Array) -> jax.Array:
    """[B, H, S, D] cache layer <- [B, H, 1, D] new row at per-lane pos."""
    return jax.vmap(
        lambda c, x, p: jax.lax.dynamic_update_slice(c, x, (0, p, 0))
    )(cache_l, kv, pos)


@jax.named_scope("cache_write")
def _write_lane_rows(cache_l: jax.Array, kv: jax.Array,
                     pos: jax.Array) -> jax.Array:
    """[B, H, S, D] cache layer <- [B, H, T, D] new rows at per-lane
    start positions ``pos``.  Unrolled per lane (static slot count) for
    the same reason as :func:`_write_lane_stacked`: a vmapped update
    over ragged positions lowers to a scatter that copies the carry."""
    for lane in range(kv.shape[0]):
        cache_l = jax.lax.dynamic_update_slice(
            cache_l, kv[lane][None], (lane, 0, pos[lane], 0))
    return cache_l


@jax.named_scope("cache_write")
def _write_lane_stacked(stack: jax.Array, kv: jax.Array, li: jax.Array,
                        pos: jax.Array) -> jax.Array:
    """[L, B, H, S, D] stacked cache <- [B, H, 1, D] new rows at layer
    ``li`` and per-lane positions ``pos``.

    One dynamic_update_slice PER LANE (a static unroll over the slot
    count), not a vmapped/batched update: vmapping over ragged lane
    positions lowers to a scatter, and a scatter into the scan-carried
    stack makes XLA materialize a copy of the whole ring cache per
    layer per tick — measured 30x slower than raw decode.  Chained
    single-row dus ops update the carry in place."""
    b = kv.shape[0]
    for lane in range(b):
        stack = jax.lax.dynamic_update_slice(
            stack, kv[lane][None, None], (li, lane, 0, pos[lane], 0))
    return stack


def _splice_lane(ring: Dict[str, jax.Array], lane: Dict[str, jax.Array],
                 slot, prompt_len) -> Dict[str, jax.Array]:
    """Zero ring lane ``slot`` and splice a freshly prefilled
    batch-of-one lane cache into it, setting the lane's fill position
    to ``prompt_len`` — the device half of admission, shared by the
    plain, speculative and chunked-final inserts so their splice
    semantics cannot drift.  A lane cache LONGER than the ring lane
    (a chunk-width-padded staging cache) is truncated: rows past the
    ring allocation are pads by construction."""
    ring_alloc = ring["k"].shape[3]
    lane_k, lane_v = lane["k"], lane["v"]
    if lane_k.shape[3] > ring_alloc:
        lane_k = lane_k[:, :, :, :ring_alloc]
        lane_v = lane_v[:, :, :, :ring_alloc]
    k = jnp.zeros_like(ring["k"][:, 0])
    k = jax.lax.dynamic_update_slice(k, lane_k[:, 0], (0, 0, 0, 0))
    v = jnp.zeros_like(ring["v"][:, 0])
    v = jax.lax.dynamic_update_slice(v, lane_v[:, 0], (0, 0, 0, 0))
    new_k = jax.lax.dynamic_update_slice(
        ring["k"], k[:, None], (0, slot, 0, 0, 0))
    new_v = jax.lax.dynamic_update_slice(
        ring["v"], v[:, None], (0, slot, 0, 0, 0))
    return {"k": new_k, "v": new_v,
            "pos": ring["pos"].at[slot].set(prompt_len)}


class ContiguousView:
    """The contiguous ring as :func:`cached_forward` sees a cache — a
    view owns the two decisions a cache format brings and nothing else:
    how ``T`` new rows a lane are written, and what attends over them
    (which also says how the buffers ride the layer scan).  The ring,
    the speculative draft and a chunked prefill's staging lane all hold
    one: ``k``/``v`` ``[L, B, H_kv, S, D]`` and ``pos [B]``.

    One token a lane with the decode kernel on: the caches stay STACKED
    and ride the scan as carry, the layer's index steering the kernel's
    block index map (:func:`_forward` has the measured reason), and
    under a serving mesh the kernel and the output projection run
    TP-sharded in one manual region a layer (``projects``: the view's
    ``attend`` then returns the projected residual).  Else the einsum
    (:func:`_attend_cache`) over one layer's caches, scanned as xs — the
    oracle's program.

    The paged pool's views are beside the pool (infer/paged.py)."""

    def __init__(self, cfg: LlamaConfig, cache: Dict[str, jax.Array],
                 mesh=None) -> None:
        self.cfg, self.mesh = cfg, mesh
        self.k, self.v, self.pos = cache["k"], cache["v"], cache["pos"]

    def begin(self, t: int):
        """``(held, rode)`` for a forward of ``t`` tokens a lane: what
        the layer scan carries whole and what it scans layer by layer
        (``stacked``: the caches and the layer's index; else nothing
        and the caches)."""
        self.stacked, self.projects, self.interpret = decode_kernel_mode(
            self.cfg, self.mesh, t == 1)
        if self.stacked:
            return (self.k, self.v), jnp.arange(self.cfg.n_layers)
        return (), (self.k, self.v)

    def write(self, bufs, li, k: jax.Array, v: jax.Array):
        """New ``[B, T, H, D]`` rows at ``pos[b] + j`` into ``bufs``."""
        k_c, v_c = bufs
        if self.stacked:
            k_c = _write_lane_stacked(k_c, k.transpose(0, 2, 1, 3), li,
                                      self.pos)
            v_c = _write_lane_stacked(v_c, v.transpose(0, 2, 1, 3), li,
                                      self.pos)
            return k_c, v_c
        write = _write_lane if k.shape[1] == 1 else _write_lane_rows
        k_c = write(k_c, k.transpose(0, 2, 1, 3), self.pos)
        v_c = write(v_c, v.transpose(0, 2, 1, 3), self.pos)
        return k_c, v_c

    def attend(self, bufs, li, q: jax.Array, rows: jax.Array, wo):
        """``q [B, T, Hq, D]`` at absolute positions ``rows`` over what
        :meth:`write` left -> ``[B, T, Hq*D]``, or (``projects``) the
        residual ``[B, dim]`` already through ``wo``."""
        k_c, v_c = bufs
        if not self.stacked:
            return _attend_cache(self.cfg, q, k_c, v_c, rows)
        from paddle_operator_tpu.ops.decode_attention import (
            decode_attention,
            sharded_decode_attention,
        )

        if self.projects:
            return sharded_decode_attention(
                self.mesh, q[:, 0], k_c, v_c, self.pos + 1, wo, layer=li,
                interpret=self.interpret, compute_dtype=self.cfg.dtype)
        out = decode_attention(q[:, 0], k_c, v_c, self.pos + 1, layer=li,
                               interpret=self.interpret)
        return out.reshape(q.shape[0], 1, -1).astype(self.cfg.dtype)

    def end(self, bufs, t: int) -> Dict[str, jax.Array]:
        """The cache dict back, ``t`` rows a lane further."""
        k, v = bufs
        return {"k": k, "v": v, "pos": self.pos + t}


def _qkv(cfg: LlamaConfig, lp: Dict[str, Any], x: jax.Array,
         cos: jax.Array, sin: jax.Array, pos: jax.Array,
         lora=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-attention half of a decoder layer: RMSNorm -> q/k/v
    projections -> RoPE at offset ``pos``.  Shapes [B, T, H, D].

    ``lora`` (ISSUE 10 many-adapter serving): ``(adp_l, aid)`` — one
    layer's stacked LoRA arrays + per-row adapter ids; the low-rank
    delta adds to the projection outputs BEFORE RoPE (qos.lora_qkv),
    so adapter KV enters the cache exactly as a merged-weight forward
    would produce it."""
    h = _rms(x, lp["attn_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    q, k, v = _qkv_proj(cfg, lp, h, x.shape[1], lora)
    return _rope(q, cos, sin, pos), _rope(k, cos, sin, pos), v


@jax.named_scope("attn.qkv")
def _qkv_proj(cfg: LlamaConfig, lp: Dict[str, Any], h: jax.Array, t: int,
              lora=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The q/k/v projections of the normed hidden state ``h`` (plus the
    LoRA deltas), split into heads: [B, t, H, D]."""
    b = h.shape[0]
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm(h, lp["attn"]["wq"]["kernel"], cfg.dtype)
    k = _mm(h, lp["attn"]["wk"]["kernel"], cfg.dtype)
    v = _mm(h, lp["attn"]["wv"]["kernel"], cfg.dtype)
    if lora is not None:
        from paddle_operator_tpu.infer.qos import lora_qkv

        q, k, v = lora_qkv(h, lora[0], lora[1], q, k, v, cfg.dtype)
    return (q.reshape(b, t, hq, d), k.reshape(b, t, hkv, d),
            v.reshape(b, t, hkv, d))


def _ffn_residual(cfg: LlamaConfig, lp: Dict[str, Any],
                  x: jax.Array) -> jax.Array:
    """The FFN half of a decoder layer: norm -> (SwiGLU or MoE) -> +x.
    Split out of :func:`_finish_layer` because the TP-sharded kernel
    path applies the output projection INSIDE its shard_map region
    (attention out is head-sharded there; the wo contraction + psum is
    the Megatron row-parallel reduction) and re-enters GSPMD here."""
    n = _rms(x, lp["mlp_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    with jax.named_scope("ffn"):
        if cfg.n_experts > 0:
            ffn = _moe_ffn(cfg, lp["moe"], n)
        else:
            gate = _mm(n, lp["mlp"]["w1"]["kernel"], cfg.dtype)
            up = _mm(n, lp["mlp"]["w3"]["kernel"], cfg.dtype)
            ffn = _mm(jax.nn.silu(gate) * up, lp["mlp"]["w2"]["kernel"],
                      cfg.dtype)
        return x + ffn


def _finish_layer(cfg: LlamaConfig, lp: Dict[str, Any], x: jax.Array,
                  out: jax.Array) -> jax.Array:
    """Post-attention half: output projection + residual, then the
    (dense SwiGLU or MoE) FFN + residual."""
    with jax.named_scope("attn.out"):
        x = x + _mm(out, lp["attn"]["wo"]["kernel"], cfg.dtype)
    return _ffn_residual(cfg, lp, x)


def _layer(cfg: LlamaConfig, lp: Dict[str, Any], x: jax.Array,
           cos: jax.Array, sin: jax.Array, k_cache: jax.Array,
           v_cache: jax.Array, pos: jax.Array, lora=None
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decoder layer over [B, T] new positions starting at ``pos``,
    attending to the cache's [0, pos+T), with the XLA einsum attention:
    every forward that must read what earlier calls wrote (a chunked
    slice, any entry at ``pos > 0``), single-token decode where the
    kernel is off, and whole-prompt prefill wherever the flash kernel
    does not apply (:func:`prefill_attn_impl`).
    Returns (y, k_cache', v_cache').  lp is ONE layer's param subtree
    (unstacked); caches are head-major [B, H_kv, S, D] (init_cache).
    The pallas decode path does NOT go through here — it keeps the
    caches stacked (see _forward) so the kernel reads them copy-free —
    and neither does a whole-prompt prefill the flash kernel takes
    (:func:`_prefill_layer`)."""
    q, k, v = _qkv(cfg, lp, x, cos, sin, pos, lora=lora)
    k_cache, v_cache = _write_rows(k_cache, v_cache, k, v, pos)
    out = _attend_cache(cfg, q, k_cache, v_cache, pos)
    return _finish_layer(cfg, lp, x, out), k_cache, v_cache


@jax.named_scope("cache_write")
def _write_rows(k_cache: jax.Array, v_cache: jax.Array, k: jax.Array,
                v: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """New [B, T, H, D] rows into one layer's head-major [B, H, S, D]
    caches at ``pos``."""
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.transpose(0, 2, 1, 3), (0, 0, pos, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.transpose(0, 2, 1, 3), (0, 0, pos, 0))
    return k_cache, v_cache


@jax.named_scope("attn.kernel")
def _attend_cache(cfg: LlamaConfig, q: jax.Array, k_cache: jax.Array,
                  v_cache: jax.Array, rows: jax.Array) -> jax.Array:
    """THE XLA einsum attention of [B, T] new positions against
    head-major caches [B, H_kv, S, D] (one layer of a contiguous cache,
    or a paged pool's gathered lane view) -> [B, T, Hq*D].  ``rows``
    says where the query rows sit, and so what each may attend — cache
    columns up to its own absolute position, causal and fill mask in
    one: a scalar (every lane's T rows start there: :func:`_forward`),
    ``[B]`` (one row a lane, at the lane's position) or ``[B, T]``.
    Masked columns contribute exact zeros, so a view's stale or unmapped
    tail never shows.
    Quadratic in HBM for a multi-token block (float32 scores
    [B, T, Hkv, n_rep, S]): whole-prompt prefill leaves it for the flash
    kernel where that runs (:func:`_prefill_layer`)."""
    b, t = q.shape[:2]
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # GQA: group query heads onto kv heads; single-query (or prefill-
    # block) attention against the cache with a causal+fill mask.  The
    # einsums read the cache in its storage dtype and accumulate in f32
    # (preferred_element_type) — upcasting the cache itself would
    # stream a full f32 copy of it from HBM every step, doubling the
    # bandwidth of the decode hot loop.
    n_rep = hq // hkv
    max_len = k_cache.shape[2]
    qg = q.reshape(b, t, hkv, n_rep, d)
    # scores [B, T, Hkv, n_rep, max_len]
    scores = jnp.einsum("bthrd,bhsd->bthrs", qg, k_cache,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    if rows.ndim == 0:
        cols = jnp.arange(max_len)                           # [S]
        rows = rows + jnp.arange(t)                          # [T]
        mask = (cols[None, :] <= rows[:, None])[None, :, None, None, :]
    elif rows.ndim == 1:
        mask = jnp.arange(max_len)[None, :] <= rows[:, None]     # [B, S]
        mask = mask[:, None, None, None, :]
    else:
        mask = (jnp.arange(max_len)[None, None, :]
                <= rows[:, :, None])                          # [B, T, S]
        mask = mask[:, :, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bthrs,bhsd->bthrd", probs.astype(cfg.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, t, hq * d).astype(cfg.dtype)


# Below this width a whole-prompt prefill keeps the einsum.  Measured on
# the v5e (PERF.md §6, PR 29: one paged insert of the 16-layer Mistral-7B
# configuration, host clock, einsum -> kernel): 256: 15.0 -> 15.6 ms,
# 512: 26.2 -> 26.7 (the [32, W, W] scores are small there and the
# kernel's grid has a floor of its own); 1024: 53.7 -> 46.9,
# 2048: 120.8 -> 92.6, 3072: 204.7 -> 140.0, 4096: 317.2 -> 201.7.
_FLASH_MIN_WIDTH = 1024


def _prefill_blocks(width: int) -> Optional[Tuple[int, int]]:
    """The flash kernel's blocks for a whole-prompt prefill: the forward's
    own (pallas_attention.FORWARD_BLOCK_*) where they divide the width,
    else the kernel's defaults (a 3:2 midpoint rung such as 1536)."""
    from paddle_operator_tpu.ops.pallas_attention import (
        FORWARD_BLOCK_K,
        FORWARD_BLOCK_Q,
    )

    if width % FORWARD_BLOCK_Q or width % FORWARD_BLOCK_K:
        return None
    return FORWARD_BLOCK_Q, FORWARD_BLOCK_K


def prefill_attn_impl(cfg: LlamaConfig, width: int, mesh=None) -> str:
    """Which attention a WHOLE-PROMPT prefill ``width`` positions wide
    traces (:func:`_forward` with ``whole_prompt``): ``"flash"`` — the
    pallas flash kernel over the prompt's own q, k, v — from
    ``_FLASH_MIN_WIDTH`` up, wherever
    :func:`ops.attention.picks_flash` says it runs (a TPU, a width and
    head size the kernel tiles, heads a tp mesh splits in whole GQA
    groups), else ``"einsum"`` (:func:`_attend_cache` over the lane
    cache, the only path of every CPU program and of the tiny presets).
    Static in (cfg, width, mesh): the ring reports it rung by rung as
    ``prefillAttnByBucket`` from this same function."""
    from paddle_operator_tpu.ops.attention import picks_flash

    d = cfg.head_dim
    flash = width >= _FLASH_MIN_WIDTH and picks_flash(
        (1, width, cfg.n_heads, d), (1, width, cfg.n_kv_heads, d),
        mesh=mesh, blocks=_prefill_blocks(width))
    return "flash" if flash else "einsum"


def _flash_prompt_attn(cfg: LlamaConfig, q: jax.Array, k: jax.Array,
                       v: jax.Array, mesh=None) -> jax.Array:
    """Causal self-attention of a whole prompt's own q, k, v
    ``[B, T, H, D]`` through the flash kernel -> ``[B, T, Hq*D]``."""
    from paddle_operator_tpu.ops.attention import attention

    out = attention(q, k, v, causal=True, use_pallas=True, mesh=mesh,
                    blocks=_prefill_blocks(q.shape[1]))
    return out.reshape(*q.shape[:2], cfg.n_heads * cfg.head_dim).astype(
        cfg.dtype)


def _prefill_layer(cfg: LlamaConfig, lp: Dict[str, Any], x: jax.Array,
                   cos: jax.Array, sin: jax.Array, k_cache: jax.Array,
                   v_cache: jax.Array, pos: jax.Array, lora=None, mesh=None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decoder layer of a whole-prompt prefill: :func:`_layer` for a
    prompt that enters an EMPTY cache at position 0, so the cache holds
    nothing the new k, v do not and causal self-attention over q, k, v
    is the same mathematics as :func:`_attend_cache` over the cache just
    written — without the float32 [B, T, H, S] scores in HBM and without
    the half of them above the diagonal.  K and V still land in the
    cache (the lane's decode steps read them); pad positions are
    computed and thrown away as on the einsum path (a real row never
    sees a pad: pads sit after it)."""
    q, k, v = _qkv(cfg, lp, x, cos, sin, pos, lora=lora)
    k_cache, v_cache = _write_rows(k_cache, v_cache, k, v, pos)
    out = _flash_prompt_attn(cfg, q, k, v, mesh)
    return _finish_layer(cfg, lp, x, out), k_cache, v_cache


def _moe_ffn(cfg: LlamaConfig, mp: Dict[str, Any],
             n: jax.Array) -> jax.Array:
    """Top-k MoE FFN at inference: exact conditional computation with NO
    capacity dropping (the capacity buffer of models/moe.py is a
    training-time static-shape device; drops are its approximation, not
    the model).  Experts run under lax.scan so peak memory is one
    expert's activations, then each token combines its top-k experts'
    outputs — raw Switch gate at k=1, GShard-renormalized gates at
    k>1, mirroring the training layer's routing rule."""
    from paddle_operator_tpu.models.moe import route_top_k

    b, t, d = n.shape
    kk = cfg.moe_top_k
    tokens = n.reshape(b * t, d)
    probs = jax.nn.softmax(
        tokens.astype(jnp.float32)
        @ mp["router"]["kernel"].astype(jnp.float32), axis=-1)
    gates, topi = route_top_k(probs, kk)                    # [T, k]

    def one_expert(_, w):
        w1_e, w2_e = w
        h = jax.nn.gelu(_mm(tokens, w1_e, cfg.dtype))
        return None, _mm(h, w2_e, cfg.dtype)                # [T, D]

    _, outs = jax.lax.scan(one_expert, None,
                           (mp["w1"], mp["w2"]))            # [E, T, D]
    sel = jnp.sum(jax.nn.one_hot(topi, cfg.n_experts, dtype=jnp.float32)
                  * gates[:, :, None], axis=1)              # [T, E]
    out = jnp.einsum("te,etd->td", sel.astype(cfg.dtype), outs)
    return out.reshape(b, t, d)


def _forward(cfg: LlamaConfig, params: Dict[str, Any], tokens: jax.Array,
             cache: Dict[str, jax.Array], *, last_only: bool = False,
             mesh=None, lora=None, whole_prompt: bool = False,
             head_at=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """[B, T] new tokens at cache['pos'] -> ([B, T, vocab] logits,
    advanced cache).  Layers run under lax.scan over the stacked params
    (the same ``layers`` layout nn.scan trains).

    ``last_only``: apply the norm + lm head to the final position only
    (logits [B, 1, vocab]) — prefill needs just the next-token logits,
    and head logits over a whole long prompt are the biggest tensor in
    the decode path ([B, S, V] f32 — gigabytes at real vocab sizes).
    ``head_at`` (an index among the T, may be traced): the same at that
    one position — a padded prompt's last REAL token (the whole-prompt
    inserts; XLA does not push a slice of the logits through the head's
    product, so slicing after it costs the head over all T rows).

    ``mesh``: a serving mesh with a tp axis (make_serving_mesh) makes
    the whole forward tensor-parallel: the einsum/matmul structure rides
    GSPMD off the param/cache shardings, and the pallas kernel enters
    through its own shard_map with a per-layer wo psum
    (sharded_decode_attention).  Configs the kernel cannot split
    (decode_tp_compatible) fall back to the GSPMD einsum path whole.

    ``lora``: ``(adp, aid)`` — stacked [L, ...] adapter arrays riding
    the layer scan as xs, per-row adapter ids (infer/qos.py).

    ``whole_prompt``: the caller made ``cache`` itself, in this same
    call, with :func:`init_cache` — it is empty and at position 0, and
    ``tokens`` is everything it will hold.  Set by :func:`prefill`,
    infer/paged.py ``paged_prefill`` and the ring's whole-prompt inserts, never by
    a forward that continues a cache.  Such a prefill has two attention
    paths, chosen per width before tracing (:func:`prefill_attn_impl`):
    the flash kernel over the prompt's own q, k, v
    (:func:`_prefill_layer`), or, where the kernel does not run, the
    einsum over the cache just written (:func:`_layer`) — the one path
    of every other multi-token forward, which must read the earlier
    cache."""
    if not isinstance(cfg, LlamaConfig):
        # another architecture's block (the preset's type selects it)
        from paddle_operator_tpu.infer import afmoe_serve as AF

        AF.refuse_modes(cfg, {"SERVE_TP>1": mesh_tp(mesh) > 1,
                              "SERVE_ADAPTERS": lora is not None})
        logits, cache, _ = AF.forward(
            cfg, params, tokens, cache,
            head_at=(tokens.shape[1] - 1 if last_only and head_at is None
                     else head_at),
            whole_prompt=whole_prompt)
        return logits, cache
    pos = cache["pos"]
    adp, aid = lora if lora is not None else (None, None)
    x = _embed(cfg, params, tokens)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)

    attn_impl, use_sharded = resolve_decode_attn(cfg, mesh)
    if tokens.shape[1] == 1 and attn_impl != "xla":
        # pallas decode path: the caches stay STACKED [L, B, H, S, D]
        # and flow as scan CARRY, with the layer index steering the
        # kernel's block index map.  Scanning them as xs (the einsum
        # structure below) would slice each layer out first, and a
        # dynamic-slice that feeds a pallas custom-call must be
        # materialized by XLA — a per-layer copy of the layer's whole
        # cache, measured +170us/layer at b8.  Under a tp mesh the
        # attention + output projection run inside one manual region per
        # layer (ops/decode_attention.py sharded_decode_attention).
        from paddle_operator_tpu.ops.decode_attention import (
            decode_attention,
            sharded_decode_attention,
        )

        b = x.shape[0]
        hq, d = cfg.n_heads, cfg.head_dim
        interpret = attn_impl == "pallas-interpret"

        def body(carry, layer_in):
            x, kc, vc = carry
            lp, li, lo = _layer_operands(layer_in, aid)
            q, k, v = _qkv(cfg, lp, x, cos, sin, pos, lora=lo)
            kc = jax.lax.dynamic_update_slice(
                kc, k.transpose(0, 2, 1, 3)[None], (li, 0, 0, pos, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v.transpose(0, 2, 1, 3)[None], (li, 0, 0, pos, 0))
            if use_sharded:
                proj = sharded_decode_attention(
                    mesh, q[:, 0], kc, vc, jnp.broadcast_to(pos + 1, (b,)),
                    lp["attn"]["wo"]["kernel"], layer=li,
                    interpret=interpret, compute_dtype=cfg.dtype)
                x = x + proj[:, None].astype(cfg.dtype)
                return (_ffn_residual(cfg, lp, x), kc, vc), ()
            out = decode_attention(
                q[:, 0], kc, vc, jnp.broadcast_to(pos + 1, (b,)),
                layer=li, interpret=interpret)
            out = out.reshape(b, 1, hq * d).astype(cfg.dtype)
            return (_finish_layer(cfg, lp, x, out), kc, vc), ()

        (x, k_new, v_new), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], adp, jnp.arange(cfg.n_layers)))
    else:
        flash = whole_prompt and prefill_attn_impl(
            cfg, tokens.shape[1], mesh) == "flash"

        def body(x, layer_in):
            lp, (k_c, v_c), lo = _layer_operands(layer_in, aid)
            if flash:
                y, k_c, v_c = _prefill_layer(cfg, lp, x, cos, sin, k_c,
                                             v_c, pos, lora=lo, mesh=mesh)
            else:
                y, k_c, v_c = _layer(cfg, lp, x, cos, sin, k_c, v_c, pos,
                                     lora=lo)
            return y, (k_c, v_c)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], adp, (cache["k"], cache["v"])))
    if head_at is not None:
        x = jax.lax.dynamic_slice_in_dim(x, head_at, 1, axis=1)
    elif last_only:
        x = x[:, -1:]
    logits = _lm_head(cfg, params, x)
    new_cache = {"k": k_new, "v": v_new,
                 "pos": pos + tokens.shape[1]}
    return logits, new_cache


def _layer_operands(layer_in, aid):
    """What a layer scan hands its body, ``(lp, adp_l, rode)`` — one
    layer's params, its slice of the stacked LoRA arrays (None on an
    adapterless ring: an empty node of the scanned tree, so the traced
    program is the adapterless one) and whatever else rides the scan —
    as ``(lp, rode, lora)`` with ``lora`` the ``(adp_l, aid)`` the
    projections take (:func:`_qkv_proj`)."""
    lp, adp_l, rode = layer_in
    return lp, rode, (None if adp_l is None else (adp_l, aid))


# ---------------------------------------------------------------------------
# The ONE cached forward at per-lane positions, over a cache view
# ---------------------------------------------------------------------------


def _cached_layer(cfg: LlamaConfig, lp: Dict[str, Any], x: jax.Array,
                  cos: jax.Array, sin: jax.Array, view, bufs, li, lora):
    """One decoder layer over [B, T] new tokens, lane b's at positions
    ``view.pos[b] + j``: projections, rotation at each row's own
    position, the view's write, the view's attention, output projection
    and feed-forward.  :func:`_layer`'s mathematics with the scalar
    position a vector and the cache behind a view."""
    t = x.shape[1]
    h = _rms(x, lp["attn_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    q, k, v = _qkv_proj(cfg, lp, h, t, lora)
    rows = (view.pos if t == 1
            else view.pos[:, None] + jnp.arange(t)[None, :])    # [B, T]
    q, k = _rope_lanes(q, k, cos, sin, rows)
    bufs = view.write(bufs, li, k, v)
    out = view.attend(bufs, li, q, rows, lp["attn"]["wo"]["kernel"])
    if view.projects:
        # the TP-sharded kernel applied wo inside its manual region
        x = x + out[:, None].astype(cfg.dtype)
        return _ffn_residual(cfg, lp, x), bufs
    return _finish_layer(cfg, lp, x, out), bufs


def _cached_layers(cfg: LlamaConfig, params: Dict[str, Any],
                   toks: jax.Array, view, lora):
    """Embed ``toks [B, T]`` and scan :func:`_cached_layer` over the
    stacked layers -> (hidden states, the view's buffers after the
    writes).  The view says how its buffers ride the scan
    (``view.begin``): whole, as carry, beside the layer's index — the
    decode kernels and every paged pool — or a layer at a time as xs."""
    adp, aid = lora if lora is not None else (None, None)
    x = _embed(cfg, params, toks)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    held, rode = view.begin(toks.shape[1])

    def body(carry, layer_in):
        x, held = carry
        lp, rode, lo = _layer_operands(layer_in, aid)
        bufs, li = (held, rode) if view.stacked else (rode, None)
        x, bufs = _cached_layer(cfg, lp, x, cos, sin, view, bufs, li, lo)
        return ((x, bufs), ()) if view.stacked else ((x, ()), bufs)

    (x, held), rode = jax.lax.scan(body, (x, held),
                                   (params["layers"], adp, rode))
    return x, (held if view.stacked else rode)


def cached_forward(cfg: LlamaConfig, params: Dict[str, Any],
                   toks: jax.Array, view, *, lora=None, head: bool = True
                   ) -> Tuple[Optional[jax.Array], Dict[str, jax.Array]]:
    """[B, T] new tokens, lane b's at ``view.pos[b] + j`` -> ([B, T,
    vocab] logits, the cache ``T`` rows a lane further): the speculative
    verify, the prefix cache's suffix insert, a chunked prefill's slice.
    The cache comes as a view (:class:`ContiguousView`; infer/paged.py
    ``paged_view``), chosen by whoever builds the program from the cache
    it holds; under a serving mesh every einsum rides GSPMD off the
    param and cache shardings.

    ``lora``: ``(adp, aid)`` as in :func:`_forward`.  ``head=False``
    skips the final norm + lm head and returns ``(None, cache)``: an
    intermediate prefill slice only appends KV, and head logits over a
    whole slice are the biggest tensor in the prefill path."""
    x, bufs = _cached_layers(cfg, params, toks, view, lora)
    new_cache = view.end(bufs, toks.shape[1])
    if not head:
        return None, new_cache
    return _lm_head(cfg, params, x), new_cache


def cached_step(cfg: LlamaConfig, params: Dict[str, Any], tok: jax.Array,
                view, *, lora=None
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The decode step, :func:`cached_forward` at one token a lane:
    ``tok [B]`` at ``view.pos`` -> (logits [B, vocab], the cache one row
    a lane further).  Where T is 1 a view may take the decode kernel
    (ops/decode_attention.py) for its attention.  Written out rather
    than ``cached_forward(...)[:, 0]``: the step takes its head before
    the position's advance and the multi-token forward after, as the
    programs pinned on ISSUE 30's parent have them
    (tests/test_llama_ring_pinned.py)."""
    x, bufs = _cached_layers(cfg, params, tok[:, None], view, lora)
    logits = _lm_head(cfg, params, x)[:, 0]
    return logits, view.end(bufs, 1)


def prefill_with_step(cfg: LlamaConfig, params: Dict[str, Any],
                      prompt: jax.Array, prompt_len: jax.Array,
                      tok: jax.Array, view, *, mesh=None
                      ) -> Tuple[jax.Array, Dict[str, jax.Array],
                                 Dict[str, jax.Array]]:
    """A whole-prompt prefill of ``prompt [1, W]`` AND one decode step
    of the view's ``tok [B]`` lanes in ONE scan over the layers: the
    lanes' rows ride the prompt's read of ``wo``, the feed-forward's
    three matrices and the head — nine tenths of a layer's weight bytes
    — concatenated to the prompt's rows at those products (an insert
    reads every layer once for W rows; a step of its own reads them all
    again for B).  Before attention the two stay apart, each through its
    own norm and q/k/v products: the prompt as :func:`_forward` has it
    for ``whole_prompt`` — :func:`_qkv` from position 0, its own lane
    cache, the flash kernel where :func:`prefill_attn_impl` says so,
    else the einsum over the cache just written — and each lane as
    :func:`_cached_layer` has it: rotated at its own ``view.pos``, its
    row written through the view, attention by the decode kernel over
    the tick's work list, or the einsum.  (One q/k/v product over all
    the rows was measured: the lanes' kernel asks another layout of the
    split heads than the flash kernel, and the compiler then copies and
    re-rotates the prompt's q a layer, 18 ms an insert at 3072 where the
    second read of the three matrices is under one: PERF.md section 6,
    PR 33.)  The head runs once over ``1 + B`` rows: the prompt's hidden
    state at ``prompt_len - 1``, sliced out before the final norm, and
    the lanes'.

    Returns ``(logits [1 + B, vocab], the prompt's lane cache {k, v}
    [L, 1, H_kv, alloc, D], the view's cache one row a lane further)``;
    which lanes the step counts for is the view's ``lane_mask`` and the
    caller's business, as in the ring's step."""
    w, b = prompt.shape[1], tok.shape[0]
    lane = init_cache(cfg, 1, w)
    pos0 = lane["pos"]
    x = jnp.concatenate([_embed(cfg, params, prompt),
                         _embed(cfg, params, tok[None, :])], axis=1)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    flash = prefill_attn_impl(cfg, w, mesh) == "flash"
    held, layer_ids = view.begin(1)

    def body(carry, layer_in):
        x, held = carry
        lp, (k_c, v_c), li = layer_in
        # the prompt's rows: rotation from 0, its own lane cache
        q, k, v = _qkv(cfg, lp, x[:, :w], cos, sin, pos0)
        k_c, v_c = _write_rows(k_c, v_c, k, v, pos0)
        out_p = (_flash_prompt_attn(cfg, q, k, v, mesh) if flash
                 else _attend_cache(cfg, q, k_c, v_c, pos0))
        # the lanes' rows: one a lane, at the lane's own position
        h = _rms(x[0, w:, None], lp["attn_norm"]["scale"], cfg.norm_eps,
                 cfg.dtype)
        q, k, v = _qkv_proj(cfg, lp, h, 1)
        q, k = _rope_lanes(q, k, cos, sin, view.pos)
        held = view.write(held, li, k, v)
        out_d = view.attend(held, li, q, view.pos,
                            lp["attn"]["wo"]["kernel"])
        out = jnp.concatenate([out_p, out_d.reshape(1, b, -1)], axis=1)
        return (_finish_layer(cfg, lp, x, out), held), (k_c, v_c)

    (x, held), (lane_k, lane_v) = jax.lax.scan(
        body, (x, held),
        (params["layers"], (lane["k"], lane["v"]), layer_ids))
    last = jax.lax.dynamic_slice_in_dim(x, prompt_len - 1, 1, axis=1)
    logits = _lm_head(cfg, params,
                      jnp.concatenate([last, x[:, w:]], axis=1))[0]
    return logits, {"k": lane_k, "v": lane_v}, view.end(held, 1)


def prefill(params: Dict[str, Any], cfg: LlamaConfig, tokens: jax.Array,
            max_len: Optional[int] = None, mesh=None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Process the whole prompt [B, S] in one pass.  Returns
    ([B, vocab] last-position logits, filled cache)."""
    cache_len = max_len or cfg.max_seq_len
    if tokens.shape[1] > cache_len:
        raise ValueError(f"prompt length {tokens.shape[1]} exceeds the "
                         f"cache ({cache_len} positions)")
    cache = init_cache(cfg, tokens.shape[0], max_len, mesh=mesh)
    logits, cache = _forward(cfg, params, tokens, cache, last_only=True,
                             mesh=mesh, whole_prompt=True)
    return logits[:, 0], cache


def decode_step(params: Dict[str, Any], cfg: LlamaConfig,
                token: jax.Array, cache: Dict[str, jax.Array],
                mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One token [B] -> next-position logits [B, vocab] + advanced cache."""
    logits, cache = _forward(cfg, params, token[:, None], cache, mesh=mesh)
    return logits[:, 0], cache


def make_decode_fn(cfg: LlamaConfig, mesh=None):
    """Jitted single-token step with the cache DONATED: driving
    decode_step yourself (serving loops, speculative drafts) without
    donation would copy the whole KV cache every step — for a 7B-shaped
    cache that is gigabytes of HBM traffic per token.  Inside
    :func:`generate` the scan already keeps the cache on-device, so this
    matters only for host-driven loops.

    Returns ``step(params, token [B], cache) -> (logits [B, V], cache)``;
    the passed cache buffer is consumed."""

    def step(params, token, cache):
        logits, cache = _forward(cfg, params, token[:, None], cache,
                                 mesh=mesh)
        return logits[:, 0], cache

    return jax.jit(step, donate_argnums=(2,))


def _filter_logits(logits: jax.Array, top_k: Optional[int],
                   top_p: Optional[float]) -> jax.Array:
    """Standard sampling filters, static-shaped: top-k keeps the k highest
    logits; top-p (nucleus) keeps the smallest set of tokens whose
    probability mass reaches p.  Filtered entries go to -inf."""
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens until the cumulative mass FIRST exceeds p (the
        # token crossing the threshold is kept — standard nucleus rule)
        keep_sorted = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


@jax.named_scope("sample")
def _sample_tokens(logits, temp, keys, pos, top_k, top_p):
    """THE per-lane sampling rule — shared by the chunk step and EVERY
    admission insert (inline, chunked final, suffix, disagg) so token 1
    and tokens 2..N can never be drawn under different rules.  logits
    [B, V], temp [B], keys [B, 2], pos [B] -> [B] int32: greedy at temp
    0, else per-lane fold_in(position) (deterministic given (seed,
    pos), independent across lanes and steps) feeding temperature +
    top-k/top-p filtered categorical sampling."""
    greedy = logits.argmax(-1).astype(jnp.int32)
    filt = _filter_logits(
        logits / jnp.maximum(temp, 1e-6)[:, None], top_k, top_p)
    sub = jax.vmap(jax.random.fold_in)(keys, pos)
    drawn = jax.vmap(
        lambda k, l: jax.random.categorical(k, l))(sub, filt)
    return jnp.where(temp > 0, drawn.astype(jnp.int32), greedy)


def _mega_advance(toks, raw, live, left, eos):
    """On-device continuation bookkeeping at one fused-iteration
    boundary of a megastep (ISSUE 11) — the EXACT decision the host
    makes between two 1-step dispatches, in compiled form so N ring
    iterations can run without a host round-trip.

    ``toks`` [T, B] is the boundary's emitted tokens (a chunk's ticks,
    or a spec round's committed block), ``raw`` [B] the device-valid
    row count per lane (``chunk`` for plain chunks, ``n_commit`` for
    spec rounds, 0 for lanes that sat the iteration out), ``live`` [B]
    the continuation mask at the iteration's START, ``left`` [B] the
    per-lane remaining token budget and ``eos`` [B] the per-lane eos id
    (-1: none).  Returns ``(count, live', left')``: the tokens the host
    will actually consume for this boundary (up to and INCLUDING an
    eos, capped by the budget — the same walk scheduler._consume runs),
    and the advanced continuation state.  A lane that saw eos or
    exhausted its budget goes dead and free-runs masked until the
    megastep ends."""
    t = toks.shape[0]
    idx = jnp.arange(t)[:, None]
    hitv = (eos[None, :] >= 0) & (toks == eos[None, :])
    hit = hitv.astype(jnp.int32)
    eos_before = (jnp.cumsum(hit, axis=0) - hit) > 0
    valid = ((idx < raw[None, :]) & ~eos_before
             & (idx < left[None, :]) & live[None, :])
    count = valid.sum(axis=0).astype(jnp.int32)
    saw_eos = (hitv & valid).any(axis=0)
    left2 = left - count
    live2 = live & ~saw_eos & (left2 > 0)
    return count, live2, left2


def _mega_continue(toks, raw, live, left, steps, eos):
    """The WHOLE per-boundary continuation update, shared by every
    megastep builder (contiguous, paged, spec) so the token-budget walk
    and the step-budget decrement can never drift between them:
    :func:`_mega_advance` plus the deadline-tick step accounting.
    Returns ``(count, live', left', steps')``."""
    count, live2, left2 = _mega_advance(toks, raw, live, left, eos)
    steps2 = steps - live.astype(jnp.int32)
    live2 = live2 & (steps2 > 0)
    return count, live2, left2, steps2


def generate(params: Dict[str, Any], cfg: LlamaConfig, prompt: jax.Array,
             *, max_new_tokens: int, temperature: float = 0.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             key: Optional[jax.Array] = None,
             max_len: Optional[int] = None,
             eos_token: Optional[int] = None, mesh=None) -> jax.Array:
    """Greedy (temperature=0) or temperature sampling, with optional
    top-k / nucleus (top-p) filtering.  prompt [B, S] ->
    [B, S + max_new_tokens].  jit-friendly: the step loop is a lax.scan
    with static trip count (shapes never depend on when sequences stop).
    With ``eos_token``, a sequence that emits it keeps emitting eos for
    its remaining positions (the scan still runs max_new_tokens ticks —
    static shapes beat early exit on TPU).

    ``mesh`` (make_serving_mesh) serves tensor-parallel: params must be
    laid out with :func:`shard_params_for_serving`; output tokens are
    identical to the single-device path (same math, head-sharded)."""
    if temperature > 0 and key is None:
        key = jax.random.PRNGKey(0)
    need = prompt.shape[1] + max_new_tokens
    cache_len = max_len or cfg.max_seq_len
    if need > cache_len:
        raise ValueError(f"prompt ({prompt.shape[1]}) + max_new_tokens "
                         f"({max_new_tokens}) = {need} exceeds the cache "
                         f"({cache_len} positions)")

    logits, cache = prefill(params, cfg, prompt, max_len, mesh=mesh)
    done0 = jnp.zeros((prompt.shape[0],), bool)

    def sample(logits, k):
        if temperature <= 0:
            return logits.argmax(-1).astype(prompt.dtype)
        logits = _filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(k, logits).astype(prompt.dtype)

    def step(carry, k):
        logits, cache, done = carry
        tok = sample(logits, k)
        if eos_token is not None:
            tok = jnp.where(done, jnp.asarray(eos_token, tok.dtype), tok)
            done = done | (tok == eos_token)
        logits, cache = decode_step(params, cfg, tok, cache, mesh=mesh)
        return (logits, cache, done), tok

    keys = (jax.random.split(key, max_new_tokens) if temperature > 0
            else jnp.zeros((max_new_tokens, 2), jnp.uint32))
    (_, _, _), toks = jax.lax.scan(step, (logits, cache, done0), keys)
    return jnp.concatenate([prompt, toks.T], axis=1)
