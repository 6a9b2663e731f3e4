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
    decode._forward and batcher._ring_forward: the pallas kernel enters
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
# infer/executor.py, infer/paged.py, infer/speculative.py,
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
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, alloc, cfg.head_dim)
    return {
        "k": alloc_kv_buffer(cfg, shape, mesh),
        "v": alloc_kv_buffer(cfg, shape, mesh),
        "pos": jnp.zeros((), jnp.int32),
    }


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
                  v_cache: jax.Array, pos: jax.Array) -> jax.Array:
    """The XLA einsum attention of [B, T] new positions starting at
    ``pos`` against head-major caches [B, H_kv, S, D] -> [B, T, Hq*D].
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
    # scores [B, T, Hkv, n_rep, max_len]; rows may attend cache cols
    # up to their own absolute position (causal + fill mask in one)
    scores = jnp.einsum("bthrd,bhsd->bthrs", qg, k_cache,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    cols = jnp.arange(max_len)                           # [S]
    rows = pos + jnp.arange(t)                           # [T]
    mask = cols[None, :] <= rows[:, None]                # [T, S]
    scores = jnp.where(mask[None, :, None, None, :], scores, -1e30)
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
    from paddle_operator_tpu.ops.attention import attention

    q, k, v = _qkv(cfg, lp, x, cos, sin, pos, lora=lora)
    k_cache, v_cache = _write_rows(k_cache, v_cache, k, v, pos)
    out = attention(q, k, v, causal=True, use_pallas=True, mesh=mesh,
                    blocks=_prefill_blocks(x.shape[1]))
    out = out.reshape(*x.shape[:2], cfg.n_heads * cfg.head_dim)
    return _finish_layer(cfg, lp, x, out.astype(cfg.dtype)), k_cache, v_cache


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
             mesh=None, lora=None, whole_prompt: bool = False
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """[B, T] new tokens at cache['pos'] -> ([B, T, vocab] logits,
    advanced cache).  Layers run under lax.scan over the stacked params
    (the same ``layers`` layout nn.scan trains).

    ``last_only``: apply the norm + lm head to the final position only
    (logits [B, 1, vocab]) — prefill needs just the next-token logits,
    and head logits over a whole long prompt are the biggest tensor in
    the decode path ([B, S, V] f32 — gigabytes at real vocab sizes).

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
    :func:`paged_prefill` and the ring's whole-prompt inserts, never by
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
            head_at=tokens.shape[1] - 1 if last_only else None)
        return logits, cache
    pos = cache["pos"]
    adp, aid = lora if lora is not None else (None, None)
    x = _embed(cfg, params, tokens)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)

    attn_impl, use_sharded = resolve_decode_attn(cfg, mesh)
    if tokens.shape[1] == 1 and use_sharded:
        # TP-sharded kernel: same stacked-cache scan as below, but the
        # attention + output projection run inside one manual region per
        # layer (ops/decode_attention.py sharded_decode_attention)
        from paddle_operator_tpu.ops.decode_attention import (
            sharded_decode_attention,
        )

        b = x.shape[0]

        def body(carry, layer_in):
            x, kc, vc = carry
            if adp is not None:
                lp, adp_l, li = layer_in
                lo = (adp_l, aid)
            else:
                lp, li = layer_in
                lo = None
            q, k, v = _qkv(cfg, lp, x, cos, sin, pos, lora=lo)
            kc = jax.lax.dynamic_update_slice(
                kc, k.transpose(0, 2, 1, 3)[None], (li, 0, 0, pos, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v.transpose(0, 2, 1, 3)[None], (li, 0, 0, pos, 0))
            proj = sharded_decode_attention(
                mesh, q[:, 0], kc, vc, jnp.broadcast_to(pos + 1, (b,)),
                lp["attn"]["wo"]["kernel"], layer=li,
                interpret=(attn_impl == "pallas-interpret"),
                compute_dtype=cfg.dtype)
            x = x + proj[:, None].astype(cfg.dtype)
            return (_ffn_residual(cfg, lp, x), kc, vc), ()

        xs = ((params["layers"], adp, jnp.arange(cfg.n_layers))
              if adp is not None
              else (params["layers"], jnp.arange(cfg.n_layers)))
        (x, k_new, v_new), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]), xs)
    elif tokens.shape[1] == 1 and attn_impl != "xla":
        # pallas decode path: the caches stay STACKED [L, B, H, S, D]
        # and flow as scan CARRY, with the layer index steering the
        # kernel's block index map.  Scanning them as xs (the einsum
        # structure below) would slice each layer out first, and a
        # dynamic-slice that feeds a pallas custom-call must be
        # materialized by XLA — a per-layer copy of the layer's whole
        # cache, measured +170us/layer at b8.
        from paddle_operator_tpu.ops.decode_attention import decode_attention

        b = x.shape[0]
        hq, d = cfg.n_heads, cfg.head_dim

        def body(carry, layer_in):
            x, kc, vc = carry
            if adp is not None:
                lp, adp_l, li = layer_in
                lo = (adp_l, aid)
            else:
                lp, li = layer_in
                lo = None
            q, k, v = _qkv(cfg, lp, x, cos, sin, pos, lora=lo)
            kc = jax.lax.dynamic_update_slice(
                kc, k.transpose(0, 2, 1, 3)[None], (li, 0, 0, pos, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v.transpose(0, 2, 1, 3)[None], (li, 0, 0, pos, 0))
            out = decode_attention(
                q[:, 0], kc, vc, jnp.broadcast_to(pos + 1, (b,)),
                layer=li, interpret=(attn_impl == "pallas-interpret"))
            out = out.reshape(b, 1, hq * d).astype(cfg.dtype)
            return (_finish_layer(cfg, lp, x, out), kc, vc), ()

        xs = ((params["layers"], adp, jnp.arange(cfg.n_layers))
              if adp is not None
              else (params["layers"], jnp.arange(cfg.n_layers)))
        (x, k_new, v_new), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]), xs)
    else:
        flash = whole_prompt and prefill_attn_impl(
            cfg, tokens.shape[1], mesh) == "flash"

        def body(x, layer_in):
            if adp is not None:
                lp, adp_l, k_c, v_c = layer_in
                lo = (adp_l, aid)
            else:
                lp, k_c, v_c = layer_in
                lo = None
            if flash:
                y, k_c, v_c = _prefill_layer(cfg, lp, x, cos, sin, k_c,
                                             v_c, pos, lora=lo, mesh=mesh)
            else:
                y, k_c, v_c = _layer(cfg, lp, x, cos, sin, k_c, v_c, pos,
                                     lora=lo)
            return y, (k_c, v_c)

        xs = ((params["layers"], adp, cache["k"], cache["v"])
              if adp is not None
              else (params["layers"], cache["k"], cache["v"]))
        x, (k_new, v_new) = jax.lax.scan(body, x, xs)
    if last_only:
        x = x[:, -1:]
    logits = _lm_head(cfg, params, x)
    new_cache = {"k": k_new, "v": v_new,
                 "pos": pos + tokens.shape[1]}
    return logits, new_cache


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


def paged_prefill(params: Dict[str, Any], cfg: LlamaConfig,
                  tokens: jax.Array, pool_cache: Dict[str, jax.Array],
                  table_row: jax.Array, *, block_size: Optional[int] = None,
                  mesh=None, quant: bool = False,
                  prompt_len: Optional[jax.Array] = None, lora=None):
    """Prefill a whole [1, bucket] prompt and write its KV into the
    PAGED block pool (infer/paged.py) as block-aligned chunks at the
    lane's ``table_row`` entries — the cold-admission half of paged
    serving.  The forward itself is exactly :func:`prefill`'s (same
    compiled ops — what keeps the paged ring's first token
    bit-identical to the contiguous ring's — and the same choice of
    attention by width, :func:`prefill_attn_impl`: the flash kernel
    over the prompt's own q, k, v where it runs, else the einsum over
    the lane cache); only the destination
    changes: block ``j`` of the lane cache lands in pool block
    ``table_row[j]``, pad blocks land wherever the table maps them
    (the trash block when unmapped — exactness-with-padding,
    block-granular).  Returns ([1, bucket, vocab] logits — the caller
    samples at ``prompt_len - 1`` — and the pool cache with this
    lane's position untouched (the caller's insert sets it).

    ``quant=True`` (needs ``prompt_len``, traced): whole blocks
    quantize ONCE on the way into the int8 pool
    (ops/decode_attention.py scatter_prefill_blocks_quant), and the
    prompt's partial last block is returned as exact bf16 tail tiles
    ``(logits, cache', tail_k, tail_v)`` [L, 1, H, bs, D] for the
    caller's insert to splice into the lane's staging tail — the one
    block whose scale cannot be final yet."""
    from paddle_operator_tpu.infer.paged import _scatter_prompt_blocks

    bs = block_size or pool_cache["k"].shape[3]
    lane = init_cache(cfg, 1, tokens.shape[1])
    logits, lane = _forward(cfg, params, tokens, lane, mesh=mesh,
                            lora=lora, whole_prompt=True)
    if not quant:
        k = _scatter_prompt_blocks(pool_cache["k"], lane["k"], table_row,
                                   bs)
        v = _scatter_prompt_blocks(pool_cache["v"], lane["v"], table_row,
                                   bs)
        return logits, {"k": k, "v": v, "pos": pool_cache["pos"]}
    from paddle_operator_tpu.ops.decode_attention import (
        scatter_prefill_blocks_quant,
    )

    if prompt_len is None:
        raise ValueError("quant paged_prefill needs prompt_len for the "
                         "staging-tail slice")
    k, ks = scatter_prefill_blocks_quant(
        pool_cache["k"], pool_cache["ks"], lane["k"], table_row, bs)
    v, vs = scatter_prefill_blocks_quant(
        pool_cache["v"], pool_cache["vs"], lane["v"], table_row, bs)
    # the write-frontier block's exact rows: [start, start + bs) of the
    # lane cache.  The lane alloc need not be a block multiple, and
    # dynamic_slice CLAMPS an out-of-range start backwards — which
    # would hand back rows of the PREVIOUS block at the wrong tail
    # offsets (positions start+o would attend K/V of start-pad+o) —
    # so pad the time axis up to a block multiple first.  The one
    # remaining clamp (block-aligned prompt filling the whole padded
    # alloc, start == padded len) is harmless: decode then begins a
    # FRESH block and every stale tail row sits behind the fill mask.
    L, _, h, t_alloc, dd = lane["k"].shape
    pad = -t_alloc % bs
    lane_k, lane_v = lane["k"], lane["v"]
    if pad:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
        lane_k = jnp.pad(lane_k, widths)
        lane_v = jnp.pad(lane_v, widths)
    start = (prompt_len // bs) * bs
    tail_k = jax.lax.dynamic_slice(lane_k, (0, 0, 0, start, 0),
                                   (L, 1, h, bs, dd))
    tail_v = jax.lax.dynamic_slice(lane_v, (0, 0, 0, start, 0),
                                   (L, 1, h, bs, dd))
    cache = {"k": k, "v": v, "ks": ks, "vs": vs, "kt": pool_cache["kt"],
             "vt": pool_cache["vt"], "pos": pool_cache["pos"]}
    return logits, cache, tail_k, tail_v


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


def speculative_generate(params, draft_params, cfg: LlamaConfig,
                         draft_cfg: LlamaConfig, prompt: jax.Array, **kw):
    """Draft-propose + chunked-verify counterpart of :func:`generate`:
    a small draft model (``LlamaConfig.draft()``) proposes ``spec_k``
    tokens per round and the target verifies all of them in one
    multi-token forward — token-identical to :func:`generate` at
    temperature 0, distribution-preserving (rejection sampling) above.
    Implementation and the full contract live in infer/speculative.py;
    this re-export keeps the serving entrypoints in one module."""
    from paddle_operator_tpu.infer.speculative import (
        speculative_generate as _impl,
    )

    return _impl(params, draft_params, cfg, draft_cfg, prompt, **kw)
