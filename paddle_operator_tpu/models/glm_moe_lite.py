"""The ``glm4_moe_lite`` decoder (GLM-4.7-Flash): multi-head latent
attention (MLA) over the sigmoid-routed expert layer ``models/afmoe.py``
already has — low-rank queries, ONE cached row a token a layer shared by
all heads (a normed latent and a rotated key), keys and values expanded
from the latent for a prompt and never for a decode step.

One definition of the architecture for the serving paths
(``infer/afmoe_serve.py``: the expert stack's ``generate`` forward and the
paged ring's step and insert).  The trainer refuses it
(``models/llama.py make_model``), as it refuses ``afmoe``.

Layer equations (``transformers`` ``modeling_glm4_moe_lite.py``, after
``modeling_deepseek_v3.py``), with ``u = input_norm(x)``::

    a   = x + Attn(u)
    y   = a + FFN(post_attn_norm(a))
    Attn: c_q = q_a_norm(u Wqa);  q = c_q Wqb -> heads of [nope | rope]
          [c_kv | k_pe] = u Wkva;  c = kv_a_norm(c_kv)
          q_pe, k_pe = RoPE(q_pe), RoPE(k_pe)     (k_pe: one for all heads)
          THE CACHED ROW is [c | k_pe]: after the norm, after the rotation
      expanded (a prompt; the reference everywhere):
          [k_nope | v]_h = c Wkvb_h;  k_h = [k_nope_h | k_pe]
          s = q_h k_h / sqrt(nope + rope);  o_h = softmax(s) v_h
      absorbed (a decode step; equal in exact arithmetic):
          Wkvb_h = [Wuk_h | Wuv_h];  q_lat_h = q_nope_h Wuk_h^T
          s = (q_lat_h c + q_pe_h k_pe) / sqrt(nope + rope)
          o_h = (softmax(s) c) Wuv_h
          out = concat_h(o_h) Wo
    FFN:  SwiGLU(ffn_dim) on the first n_dense_layers; on the others
          ``models/afmoe.py moe_ffn`` at this configuration's numbers
          (sigmoid scores, top_k of score + bias, the unbiased scores of
          the selected normalised and scaled; a shared expert).

RoPE pairs dimensions ``(2i, 2i + 1)`` of the rope part (the family's
``rope_interleave``) and leaves the rotated halves apart, ``[evens |
odds]``, as the published code does: q and k are laid out alike, so the
scores are those of the interleaved layout.

Parameter tree (serving layout; kernels ``[in, out]``).  ``kv_b_proj`` is
held as its two halves, so that the decode step's absorbed products take
whole leaves (a slice of one leaf a layer a step would be a copy)::

    tok_embed/embedding [V, D]   final_norm/scale [D]   lm_head/kernel [D, V]
    dense_layers/...  stacked [n_dense_layers, ...]
    moe_layers/...    stacked [n_layers - n_dense_layers, ...]
      attn/q_a/kernel [D, q_rank]   attn/q_a_norm/scale [q_rank]
      attn/q_b/kernel [q_rank, H * (nope + rope)]
      attn/kv_a/kernel [D, kv_rank + rope]   attn/kv_a_norm/scale [kv_rank]
      attn/kv_b_k/kernel [kv_rank, H * nope]   attn/kv_b_v/kernel [kv_rank, H * v]
      attn/wo/kernel [H * v, D]
      {input,post_attn}_norm/scale [D]
      mlp/{w1,w3,w2}/kernel                     (dense layers)
      moe/...                                   (as models/afmoe.py)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from paddle_operator_tpu.models import afmoe as A
from paddle_operator_tpu.models.afmoe import (  # noqa: F401  (the stack's)
    layer_at,
    rms,
    mm,
    split_experts,
)


@dataclasses.dataclass(frozen=True)
class GlmMoeLiteConfig:
    vocab_size: int = 154880
    dim: int = 2048
    n_layers: int = 47
    n_dense_layers: int = 1
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    ffn_dim: int = 10240
    moe_ffn_dim: int = 1536
    n_experts: int = 64
    top_k: int = 4
    n_shared_experts: int = 1
    route_scale: float = 1.8
    route_norm: bool = True
    max_seq_len: int = 8192
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # compute dtype
    param_dtype: Any = jnp.bfloat16  # storage dtype
    decode_attn: str = "auto"        # as LlamaConfig.decode_attn

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    # A head of the EXPANDED form, as the flash kernel and the insert's
    # rule (infer/decode.py prefill_attn_impl) see one: a key head a
    # query head, nope and rope parts side by side.
    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_kv_heads(self) -> int:
        return self.n_heads

    @property
    def cache_row(self) -> int:
        """Values a token a layer the cache holds: ``[c | k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def cache_buffers(self) -> Dict[str, Tuple[int, int]]:
        """The cache's buffers, name -> (heads, width): the latents and
        the rotated keys, one row of each for all heads."""
        return {"c": (1, self.kv_lora_rank), "pe": (1, self.qk_rope_head_dim)}

    def resolved_decode_attn(self) -> str:
        """``LlamaConfig.resolved_decode_attn``'s rule for the latent
        kernel: on the TPU when the latent is lane-aligned, the einsum
        elsewhere."""
        if self.decode_attn == "auto":
            if self.kv_lora_rank % 128:
                return "xla"
            return "pallas" if jax.default_backend() == "tpu" else "xla"
        return self.decode_attn


CONFIGS = {
    # the real structure at test widths, no two sizes alike: nope 24,
    # rope 8, values 16, ranks under the hidden size, 1 dense + 2 expert
    # layers
    "glm-lite-tiny": GlmMoeLiteConfig(
        vocab_size=256, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
        q_lora_rank=40, kv_lora_rank=48, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=16, ffn_dim=96, moe_ffn_dim=32,
        n_experts=8, top_k=2, max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def param_shapes(cfg: GlmMoeLiteConfig) -> Dict[str, Any]:
    """The parameter tree as ``ShapeDtypeStruct``s."""
    d, f, fm, e = cfg.dim, cfg.ffn_dim, cfg.moe_ffn_dim, cfg.n_experts
    h, rq, rkv = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    fs = fm * cfg.n_shared_experts
    dt = jnp.dtype(cfg.param_dtype)

    def leaf(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def kern(n, i, o):
        return {"kernel": leaf(n, i, o)}

    def block(n):
        return {
            "attn": {"q_a": kern(n, d, rq), "q_a_norm": {"scale": leaf(n, rq)},
                     "q_b": kern(n, rq, h * cfg.head_dim),
                     "kv_a": kern(n, d, cfg.cache_row),
                     "kv_a_norm": {"scale": leaf(n, rkv)},
                     "kv_b_k": kern(n, rkv, h * cfg.qk_nope_head_dim),
                     "kv_b_v": kern(n, rkv, h * cfg.v_head_dim),
                     "wo": kern(n, h * cfg.v_head_dim, d)},
            "input_norm": {"scale": leaf(n, d)},
            "post_attn_norm": {"scale": leaf(n, d)},
        }

    nd, nm = cfg.n_dense_layers, cfg.n_moe_layers
    dense = block(nd)
    dense["mlp"] = {"w1": kern(nd, d, f), "w3": kern(nd, d, f),
                    "w2": kern(nd, f, d)}
    moe = block(nm)
    moe["moe"] = {
        "router": kern(nm, d, e),
        "expert_bias": leaf(nm, e, dtype=jnp.float32),
        "shared": {"w1": kern(nm, d, fs), "w3": kern(nm, d, fs),
                   "w2": kern(nm, fs, d)},
        "experts": {"w1": leaf(nm, e, d, fm), "w3": leaf(nm, e, d, fm),
                    "w2": leaf(nm, e, fm, d)},
    }
    return {
        "tok_embed": {"embedding": leaf(cfg.vocab_size, d)},
        "final_norm": {"scale": leaf(d)},
        "lm_head": {"kernel": leaf(d, cfg.vocab_size)},
        "dense_layers": dense, "moe_layers": moe,
    }


def init_params(cfg: GlmMoeLiteConfig, rng: jax.Array) -> Dict[str, Any]:
    """Smoke-mode weights, ``models/afmoe.py init_params``'s rule over
    this tree."""
    return A.init_tree(param_shapes(cfg), rng)


# ---------------------------------------------------------------------------
# The block, piece by piece (named scopes: PERF.md section 3)
# ---------------------------------------------------------------------------


@jax.named_scope("embed")
def embed(cfg: GlmMoeLiteConfig, params, tokens: jax.Array) -> jax.Array:
    return params["tok_embed"]["embedding"].astype(cfg.dtype)[tokens]


lm_head = A.lm_head


def rope_tables(cfg: GlmMoeLiteConfig) -> Tuple[jax.Array, jax.Array]:
    from paddle_operator_tpu.models.llama import rope_frequencies

    return rope_frequencies(cfg.qk_rope_head_dim, cfg.max_seq_len,
                            cfg.rope_theta)


def layer_kinds(cfg: GlmMoeLiteConfig) -> tuple:
    """What differs from layer to layer besides the weights: nothing."""
    return ()


def kernel_windows(cfg: GlmMoeLiteConfig) -> tuple:
    """Per layer the window of the decode kernel's work list: every
    layer attends the whole context."""
    return (None,) * cfg.n_layers


def whole_prompt_flash(cfg: GlmMoeLiteConfig) -> bool:
    """Whether a whole-prompt insert may attend its expanded heads
    through the flash kernel (which takes q, k and v of one width)."""
    return cfg.v_head_dim == cfg.head_dim


@jax.named_scope("attn.rope")
def rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
         pos: jax.Array) -> jax.Array:
    """``x [B, T, H, rope]`` at positions ``pos [B, T]``: pairs
    ``(2i, 2i + 1)`` rotated by the i-th frequency, the halves left
    apart (``[evens | odds]``)."""
    cos_p, sin_p = cos[pos][:, :, None, :], sin[pos][:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * cos_p - x2 * sin_p,
                            x2 * cos_p + x1 * sin_p], axis=-1).astype(x.dtype)


def attn_inputs(cfg: GlmMoeLiteConfig, lp, x: jax.Array, cos: jax.Array,
                sin: jax.Array, pos: jax.Array):
    """``x [B, T, D]`` at positions ``pos [B, T]`` -> the queries' parts
    ``q_nope [B, T, H, nope]``, ``q_pe [B, T, H, rope]`` (rotated) and
    the row to cache, ``c [B, T, 1, kv_rank]`` (normed) and ``k_pe
    [B, T, 1, rope]`` (rotated)."""
    b, t, _ = x.shape
    at = lp["attn"]
    u = rms(x, lp["input_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    with jax.named_scope("attn.q_lora"):
        c_q = rms(mm(u, at["q_a"]["kernel"], cfg.dtype),
                  at["q_a_norm"]["scale"], cfg.norm_eps, cfg.dtype)
        q = mm(c_q, at["q_b"]["kernel"], cfg.dtype).reshape(
            b, t, cfg.n_heads, cfg.head_dim)
    with jax.named_scope("attn.kv_lora"):
        kv = mm(u, at["kv_a"]["kernel"], cfg.dtype)[:, :, None, :]
        c = rms(kv[..., :cfg.kv_lora_rank], at["kv_a_norm"]["scale"],
                cfg.norm_eps, cfg.dtype)
    q_nope, q_pe = (q[..., :cfg.qk_nope_head_dim],
                    q[..., cfg.qk_nope_head_dim:])
    return (q_nope, rope(q_pe, cos, sin, pos), c,
            rope(kv[..., cfg.kv_lora_rank:], cos, sin, pos))


@jax.named_scope("attn.expand")
def expand(cfg: GlmMoeLiteConfig, lp, c: jax.Array, k_pe: jax.Array):
    """Cached rows ``c [B, S, kv_rank]``, ``k_pe [B, S, rope]`` -> every
    head's key and value, ``k [B, S, H, nope + rope]`` and ``v [B, S, H,
    v]``."""
    b, s, _ = c.shape
    k_nope = mm(c, lp["attn"]["kv_b_k"]["kernel"], cfg.dtype).reshape(
        b, s, cfg.n_heads, cfg.qk_nope_head_dim)
    v = mm(c, lp["attn"]["kv_b_v"]["kernel"], cfg.dtype).reshape(
        b, s, cfg.n_heads, cfg.v_head_dim)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :],
                            (b, s, cfg.n_heads, cfg.qk_rope_head_dim))
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


@jax.named_scope("attn.absorb")
def absorb_query(cfg: GlmMoeLiteConfig, lp, q_nope: jax.Array) -> jax.Array:
    """The nope part of the query against the cached latent itself,
    ``[B, T, H, kv_rank]``: ``Wuk`` folded into it."""
    w_uk = lp["attn"]["kv_b_k"]["kernel"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim)
    return jnp.einsum("bthn,chn->bthc", q_nope, w_uk)


@jax.named_scope("attn.absorb")
def absorb_output(cfg: GlmMoeLiteConfig, lp, o_lat: jax.Array) -> jax.Array:
    """The latent the heads attended ``[B, T, H, kv_rank]`` -> their
    values ``[B, T, H * v]``: ``Wuv`` applied after the softmax."""
    w_uv = lp["attn"]["kv_b_v"]["kernel"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim)
    o = jnp.einsum("bthc,chv->bthv", o_lat.astype(cfg.dtype), w_uv)
    return o.reshape(*o.shape[:2], cfg.n_heads * cfg.v_head_dim)


@jax.named_scope("attn.kernel")
def attend_expanded(cfg: GlmMoeLiteConfig, q: jax.Array, k: jax.Array,
                    v: jax.Array, q_pos: jax.Array) -> jax.Array:
    """Einsum attention of ``q [B, T, H, hd]`` at positions ``q_pos
    [B, T]`` over expanded ``k [B, S, H, hd]``, ``v [B, S, H, v]`` at
    positions ``0..S-1``, causal.  Returns ``[B, T, H * v]``."""
    scores = jnp.einsum("bthd,bshd->bhts", q, k,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(cfg.head_dim))
    seen = q_pos[:, :, None] >= jnp.arange(k.shape[1])[None, None, :]
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bhts,bshv->bthv", probs.astype(cfg.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(*q.shape[:2], -1).astype(cfg.dtype)


def attention(cfg: GlmMoeLiteConfig, lp, x: jax.Array, tables, q_pos, view,
              bufs, li, kind=(), cells=None, flash: bool = False,
              blocks=None):
    """The attention half of a block over a cache view -> ``(x + Attn,
    the view's buffers after the write)``.  Three ways to attend, chosen
    before tracing: a decode step through the view's kernel and, where
    that is off, through its einsum twin, both ABSORBED (the cached rows
    are read as they lie); a whole-prompt insert the flash kernel runs
    for (`flash`, with the kernel's `blocks`), EXPANDED from the prompt's
    own rows; anything else the expanded einsum over the view's lanes (the
    oracle's path)."""
    q_nope, q_pe, c, k_pe = attn_inputs(cfg, lp, x, *tables, q_pos)
    bufs = view.write(bufs, li, c, k_pe)
    if x.shape[1] == 1:
        q_lat = absorb_query(cfg, lp, q_nope)
        if view.kernel:
            o_lat = view.kernel_attend(bufs, li, q_lat, q_pe, cells=cells)
        else:
            from paddle_operator_tpu.ops.decode_attention import (
                latent_decode_attention_reference,
            )

            lat, pe = view.lanes(bufs, li)
            with jax.named_scope("attn.kernel"):
                o_lat = latent_decode_attention_reference(
                    q_lat[:, 0], q_pe[:, 0], lat[:, 0], pe[:, 0],
                    q_pos[:, 0] + 1, cfg.head_dim ** -0.5)[:, None]
        att = absorb_output(cfg, lp, o_lat)
    else:
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        if flash:
            from paddle_operator_tpu.ops.attention import attention as attn

            k, v = expand(cfg, lp, c[:, :, 0], k_pe[:, :, 0])
            att = attn(q, k, v, causal=True, use_pallas=True,
                       blocks=blocks).reshape(*x.shape[:2], -1).astype(
                           cfg.dtype)
        else:
            lat, pe = view.lanes(bufs, li)
            k, v = expand(cfg, lp, lat[:, 0], pe[:, 0])
            att = attend_expanded(cfg, q, k, v, q_pos)
    with jax.named_scope("attn.out"):
        o = mm(att, lp["attn"]["wo"]["kernel"], cfg.dtype)
    return x + o, bufs


def ffn_residual(cfg: GlmMoeLiteConfig, lp, a: jax.Array, experts=None,
                 layer=None, counted=None):
    """The feed-forward half of a block -> ``(y, load [E] or None)``:
    ``models/afmoe.py``'s expert layer `layer` of the stacked `experts`,
    or with none the dense SwiGLU."""
    n = rms(a, lp["post_attn_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    with jax.named_scope("ffn"):
        if experts is not None:
            f, load = A.moe_ffn(cfg, lp["moe"], experts, layer, n, counted)
        else:
            f, load = A.swiglu(n, lp["mlp"], cfg.dtype), None
    return a + f, load
