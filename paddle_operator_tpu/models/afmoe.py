"""The ``afmoe`` decoder (Arcee Trinity): sigmoid-routed sparse experts
beside a shared expert, sliding-window and full attention layers in one
stack, a gated attention output, QK-norm, sandwich norms.

One definition of the architecture for the serving paths
(``infer/afmoe_serve.py``: ``generate`` and the paged ring's step and
insert).  The trainer refuses it (``models/llama.py make_model``): a
dropless expert layer with its backward pass and the bias update are
ROADMAP B-I.

Layer equations (``transformers`` ``modeling_afmoe.py``)::

    x   = E[ids] * sqrt(dim)                                  (mup)
    a   = x + post_attn_norm(Attn(input_norm(x)))
    y   = a + post_mlp_norm(FFN(pre_mlp_norm(a)))
    Attn: q, k, v, g = Wq h, Wk h, Wv h, Wg h; RMSNorm over head_dim on
          each head of q and k; rotate-half RoPE on sliding layers only
          (a full layer has no positional encoding); causal scores
          q k^T / sqrt(head_dim), on a sliding layer key j visible to
          query i iff 0 <= i - j < sliding_window;
          out = Wo (softmax(.) v * sigmoid(g))
    FFN:  SwiGLU(ffn_dim) on the first n_dense_layers; on the others
          s = sigmoid(Wr h) in float32, selection top_k(s + bias),
          weights s_sel / (sum s_sel + 1e-20) * route_scale, experts
          SwiGLU(moe_ffn_dim), FFN = shared(h) + sum_k w_k expert_k(h).
          No capacity, no drops.

Parameter tree (serving layout; kernels ``[in, out]``)::

    tok_embed/embedding [V, D]   final_norm/scale [D]   lm_head/kernel [D, V]
    dense_layers/...  stacked [n_dense_layers, ...]
    moe_layers/...    stacked [n_layers - n_dense_layers, ...]
      attn/{wq,wk,wv,wo,wg}/kernel  attn/{q_norm,k_norm}/scale [head_dim]
      {input,post_attn,pre_mlp,post_mlp}_norm/scale [D]
      mlp/{w1,w3,w2}/kernel                     (dense layers)
      moe/router/kernel [D, E]   moe/expert_bias [E] float32
      moe/shared/{w1,w3,w2}/kernel
      moe/experts/{w1,w3} [E, D, F]   moe/experts/w2 [E, F, D]
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"
HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    dim: int = 2048
    n_layers: int = 32
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128              # independent of dim / n_heads
    ffn_dim: int = 6144
    moe_ffn_dim: int = 1024
    n_experts: int = 128
    top_k: int = 8
    n_shared_experts: int = 1
    route_scale: float = 2.826
    route_norm: bool = True
    sliding_window: int = 2048
    # one kind per layer; empty: three sliding, then one full, repeated
    layer_types: Tuple[str, ...] = ()
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # compute dtype
    param_dtype: Any = jnp.bfloat16  # storage dtype
    decode_attn: str = "auto"        # as LlamaConfig.decode_attn

    def __post_init__(self):
        kinds = self.layer_types or tuple(
            FULL if (i + 1) % 4 == 0 else SLIDING
            for i in range(self.n_layers))
        if len(kinds) != self.n_layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {kinds} do not describe "
                             f"{self.n_layers} layers")
        object.__setattr__(self, "layer_types", tuple(kinds))

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    def windows(self) -> Tuple[int, ...]:
        """Per layer: how many keys a query sees, itself included.  A
        full layer's is past any position (``max_seq_len + 1``), so one
        mask rule serves both kinds."""
        return tuple(self.sliding_window if k == SLIDING
                     else self.max_seq_len + 1 for k in self.layer_types)

    def ropes(self) -> Tuple[bool, ...]:
        return tuple(k == SLIDING for k in self.layer_types)

    def cache_buffers(self) -> Dict[str, Tuple[int, int]]:
        """The cache's buffers, name -> (heads, width)."""
        return {"k": (self.n_kv_heads, self.head_dim),
                "v": (self.n_kv_heads, self.head_dim)}

    def resolved_decode_attn(self) -> str:
        """``LlamaConfig.resolved_decode_attn``'s rule: the paged kernel
        on the TPU when the head is lane-aligned, the einsum elsewhere."""
        if self.decode_attn == "auto":
            if self.head_dim % 128:
                return "xla"
            return "pallas" if jax.default_backend() == "tpu" else "xla"
        return self.decode_attn


CONFIGS = {
    # the real structure at test widths: 1 dense + 4 expert layers,
    # sliding x4 / full, window 8, head width != dim / heads
    "afmoe-tiny": AfmoeConfig(
        vocab_size=256, dim=64, n_layers=5, n_dense_layers=1, n_heads=4,
        n_kv_heads=2, head_dim=32, ffn_dim=96, moe_ffn_dim=32, n_experts=8,
        top_k=2, sliding_window=8,
        layer_types=(SLIDING,) * 4 + (FULL,), max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def param_shapes(cfg: AfmoeConfig) -> Dict[str, Any]:
    """The parameter tree as ``ShapeDtypeStruct``s."""
    d, hd, f, fm, e = (cfg.dim, cfg.head_dim, cfg.ffn_dim, cfg.moe_ffn_dim,
                       cfg.n_experts)
    hq, hkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    fs = fm * cfg.n_shared_experts
    dt = jnp.dtype(cfg.param_dtype)

    def leaf(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def kern(n, i, o):
        return {"kernel": leaf(n, i, o)}

    def block(n):
        return {
            "attn": {"wq": kern(n, d, hq), "wk": kern(n, d, hkv),
                     "wv": kern(n, d, hkv), "wo": kern(n, hq, d),
                     "wg": kern(n, d, hq),
                     "q_norm": {"scale": leaf(n, hd)},
                     "k_norm": {"scale": leaf(n, hd)}},
            **{name: {"scale": leaf(n, d)} for name in
               ("input_norm", "post_attn_norm", "pre_mlp_norm",
                "post_mlp_norm")},
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


def init_params(cfg: AfmoeConfig, rng: jax.Array) -> Dict[str, Any]:
    """Smoke-mode weights (:func:`init_tree`)."""
    return init_tree(param_shapes(cfg), rng)


def init_tree(shapes: Dict[str, Any], rng: jax.Array) -> Dict[str, Any]:
    """Smoke-mode weights for a tree of shapes: N(0, 0.02) matrices, norm
    scales 1, the routing bias 0 (the published buffer's starting
    value)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(rng, len(leaves))

    def make(path, s, key):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            return jnp.ones(s.shape, s.dtype)
        if name == "expert_bias":
            return jnp.zeros(s.shape, s.dtype)
        return (0.02 * jax.random.normal(key, s.shape, jnp.float32)
                ).astype(s.dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [make(p, s, k) for (p, s), k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# The block, piece by piece (named scopes: PERF.md section 3)
# ---------------------------------------------------------------------------


@jax.named_scope("norm")
def rms(x: jax.Array, scale: jax.Array, eps: float, dtype) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * scale.astype(jnp.float32)).astype(dtype)


def mm(x: jax.Array, kernel: jax.Array, dtype) -> jax.Array:
    return x @ kernel.astype(dtype)


@jax.named_scope("embed")
def embed(cfg: AfmoeConfig, params, tokens: jax.Array) -> jax.Array:
    x = params["tok_embed"]["embedding"].astype(cfg.dtype)[tokens]
    return x * jnp.asarray(cfg.dim ** 0.5, cfg.dtype)


def lm_head(cfg: AfmoeConfig, params, x: jax.Array) -> jax.Array:
    x = rms(x, params["final_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    with jax.named_scope("lm_head"):
        return mm(x, params["lm_head"]["kernel"],
                  cfg.dtype).astype(jnp.float32)


def rope_tables(cfg: AfmoeConfig) -> Tuple[jax.Array, jax.Array]:
    from paddle_operator_tpu.models.llama import rope_frequencies

    return rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)


def attn_inputs(cfg: AfmoeConfig, lp, x: jax.Array, cos: jax.Array,
                sin: jax.Array, pos: jax.Array, use_rope):
    """``x [B, T, D]`` at positions ``pos [B, T]`` -> ``q [B, T, Hq, hd]``,
    ``k``, ``v [B, T, Hkv, hd]`` and the output gate ``g [B, T, Hq*hd]``.
    `use_rope` may be traced (a scanned layer's kind)."""
    b, t, _ = x.shape
    hd = cfg.head_dim
    h = rms(x, lp["input_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    with jax.named_scope("attn.qkv"):
        q = mm(h, lp["attn"]["wq"]["kernel"], cfg.dtype)
        k = mm(h, lp["attn"]["wk"]["kernel"], cfg.dtype)
        v = mm(h, lp["attn"]["wv"]["kernel"], cfg.dtype)
    with jax.named_scope("attn.gate"):
        g = mm(h, lp["attn"]["wg"]["kernel"], cfg.dtype)
    q = q.reshape(b, t, cfg.n_heads, hd)
    k = k.reshape(b, t, cfg.n_kv_heads, hd)
    v = v.reshape(b, t, cfg.n_kv_heads, hd)
    with jax.named_scope("attn.qknorm"):
        q = rms(q, lp["attn"]["q_norm"]["scale"], cfg.norm_eps, cfg.dtype)
        k = rms(k, lp["attn"]["k_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    with jax.named_scope("attn.rope"):
        cos_p = cos[pos][:, :, None, :]               # [B, T, 1, hd/2]
        sin_p = sin[pos][:, :, None, :]

        def rot(u):
            u1, u2 = jnp.split(u.astype(jnp.float32), 2, axis=-1)
            r = jnp.concatenate([u1 * cos_p - u2 * sin_p,
                                 u2 * cos_p + u1 * sin_p], axis=-1)
            return jnp.where(use_rope, r, u.astype(jnp.float32)
                             ).astype(u.dtype)

        q, k = rot(q), rot(k)
    return q, k, v, g


def attn_residual(cfg: AfmoeConfig, lp, x: jax.Array, att: jax.Array,
                  g: jax.Array) -> jax.Array:
    """``att [B, T, Hq*hd]`` (softmax(.) v, heads concatenated) -> the
    gated output projection, its sandwich norm and the residual."""
    with jax.named_scope("attn.gate"):
        att = (att.astype(jnp.float32)
               * jax.nn.sigmoid(g.astype(jnp.float32))).astype(cfg.dtype)
    with jax.named_scope("attn.out"):
        o = mm(att, lp["attn"]["wo"]["kernel"], cfg.dtype)
    return x + rms(o, lp["post_attn_norm"]["scale"], cfg.norm_eps, cfg.dtype)


@jax.named_scope("attn.kernel")
def attend(cfg: AfmoeConfig, q: jax.Array, k_all: jax.Array,
           v_all: jax.Array, q_pos: jax.Array, window) -> jax.Array:
    """Einsum attention of ``q [B, T, Hq, hd]`` at positions
    ``q_pos [B, T]`` against head-major keys ``[B, Hkv, S, hd]`` at
    positions ``0..S-1``: key j is visible iff ``0 <= q_pos - j <
    window`` (`window` may be traced).  Returns ``[B, T, Hq*hd]``."""
    b, t = q.shape[:2]
    hkv, d = cfg.n_kv_heads, cfg.head_dim
    qg = q.reshape(b, t, hkv, cfg.n_heads // hkv, d)
    scores = jnp.einsum("bthrd,bhsd->bthrs", qg, k_all,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    gap = q_pos[:, :, None] - jnp.arange(k_all.shape[2])[None, None, :]
    seen = (gap >= 0) & (gap < window)                       # [B, T, S]
    scores = jnp.where(seen[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bthrs,bhsd->bthrd", probs.astype(cfg.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, cfg.n_heads * d).astype(cfg.dtype)


def layer_kinds(cfg: AfmoeConfig) -> tuple:
    """What differs from layer to layer besides the weights, one tuple a
    quantity: the window and whether q and k are rotated."""
    return cfg.windows(), cfg.ropes()


def kernel_windows(cfg: AfmoeConfig) -> tuple:
    """Per layer the window of the decode kernel's work list."""
    return cfg.windows()


def whole_prompt_flash(cfg: AfmoeConfig) -> bool:
    """The window mask and the gate are written over the einsum only."""
    return False


def attention(cfg: AfmoeConfig, lp, x: jax.Array, tables, q_pos, view, bufs,
              li, kind, cells=None, flash: bool = False, blocks=None):
    """The attention half of a block over a cache view -> ``(a, the
    view's buffers after the write)``: the view's decode kernel where it
    is on, else :func:`attend` over the view's lanes.  `kind` is the
    layer's ``(window, use_rope)`` (:func:`layer_kinds`; may be traced)."""
    window, use_rope = kind
    q, k, v, g = attn_inputs(cfg, lp, x, *tables, q_pos, use_rope)
    bufs = view.write(bufs, li, k, v)
    if view.kernel:
        att = view.kernel_attend(bufs, li, q, cells=cells)
    else:
        att = attend(cfg, q, *view.lanes(bufs, li), q_pos, window)
    return attn_residual(cfg, lp, x, att, g), bufs


def swiglu(x: jax.Array, w, dtype) -> jax.Array:
    gate = mm(x, w["w1"]["kernel"], dtype)
    up = mm(x, w["w3"]["kernel"], dtype)
    return mm(jax.nn.silu(gate) * up, w["w2"]["kernel"], dtype)


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------


@jax.named_scope("ffn.router")
def route(cfg: AfmoeConfig, mp, h: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """``h [T, D]`` -> ``(experts [T, k] int32, weights [T, k] f32)``:
    sigmoid scores in float32 (the product at full precision: one
    bf16 pass flips the eighth expert far more often), selection by
    score + bias, weights from the UNBIASED scores of the selected."""
    s = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), mp["router"]["kernel"].astype(jnp.float32),
        precision=HI))
    _, idx = jax.lax.top_k(s + mp["expert_bias"].astype(jnp.float32),
                           cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * cfg.route_scale


# The grouped product.  On the TPU: megablox's Pallas grouped matmul,
# which skips empty groups (its grid is as long as the groups that have
# rows), so a decode step reads only the experts its tokens were sent to;
# ``jax.lax.ragged_dot`` elsewhere (the CPU expands it to a masked dense
# product, fine at test widths).  The kernel is handed ALL the expert
# layers' matrices as one ``[layers * E, K, N]`` stack, with the other
# layers' groups empty: a layer sliced out of the stack first would be an
# operand XLA has to materialise, a copy of the layer's experts a call
# (the decode kernel reads the stacked pool for the same reason).
# PERF.md section 6 (PR 28) has the chip readings behind the choice.
GMM_TILING = (128, 2048, 1024)


def grouped_matmul(lhs: jax.Array, stack: jax.Array, layer, sizes: jax.Array,
                   out_dtype) -> jax.Array:
    """``lhs [M, K]`` rows sorted by group, ``stack [layers, G, K, N]``,
    ``sizes [G]`` rows a group: row r of group g -> ``lhs[r] @
    stack[layer, g]`` (`layer` may be traced)."""
    m, k = lhs.shape
    n_layers, g, _, n = stack.shape
    if jax.default_backend() == "tpu" and not (m % 8 or k % 128 or n % 128):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tm, tk, tn = GMM_TILING
        # the widest tile of output columns, a multiple of 128, that
        # divides n (1,536 columns: 768): the kernel masks a ragged last
        # tile of k, not of n
        tn = next(w for w in range(min(tn, n), 0, -128) if n % w == 0)
        all_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * g,), jnp.int32), sizes, (layer * g,))
        return gmm(lhs, stack.reshape(n_layers * g, k, n), all_sizes,
                   preferred_element_type=out_dtype,
                   tiling=(min(tm, m), min(tk, k), tn))
    return jax.lax.ragged_dot(lhs, stack[layer], sizes,
                              preferred_element_type=out_dtype)


@jax.named_scope("ffn.experts")
def expert_ffn(cfg: AfmoeConfig, experts, layer, h: jax.Array,
               idx: jax.Array, w: jax.Array) -> jax.Array:
    """The routed experts' part: ``sum_k w[t, k] * expert_{idx[t, k]}
    (h[t])`` for ``h [T, D]``, with expert layer `layer`'s matrices out
    of the stacked `experts`.  Dropless: the T*k assignments are sorted
    by expert and each of the three matrices is one grouped product, so
    the operations grow with the assignments, not with experts x tokens."""
    t, k = idx.shape
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)           # assignment ids by expert
    sizes = jnp.sum(jax.nn.one_hot(flat, cfg.n_experts, dtype=jnp.int32), 0)
    xs = h[order // k]                               # [T*k, D]
    gate = grouped_matmul(xs, experts["w1"], layer, sizes, cfg.dtype)
    up = grouped_matmul(xs, experts["w3"], layer, sizes, cfg.dtype)
    ys = grouped_matmul(jax.nn.silu(gate) * up, experts["w2"], layer, sizes,
                        jnp.float32)                 # [T*k, D]
    ys = ys[jnp.argsort(order)].reshape(t, k, -1)    # back by token
    return jnp.sum(ys * w[:, :, None], axis=1).astype(cfg.dtype)


def moe_ffn(cfg: AfmoeConfig, mp, experts, layer, h: jax.Array,
            counted: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """``FFN(h) = shared(h) + routed(h)`` for ``h [B, T, D]`` and the
    layer's load: assignments by expert ``[E]`` int32 over the tokens
    `counted` ``[B, T]`` marks (all when None).  `mp` is the layer's
    router, bias and shared expert; `experts` every expert layer's."""
    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    idx, w = route(cfg, mp, flat)
    with jax.named_scope("ffn.shared"):
        shared = swiglu(flat, mp["shared"], cfg.dtype)
    out = shared + expert_ffn(cfg, experts, layer, flat, idx, w)
    hits = jnp.sum(jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.int32), 1)
    if counted is not None:
        hits = hits * counted.reshape(b * t, 1).astype(jnp.int32)
    return out.reshape(b, t, d), hits.sum(0)


def ffn_residual(cfg: AfmoeConfig, lp, a: jax.Array, experts=None,
                 layer=None, counted: Optional[jax.Array] = None):
    """The feed-forward half of a block -> ``(y, load [E] or None)``: the
    expert layer `layer` of the stacked `experts`, or with none the
    dense SwiGLU."""
    n = rms(a, lp["pre_mlp_norm"]["scale"], cfg.norm_eps, cfg.dtype)
    with jax.named_scope("ffn"):
        if experts is not None:
            f, load = moe_ffn(cfg, lp["moe"], experts, layer, n, counted)
        else:
            f, load = swiglu(n, lp["mlp"], cfg.dtype), None
    return a + rms(f, lp["post_mlp_norm"]["scale"], cfg.norm_eps,
                   cfg.dtype), load


def split_experts(moe_layers):
    """The stacked expert layers as (what a scan slices layer by layer,
    the experts' matrices, which stay whole)."""
    moe = dict(moe_layers["moe"])
    experts = moe.pop("experts")
    return dict(moe_layers, moe=moe), experts


def layer_at(tree, i):
    """Layer `i` (static or traced) of a stacked subtree."""
    return jax.tree.map(lambda leaf: leaf[i], tree)
