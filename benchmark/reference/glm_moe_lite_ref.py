"""The plain reference of the ``glm4_moe_lite`` architecture (GLM-4.7-Flash):
the decoder in straightforward ``jax.numpy`` and float32 — no kernels, no
cache, no batching, no grouped product, no absorbed form, nothing imported
from the program.

Follows the family's published modelling code (``transformers``
``modeling_glm4_moe_lite.py``, after ``modeling_deepseek_v3.py``; `cfg` is
the published ``config.json`` as a dict):

- untied embedding and head, final RMSNorm; two RMSNorms a layer:
  ``a = x + Attn(input_norm(x))``, ``y = a + FFN(post_attn_norm(a))``;
- multi-head latent attention, EXPANDED: ``c_q = q_a_norm(u Wqa)``,
  ``q = c_q Wqb`` -> heads of ``[q_nope | q_pe]``; ``[c_kv | k_pe] = u
  Wkva``, ``c = kv_a_norm(c_kv)``; ``[k_nope | v]_h = c Wkvb_h`` for every
  head; RoPE on ``q_pe`` and on the ONE ``k_pe`` all heads share; ``k_h =
  [k_nope_h | k_pe]``; causal scores ``q_h k_h / sqrt(nope + rope)`` (no
  extra factor: ``rope_scaling`` is null), softmax in float32, ``o_h = p
  v_h``, ``out = concat_h(o_h) Wo``;
- RoPE rotates the pairs ``(2i, 2i + 1)`` of the rope part by the i-th
  frequency (``rope_interleave``).  Here the rotated pair stays where it
  was; the published code moves it to ``(i, i + rope/2)``, which permutes
  q and k alike and leaves every score as it is;
- SwiGLU of ``intermediate_size`` on the first ``first_k_dense_replace``
  layers;
- on the others (``noaux_tc``): ``s = sigmoid(Wg h)`` in float32,
  selection ``top_k(s + e_score_correction_bias)`` (``n_group =
  topk_group = 1``: no group step), weights the unbiased ``s`` of the
  selected, normalised (``norm_topk_prob``, ``+ 1e-20``) times
  ``routed_scaling_factor``; experts SwiGLU of ``moe_intermediate_size``;
  the shared expert sees every token.  EVERY expert is applied to every
  token and the results are combined by a dense ``[S, E]`` weight matrix
  that is zero off the selection.

Departures, each where it is made: weights are made leaf by leaf from the
seed (``harness/weights.py``; the correction bias is a seeded leaf like any
matrix, so that selection and weight really differ; norm scales 1); the
multi-token-prediction layer is not part of the causal LM's forward and is
left out; queries are attended in blocks (same sums, less memory).

``precision``: ``"f32"`` (the reference: float32 at ``highest``), ``"fp8"``
(the control: every matrix product's operands, the router's too, rounded to
float8_e4m3 with a per-tensor scale — the nearest precision below the bf16
the configuration states) or ``"bf16"`` (calibration only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import weights as W
from benchmark.reference.afmoe_ref import (  # plain numeric helpers
    HI,
    fp8,
    mm,
    rms_norm,
    swiglu,
)


def layer_leaves(c: dict, layer: int) -> dict:
    """Leaf name -> shape (kernels ``[in, out]``) of layer `layer`: a dense
    layer's or an expert layer's."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    out = {
        "attn/q_a/kernel": (d, rq), "attn/q_a_norm/scale": (rq,),
        "attn/q_b/kernel": (rq, h * (nope + rope)),
        "attn/kv_a/kernel": (d, rkv + rope), "attn/kv_a_norm/scale": (rkv,),
        "attn/kv_b/kernel": (rkv, h * (nope + v)),
        "attn/wo/kernel": (h * v, d),
        "input_norm/scale": (d,), "post_attn_norm/scale": (d,),
    }
    if layer < c["first_k_dense_replace"]:
        f = c["intermediate_size"]
        out.update({"mlp/w1/kernel": (d, f), "mlp/w3/kernel": (d, f),
                    "mlp/w2/kernel": (f, d)})
        return out
    e, f = c["n_routed_experts"], c["moe_intermediate_size"]
    fs = f * c["n_shared_experts"]
    out.update({
        "moe/router/kernel": (d, e), "moe/expert_bias": (e,),
        "moe/shared/w1/kernel": (d, fs), "moe/shared/w3/kernel": (d, fs),
        "moe/shared/w2/kernel": (fs, d),
        "moe/experts/w1": (e, d, f), "moe/experts/w3": (e, d, f),
        "moe/experts/w2": (e, f, d)})
    return out


TOP_LEAVES = {
    "tok_embed/embedding": lambda c: (c["vocab_size"], c["hidden_size"]),
    "final_norm/scale": lambda c: (c["hidden_size"],),
    "lm_head/kernel": lambda c: (c["hidden_size"], c["vocab_size"]),
}


def leaf_dtype(cfg: dict, name: str):
    """What a leaf is stored in: the configuration's dtype, but the
    correction bias, a float32 buffer in the published code."""
    return jnp.float32 if name.endswith("expert_bias") else jnp.dtype(
        cfg["torch_dtype"])


def layer_weights(cfg: dict, seed_key, layer: int) -> dict:
    """Layer `layer`'s weights from the seed: the values the program holds
    (rounded to the dtype they are stored in), as float32."""
    return {n: W.make_leaf(seed_key, "layers/" + n, shape,
                           leaf_dtype(cfg, n), layer).astype(jnp.float32)
            for n, shape in layer_leaves(cfg, layer).items()}


def top_weight(cfg: dict, seed_key, name: str):
    return W.make_leaf(seed_key, name, TOP_LEAVES[name](cfg),
                       leaf_dtype(cfg, name)).astype(jnp.float32)


def rope(x, theta):
    """``[S, H, R]`` at positions 0..S-1: the pair ``(2i, 2i + 1)`` turned
    by ``pos * theta ** (-2i / R)``, left in place."""
    s, h, r = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pair = x.reshape(s, h, r // 2, 2)
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        s, h, r)


Q_BLOCK = 512     # queries are attended in blocks: same sums, less memory


def attend(q, k, v, precision: str):
    """Causal softmax attention ``q k [S, H, Dk]``, ``v [S, H, Dv]`` ->
    ``[S, H, Dv]``, scores over ``sqrt(Dk)``."""
    s, _, d = q.shape
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        seen = (start + jnp.arange(blk))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        if precision == "fp8":
            probs = fp8(probs)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, *q.shape[1:]),
                              jnp.arange(0, s, blk)))
    return out.reshape(s, *v.shape[1:])


def attention(cfg: dict, w: dict, u, precision: str = "f32"):
    """Multi-head latent attention, expanded, on one normed sequence
    ``u [S, hidden]`` -> ``[S, hidden]``."""
    s = u.shape[0]
    h, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rp, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    c_q = rms_norm(mm(u, w["attn/q_a/kernel"], precision),
                   w["attn/q_a_norm/scale"], eps)
    q = mm(c_q, w["attn/q_b/kernel"], precision).reshape(s, h, nope + rp)
    kv = mm(u, w["attn/kv_a/kernel"], precision)
    c = rms_norm(kv[:, :rkv], w["attn/kv_a_norm/scale"], eps)
    k_pe = rope(kv[:, None, rkv:], theta)                    # [S, 1, R]
    up = mm(c, w["attn/kv_b/kernel"], precision).reshape(s, h, nope + vd)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_pe, (s, h, rp))], -1)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    v = up[..., nope:]
    if precision == "fp8":
        q, k, v = fp8(q), fp8(k), fp8(v)
    att = attend(q, k, v, precision).reshape(s, h * vd)
    return mm(att, w["attn/wo/kernel"], precision)


def routing(cfg: dict, w: dict, h, precision: str = "f32"):
    """``h [S, hidden]`` -> (selected experts ``[S, k]``, their weights
    ``[S, k]``): sigmoid scores, selection by score + correction bias,
    weights from the unbiased scores of the selected."""
    s = jax.nn.sigmoid(mm(h, w["moe/router/kernel"], precision))
    _, idx = jax.lax.top_k(s + w["moe/expert_bias"],
                           cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        sel = sel / (sel.sum(-1, keepdims=True) + 1e-20)
    return idx, sel * cfg["routed_scaling_factor"]


def moe_ffn(cfg: dict, w: dict, h, precision: str = "f32"):
    """Shared expert plus every routed expert applied to every token,
    combined by the routing weights (zero off the selection).  Returns
    the layer's output and the selection ``[S, k]``."""
    idx, sel = routing(cfg, w, h, precision)
    dense = jnp.zeros((h.shape[0], cfg["n_routed_experts"]),
                      jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx].set(sel)         # [S, E]

    def one(acc, e):
        w1, w3, w2, col = e
        return acc + col[:, None] * swiglu(h, w1, w3, w2, precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (w["moe/experts/w1"], w["moe/experts/w3"], w["moe/experts/w2"],
         dense.T))
    return swiglu(h, w["moe/shared/w1/kernel"], w["moe/shared/w3/kernel"],
                  w["moe/shared/w2/kernel"], precision) + routed, idx


def layer(cfg: dict, w: dict, x, layer_idx: int, precision: str = "f32",
          with_routing: bool = False):
    """Block `layer_idx` (a Python int: its kind is static) on one sequence
    ``x [S, hidden]`` (float32); `with_routing`: also the experts each
    token selected, ``[S, k]`` (None on a dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, w, rms_norm(x, w["input_norm/scale"], eps),
                      precision)
    m = rms_norm(x, w["post_attn_norm/scale"], eps)
    if layer_idx < cfg["first_k_dense_replace"]:
        f, idx = swiglu(m, w["mlp/w1/kernel"], w["mlp/w3/kernel"],
                        w["mlp/w2/kernel"], precision), None
    else:
        f, idx = moe_ffn(cfg, w, m, precision)
    y = x + f
    return (y, idx) if with_routing else y


def embed(cfg: dict, table, ids):
    return table[ids]


def head(cfg: dict, final_scale, lm_head, x, precision: str = "f32"):
    return mm(rms_norm(x, final_scale, cfg["rms_norm_eps"]), lm_head,
              precision)


def forward(cfg: dict, seed_key, ids, precision: str = "f32"):
    """The whole model on one sequence of token ids -> logits ``[S, V]``
    (tests at tiny widths; the chip's check goes layer by layer)."""
    x = embed(cfg, top_weight(cfg, seed_key, "tok_embed/embedding"), ids)
    for l in range(cfg["num_hidden_layers"]):
        x = layer(cfg, layer_weights(cfg, seed_key, l), x, l, precision)
    return head(cfg, top_weight(cfg, seed_key, "final_norm/scale"),
                top_weight(cfg, seed_key, "lm_head/kernel"), x, precision)
