"""The plain reference of the ``afmoe`` architecture (Arcee Trinity): the
decoder in straightforward ``jax.numpy`` and float32 — no kernels, no
cache, no batching, no grouped product, nothing imported from the program.

Follows the family's published modelling code (``transformers``
``modeling_afmoe.py``; `cfg` is the published ``config.json`` as a dict):

- embedding scaled by ``sqrt(hidden_size)`` (``mup_enabled``), untied head,
  final RMSNorm;
- sandwich norm, four RMSNorms a layer:
  ``a = x + post_attn_norm(Attn(input_norm(x)))``,
  ``y = a + post_mlp_norm(FFN(pre_mlp_norm(a)))``;
- attention with ``head_dim`` independent of ``hidden/heads``, RMSNorm over
  ``head_dim`` on each head of q and k, rotate-half RoPE on
  ``sliding_attention`` layers only (a ``full_attention`` layer has no
  positional encoding), causal scores ``q k^T / sqrt(head_dim)``, on a
  sliding layer key j visible to query i iff ``0 <= i - j <
  sliding_window``, and the output gate: ``Wo (softmax(.) v *
  sigmoid(Wg h))``;
- SwiGLU of ``intermediate_size`` on the first ``num_dense_layers``;
- on the others: ``s = sigmoid(Wr h)`` in float32, selection
  ``top_k(s + expert_bias)``, weights the unbiased ``s`` of the selected,
  normalised (``route_norm``, ``+ 1e-20``) times ``route_scale``; experts
  SwiGLU of ``moe_intermediate_size``; the shared expert sees every token.
  Here EVERY expert is applied to every token and the results are combined
  by a dense ``[S, E]`` weight matrix that is zero off the selection.

Departures, each where it is made: weights are made leaf by leaf from the
seed (``harness/weights.py``; ``expert_bias`` is a seeded leaf like any
matrix, so that selection and weight really differ; norm scales 1);
``n_group = topk_group = 1``, so no group limit is written; queries are
attended in blocks (same sums, less memory).

``precision``: ``"f32"`` (the reference: float32 at ``highest``),
``"fp8"`` (the control: every matrix product's operands, the router's too,
rounded to float8_e4m3 with a per-tensor scale — the nearest precision
below the bf16 the configuration states) or ``"bf16"`` (calibration only:
operands rounded to bfloat16, the precision the configuration states, to
read how many of the top-k selections that precision alone flips).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import weights as W

HI = jax.lax.Precision.HIGHEST
SLIDING = "sliding_attention"


def _attn_leaves(c):
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return {
        "attn/wq/kernel": (d, hq), "attn/wk/kernel": (d, hkv),
        "attn/wv/kernel": (d, hkv), "attn/wo/kernel": (hq, d),
        "attn/wg/kernel": (d, hq),
        "attn/q_norm/scale": (hd,), "attn/k_norm/scale": (hd,),
        "input_norm/scale": (d,), "post_attn_norm/scale": (d,),
        "pre_mlp_norm/scale": (d,), "post_mlp_norm/scale": (d,),
    }


def layer_leaves(c: dict, layer: int) -> dict:
    """Leaf name -> shape (kernels ``[in, out]``) of layer `layer`: a dense
    layer's or an expert layer's."""
    d = c["hidden_size"]
    out = _attn_leaves(c)
    if layer < c["num_dense_layers"]:
        f = c["intermediate_size"]
        out.update({"mlp/w1/kernel": (d, f), "mlp/w3/kernel": (d, f),
                    "mlp/w2/kernel": (f, d)})
        return out
    e, f = c["num_experts"], c["moe_intermediate_size"]
    fs = f * c["num_shared_experts"]
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
    """What a leaf is stored in: the configuration's dtype, but the routing
    bias, a float32 buffer in the published code."""
    return jnp.float32 if name.endswith("expert_bias") else jnp.dtype(
        cfg["torch_dtype"])


def layer_weights(cfg: dict, seed_key, layer: int) -> dict:
    """Layer `layer`'s weights from the seed: the values the program holds
    (rounded to the dtype they are stored in), as float32.  `layer` is a
    Python int: a dense and an expert layer have other leaves."""
    return {n: W.make_leaf(seed_key, "layers/" + n, shape,
                           leaf_dtype(cfg, n), layer).astype(jnp.float32)
            for n, shape in layer_leaves(cfg, layer).items()}


def top_weight(cfg: dict, seed_key, name: str):
    return W.make_leaf(seed_key, name, TOP_LEAVES[name](cfg),
                       leaf_dtype(cfg, name)).astype(jnp.float32)


def fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def mm(a, b, precision: str):
    if precision == "fp8":
        a, b = fp8(a), fp8(b)
    elif precision == "bf16":
        a, b = bf16(a), bf16(b)
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """[S, H, D] rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


Q_BLOCK = 512     # queries are attended in blocks: same sums, less memory


def attend(q, k, v, window, precision: str):
    """Causal softmax attention ``q k v [S, H, D]`` -> ``[S, H, D]``; with
    `window` (an int, or None on a full layer) key j is visible to query i
    iff ``0 <= i - j < window``."""
    s, _, d = q.shape
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        gap = (start + jnp.arange(blk))[:, None] - key_pos[None, :]
        seen = gap >= 0
        if window is not None:
            seen = seen & (gap < window)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        if precision == "fp8":
            probs = fp8(probs)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, *q.shape[1:]),
                              jnp.arange(0, s, blk)))
    return out.reshape(q.shape)


def swiglu(x, w1, w3, w2, precision: str):
    return mm(jax.nn.silu(mm(x, w1, precision)) * mm(x, w3, precision), w2,
              precision)


def routing(cfg: dict, w: dict, h, precision: str = "f32"):
    """``h [S, hidden]`` -> (selected experts ``[S, k]``, their weights
    ``[S, k]``): sigmoid scores, selection by score + bias, weights from
    the unbiased scores of the selected."""
    s = jax.nn.sigmoid(mm(h, w["moe/router/kernel"], precision))
    _, idx = jax.lax.top_k(s + w["moe/expert_bias"],
                           cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(s, idx, -1)
    if cfg["route_norm"]:
        sel = sel / (sel.sum(-1, keepdims=True) + 1e-20)
    return idx, sel * cfg["route_scale"]


def moe_ffn(cfg: dict, w: dict, h, precision: str = "f32"):
    """Shared expert plus every routed expert applied to every token,
    combined by the routing weights (zero off the selection).  Returns
    the layer's output and the selection ``[S, k]``."""
    idx, sel = routing(cfg, w, h, precision)
    dense = jnp.zeros((h.shape[0], cfg["num_experts"]), jnp.float32).at[
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
    s = x.shape[0]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    sliding = cfg["layer_types"][layer_idx] == SLIDING
    a = rms_norm(x, w["input_norm/scale"], eps)
    q = mm(a, w["attn/wq/kernel"], precision).reshape(s, h, d)
    k = mm(a, w["attn/wk/kernel"], precision).reshape(s, kv, d)
    v = mm(a, w["attn/wv/kernel"], precision).reshape(s, kv, d)
    gate = mm(a, w["attn/wg/kernel"], precision)
    q = rms_norm(q, w["attn/q_norm/scale"], eps)
    k = rms_norm(k, w["attn/k_norm/scale"], eps)
    if sliding:             # a full layer has no positional encoding
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    if precision == "fp8":
        q, k, v = fp8(q), fp8(k), fp8(v)
    att = attend(q, k, v, cfg["sliding_window"] if sliding else None,
                 precision).reshape(s, h * d)
    att = att * jax.nn.sigmoid(gate)        # the gate, before Wo
    x = x + rms_norm(mm(att, w["attn/wo/kernel"], precision),
                     w["post_attn_norm/scale"], eps)
    m = rms_norm(x, w["pre_mlp_norm/scale"], eps)
    if layer_idx < cfg["num_dense_layers"]:
        f, idx = swiglu(m, w["mlp/w1/kernel"], w["mlp/w3/kernel"],
                        w["mlp/w2/kernel"], precision), None
    else:
        f, idx = moe_ffn(cfg, w, m, precision)
    y = x + rms_norm(f, w["post_mlp_norm/scale"], eps)
    return (y, idx) if with_routing else y


def embed(cfg: dict, table, ids):
    x = table[ids]
    return x * jnp.sqrt(jnp.float32(cfg["hidden_size"])) if cfg.get(
        "mup_enabled") else x


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
