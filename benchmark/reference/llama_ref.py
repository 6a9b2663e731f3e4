"""The plain reference of the architecture: a Mistral/LLaMA-style decoder
in straightforward ``jax.numpy`` and float32 — no kernels, no cache, no
batching tricks, nothing imported from the program.

Follows the published description (``modeling_mistral.py``): pre-norm
blocks, RMSNorm in float32, grouped-query attention with rotate-half RoPE,
SwiGLU, an untied output head.  Departures, each noted where it is made:
full causal attention (every context of every cell is within the published
``sliding_window``, so the window never cuts anything), and weights made
layer by layer from the seed instead of loaded.

``precision`` selects the arithmetic of every matrix product:
``"f32"`` (the reference: float32 at ``highest``) or ``"fp8"`` (the
control: both operands rounded to float8_e4m3 with a per-tensor scale, the
nearest precision below the bf16 the configurations state).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import weights as W

HI = jax.lax.Precision.HIGHEST
LAYER_LEAVES = {            # name -> shape from the config, [in, out]
    "attn/wq/kernel": lambda c: (c["hidden_size"],
                                 c["num_attention_heads"] * c["head_dim"]),
    "attn/wk/kernel": lambda c: (c["hidden_size"],
                                 c["num_key_value_heads"] * c["head_dim"]),
    "attn/wv/kernel": lambda c: (c["hidden_size"],
                                 c["num_key_value_heads"] * c["head_dim"]),
    "attn/wo/kernel": lambda c: (c["num_attention_heads"] * c["head_dim"],
                                 c["hidden_size"]),
    "mlp/w1/kernel": lambda c: (c["hidden_size"], c["intermediate_size"]),
    "mlp/w3/kernel": lambda c: (c["hidden_size"], c["intermediate_size"]),
    "mlp/w2/kernel": lambda c: (c["intermediate_size"], c["hidden_size"]),
    "attn_norm/scale": lambda c: (c["hidden_size"],),
    "mlp_norm/scale": lambda c: (c["hidden_size"],),
}
TOP_LEAVES = {
    "tok_embed/embedding": lambda c: (c["vocab_size"], c["hidden_size"]),
    "final_norm/scale": lambda c: (c["hidden_size"],),
    "lm_head/kernel": lambda c: (c["hidden_size"], c["vocab_size"]),
}


def with_head_dim(cfg: dict) -> dict:
    cfg = dict(cfg)
    cfg.setdefault("head_dim",
                   cfg["hidden_size"] // cfg["num_attention_heads"])
    return cfg


def layer_weights(cfg: dict, seed_key, layer, dtype=None) -> dict:
    """One layer's weights from the seed: the values the program holds (in
    the dtype the configuration stores them in), as float32."""
    store = jnp.dtype(dtype or cfg["torch_dtype"])
    return {n: W.make_leaf(seed_key, "layers/" + n, f(cfg), store,
                           layer).astype(jnp.float32)
            for n, f in LAYER_LEAVES.items()}


def top_weight(cfg: dict, seed_key, name: str, dtype=None):
    store = jnp.dtype(dtype or cfg["torch_dtype"])
    return W.make_leaf(seed_key, name, TOP_LEAVES[name](cfg),
                       store).astype(jnp.float32)


def fp8(x):
    """Round to float8_e4m3 with one scale per tensor; straight-through for
    gradients, so the control's backward pass is in the same precision."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(a, b, precision: str):
    if precision == "fp8":
        a, b = fp8(a), fp8(b)
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


def attend(q, k, v, precision: str):
    """Causal softmax attention, ``q k v [S, H, D]`` -> ``[S, H, D]``."""
    s, _, d = q.shape
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        seen = key_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        if precision == "fp8":
            probs = fp8(probs)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, *q.shape[1:]),
                              jnp.arange(0, s, blk)))
    return out.reshape(q.shape)


def layer(cfg: dict, w: dict, x, precision: str = "f32"):
    """One decoder block on one sequence ``x [S, hidden]`` (float32)."""
    s = x.shape[0]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    a = rms_norm(x, w["attn_norm/scale"], cfg["rms_norm_eps"])
    q = rope(mm(a, w["attn/wq/kernel"], precision).reshape(s, h, d),
             cfg["rope_theta"])
    k = rope(mm(a, w["attn/wk/kernel"], precision).reshape(s, kv, d),
             cfg["rope_theta"])
    v = mm(a, w["attn/wv/kernel"], precision).reshape(s, kv, d)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    if precision == "fp8":
        q, k, v = fp8(q), fp8(k), fp8(v)
    att = attend(q, k, v, precision).reshape(s, h * d)
    x = x + mm(att, w["attn/wo/kernel"], precision)
    m = rms_norm(x, w["mlp_norm/scale"], cfg["rms_norm_eps"])
    gate = mm(m, w["mlp/w1/kernel"], precision)
    up = mm(m, w["mlp/w3/kernel"], precision)
    return x + mm(jax.nn.silu(gate) * up, w["mlp/w2/kernel"], precision)


def head(cfg: dict, final_scale, lm_head, x, precision: str = "f32"):
    return mm(rms_norm(x, final_scale, cfg["rms_norm_eps"]), lm_head,
              precision)
