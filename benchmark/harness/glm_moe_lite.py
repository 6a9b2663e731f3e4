"""What the ``serve_glm_moe_lite`` children share: the program's
configuration for a published ``glm4_moe_lite`` ``config.json``, and the
seeded weights laid out as the program's parameter tree.

A leaf is named as the reference names it (``layers/attn/kv_b/kernel`` with
the layer's index in the model as run), so the reference makes one layer at
a time from the same seed; the program stacks its leading dense layers and
its expert layers apart (``dense_layers/``, ``moe_layers/``) and holds
``kv_b_proj`` as its two halves (``attn/kv_b_k``: every head's ``k_nope``
columns, ``attn/kv_b_v``: every head's value columns), cut here from the
one published matrix."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import weights as W


def config(cfg: dict, max_len: int):
    """``models/glm_moe_lite.py GlmMoeLiteConfig`` for a published
    ``config.json``."""
    from paddle_operator_tpu.models.glm_moe_lite import GlmMoeLiteConfig

    dtype = jnp.dtype(cfg["torch_dtype"])
    return GlmMoeLiteConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"],
        n_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], ffn_dim=cfg["intermediate_size"],
        moe_ffn_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        route_scale=cfg["routed_scaling_factor"],
        route_norm=cfg["norm_topk_prob"], max_seq_len=max_len,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype=dtype, param_dtype=dtype)


def make_tree(seed_key: jax.Array, shapes, acfg):
    """A parameter tree shaped like `shapes` (``models/glm_moe_lite.py
    param_shapes`` of `acfg`).  Stacked leaves are made layer by layer, so
    that no float32 image of a stacked leaf ever exists."""
    h, nope, v = acfg.n_heads, acfg.qk_nope_head_dim, acfg.v_head_dim

    def kv_b_half(name: str, s, l):
        """One layer's ``kv_b_k`` or ``kv_b_v`` out of the published
        ``kv_b`` ``[rank, H * (nope + v)]``, whose columns lie head by
        head, ``[k_nope | v]`` each."""
        whole = W.make_leaf(seed_key, "layers/attn/kv_b/kernel",
                            (s.shape[1], h * (nope + v)), s.dtype, l)
        whole = whole.reshape(s.shape[1], h, nope + v)
        half = whole[..., :nope] if name.endswith("kv_b_k/kernel") else \
            whole[..., nope:]
        return half.reshape(s.shape[1:])

    def one(path, s):
        name = W.path_name(path)
        for prefix, first in (("dense_layers/", 0),
                              ("moe_layers/", acfg.n_dense_layers)):
            if name.startswith(prefix):
                ref = "layers/" + name[len(prefix):]
                layers = jnp.arange(first, first + s.shape[0])
                if "/kv_b_" in name:
                    return jax.lax.map(lambda l: kv_b_half(name, s, l),
                                       layers)
                return jax.lax.map(
                    lambda l: W.make_leaf(seed_key, ref, s.shape[1:],
                                          s.dtype, l), layers)
        return W.make_leaf(seed_key, name, s.shape, s.dtype)

    return jax.tree_util.tree_map_with_path(one, shapes)
