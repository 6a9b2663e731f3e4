"""What the ``serve_afmoe`` children share: the program's configuration for
a published ``afmoe`` ``config.json``, and the seeded weights laid out as
the program's parameter tree.

A leaf is named as the reference names it (``layers/attn/wq/kernel`` with
the layer's index in the model as run), so the reference makes one layer
at a time from the same seed; the program stacks its leading dense layers
and its expert layers apart (``dense_layers/``, ``moe_layers/``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import weights as W


def config(cfg: dict, max_len: int):
    """``models/afmoe.py AfmoeConfig`` for a published ``config.json``."""
    from paddle_operator_tpu.models.afmoe import AfmoeConfig

    dtype = jnp.dtype(cfg["torch_dtype"])
    return AfmoeConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["num_dense_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_dim=cfg["intermediate_size"],
        moe_ffn_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["num_shared_experts"],
        route_scale=cfg["route_scale"], route_norm=cfg["route_norm"],
        sliding_window=cfg["sliding_window"],
        layer_types=tuple(cfg["layer_types"]), max_seq_len=max_len,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype=dtype, param_dtype=dtype)


def make_tree(seed_key: jax.Array, shapes, n_dense: int):
    """A parameter tree shaped like `shapes` (``models/afmoe.py
    param_shapes``).  Stacked leaves are made layer by layer, so that no
    float32 image of a stacked leaf ever exists."""

    def one(path, s):
        name = W.path_name(path)
        for prefix, first in (("dense_layers/", 0), ("moe_layers/", n_dense)):
            if name.startswith(prefix):
                ref = "layers/" + name[len(prefix):]
                return jax.lax.map(
                    lambda l: W.make_leaf(seed_key, ref, s.shape[1:],
                                          s.dtype, l),
                    jnp.arange(first, first + s.shape[0]))
        return W.make_leaf(seed_key, name, s.shape, s.dtype)

    return jax.tree_util.tree_map_with_path(one, shapes)
