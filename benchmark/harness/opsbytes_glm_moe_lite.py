"""Operations and bytes the ``glm4_moe_lite`` architecture needs, computed
from the published ``config.json``'s keys (``opsbytes.py`` for this block).

The yardstick's: nothing here counts work the algorithm does not need — an
expert no token was sent to, a lane that is not live, a prompt's padding,
the half of a causal product above the diagonal, keys and values expanded
for a decode step that can read the latent as it lies.
"""

from __future__ import annotations


def cache_row_bytes(cfg: dict) -> int:
    """What a cached token costs a layer: the latent and the one rotated
    key all heads share, in the pool's bf16."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2


def attention_params(cfg: dict) -> int:
    """The five projections of latent attention: q_a, q_b, kv_a (latent and
    rotated key), kv_b (every head's k_nope and v) and o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + v) + h * v * d)


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_params(cfg: dict) -> int:
    return attention_params(cfg) + 3 * cfg["hidden_size"] * cfg[
        "intermediate_size"]


def expert_layer_params(cfg: dict) -> int:
    """Everything an expert layer holds: attention, router, the shared
    expert(s) and every routed expert."""
    return (attention_params(cfg)
            + cfg["hidden_size"] * cfg["n_routed_experts"]
            + expert_params(cfg) * (cfg["n_shared_experts"]
                                    + cfg["n_routed_experts"]))


def active_matmul_params(cfg: dict) -> int:
    """Matrix parameters that take part in a product for ONE token: a dense
    layer's attention and SwiGLU; an expert layer's attention, router,
    shared expert and the ``num_experts_per_tok`` experts the token is sent
    to; the output head (the embedding is a lookup)."""
    d = cfg["hidden_size"]
    moe = (attention_params(cfg) + d * cfg["n_routed_experts"]
           + expert_params(cfg) * (cfg["n_shared_experts"]
                                   + cfg["num_experts_per_tok"]))
    n_dense = cfg["first_k_dense_replace"]
    return (n_dense * dense_layer_params(cfg)
            + (cfg["num_hidden_layers"] - n_dense) * moe
            + d * cfg["vocab_size"])


def serve_flops_per_token(cfg: dict) -> float:
    """2 per active matrix parameter; attention over the context left out
    (the two attention kernels have rooflines of their own)."""
    return 2.0 * active_matmul_params(cfg)


def grouped_products_layer_step(cfg: dict, assignments: float,
                                experts_touched: float) -> dict:
    """The three grouped products of one expert layer in one decode step
    over `assignments` rows sent to `experts_touched` distinct experts:
    each touched expert's three matrices are read once (bf16), each
    assignment's row goes in and out, 2 operations a parameter a row."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = experts_touched * expert_params(cfg) * 2
    # in: the row twice (gate, up) and the hidden row; out: gate, up in
    # bf16, the result in float32
    rows = assignments * (2 * d * 2 + f * 2 + 2 * f * 2 + d * 4)
    return {"bytes": weights + rows,
            "flops": assignments * 2.0 * expert_params(cfg)}


def latent_decode_step(cfg: dict, lane_positions) -> dict:
    """The latent decode kernel's calls of ONE decode step (one a layer)
    over lanes at `lane_positions` (0: not live): a lane's cached rows are
    read ONCE for all heads; each head's query meets the whole row (latent
    and rotated key) and its probabilities the latent again; the absorbed
    queries go in and the attended latents come out, in bf16."""
    h, c, r = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
               cfg["qk_rope_head_dim"])
    layers = cfg["num_hidden_layers"]
    tokens = float(sum(lane_positions))
    live = sum(1 for p in lane_positions if p > 0)
    return {"calls": layers,
            "bytes": layers * (tokens * cache_row_bytes(cfg)
                               + live * h * (2 * c + r) * 2),
            "flops": layers * tokens * 2.0 * h * (2 * c + r)}


def insert_attention_layer(cfg: dict, n: float, n_squared: float) -> dict:
    """The flash kernel's call of one layer of one whole-prompt insert over
    a prompt of `n` real tokens (`n_squared`: n^2, or its mean over the
    prompts a call may have been): every head's QK^T and PV over the
    causal half at the expanded widths; q, k, v in and the output out."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    v = cfg["v_head_dim"]
    return {"flops": h * n_squared * (qk + v),      # 2 x n^2 / 2 x width
            "bytes": n * h * (2 * qk + 2 * v) * 2.0}


def is_attention_call(kernel: dict) -> bool:
    """Whether a traced custom call is an attention kernel's (the latent
    kernel inside ``jit_step``: output ``[lanes, heads, latent]``; the flash
    kernel inside ``jit_insert``: ``[1, heads, width, head]``) and not a
    grouped product's (``[rows, width]``).  Told by the call's name where
    the executable kept its scopes, by the output's rank either way (a
    cached executable runs without the names: PERF.md section 7)."""
    name = kernel["name"]
    if "attn" in name:
        return True
    if "ffn" in name or "gmm" in name:
        return False
    return kernel["shape"].count(",") >= 2
