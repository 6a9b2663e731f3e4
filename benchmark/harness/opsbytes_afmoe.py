"""Operations and bytes the ``afmoe`` architecture needs, computed from the
published ``config.json``'s keys (``opsbytes.py`` for this block).

The yardstick's: nothing here counts work the algorithm does not need — an
expert no token was sent to, a key before a sliding layer's window, a lane
that is not live.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """q, k, v, o and the output gate."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * hq + 2 * d * hkv + hq * d + d * hq


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def active_matmul_params(cfg: dict) -> int:
    """Matrix parameters that take part in a product for ONE token: a dense
    layer's attention and SwiGLU; an expert layer's attention, router,
    shared expert and the ``num_experts_per_tok`` experts the token is sent
    to; the output head (the embedding is a lookup)."""
    d = cfg["hidden_size"]
    dense = attention_params(cfg) + 3 * d * cfg["intermediate_size"]
    moe = (attention_params(cfg) + d * cfg["num_experts"]
           + expert_params(cfg) * (cfg["num_shared_experts"]
                                   + cfg["num_experts_per_tok"]))
    n_dense = cfg["num_dense_layers"]
    return (n_dense * dense + (cfg["num_hidden_layers"] - n_dense) * moe
            + d * cfg["vocab_size"])


def serve_flops_per_token(cfg: dict) -> float:
    """2 per active matrix parameter; attention over the context left out
    (the decode kernel's, bound by bytes, with its own roofline)."""
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


def decode_attention_step(cfg: dict, lane_positions) -> dict:
    """The decode kernel's calls of ONE decode step (one a layer) over lanes
    at `lane_positions`: a full layer has to read every cached key and
    value of a lane once, a sliding layer only the last ``sliding_window``
    of them; QK^T and PV over what is read."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    tokens = 0.0
    for kind in cfg["layer_types"]:
        for p in lane_positions:
            tokens += (min(p, cfg["sliding_window"])
                       if kind == "sliding_attention" else p)
    return {"calls": len(cfg["layer_types"]),
            "bytes": tokens * 2 * kv * hd * 2,          # bf16 pool
            "flops": tokens * 2 * 2 * h * hd}


def is_attention_call(cfg: dict, kernel: dict) -> bool:
    """Whether a traced custom call inside ``jit_step`` is the decode
    kernel's: its output is ``[lanes, heads, head_dim]``; the grouped
    products' are ``[rows, width]``.  Told by the call's name where the
    executable kept its scopes, by the output's rank either way (a cached
    executable runs without the names: PERF.md section 7)."""
    name = kernel["name"]
    if "attn" in name:
        return True
    if "ffn" in name or "gmm" in name:
        return False
    return kernel["shape"].count(",") == 2 and kernel["shape"].endswith(
        f",{cfg['num_attention_heads']},{cfg['head_dim']}]")

