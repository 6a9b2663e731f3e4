"""Operations and bytes that the algorithms need, computed from shapes.

These are the yardstick's: a roofline share or an MFU divides what is
counted here by a measured time, so nothing here may count work the
algorithm does not need (recomputation, padding, masked-out halves).
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    layers' projections and the output head (the embedding is a lookup)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (matmul_params(cfg) + cfg["vocab_size"] * d
            + cfg["num_hidden_layers"] * 2 * d + d)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul
    parameter, plus causal attention: QK^T and PV each 2*seq/2*width per
    token forward, three times that with the backward pass."""
    width = cfg["num_attention_heads"] * head_dim(cfg)
    attn_fwd = 2 * (2 * (seq / 2) * width)
    return 6.0 * matmul_params(cfg) + cfg["num_hidden_layers"] * 3 * attn_fwd


def serve_flops_per_token(cfg: dict) -> float:
    """2 per matmul parameter; attention over the context left out (it is
    the decode kernel's, bound by bytes, and has its own roofline)."""
    return 2.0 * matmul_params(cfg)


def decode_attention_call(cfg: dict, context_tokens: float) -> dict:
    """One call of the decode kernel (one layer, one new token per lane)
    over lanes whose contexts add up to `context_tokens`: it has to read
    every cached key and value once and do QK^T and PV over them."""
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    cache_bytes = 2  # bf16 pool
    return {"bytes": context_tokens * 2 * kv * hd * cache_bytes,
            "flops": context_tokens * 2 * 2 * h * hd}


def flash_attention_layer(cfg: dict, batch: int, seq: int) -> dict:
    """The flash kernel's calls of one layer in one train step as the
    trainer makes them under full recomputation: forward, forward again in
    the backward pass, dK/dV (recomputes S, then dP, dV, dK) and dQ
    (recomputes S, then dP, dQ).  Each product over the causal half."""
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    unit = 2.0 * batch * h * seq * seq * hd / 2       # one causal product
    calls = {"fwd": 2, "fwd_remat": 2, "dkv": 4, "dq": 3}
    act = 2                                           # bf16 activations
    q_bytes = batch * seq * h * hd * act
    kv_bytes = batch * seq * kv * hd * act
    # least traffic: each call reads q, k, v (and dO, O for the backward
    # ones) once and writes its outputs once
    traffic = {"fwd": 2 * q_bytes + 2 * kv_bytes,
               "fwd_remat": 2 * q_bytes + 2 * kv_bytes,
               "dkv": 3 * q_bytes + 4 * kv_bytes,
               "dq": 4 * q_bytes + 2 * kv_bytes}
    return {"calls": len(calls),
            "flops": unit * sum(calls.values()),
            "bytes": float(sum(traffic.values()))}


def roofline_share_pct(flops: float, nbytes: float, seconds: float,
                       peak: dict) -> float:
    """The least time the chip could take over the time it took."""
    least = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
