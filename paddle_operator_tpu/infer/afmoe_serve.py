"""Serving the ``afmoe`` architecture (``models/afmoe.py``): the cached
forward behind ``infer/decode.py generate`` and the paged continuous
ring's decode step and prefill insert at tp 1 with a bf16 pool — the
programs the serving cells time.  Every other serving mode refuses the
architecture at start-up (:func:`refuse_modes`); none runs the LLaMA
block over these weights.

The stack is not one scan over identical layers: the leading dense
layers run unrolled, then one ``lax.scan`` over the expert layers with
each layer's kind as scanned operands (its window, a full layer's being
past any position; whether it rotates q and k).  Pool layout and block
tables are the ring's own: every layer keeps the whole context (a pool
per layer kind that frees blocks behind the window is not written).

Routing counters are computed on the device and ride the decode
dispatch's results home: per dispatch the decode steps' assignments by
expert and the experts touched (summed over its layer-steps, live lanes
only), and the assignments by expert of the prefill inserts since the
last dispatch, which accumulate in the donated cache (``moe_pf``) so
that an insert needs no read of its own.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.models import afmoe as M
from paddle_operator_tpu.models.afmoe import AfmoeConfig


def is_afmoe(cfg) -> bool:
    return isinstance(cfg, AfmoeConfig)


def refuse_modes(cfg, modes: Dict[str, Any]) -> None:
    """One sentence and a ``ValueError`` naming the serving modes the
    architecture is not written for; a no-op for any other config.
    `modes` maps a mode's name (as the operator sets it) to whether it
    is on."""
    if not is_afmoe(cfg):
        return
    on = sorted(name for name, value in modes.items() if value)
    if on:
        raise ValueError(
            f"{type(cfg).__name__} is served by the paged continuous ring "
            "at tp 1 with a bf16 pool (SERVE_CONTINUOUS=1 SERVE_PAGED=1 "
            "SERVE_PREFIX_CACHE=0) and by generate only; not written for: "
            + ", ".join(on))


def load_params(cfg: AfmoeConfig, ckpt, seed: int):
    """``load_serving_params`` for this architecture: smoke-mode weights
    under one jit on the first device.  The trainer refuses the
    architecture, so there is no checkpoint to restore."""
    if ckpt is not None and ckpt.enabled and ckpt.latest_step() is not None:
        raise ValueError("AfmoeConfig has no trainer, so no checkpoint "
                         "layout to restore from")
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    shardings = jax.tree.map(lambda _: one, M.param_shapes(cfg))
    return jax.jit(lambda rng: M.init_params(cfg, rng),
                   out_shardings=shardings)(jax.random.PRNGKey(seed)), False


# ---------------------------------------------------------------------------
# generate: [B, T] new tokens against a contiguous cache
# ---------------------------------------------------------------------------


def forward(cfg: AfmoeConfig, params: Dict[str, Any], tokens: jax.Array,
            cache: Dict[str, jax.Array], *, head_at=None,
            counted: Optional[jax.Array] = None):
    """``decode._forward`` for this architecture: ``[B, T]`` new tokens at
    ``cache['pos']`` -> (logits, advanced cache, prefill load ``[E]``).
    ``head_at`` (an index among the T, may be traced): norm and head at
    that one position only, logits ``[B, 1, V]`` — the whole prompt's
    logits at this vocabulary are gigabytes.
    Caches are head-major ``[L, B, H_kv, S, hd]``; attention is the
    einsum over the whole allocation under the causal, fill and window
    mask (prefill and cached decoding alike: the test oracle's path)."""
    pos = cache["pos"]
    b, t = tokens.shape
    x = M.embed(cfg, params, tokens)
    cos, sin = M.rope_tables(cfg)
    q_pos = jnp.broadcast_to(pos + jnp.arange(t), (b, t))
    nd = cfg.n_dense_layers
    windows, ropes = cfg.windows(), cfg.ropes()

    scanned, experts = M.split_experts(params["moe_layers"])

    def block(lp, x, k_c, v_c, window, use_rope, moe_layer=None):
        q, k, v, g = M.attn_inputs(cfg, lp, x, cos, sin, q_pos, use_rope)
        with jax.named_scope("cache_write"):
            k_c = jax.lax.dynamic_update_slice(
                k_c, k.transpose(0, 2, 1, 3), (0, 0, pos, 0))
            v_c = jax.lax.dynamic_update_slice(
                v_c, v.transpose(0, 2, 1, 3), (0, 0, pos, 0))
        att = M.attend(cfg, q, k_c, v_c, q_pos, window)
        a = M.attn_residual(cfg, lp, x, att, g)
        y, load = M.ffn_residual(
            cfg, lp, a, None if moe_layer is None else experts, moe_layer,
            counted)
        return y, k_c, v_c, load

    dense_k, dense_v = [], []
    for i in range(nd):
        x, k_c, v_c, _ = block(M.layer_at(params["dense_layers"], i), x,
                               cache["k"][i], cache["v"][i], windows[i],
                               ropes[i])
        dense_k.append(k_c)
        dense_v.append(v_c)

    def body(x, layer_in):
        lp, k_c, v_c, window, use_rope, l = layer_in
        y, k_c, v_c, load = block(lp, x, k_c, v_c, window, use_rope, l)
        return y, (k_c, v_c, load)

    x, (moe_k, moe_v, loads) = jax.lax.scan(
        body, x, (scanned, cache["k"][nd:], cache["v"][nd:],
                  jnp.asarray(windows[nd:], jnp.int32),
                  jnp.asarray(ropes[nd:], bool),
                  jnp.arange(cfg.n_moe_layers)))
    if head_at is not None:
        x = jax.lax.dynamic_slice_in_dim(x, head_at, 1, axis=1)
    new_cache = {"k": jnp.concatenate([jnp.stack(dense_k), moe_k]),
                 "v": jnp.concatenate([jnp.stack(dense_v), moe_v]),
                 "pos": pos + t}
    return M.lm_head(cfg, params, x), new_cache, loads.sum(0)


# ---------------------------------------------------------------------------
# The paged ring: decode step
# ---------------------------------------------------------------------------


def paged_ring_forward(cfg: AfmoeConfig, params, tok: jax.Array, cache,
                       table: jax.Array, active: jax.Array):
    """The paged ring's decode step for this architecture: ``tok [B]`` at
    per-lane ``cache['pos']`` -> (logits ``[B, V]``, advanced cache, the
    step's load ``[E]`` over `active` lanes and its experts touched,
    summed over the expert layers).  The pool is reached through its
    view (``paged.PagedView``: the token write, the decode kernel with a
    layer's window, the gathered lanes for :func:`models.afmoe.attend`);
    the block is this architecture's own."""
    pos = cache["pos"]
    x = M.embed(cfg, params, tok[:, None])
    cos, sin = M.rope_tables(cfg)
    view = PG.PagedView(cfg, cache, table, lane_mask=active)
    view.enter(1)
    nd = cfg.n_dense_layers
    windows, ropes = cfg.windows(), cfg.ropes()
    counted = active[:, None]
    scanned, experts = M.split_experts(params["moe_layers"])
    lists = None
    if view.kernel:   # the tick's work lists: one a kind of layer
        kinds = {w: view.cells(w) for w in set(windows)}
        lists = [kinds[w] for w in windows]

    def block(lp, li, x, bufs, window, use_rope, cells, moe_layer=None):
        q, k, v, g = M.attn_inputs(cfg, lp, x, cos, sin, pos[:, None],
                                   use_rope)
        bufs = view.write(bufs, li, k, v)
        if view.kernel:
            att = view.kernel_attend(bufs, li, q, cells=cells)
        else:
            att = M.attend(cfg, q, *view.lanes(bufs, li), pos[:, None],
                           window)
        a = M.attn_residual(cfg, lp, x, att, g)
        y, load = M.ffn_residual(
            cfg, lp, a, None if moe_layer is None else experts, moe_layer,
            counted)
        return y, bufs, load

    bufs = (cache["k"], cache["v"])
    for i in range(nd):
        x, bufs, _ = block(M.layer_at(params["dense_layers"], i),
                           jnp.int32(i), x, bufs, windows[i], ropes[i],
                           lists and lists[i])

    def body(carry, layer_in):
        x, bufs = carry
        lp, li, window, use_rope, cells = layer_in
        y, bufs, load = block(lp, li, x, bufs, window, use_rope, cells,
                              li - nd)
        return (y, bufs), load

    (x, (kc, vc)), loads = jax.lax.scan(
        body, (x, bufs),
        (scanned, jnp.arange(nd, cfg.n_layers),
         jnp.asarray(windows[nd:], jnp.int32),
         jnp.asarray(ropes[nd:], bool),
         lists and jax.tree.map(lambda *c: jnp.stack(c), *lists[nd:])))
    new_cache = dict(cache, k=kc, v=vc, pos=pos + 1)
    return (M.lm_head(cfg, params, x)[:, 0], new_cache, loads.sum(0),
            jnp.sum(loads > 0))


def make_paged_chunk_step(cfg: AfmoeConfig, chunk_tokens: int,
                          top_k: Optional[int] = None,
                          top_p: Optional[float] = None,
                          check_finite: bool = False):
    """``paged.make_paged_chunk_step``'s contract with one more output,
    last: ``moe [2E + 1]`` int32 — the dispatch's decode assignments by
    expert, its experts touched (summed over layer-steps), and the
    prefill assignments by expert accumulated in the cache since the last
    dispatch (read out and zeroed here).

    ``step(params, cache, table, tok, temp, keys, active)
    -> (cache', tok', toks [chunk, B][, ok [B]], moe)``"""
    def step(params, cache, table, tok, temp, keys, active):
        def tick(carry, _):
            cache, tok, ok, load, touched = carry
            logits, new_cache, l, t = paged_ring_forward(
                cfg, params, tok, cache, table, active)
            nxt = D._sample_tokens(logits, temp, keys, cache["pos"],
                                   top_k, top_p)
            new_cache["pos"] = jnp.where(active, new_cache["pos"], 0)
            nxt = jnp.where(active, nxt, tok)
            if check_finite:
                ok = ok & jnp.all(jnp.isfinite(logits), axis=-1)
            return (new_cache, nxt, ok, load + l, touched + t), nxt

        zero = jnp.zeros((cfg.n_experts,), jnp.int32)
        (cache, tok, ok, load, touched), toks = jax.lax.scan(
            tick, (cache, tok, jnp.ones(tok.shape, bool), zero,
                   jnp.zeros((), jnp.int32)), None, length=chunk_tokens)
        moe = jnp.concatenate([load, touched[None], cache["moe_pf"]])
        cache = dict(cache, moe_pf=zero)
        if check_finite:
            return cache, tok, toks, ok, moe
        return cache, tok, toks, moe

    return jax.jit(step, donate_argnums=(1,))


# ---------------------------------------------------------------------------
# The paged ring: prefill insert
# ---------------------------------------------------------------------------


def make_paged_prefill_insert(cfg: AfmoeConfig, bucket: int, block_size: int,
                              top_k: Optional[int] = None,
                              top_p: Optional[float] = None):
    """``paged.make_paged_prefill_insert``'s contract (cold admission: the
    whole ``[1, bucket]`` prompt forward, its keys and values scattered
    into the pool as whole blocks at the lane's table entries, the first
    token sampled).  The real prompt tokens' assignments by expert add
    to ``cache['moe_pf']``."""
    if bucket % block_size:
        raise ValueError(f"prefill bucket {bucket} not a multiple of the "
                         f"block size {block_size}")

    def insert(params, cache, table_row, tok, temp, keys, prompt,
               prompt_len, slot, temp_val, seed):
        lane = D.init_cache(cfg, 1, bucket)
        counted = jnp.arange(bucket)[None, :] < prompt_len
        logits, lane, load = forward(cfg, params, prompt, lane,
                                     head_at=prompt_len - 1,
                                     counted=counted)
        new_cache = dict(
            cache,
            k=PG.scatter_prompt_blocks(cache["k"], lane["k"], table_row,
                                       block_size),
            v=PG.scatter_prompt_blocks(cache["v"], lane["v"], table_row,
                                       block_size),
            pos=cache["pos"].at[slot].set(prompt_len),
            moe_pf=cache["moe_pf"] + load)
        key = jax.random.PRNGKey(seed)
        first = D._sample_tokens(
            logits[0], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)), top_k, top_p)[0]
        return (new_cache, tok.at[slot].set(first),
                temp.at[slot].set(temp_val), keys.at[slot].set(key), first)

    return jax.jit(insert, donate_argnums=(1, 3, 4, 5))


def split_moe(cfg: AfmoeConfig, moe) -> Tuple[Any, int, Any]:
    """A dispatch's ``moe`` output -> (decode load [E], experts touched,
    prefill load [E])."""
    e = cfg.n_experts
    return moe[:e], int(moe[e]), moe[e + 1:]
