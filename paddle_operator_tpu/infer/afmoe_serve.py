"""Serving an EXPERT STACK — a decoder whose leading layers are dense and
whose other layers route every token to a few of many experts
(``models/afmoe.py``: Arcee Trinity; ``models/glm_moe_lite.py``:
GLM-4.7-Flash): the cached forward behind ``infer/decode.py generate``
and the paged continuous ring's decode step and prefill insert at tp 1
with a bf16 pool — the programs the serving cells time.  Every other
serving mode refuses such an architecture at start-up
(:func:`refuse_modes`); none runs the LLaMA block over its weights.

ONE stack for every such architecture.  What an architecture brings is
its model module (:func:`stack_of`): the parameter tree, embedding and
head, the attention half of a block over a cache view (inputs, the
view's write, the view's kernel or an einsum over its lanes, residual)
and the feed-forward half; and its cache's buffers, which pick the view
(``cfg.cache_buffers()``; infer/paged.py ``paged_view``: K and V a head,
or one latent row for all heads).

The stack is not one scan over identical layers: the leading dense
layers run unrolled, then one ``lax.scan`` over the expert layers with
whatever differs from layer to layer as scanned operands (``afmoe``: a
layer's window, a full layer's being past any position, and whether it
rotates q and k).  Pool layout and block tables are the ring's own:
every layer keeps the whole context (a pool per layer kind that frees
blocks behind the window is not written).

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
from paddle_operator_tpu.models import afmoe, glm_moe_lite

_STACKS = {afmoe.AfmoeConfig: afmoe,
           glm_moe_lite.GlmMoeLiteConfig: glm_moe_lite}


def stack_of(cfg):
    """The model module whose block the expert stack runs for `cfg`;
    None for a configuration that is not an expert stack."""
    return _STACKS.get(type(cfg))


def is_expert_stack(cfg) -> bool:
    return type(cfg) in _STACKS


def refuse_modes(cfg, modes: Dict[str, Any]) -> None:
    """One sentence and a ``ValueError`` naming the serving modes an
    expert stack is not written for; a no-op for any other config.
    `modes` maps a mode's name (as the operator sets it) to whether it
    is on."""
    if not is_expert_stack(cfg):
        return
    on = sorted(name for name, value in modes.items() if value)
    if on:
        raise ValueError(
            f"{type(cfg).__name__} is served by the paged continuous ring "
            "at tp 1 with a bf16 pool (SERVE_CONTINUOUS=1 SERVE_PAGED=1 "
            "SERVE_PREFIX_CACHE=0) and by generate only; not written for: "
            + ", ".join(on))


def load_params(cfg, ckpt, seed: int):
    """``load_serving_params`` for an expert stack: smoke-mode weights
    under one jit on the first device.  The trainer refuses the
    architecture, so there is no checkpoint to restore."""
    if ckpt is not None and ckpt.enabled and ckpt.latest_step() is not None:
        raise ValueError(f"{type(cfg).__name__} has no trainer, so no "
                         "checkpoint layout to restore from")
    M = stack_of(cfg)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    shardings = jax.tree.map(lambda _: one, M.param_shapes(cfg))
    return jax.jit(lambda rng: M.init_params(cfg, rng),
                   out_shardings=shardings)(jax.random.PRNGKey(seed)), False


def prefill_attn_impl(cfg, width: int) -> str:
    """``decode.prefill_attn_impl`` for an expert stack's whole-prompt
    insert: the LLaMA insert's rule where the architecture's block can
    attend a prompt through the flash kernel, the einsum elsewhere."""
    if not stack_of(cfg).whole_prompt_flash(cfg):
        return "einsum"
    return D.prefill_attn_impl(cfg, width)


# ---------------------------------------------------------------------------
# generate: [B, T] new tokens against a contiguous cache
# ---------------------------------------------------------------------------


class _LaneView:
    """A contiguous cache as a block's attention sees a cache (the paged
    pool's view is infer/paged.py ``paged_view``): head-major buffers
    ``[B, H, S, W]``, a layer's at a time; ``T`` new rows land at the
    scalar position; attention is the einsum over the lanes themselves,
    the whole allocation under the mask."""

    kernel = False

    def __init__(self, pos: jax.Array) -> None:
        self.pos = pos

    @jax.named_scope("cache_write")
    def write(self, bufs, li, *rows):
        return tuple(jax.lax.dynamic_update_slice(
            buf, r.transpose(0, 2, 1, 3), (0, 0, self.pos, 0))
            for buf, r in zip(bufs, rows))

    def lanes(self, bufs, li):
        return bufs


def forward(cfg, params: Dict[str, Any], tokens: jax.Array,
            cache: Dict[str, jax.Array], *, head_at=None,
            counted: Optional[jax.Array] = None, whole_prompt: bool = False):
    """``decode._forward`` for an expert stack: ``[B, T]`` new tokens at
    ``cache['pos']`` -> (logits, advanced cache, prefill load ``[E]``).
    ``head_at`` (an index among the T, may be traced): norm and head at
    that one position only, logits ``[B, 1, V]`` — the whole prompt's
    logits at this vocabulary are gigabytes.
    Caches are head-major ``[L, B, H, S, W]``, one a buffer of
    ``cfg.cache_buffers()``; attention is the einsum over the whole
    allocation under the causal, fill and window mask (prefill and cached
    decoding alike: the test oracle's path) — but a ``whole_prompt`` (the
    cache is empty and ``tokens`` is all it will hold: the ring's insert)
    on a rung :func:`prefill_attn_impl` sends to the flash kernel."""
    M = stack_of(cfg)
    pos = cache["pos"]
    b, t = tokens.shape
    x = M.embed(cfg, params, tokens)
    tables = M.rope_tables(cfg)
    q_pos = jnp.broadcast_to(pos + jnp.arange(t), (b, t))
    nd = cfg.n_dense_layers
    names = tuple(cfg.cache_buffers())
    kinds = M.layer_kinds(cfg)      # what differs from layer to layer
    view = _LaneView(pos)
    attend = {}
    if whole_prompt and prefill_attn_impl(cfg, t) == "flash":
        attend = {"flash": True, "blocks": D._prefill_blocks(t)}

    scanned, experts = M.split_experts(params["moe_layers"])

    def block(lp, x, bufs, kind, moe_layer=None):
        a, bufs = M.attention(cfg, lp, x, tables, q_pos, view, bufs, None,
                              kind, **attend)
        y, load = M.ffn_residual(
            cfg, lp, a, None if moe_layer is None else experts, moe_layer,
            counted)
        return y, bufs, load

    dense = []
    for i in range(nd):
        x, bufs, _ = block(M.layer_at(params["dense_layers"], i), x,
                           tuple(cache[n][i] for n in names),
                           tuple(k[i] for k in kinds))
        dense.append(bufs)

    def body(x, layer_in):
        lp, bufs, kind, l = layer_in
        y, bufs, load = block(lp, x, bufs, kind, l)
        return y, (bufs, load)

    x, (moe, loads) = jax.lax.scan(
        body, x, (scanned, tuple(cache[n][nd:] for n in names),
                  tuple(jnp.asarray(k[nd:]) for k in kinds),
                  jnp.arange(cfg.n_moe_layers)))
    if head_at is not None:
        x = jax.lax.dynamic_slice_in_dim(x, head_at, 1, axis=1)
    new_cache = {n: jnp.concatenate([jnp.stack([d[j] for d in dense]),
                                     moe[j]])
                 for j, n in enumerate(names)}
    new_cache["pos"] = pos + t
    return M.lm_head(cfg, params, x), new_cache, loads.sum(0)


# ---------------------------------------------------------------------------
# The paged ring: decode step
# ---------------------------------------------------------------------------


def paged_ring_forward(cfg, params, tok: jax.Array, cache,
                       table: jax.Array, active: jax.Array):
    """The paged ring's decode step for an expert stack: ``tok [B]`` at
    per-lane ``cache['pos']`` -> (logits ``[B, V]``, advanced cache, the
    step's load ``[E]`` over `active` lanes and its experts touched,
    summed over the expert layers).  The pool is reached through its
    view (infer/paged.py ``paged_view``: the token write, the decode
    kernel over the tick's work list, the gathered lanes for an einsum);
    the block is the architecture's own."""
    M = stack_of(cfg)
    pos = cache["pos"]
    x = M.embed(cfg, params, tok[:, None])
    tables = M.rope_tables(cfg)
    view = PG.paged_view(cfg, cache, table, lane_mask=active)
    view.enter(1)
    nd = cfg.n_dense_layers
    kinds = M.layer_kinds(cfg)
    counted = active[:, None]
    scanned, experts = M.split_experts(params["moe_layers"])
    lists = None
    if view.kernel:   # the tick's work lists: one a window among the layers
        windows = M.kernel_windows(cfg)
        by_window = {w: view.cells(w) for w in set(windows)}
        lists = [by_window[w] for w in windows]

    def block(lp, li, x, bufs, kind, cells, moe_layer=None):
        a, bufs = M.attention(cfg, lp, x, tables, pos[:, None], view, bufs,
                              li, kind, cells)
        y, load = M.ffn_residual(
            cfg, lp, a, None if moe_layer is None else experts, moe_layer,
            counted)
        return y, bufs, load

    bufs = view.buffers()
    for i in range(nd):
        x, bufs, _ = block(M.layer_at(params["dense_layers"], i),
                           jnp.int32(i), x, bufs,
                           tuple(k[i] for k in kinds), lists and lists[i])

    def body(carry, layer_in):
        x, bufs = carry
        lp, li, kind, cells = layer_in
        y, bufs, load = block(lp, li, x, bufs, kind, cells, li - nd)
        return (y, bufs), load

    (x, bufs), loads = jax.lax.scan(
        body, (x, bufs),
        (scanned, jnp.arange(nd, cfg.n_layers),
         tuple(jnp.asarray(k[nd:]) for k in kinds),
         lists and jax.tree.map(lambda *c: jnp.stack(c), *lists[nd:])))
    new_cache = dict(cache, **dict(zip(view.names, bufs)), pos=pos + 1)
    return (M.lm_head(cfg, params, x)[:, 0], new_cache, loads.sum(0),
            jnp.sum(loads > 0))


def make_paged_chunk_step(cfg, chunk_tokens: int,
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


def make_paged_prefill_insert(cfg, bucket: int, block_size: int,
                              top_k: Optional[int] = None,
                              top_p: Optional[float] = None):
    """``paged.make_paged_prefill_insert``'s contract (cold admission: the
    whole ``[1, bucket]`` prompt forward, the rows it cached scattered
    into the pool as whole blocks at the lane's table entries — the
    pool's view says how — and the first token sampled).  The real prompt
    tokens' assignments by expert add to ``cache['moe_pf']``."""
    if bucket % block_size:
        raise ValueError(f"prefill bucket {bucket} not a multiple of the "
                         f"block size {block_size}")

    def insert(params, cache, table_row, tok, temp, keys, prompt,
               prompt_len, slot, temp_val, seed):
        lane = D.init_cache(cfg, 1, bucket)
        counted = jnp.arange(bucket)[None, :] < prompt_len
        logits, lane, load = forward(cfg, params, prompt, lane,
                                     head_at=prompt_len - 1,
                                     counted=counted, whole_prompt=True)
        new_cache = dict(
            cache,
            **PG.view_class(cfg).scatter_prompt(cache, lane, table_row,
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


def split_moe(cfg, moe) -> Tuple[Any, int, Any]:
    """A dispatch's ``moe`` output -> (decode load [E], experts touched,
    prefill load [E])."""
    e = cfg.n_experts
    return moe[:e], int(moe[e]), moe[e + 1:]
