"""Device half of the serving ring: compiled programs + ring state.

ISSUE 6 split the ~1.6k-line one-file ring into a **scheduler**
(infer/scheduler.py — admission, queues, deadlines, request lifecycle,
resilience hooks; pure host code) and this **executor** (compiled
dispatch, ring/paged caches, prefill and decode step functions; every
``jax`` touch of the serving hot path).  The split is what lets prefill
and decode executors differ: :class:`RingExecutor` owns the decode
ring's resident programs and device state, while
:class:`PrefillExecutor` is a SEPARATE prefill engine (its own thread,
its own block pool) that fills paged KV blocks and hands completed
block tables to the decode ring — the in-process half of DistServe-
style disaggregation (Zhong et al., 2024).

Three prefill paths feed the ring (scheduler knob ``prefill_mode``,
serve.py env ``SERVE_PREFILL``):

- **inline** (the original): admission is ONE compiled prefill-insert
  dispatch on the ring thread — a cold 2k prompt stalls every resident
  decode lane for the full prefill.
- **chunked** (Sarathi-Serve, Agrawal et al., 2024): prefill runs in
  decode-sized token slices (``prefill_chunk``) interleaved into ring
  iterations — intermediate slices only append KV (no lm head), the
  final slice reuses the paged SUFFIX-insert (or the contiguous
  equivalent) to sample the first token, so resident lanes never wait
  more than one slice.
- **disagg**: cold prompts prefill on :class:`PrefillExecutor`'s own
  thread into its own pool; the decode ring's only work is a
  device-to-device block copy + a tiny attach dispatch at handoff.
  Prefix HITS still admit through the radix suffix-insert on the ring
  thread (only uncached suffix tokens are ever prefilled anywhere).

All three are greedy-bit-identical to the inline ring: every prefill
path runs the same compiled op sequences (``decode._forward`` /
``decode.cached_forward`` over the lane's cache view) and samples the
first token through the shared ``_sample_tokens`` rule — pinned by
tests/test_prefill_modes.py and the dryrun ``serve-disagg`` line.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.infer.decode import (
    _mega_continue,
    _sample_tokens,
    _splice_lane,
    init_ring_cache,
)
from paddle_operator_tpu.models.llama import LlamaConfig


class ExecPlan:
    """One resident ring dispatch, fully described host-side
    (ISSUE 11).  The scheduler FILLS a plan (which lanes step, the
    block table snapshot, the adapter tail, how many fused iterations,
    and the per-lane continuation budgets) and the executor REPLAYS it
    (:meth:`RingExecutor.replay`) — one code path serving N=1 (the
    byte-identical legacy dispatch, the oracle) and N>1 (the fused
    megastep).  Admission, preemption, promotions, CoW and handoffs
    all happen BETWEEN plans, so a replay is a pure function of ring
    state + plan — which is what lets the chaos injector and the
    dispatch watchdog wrap it as a unit.

    - ``n_steps``  fused ring iterations (1 = today's dispatch);
    - ``active``   per-lane participation (host bools, [slots]);
    - ``table``    block-table snapshot (np [slots, M]; None on the
      contiguous ring) — prefill-pending rows already trash-masked;
    - ``lora``     trailing adapter operands (lora_step_tail());
    - ``eos``      per-lane eos token id, -1 for none (np int32);
    - ``left``     per-lane remaining token budget — what the device
      may still emit (the admission-sampled first token, if still
      unmaterialized, is already subtracted);
    - ``steps``    per-lane max fused iterations this dispatch (the
      deadline-tick budget; ``n_steps`` when unconstrained).

    ``eos``/``left``/``steps`` are only consulted when ``n_steps > 1``
    — the N=1 replay is operand-for-operand today's dispatch."""

    __slots__ = ("n_steps", "active", "table", "lora", "eos", "left",
                 "steps")

    def __init__(self, n_steps, active, table=None, lora=(),
                 eos=None, left=None, steps=None):
        self.n_steps = int(n_steps)
        self.active = active
        self.table = table
        self.lora = tuple(lora)
        self.eos = eos
        self.left = left
        self.steps = steps


class DispatchResult:
    """Device futures one :meth:`RingExecutor.replay` returns — what
    the scheduler's pipelining queue holds until the consume boundary.
    ``toks`` is [chunk, B] at n_steps=1 and [n, chunk(|K+1), B] fused;
    ``counts`` the host-consumable row counts ([B] spec at N=1,
    [n, B] fused, None plain-1-step); ``raw`` the spec rounds' device
    commit counts (acceptance telemetry); ``ok`` the isfinite
    verdicts (check_finite only); ``rows`` the most positions a lane
    advances in it (what the plan's block projection counts for a
    dispatch in flight: every fused iteration's chunk or speculated
    block, and 1 for the step an insert carried)."""

    __slots__ = ("toks", "counts", "ok", "raw", "n_steps", "rows", "moe")

    def __init__(self, toks, counts, ok, raw, n_steps, rows, moe=None):
        self.toks = toks
        self.counts = counts
        self.ok = ok
        self.raw = raw
        self.n_steps = n_steps
        self.rows = rows
        # routing counters of an expert architecture's dispatch
        # (infer/afmoe_serve.py make_paged_chunk_step), else None
        self.moe = moe


# ---------------------------------------------------------------------------
# The contiguous ring's programs (its cache, the one cached forward and
# the sampling rule live below, in infer/decode.py)
# ---------------------------------------------------------------------------


def make_chunk_step(cfg: LlamaConfig, chunk_tokens: int,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None, mesh=None,
                    check_finite: bool = False):
    """The ONE resident compiled decode program.

    ``step(params, cache, tok [B], temp [B], keys [B,2], active [B])
    -> (cache', tok', toks [chunk, B])``

    Runs ``chunk_tokens`` ticks for every lane.  Inactive lanes compute
    (their FLOPs are the price of static shapes — standard slot-server
    trade) but neither advance their position nor write meaningful
    state; their emitted tokens are ignored host-side.  The cache is
    donated: the ring buffer must never be copied per chunk.  Under a
    serving mesh the whole chunk remains ONE sharded dispatch — the
    shard_map kernel regions and GSPMD einsums compile into the same
    resident program, no eager per-device ops anywhere.

    ``check_finite=True`` (infer/resilience.py nan_check): the step
    additionally returns ``ok [B]`` — an isfinite fold of every tick's
    logits per lane, so the host can quarantine a NaN-producing lane
    (fail ONE request, never the ring) without shipping the logits
    home.  Token outputs are unchanged; the fold rides the same scan.
    """

    def step(params, cache, tok, temp, keys, active, *lora_args):
        # adapter serving (ISSUE 10): the stacked LoRA arrays + per-lane
        # adapter ids arrive as trailing operands — absent, the traced
        # program is byte-identical to the adapterless ring
        lora = tuple(lora_args) if lora_args else None

        def tick(carry, _):
            # the isfinite fold rides the carry ONLY when requested —
            # the default resident program is unchanged
            if check_finite:
                cache, tok, ok = carry
            else:
                cache, tok = carry
            logits, new_cache = D.cached_step(
                cfg, params, tok, D.ContiguousView(cfg, cache, mesh),
                lora=lora)
            nxt = _sample_tokens(logits, temp, keys, cache["pos"],
                                 top_k, top_p)
            # retired/free lanes: position ZEROED (a stale fill
            # position must never outlive its request — the
            # serving_status staleness fix); their (ignored) writes
            # land at row 0, which the next admission's splice
            # overwrites along with the rest of the lane
            new_cache["pos"] = jnp.where(active, new_cache["pos"], 0)
            nxt = jnp.where(active, nxt, tok)
            if check_finite:
                ok = ok & jnp.all(jnp.isfinite(logits), axis=-1)
                return (new_cache, nxt, ok), nxt
            return (new_cache, nxt), nxt

        if check_finite:
            (cache, tok, ok), toks = jax.lax.scan(
                tick, (cache, tok, jnp.ones(tok.shape, bool)), None,
                length=chunk_tokens)
            return cache, tok, toks, ok
        (cache, tok), toks = jax.lax.scan(
            tick, (cache, tok), None, length=chunk_tokens)
        return cache, tok, toks

    return jax.jit(step, donate_argnums=(1,))


def make_megastep(cfg: LlamaConfig, chunk_tokens: int, n_steps: int,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None, mesh=None,
                  check_finite: bool = False):
    """N fused ring iterations in ONE compiled dispatch (ISSUE 11): the
    contiguous ring's ``make_chunk_step`` body scanned ``n_steps``
    times with the host's boundary decisions — eos detection, token
    budget, step budget — carried ON DEVICE (:func:`_mega_advance`).
    A lane that finishes mid-megastep free-runs masked: its position
    stops advancing (the pos a live lane would carry is restored from
    the pre-chunk snapshot, so a step-budget-frozen lane could resume)
    and its writes land at its own row 0 exactly like an inactive
    lane's in the 1-step program — which the next admission's splice
    overwrites.  NOTE the contiguous ring must only freeze lanes it
    will EVICT at the boundary (eos / budget exhausted): the masked
    row-0 writes make a frozen-and-resumed lane unsound here (they
    overwrite the first prompt row), so the scheduler never hands a
    contiguous ring a per-lane step budget below ``n_steps`` — the
    paged megastep (trash-block redirect) is the resumable one.

    ``mega(params, cache, tok, temp, keys, active, eos, left, steps,
    *lora) -> (cache', tok', toks [n, chunk, B], counts [n, B]
    [, oks [n, B]])``

    ``counts[r, b]`` is the number of ``toks[r, :, b]`` rows the host
    consumes for iteration ``r`` (0 once the lane is dead); ``oks``
    (check_finite) is the per-iteration isfinite verdict, forced True
    for masked lanes (a free-running dead lane's garbage must not
    quarantine it)."""

    def mega(params, cache, tok, temp, keys, active, eos, left, steps,
             *lora_args):
        lora = tuple(lora_args) if lora_args else None

        def outer(carry, _):
            cache, tok, live, lleft, lsteps = carry
            p0 = cache["pos"]

            def tick(c, _):
                if check_finite:
                    cache, tok, ok = c
                else:
                    cache, tok = c
                logits, new_cache = D.cached_step(
                    cfg, params, tok, D.ContiguousView(cfg, cache, mesh),
                    lora=lora)
                nxt = _sample_tokens(logits, temp, keys, cache["pos"],
                                     top_k, top_p)
                new_cache["pos"] = jnp.where(live, new_cache["pos"], 0)
                nxt = jnp.where(live, nxt, tok)
                if check_finite:
                    ok = ok & (jnp.all(jnp.isfinite(logits), axis=-1)
                               | ~live)
                    return (new_cache, nxt, ok), nxt
                return (new_cache, nxt), nxt

            if check_finite:
                (cache, tok, ok), toks = jax.lax.scan(
                    tick, (cache, tok, jnp.ones(tok.shape, bool)), None,
                    length=chunk_tokens)
            else:
                (cache, tok), toks = jax.lax.scan(
                    tick, (cache, tok), None, length=chunk_tokens)
            raw = jnp.where(live, chunk_tokens, 0).astype(jnp.int32)
            count, live2, left2, lsteps2 = _mega_continue(
                toks, raw, live, lleft, lsteps, eos)
            # a lane frozen THIS boundary keeps the position it earned
            # (the tick zeroed it); lanes dead from the start stay at
            # their (zeroed) entry position
            cache["pos"] = jnp.where(live, cache["pos"], p0)
            out = (toks, count, ok) if check_finite else (toks, count)
            return (cache, tok, live2, left2, lsteps2), out

        live0 = active & (left > 0) & (steps > 0)
        if check_finite:
            (cache, tok, _, _, _), (toks, counts, oks) = jax.lax.scan(
                outer, (cache, tok, live0, left, steps), None,
                length=n_steps)
            return cache, tok, toks, counts, oks
        (cache, tok, _, _, _), (toks, counts) = jax.lax.scan(
            outer, (cache, tok, live0, left, steps), None,
            length=n_steps)
        return cache, tok, toks, counts

    return jax.jit(mega, donate_argnums=(1,))


def make_prefill_insert(cfg: LlamaConfig, bucket: int,
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None, mesh=None):
    """Per-prompt-bucket compiled admission: prefill a [1, bucket]
    (right-padded) prompt, splice its KV into ring lane ``slot``, sample
    the first token, and update EVERY piece of lane state — tok, temp,
    keys — in the same compiled program.

    One dispatch on purpose: EAGER ops (``.at[].set``, ``argmax``)
    block until all in-flight device work drains, so an admission built
    from eager lane updates stalls the whole ring behind a decoding
    chunk (how long, on the v5e, is not re-measured).
    Everything device-side about admission lives inside this jit; the
    host's only jobs are bookkeeping lists.

    Exactness with padding: pad rows fill cache positions PAST the real
    prompt; the causal mask keeps real rows from attending them, the
    first token samples from ``prompt_len - 1`` (the last REAL
    position), the lane position is set to ``prompt_len`` so decode
    overwrites the pad rows before they ever become attendable.

    ``insert(params, cache, tok, temp, keys, prompt [1,bucket],
    prompt_len, slot, temp_val, seed)
    -> (cache', tok', temp', keys', first_token)``
    """

    def insert(params, cache, tok, temp, keys, prompt, prompt_len, slot,
               temp_val, seed, *lora_args):
        lane = D.init_cache(cfg, 1, bucket)
        logits, lane = D._forward(
            cfg, params, prompt, lane, mesh=mesh,
            lora=tuple(lora_args) if lora_args else None,
            whole_prompt=True)
        logits = logits[0, prompt_len - 1]                  # last real row
        new_cache = _splice_lane(cache, lane, slot, prompt_len)
        # first token through the SHARED sampling rule (_sample_tokens),
        # batch-of-one shaped
        key = jax.random.PRNGKey(seed)
        first = _sample_tokens(
            logits[None], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)),
            top_k, top_p)[0]
        return (new_cache,
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(key),
                first)

    return jax.jit(insert, donate_argnums=(1, 2, 3, 4))


def make_spec_prefill_insert(cfg: LlamaConfig, dcfg: LlamaConfig,
                             bucket: int, top_k: Optional[int] = None,
                             top_p: Optional[float] = None, mesh=None):
    """Admission for the SPECULATIVE ring: one compiled dispatch that
    prefills the prompt into BOTH the target and the draft lane (the
    draft's logits are discarded — it only needs the KV context to
    propose from) and samples the first token from the target, with the
    same exactness-with-padding story as :func:`make_prefill_insert`.

    ``insert(params, dparams, cache, dcache, tok, temp, keys,
    prompt [1,bucket], prompt_len, slot, temp_val, seed)
    -> (cache', dcache', tok', temp', keys', first_token)``
    """

    def insert(params, dparams, cache, dcache, tok, temp, keys, prompt,
               prompt_len, slot, temp_val, seed):
        lane = D.init_cache(cfg, 1, bucket)
        logits, lane = D._forward(cfg, params, prompt, lane, mesh=mesh,
                                  whole_prompt=True)
        logits = logits[0, prompt_len - 1]
        new_cache = _splice_lane(cache, lane, slot, prompt_len)
        dlane = D.init_cache(dcfg, 1, bucket)
        _, dlane = D._forward(dcfg, dparams, prompt, dlane,
                              last_only=True, mesh=mesh,
                              whole_prompt=True)
        new_dcache = _splice_lane(dcache, dlane, slot, prompt_len)
        key = jax.random.PRNGKey(seed)
        first = _sample_tokens(
            logits[None], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)),
            top_k, top_p)[0]
        return (new_cache, new_dcache,
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(key),
                first)

    return jax.jit(insert, donate_argnums=(2, 3, 4, 5, 6))


# ---------------------------------------------------------------------------
# Chunked prefill: intermediate slice + final-insert programs
# ---------------------------------------------------------------------------


def make_prefill_chunk(cfg: LlamaConfig, slice_bucket: int,
                       staging_len: int, mesh=None):
    """One INTERMEDIATE chunked-prefill slice against a contiguous
    staging lane cache ([L, 1, H, staging_len, D], donated): append the
    slice's KV rows at absolute positions [start, start + slice_bucket)
    and skip the lm head entirely (only the FINAL slice needs logits).
    Pad rows of the last full-width slice land past the real prompt and
    are either overwritten by the next slice or truncated/masked at
    splice — the contiguous ring's exactness-with-padding story.

    ``chunk(params, lane_k, lane_v, toks [1, slice_bucket], start)
    -> (lane_k', lane_v')``
    """
    def chunk(params, lane_k, lane_v, toks, start, *lora_args):
        cache = {"k": lane_k, "v": lane_v,
                 "pos": jnp.reshape(start, (1,)).astype(jnp.int32)}
        _, new = D.cached_forward(
            cfg, params, toks, D.ContiguousView(cfg, cache, mesh),
            head=False, lora=tuple(lora_args) if lora_args else None)
        return new["k"], new["v"]

    return jax.jit(chunk, donate_argnums=(1, 2))


def make_chunked_final_insert(cfg: LlamaConfig, slice_bucket: int,
                              staging_len: int,
                              top_k: Optional[int] = None,
                              top_p: Optional[float] = None, mesh=None):
    """The FINAL chunked-prefill slice for the contiguous ring: run the
    last (right-padded) slice over the staging lane cache, splice the
    completed lane into ring slot ``slot``, and sample the first token
    — the back half of :func:`make_prefill_insert` with the forward
    restricted to the rows the intermediate slices did not cover.

    ``insert(params, cache, lane_k, lane_v, tok, temp, keys,
    toks [1, slice_bucket], n_rows, start, prompt_len, slot, temp_val,
    seed) -> (cache', tok', temp', keys', first_token)``
    """
    def insert(params, cache, lane_k, lane_v, tok, temp, keys, toks,
               n_rows, start, prompt_len, slot, temp_val, seed,
               *lora_args):
        stage = {"k": lane_k, "v": lane_v,
                 "pos": jnp.reshape(start, (1,)).astype(jnp.int32)}
        logits, new_lane = D.cached_forward(
            cfg, params, toks, D.ContiguousView(cfg, stage, mesh),
            lora=tuple(lora_args) if lora_args else None)
        logits = logits[0, n_rows - 1]
        new_cache = _splice_lane(cache, new_lane, slot, prompt_len)
        key = jax.random.PRNGKey(seed)
        first = _sample_tokens(
            logits[None], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)),
            top_k, top_p)[0]
        return (new_cache,
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(key),
                first)

    # the staging lane_k/lane_v are consumed but NOT donated: no output
    # shares their shape, so donation only buys an XLA warning
    return jax.jit(insert, donate_argnums=(1, 4, 5, 6))


def make_spec_chunked_final_insert(cfg: LlamaConfig, dcfg: LlamaConfig,
                                   slice_bucket: int, staging_len: int,
                                   bucket: int,
                                   top_k: Optional[int] = None,
                                   top_p: Optional[float] = None,
                                   mesh=None):
    """Chunked final insert for the SPECULATIVE contiguous ring: the
    target's last slice rides the staging cache like
    :func:`make_chunked_final_insert`; the DRAFT prefills its whole
    prompt here in one pass (the draft is depth/4 x heads/2 by
    construction — chunking it would buy a fraction of a fraction) and
    splices alongside.

    ``insert(params, dparams, cache, dcache, lane_k, lane_v, tok, temp,
    keys, toks, n_rows, start, prompt [1, bucket], prompt_len, slot,
    temp_val, seed) -> (cache', dcache', tok', temp', keys', first)``
    """
    def insert(params, dparams, cache, dcache, lane_k, lane_v, tok, temp,
               keys, toks, n_rows, start, prompt, prompt_len, slot,
               temp_val, seed):
        stage = {"k": lane_k, "v": lane_v,
                 "pos": jnp.reshape(start, (1,)).astype(jnp.int32)}
        logits, new_lane = D.cached_forward(
            cfg, params, toks, D.ContiguousView(cfg, stage, mesh))
        logits = logits[0, n_rows - 1]
        new_cache = _splice_lane(cache, new_lane, slot, prompt_len)
        dlane = D.init_cache(dcfg, 1, bucket)
        _, dlane = D._forward(dcfg, dparams, prompt, dlane,
                              last_only=True, mesh=mesh,
                              whole_prompt=True)
        new_dcache = _splice_lane(dcache, dlane, slot, prompt_len)
        key = jax.random.PRNGKey(seed)
        first = _sample_tokens(
            logits[None], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)),
            top_k, top_p)[0]
        return (new_cache, new_dcache,
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(key),
                first)

    return jax.jit(insert, donate_argnums=(2, 3, 6, 7, 8))


# ---------------------------------------------------------------------------
# Disaggregated prefill: handoff programs + the prefill executor
# ---------------------------------------------------------------------------


def make_attach_lane():
    """The decode ring's half of a disaggregated handoff: ONE tiny
    compiled dispatch that activates lane ``slot`` — fill position,
    carry token, temperature, sampling key — once the prefilled blocks
    have been copied into the decode pool.  No forward runs here;
    that is the point of disaggregation.

    ``attach(pos, tok, temp, keys, slot, first, prompt_len, temp_val,
    seed) -> (pos', tok', temp', keys')``
    """

    def attach(pos, tok, temp, keys, slot, first, prompt_len, temp_val,
               seed):
        return (pos.at[slot].set(prompt_len),
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(jax.random.PRNGKey(seed)))

    return jax.jit(attach, donate_argnums=(0, 1, 2, 3))


def make_spec_attach(cfg: LlamaConfig, dcfg: LlamaConfig, bucket: int,
                     mesh=None):
    """Disaggregated handoff for the SPECULATIVE ring: the target KV
    arrived by block copy, but the DRAFT lane still needs its prompt
    context to propose from — prefill it here (contiguous splice, the
    draft never pages) together with the lane activation.

    ``attach(dparams, dcache, pos, tok, temp, keys, prompt [1, bucket],
    prompt_len, slot, first, temp_val, seed)
    -> (dcache', pos', tok', temp', keys')``
    """

    def attach(dparams, dcache, pos, tok, temp, keys, prompt, prompt_len,
               slot, first, temp_val, seed):
        dlane = D.init_cache(dcfg, 1, bucket)
        _, dlane = D._forward(dcfg, dparams, prompt, dlane,
                              last_only=True, mesh=mesh,
                              whole_prompt=True)
        new_dcache = _splice_lane(dcache, dlane, slot, prompt_len)
        return (new_dcache,
                pos.at[slot].set(prompt_len),
                tok.at[slot].set(first),
                temp.at[slot].set(temp_val),
                keys.at[slot].set(jax.random.PRNGKey(seed)))

    return jax.jit(attach, donate_argnums=(1, 2, 3, 4, 5))


def make_disagg_prefill(cfg: LlamaConfig, bucket: int, block_size: int,
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None, mesh=None,
                        quant: bool = False):
    """The prefill executor's whole-prompt program: prefill a
    [1, bucket] prompt into the PREFILL pool's blocks (the same
    ``decode.paged_prefill`` compiled ops as the inline paged insert —
    what keeps the disagg first token bit-identical) and sample the
    first token through the shared rule.  Unlike the ring inserts it
    touches no ring state: the handoff copies blocks and attaches the
    lane later, on the decode thread.

    ``quant=True``: blocks quantize once into the executor's own int8
    pool; the prompt's partial last block lands exact in the pool's
    tail row 0 (the executor pool is one lane wide) — the handoff
    transfer then carries codes, scales AND tail across.

    ``prefill(params, cache, table_row, prompt, prompt_len, temp_val,
    seed) -> (cache', first_token)``
    """

    def prefill(params, cache, table_row, prompt, prompt_len, temp_val,
                seed, *lora_args):
        lora = tuple(lora_args) if lora_args else None
        if quant:
            logits, new_cache, tail_k, tail_v = PG.paged_prefill(
                params, cfg, prompt, cache, table_row,
                block_size=block_size, mesh=mesh, quant=True,
                prompt_len=prompt_len, lora=lora)
            new_cache["kt"] = jax.lax.dynamic_update_slice(
                new_cache["kt"], tail_k, (0, 0, 0, 0, 0))
            new_cache["vt"] = jax.lax.dynamic_update_slice(
                new_cache["vt"], tail_v, (0, 0, 0, 0, 0))
        else:
            logits, new_cache = PG.paged_prefill(params, cfg, prompt,
                                                 cache, table_row,
                                                 block_size=block_size,
                                                 mesh=mesh, lora=lora)
        logits = logits[0, prompt_len - 1]
        key = jax.random.PRNGKey(seed)
        first = _sample_tokens(
            logits[None], jnp.reshape(temp_val, (1,)).astype(jnp.float32),
            key[None], jnp.reshape(prompt_len - 1, (1,)),
            top_k, top_p)[0]
        return new_cache, first

    # the pool is NOT donated, deliberately: each job's result rides the
    # handoff queue as a snapshot of cache["k"]/["v"], and donating the
    # cache on the NEXT job would delete exactly those buffers while the
    # decode ring's transfer dispatch may still be reading them
    return jax.jit(prefill)


def make_pool_prefill_slice(cfg: LlamaConfig, mesh=None,
                            quant: bool = False):
    """One MULTI-LANE intermediate prefill slice for the N-lane
    prefill engine (ISSUE 14): advance EVERY participating lane's job
    by up to ``slice`` tokens in ONE compiled forward — per-lane block
    tables, per-lane absolute positions, no lm head.  Lanes sitting
    the iteration out ride masked: their rows route to the trash block
    (``limits`` 0) and — quant — their staging-tail writes redirect to
    the trash tail (``mask``), so a paused lane's live tail state is
    never touched.  The batch dimension IS the engine lane index, so
    the pool's per-lane staging tails address directly.

    ``slice(params, cache, tables [N, M], toks [N, sb], starts [N],
    limits [N], mask [N]) -> cache'``

    NOT donated: streamed-handoff frames hold version snapshots of the
    pool arrays (the release protocol in :class:`PrefillExecutor`'s
    docstring), and donating a referenced buffer would delete it under
    the decode side's transfer.

    bf16 writes go WHOLE-BLOCK (``aligned=True`` — the engine rounds
    its chunk to a block multiple and every slice start is
    block-aligned by construction), so the traced write-op count is
    O(lanes x blocks), not O(lanes x rows): at production slice widths
    the per-row unroll is pathological to COMPILE.  The quant tail
    protocol is inherently per-row and keeps the row path."""
    def slice_(params, cache, tables, toks, starts, limits, mask,
               *lora_args):
        lane_cache = {"k": cache["k"], "v": cache["v"], "pos": starts}
        if quant:
            lane_cache["ks"], lane_cache["vs"] = cache["ks"], cache["vs"]
            lane_cache["kt"], lane_cache["vt"] = cache["kt"], cache["vt"]
        _, new = D.cached_forward(
            cfg, params, toks,
            PG.paged_view(cfg, lane_cache, tables, limit=limits,
                          lane_mask=mask, aligned=True, mesh=mesh),
            head=False, lora=tuple(lora_args) if lora_args else None)
        out = {"k": new["k"], "v": new["v"], "pos": cache["pos"]}
        if quant:
            out["ks"], out["vs"] = new["ks"], new["vs"]
            out["kt"], out["vt"] = new["kt"], new["vt"]
        return out

    return jax.jit(slice_)


def make_pool_prefill_final(cfg: LlamaConfig,
                            top_k: Optional[int] = None,
                            top_p: Optional[float] = None, mesh=None,
                            quant: bool = False):
    """The FINAL prefill slice for the N-lane engine: run each
    finishing lane's last ``n_rows`` prompt tokens (right-padded to
    the slice width) WITH the lm head, and sample every finishing
    lane's first token through the shared rule — the batched analogue
    of the monolithic path's ``logits[prompt_len - 1]`` +
    ``_sample_tokens`` tail, so first tokens stay bit-identical to the
    1-lane oracle.  Non-finishing lanes ride masked exactly as in
    :func:`make_pool_prefill_slice`; their sampled "firsts" are
    garbage the host ignores.

    ``final(params, cache, tables [N, M], toks [N, sb], n_rows [N],
    starts [N], temps [N], seeds [N], limits [N], mask [N])
    -> (cache', firsts [N])``

    bf16 writes are whole-block like the intermediate slice (the
    straddling block writes its pad rows into the lane's real block —
    :func:`ops.decode_attention.scatter_prefill_blocks`'s
    exactness-with-padding contract: masked in-slice, overwritten by
    decode before any read, and the prefix cache stores only full
    blocks strictly inside the prompt)."""
    def final(params, cache, tables, toks, n_rows, starts, temps,
              seeds, limits, mask, *lora_args):
        lane_cache = {"k": cache["k"], "v": cache["v"], "pos": starts}
        if quant:
            lane_cache["ks"], lane_cache["vs"] = cache["ks"], cache["vs"]
            lane_cache["kt"], lane_cache["vt"] = cache["kt"], cache["vt"]
        logits, new = D.cached_forward(
            cfg, params, toks,
            PG.paged_view(cfg, lane_cache, tables, limit=limits,
                          lane_mask=mask, aligned=True, mesh=mesh),
            lora=tuple(lora_args) if lora_args else None)
        out = {"k": new["k"], "v": new["v"], "pos": cache["pos"]}
        if quant:
            out["ks"], out["vs"] = new["ks"], new["vs"]
            out["kt"], out["vt"] = new["kt"], new["vt"]
        # per-lane last REAL row's logits, clamped so masked lanes
        # (n_rows 0) index row 0 harmlessly
        rows = jnp.take_along_axis(
            logits, jnp.maximum(n_rows - 1, 0)[:, None, None],
            axis=1)[:, 0]
        keys = jax.vmap(jax.random.PRNGKey)(seeds)
        firsts = _sample_tokens(rows, temps.astype(jnp.float32), keys,
                                starts + jnp.maximum(n_rows - 1, 0),
                                top_k, top_p)
        return out, firsts

    return jax.jit(final)


class PrefillPrefixCache:
    """The prefill pod's OWN radix prefix cache (ISSUE 14): completed
    full blocks' exact pool bytes, host-resident, keyed by the SAME
    ``utils/radixkey`` rolling-hash chain the decode radix (and the
    router's affinity) use — so a repeated system prompt prefills only
    its suffix ON THE PREFILL SIDE too.  A hit's payloads upload into
    the job's lane blocks through the promote scatter (byte-exact, no
    requantization), which is what keeps a hit bit-identical to cold.
    Bounded LRU by block count; stored chunks are compared on hit (the
    radix collision check).  Payloads may briefly be device arrays
    (async D2H in flight) — :meth:`materialize` settles them before
    the next engine touch, the ``_demote_lazy`` pattern."""

    def __init__(self, capacity_blocks: int) -> None:
        from collections import OrderedDict

        self.cap = int(capacity_blocks)
        self._d: "OrderedDict[Any, tuple]" = OrderedDict()
        self._lazy: List[Dict[str, Any]] = []
        self.hits = 0
        self.lookups = 0

    def __len__(self) -> int:
        return len(self._d)

    def materialize(self) -> None:
        for p in self._lazy:
            for key, val in p.items():
                if not isinstance(val, np.ndarray):
                    p[key] = np.asarray(val)
        self._lazy.clear()

    def put(self, key, chunk: Tuple[int, ...],
            payload: Dict[str, Any], lazy: bool = False) -> None:
        if self.cap <= 0 or key in self._d:
            if key in self._d:
                self._d.move_to_end(key)
            return
        self._d[key] = (chunk, payload)
        if lazy:
            self._lazy.append(payload)
        while len(self._d) > self.cap:
            self._d.popitem(last=False)

    def get(self, key, chunk: Tuple[int, ...]
            ) -> Optional[Dict[str, Any]]:
        ent = self._d.get(key)
        if ent is None or ent[0] != tuple(chunk):
            return None
        self._d.move_to_end(key)
        return ent[1]


class _EngineJob:
    """One in-flight job's host state on the N-lane prefill engine."""

    __slots__ = ("req", "slot", "n", "start", "hit", "frames_done",
                 "prompt")

    def __init__(self, req, slot, start, hit):
        self.req = req
        self.slot = slot
        self.prompt = [int(t) for t in req.prompt]
        self.n = len(self.prompt)
        self.start = start          # next absolute row to prefill
        self.hit = hit              # prefix-cache rows (block-aligned)
        self.frames_done = 0        # blocks already posted as frames


class PrefillExecutor:
    """The disaggregated prefill engine: its OWN thread and its OWN
    block pool, so a cold 2k-token prefill never occupies the decode
    ring's dispatch stream.  The decode scheduler submits ``(request,
    slot)`` jobs; this thread prefills prompts into its private pool
    and posts results the scheduler lands through the handoff path.

    **Two engine shapes** (ISSUE 14):

    - ``lanes == 1`` (default): the ORIGINAL monolithic engine — one
      job at a time, whole prompt in one bucketed compiled forward,
      one ``(request, slot, snapshot, n_blocks, first)`` result.  This
      path is byte-for-byte the PR 6 engine and stays the parity
      ORACLE for everything below.
    - ``lanes >= 2``: a throughput engine.  The pool is N lanes wide
      (lane ``i`` owns the FIXED identity blocks ``[1 + i*M,
      1 + (i+1)*M)``; block 0 stays trash) and the loop is a
      mini-ring: each iteration coalesces every active job into ONE
      batched compiled slice (``make_pool_prefill_slice`` — per-lane
      tables and positions, the ``make_disagg_prefill`` trace
      generalized to the batch dim), amortizing weight streaming and
      dispatch overhead across cold arrivals, and long jobs advance
      one ``prefill_chunk`` slice per iteration ALONGSIDE short jobs
      (chunk-interleaved scheduling — a 40-token prompt is never
      stuck behind a 2k-token one; the Sarathi-Serve argument applied
      to the prefill pool).  Finishing jobs run the lm head + shared
      first-token sample in the batched final program.  Intermediate
      slices append KV only (the ``head=False`` forwards), so the
      interleave is prompt-proportional work.

    **Streamed handoff + the snapshot-lifetime rule.**  With
    ``stream=True`` completed block groups post to ``results`` as
    ``("frame", req, slot, snapshot, lane, j0, j1)`` items the decode
    side uploads WHILE this engine computes the rest — long-prompt
    TTFT collapses to last-chunk + attach.  The terminal item
    ``("final", req, slot, snapshot, lane, j0, n_blocks, first,
    t_done)`` carries the remaining blocks, the (quant) staging tail
    and the sampled first token.  A multi-lane pool with REUSED lanes
    needs a real release protocol where the 1-lane engine needed
    none; the rule is: **a lane is reassigned only after its previous
    job's terminal item has been POSTED, and every posted item pins
    the pool VERSION it snapshotted** — jax arrays are immutable, so
    the next job's writes produce new versions and can never corrupt
    an outstanding snapshot; no engine program donates the pool for
    exactly this reason.  What bounds memory is the decode side
    draining ``results`` every loop pass: at most one pool version per
    undrained item stays alive, and the queue never outlives its
    scheduler.

    **Prefix reuse** (``prefix_blocks > 0``, lanes >= 2): a
    :class:`PrefillPrefixCache` keyed on the shared radix chain; a hit
    uploads cached block bytes into the job's lane and prefill starts
    at the (block-aligned) hit frontier — bit-identical to cold
    because the uploaded bytes ARE a cold run's bytes.  Adapter jobs
    skip the cache (deltas change the KV; the decode radix namespaces
    per adapter, the prefill pool simply abstains).

    Fault isolation: a prefill dispatch failure posts ``(request,
    slot, error)`` tuples — batch-granular on the N-lane engine (one
    fused dispatch serves every active job, so all of them fail and
    retry; the pool is rebuilt lane-clean by the next assignments) —
    and the decode ring (with its watchdog/heal machinery) never sees
    the fault.  Jobs whose request resolved meanwhile (cancel,
    deadline, heal) are dropped at either end."""

    def __init__(self, params: Any, cfg: LlamaConfig, *, max_len: int,
                 block_size: int, buckets: Tuple[int, ...],
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None, mesh=None,
                 kv_quant: str = "none", adapters=None,
                 lanes: int = 1, prefill_chunk: int = 64,
                 stream: bool = False,
                 prefix_blocks: int = 0) -> None:
        # adapter registry shared with the decode ring (ISSUE 10): a
        # cold adapter prompt must prefill WITH its delta — the KV the
        # handoff copies is the adapter's, not the base model's
        self.adapters = adapters
        self.params = params
        self.cfg = cfg
        self.block_size = int(block_size)
        self.mesh = mesh
        self.kv_quant = kv_quant
        self.quant = kv_quant == "int8"
        self.lanes = max(1, int(lanes))
        self.stream = bool(stream) and self.lanes > 1
        self.prefill_chunk = max(1, int(prefill_chunk))
        if self.lanes > 1 and not self.quant:
            # the bf16 slice/final programs write WHOLE BLOCKS
            # (aligned=True), which needs every slice start
            # block-aligned: round the scheduling quantum up to a
            # block multiple.  The interleave bound coarsens to one
            # block when block_size > chunk — the price of
            # O(blocks) instead of O(rows) traced writes.  The quant
            # engine keeps the configured chunk: its staging-tail
            # protocol is per-row regardless.
            self.prefill_chunk = (-(-self.prefill_chunk
                                    // self.block_size)
                                  * self.block_size)
        alloc = D.cache_alloc_len(max_len)
        self.max_blocks = -(-alloc // self.block_size)
        m = self.max_blocks
        # block 0 stays the trash block, same convention as the decode
        # pool; lane i's job owns the FIXED identity blocks
        # [1 + i*M, 1 + (i+1)*M) — fixed ownership needs no allocator
        self.cache = PG.init_paged_cache(
            cfg, self.lanes, self.lanes * m + 1, self.block_size,
            mesh=mesh, quant=kv_quant)
        self.table_row = jnp.arange(1, m + 1, dtype=jnp.int32)
        self.tables = np.stack(
            [np.arange(1 + i * m, 1 + (i + 1) * m, dtype=np.int32)
             for i in range(self.lanes)])
        # test hook: a callable the loop invokes at each iteration top
        # — the deterministic pause-gate pattern (tests/test_qos.py)
        self.pause_gate = None
        # throughput telemetry (ISSUE 14): batch occupancy EMA (lanes
        # busy / N per engine iteration) and per-job head-of-line
        # queue wait samples — the tpujob_serve_prefill_batch_occupancy
        # / _hol_wait_ms gauges
        self._occ_ema = 0.0
        self._hol: List[float] = []
        self._stats_lock = threading.Lock()
        self.iterations = 0
        self.prefix_hits = 0
        # the prefill pod's own radix prefix cache (multi-lane engine
        # only — the 1-lane path stays the byte-identical oracle)
        self.prefix = (PrefillPrefixCache(prefix_blocks)
                       if prefix_blocks > 0 and self.lanes > 1
                       else None)
        if self.lanes > 1:
            self.buckets = (self.prefill_chunk,)
            self._slice_prog = make_pool_prefill_slice(
                cfg, mesh=mesh, quant=self.quant)
            self._final_prog = make_pool_prefill_final(
                cfg, top_k, top_p, mesh=mesh, quant=self.quant)
            self._progs: Dict[int, Any] = {}
            if self.prefix is not None:
                self._fetch_prog = PG.make_block_fetch(quant=self.quant)
                self._upload_prog = PG.make_promote_blocks(
                    self.block_size, quant=self.quant, donate=False)
        else:
            # the prefill engine's OWN ladder, by the ring's rule
            # (_default_buckets) up to the ring's largest bucket:
            # prefill is stateless-per-job, so it can afford shapes
            # near the prompt length whatever coarser ladder the ring
            # was given explicitly.  Phases shaping independently is
            # the DistServe argument.
            self.buckets = _default_buckets(max(buckets), self.block_size)
            self._progs = {b: make_disagg_prefill(
                cfg, b, self.block_size, top_k, top_p, mesh=mesh,
                quant=self.quant) for b in self.buckets}
        self.jobs: "queue.Queue[tuple]" = queue.Queue()
        self.results: "queue.Queue[tuple]" = queue.Queue()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=(self._loop_engine if self.lanes > 1 else self._loop),
            daemon=True, name="prefill-executor")
        self._thread.start()

    def submit(self, req, slot: int) -> None:
        # queue depth is tracked scheduler-side (_disagg_waiting feeds
        # the prefillQueueDepth gauge); the enqueue stamp feeds the
        # head-of-line wait gauge
        self.jobs.put((req, slot, time.monotonic()))

    # -- telemetry (ISSUE 14) ---------------------------------------------

    def batch_occupancy(self) -> float:
        """EMA of lanes-busy / N per engine iteration — 1.0 is a
        saturated batch; the autoscaler divides by it so a half-empty
        pool never reads as a saturated one."""
        with self._stats_lock:
            return round(self._occ_ema, 4)

    def hol_wait_ms_p95(self) -> float:
        """p95 of recent jobs' queue wait (submit -> lane assignment),
        ms — the head-of-line blocking proxy."""
        with self._stats_lock:
            if not self._hol:
                return 0.0
            s = sorted(self._hol)
            return round(s[min(len(s) - 1,
                               int(0.95 * (len(s) - 1)))], 3)

    def _note_wait(self, t_enq: float) -> None:
        with self._stats_lock:
            self._hol.append((time.monotonic() - t_enq) * 1e3)
            if len(self._hol) > 256:
                del self._hol[:len(self._hol) - 256]

    def _note_occ(self, busy: int) -> None:
        occ = busy / self.lanes
        with self._stats_lock:
            self._occ_ema = (occ if not self._occ_ema
                             else 0.8 * self._occ_ema + 0.2 * occ)
            self.iterations += 1

    # -- the 1-lane monolithic loop (the PR 6 engine, the oracle) ----------

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                req, slot, t_enq = self.jobs.get(timeout=0.05)
            except queue.Empty:
                continue
            if self.pause_gate is not None:
                self.pause_gate()
            try:
                if req.done.is_set() or req._cancel:
                    continue        # resolved while queued: drop
                self._note_wait(t_enq)
                self._note_occ(1)
                n = len(req.prompt)
                pb = next(b for b in self.buckets if b >= n)
                if pb <= req.dev_prompt.shape[1]:
                    # re-bucket the already-shipped prompt: the ring
                    # bucket is right-padded, so a narrower device
                    # slice keeps every real token
                    prompt = req.dev_prompt[:, :pb]
                else:
                    padded = np.zeros((1, pb), np.int32)
                    padded[0, :n] = req.prompt
                    prompt = jnp.asarray(padded)
                prog = self._progs[pb]
                tail = ()
                if self.adapters is not None:
                    tail = (self.adapters.arrays(),
                            jnp.full((1,), getattr(req, "adapter_idx", 0),
                                     jnp.int32))
                self.cache, first = prog(
                    self.params, self.cache, self.table_row,
                    prompt, n, float(req.temperature), req.seed, *tail)
                n_blocks = -(-len(req.prompt) // self.block_size)
                try:
                    first.copy_to_host_async()
                except AttributeError:
                    pass
                # snapshot refs: immutable arrays — the next job's
                # writes produce a NEW pool version, this one stays
                # readable until the ring's copy dispatch consumes it
                # (quant pools snapshot codes+scales+tails alike)
                snap = {key: self.cache[key]
                        for key in ("k", "v", "ks", "vs", "kt", "vt")
                        if key in self.cache}
                self.results.put((req, slot, snap, n_blocks, first))
            except Exception as e:      # noqa: BLE001 — isolate per job
                self.results.put((req, slot, e))

    # -- the N-lane batched, chunk-interleaved engine (ISSUE 14) -----------

    def _snapshot(self) -> Dict[str, Any]:
        return {key: self.cache[key]
                for key in ("k", "v", "ks", "vs", "kt", "vt")
                if key in self.cache}

    def _prefix_walk(self, prompt: List[int]) -> Tuple[int, list]:
        """Longest cached chain of leading FULL blocks, capped so at
        least one real token remains to prefill (the final slice needs
        a real row to sample from — the same n-1 cap the decode radix
        applies); returns (hit_blocks, payloads)."""
        from paddle_operator_tpu.utils.radixkey import chain_key

        bs = self.block_size
        self.prefix.materialize()
        max_hit = (len(prompt) - 1) // bs
        key = None
        payloads = []
        for j in range(max_hit):
            chunk = tuple(prompt[j * bs:(j + 1) * bs])
            key = chain_key(key, chunk)
            p = self.prefix.get(key, chunk)
            if p is None:
                break
            payloads.append(p)
        return len(payloads), payloads

    def _prefix_upload(self, lane: int, payloads: list) -> None:
        """Land prefix-hit payloads in the lane's identity blocks
        through the (non-donating) promote scatter — byte-exact, the
        PR 8 host-hit discipline."""
        n = len(payloads)
        pad = 1
        while pad < n:
            pad *= 2
        bs = self.block_size
        p0 = payloads[0]
        lcount, _, h, _, d = p0["k"].shape
        slab_k = np.zeros((lcount, 1, h, pad * bs, d), p0["k"].dtype)
        slab_v = np.zeros_like(slab_k)
        ids = np.full((pad,), PG.TRASH_BLOCK, np.int32)
        for j, payload in enumerate(payloads):
            ids[j] = self.tables[lane][j]
            slab_k[:, 0, :, j * bs:(j + 1) * bs] = payload["k"][:, 0]
            slab_v[:, 0, :, j * bs:(j + 1) * bs] = payload["v"][:, 0]
        c = self.cache
        if self.quant:
            srow_k = np.ones((lcount, pad, h), np.float32)
            srow_v = np.ones_like(srow_k)
            for j, payload in enumerate(payloads):
                srow_k[:, j] = payload["ks"][:, 0]
                srow_v[:, j] = payload["vs"][:, 0]
            c["k"], c["v"], c["ks"], c["vs"] = self._upload_prog(
                c["k"], c["v"], c["ks"], c["vs"], jnp.asarray(slab_k),
                jnp.asarray(slab_v), jnp.asarray(srow_k),
                jnp.asarray(srow_v), jnp.asarray(ids))
        else:
            c["k"], c["v"] = self._upload_prog(
                c["k"], c["v"], jnp.asarray(slab_k),
                jnp.asarray(slab_v), jnp.asarray(ids))

    def _store_prefix(self, lane: int, job: "_EngineJob") -> None:
        """Store the finished job's full blocks (device bytes fetched
        async — the lazy-materialize pattern) under their chain keys.
        Never called for adapter jobs: their KV is delta-dependent."""
        from paddle_operator_tpu.utils.radixkey import chain_key

        bs = self.block_size
        key = None
        c = self.cache
        for j in range(job.n // bs):
            chunk = tuple(job.prompt[j * bs:(j + 1) * bs])
            key = chain_key(key, chunk)
            if self.prefix.get(key, chunk) is not None:
                continue
            blk = int(self.tables[lane][j])
            if self.quant:
                kb, vb, ksb, vsb = self._fetch_prog(
                    c["k"], c["v"], c["ks"], c["vs"], blk)
                payload = {"k": kb, "v": vb, "ks": ksb, "vs": vsb}
            else:
                kb, vb = self._fetch_prog(c["k"], c["v"], blk)
                payload = {"k": kb, "v": vb}
            for val in payload.values():
                try:
                    val.copy_to_host_async()
                except AttributeError:
                    pass
            self.prefix.put(key, chunk, payload, lazy=True)

    def _start_job(self, lane: int, req, slot: int) -> "_EngineJob":
        hit = 0
        if (self.prefix is not None
                and not getattr(req, "adapter_idx", 0)):
            try:
                n_hit, payloads = self._prefix_walk(
                    [int(t) for t in req.prompt])
            except Exception:       # cache is an optimization only
                n_hit, payloads = 0, []
            if n_hit:
                self._prefix_upload(lane, payloads)
                hit = n_hit * self.block_size
                self.prefix_hits += 1
        return _EngineJob(req, slot, hit, hit)

    def _lora_tail(self, active: Dict[int, "_EngineJob"]) -> tuple:
        if self.adapters is None:
            return ()
        aid = np.zeros((self.lanes,), np.int32)
        for lane, job in active.items():
            aid[lane] = getattr(job.req, "adapter_idx", 0)
        return (self.adapters.arrays(), jnp.asarray(aid))

    def _width(self, rows_max: int) -> int:
        """Table width (in blocks) for one batched dispatch:
        smallest power-of-two block count covering the deepest
        participating lane's attended rows, capped at the pool lane
        width.  The gathered lane view — and with it the dense
        attention score width — is the TABLE's width, so slicing the
        table keeps slice work prompt-proportional (the 1-lane
        ladder's property, which a fixed max_len-wide view would
        forfeit: a 256-token job would attend max_len columns of
        masked-out keys).  Power-of-two rounding bounds the compile
        set at log2(max_blocks) shapes per program — jit
        shape-specializes, and each shape is cheap to compile under
        the whole-block write path."""
        need = -(-rows_max // self.block_size)
        w = 1
        while w < need:
            w *= 2
        return min(w, self.max_blocks)

    def _advance(self, active: Dict[int, "_EngineJob"],
                 free: List[int]) -> None:
        """One engine iteration: ONE batched intermediate slice for
        every long job + ONE batched final slice for every finishing
        job, then frame/terminal posts."""
        sb = self.prefill_chunk
        bs = self.block_size
        nl = self.lanes
        inter = [ln for ln, j in sorted(active.items())
                 if j.n - j.start > sb]
        fin = [ln for ln, j in sorted(active.items())
               if j.n - j.start <= sb]
        self._note_occ(len(active))
        tail = self._lora_tail(active)
        if inter:
            mw = self._width(max(active[ln].start + sb
                                 for ln in inter))
            toks = np.zeros((nl, sb), np.int32)
            starts = np.zeros((nl,), np.int32)
            limits = np.zeros((nl,), np.int32)
            tables = np.full((nl, mw), PG.TRASH_BLOCK, np.int32)
            mask = np.zeros((nl,), bool)
            for ln in inter:
                j = active[ln]
                toks[ln] = j.prompt[j.start:j.start + sb]
                starts[ln] = j.start
                limits[ln] = j.start + sb
                tables[ln] = self.tables[ln][:mw]
                mask[ln] = True
            self.cache = self._slice_prog(
                self.params, self.cache, jnp.asarray(tables),
                jnp.asarray(toks), jnp.asarray(starts),
                jnp.asarray(limits), jnp.asarray(mask), *tail)
            for ln in inter:
                active[ln].start += sb
        firsts = None
        if fin:
            mw = self._width(max(active[ln].start + sb
                                 for ln in fin))
            toks = np.zeros((nl, sb), np.int32)
            starts = np.zeros((nl,), np.int32)
            limits = np.zeros((nl,), np.int32)
            n_rows = np.zeros((nl,), np.int32)
            temps = np.zeros((nl,), np.float32)
            seeds = np.zeros((nl,), np.int32)
            tables = np.full((nl, mw), PG.TRASH_BLOCK, np.int32)
            mask = np.zeros((nl,), bool)
            for ln in fin:
                j = active[ln]
                rem = j.n - j.start
                toks[ln, :rem] = j.prompt[j.start:]
                starts[ln] = j.start
                limits[ln] = j.n
                n_rows[ln] = rem
                temps[ln] = float(j.req.temperature)
                seeds[ln] = int(j.req.seed)
                tables[ln] = self.tables[ln][:mw]
                mask[ln] = True
            self.cache, firsts = self._final_prog(
                self.params, self.cache, jnp.asarray(tables),
                jnp.asarray(toks), jnp.asarray(n_rows),
                jnp.asarray(starts), jnp.asarray(temps),
                jnp.asarray(seeds), jnp.asarray(limits),
                jnp.asarray(mask), *tail)
            try:
                firsts.copy_to_host_async()
            except AttributeError:
                pass
            for ln in fin:
                active[ln].start = active[ln].n
        # streamed frames: post every lane's newly COMPLETED blocks
        # (frames carry full blocks only; the moving write frontier
        # crosses once, on the terminal item).  ONE snapshot after
        # both dispatches serves every post — it pins the pool
        # VERSION, and completed blocks never change after commit.
        snap = (self._snapshot()
                if fin or (self.stream and inter) else None)
        if self.stream:
            for ln in inter:
                j = active[ln]
                done = j.start // bs
                if done > j.frames_done:
                    self.results.put(("frame", j.req, j.slot, snap, ln,
                                      j.frames_done, done))
                    j.frames_done = done
        for ln in fin:
            j = active.pop(ln)
            free.append(ln)
            n_blocks = -(-j.n // bs)
            first = firsts[ln]
            try:
                first.copy_to_host_async()
            except AttributeError:
                pass
            self.results.put(("final", j.req, j.slot, snap, ln,
                              j.frames_done, n_blocks, first,
                              time.monotonic()))
            if (self.prefix is not None
                    and not getattr(j.req, "adapter_idx", 0)):
                try:
                    self._store_prefix(ln, j)
                except Exception:
                    pass            # cache is an optimization only
        free.sort()

    def _loop_engine(self) -> None:
        from collections import deque

        pending: "deque[tuple]" = deque()
        active: Dict[int, _EngineJob] = {}
        free = list(range(self.lanes))
        # depth-2 dispatch pacing (the megastep double-buffer
        # discipline): jax dispatch is async, so an unpaced loop would
        # enqueue a long job's ENTIRE prefill ahead of a short prompt
        # that arrives one host-tick later — the chunk-interleave HOL
        # bound holds in DEVICE order only if host run-ahead is
        # bounded.  Two iterations in flight keep the device busy
        # while a late arrival waits at most ~2 slice quanta to reach
        # the front of the queue.
        fences: "deque[Any]" = deque()
        while not self._stop.is_set():
            if self.pause_gate is not None:
                self.pause_gate()
            # drain the submit queue; block briefly only when idle
            try:
                if not active and not pending:
                    pending.append(self.jobs.get(timeout=0.05))
                while True:
                    pending.append(self.jobs.get_nowait())
            except queue.Empty:
                pass
            # assign free lanes FIFO (lowest lane first — the batch
            # index is the pool lane, determinism matters to tests)
            while free and pending:
                req, slot, t_enq = pending.popleft()
                if req.done.is_set() or req._cancel:
                    continue        # resolved while queued: drop
                lane = free.pop(0)
                try:
                    self._note_wait(t_enq)
                    active[lane] = self._start_job(lane, req, slot)
                except Exception as e:  # noqa: BLE001
                    self.results.put((req, slot, e))
                    free.append(lane)
                    free.sort()
            if not active:
                continue
            try:
                # the fence wait is INSIDE the batch-granular handler:
                # jax dispatch is async, so a device-side failure in a
                # prior slice/final dispatch surfaces HERE, not in
                # _advance — an uncaught one would kill this thread
                # and wedge every queued prefill
                while len(fences) >= 2:
                    fence = fences.popleft()
                    try:
                        fence.block_until_ready()
                    except AttributeError:
                        pass
                self._advance(active, free)
                fences.append(self.cache["k"])
            except Exception as e:      # noqa: BLE001 — batch-granular
                # one fused dispatch served every active job: fail all
                # of them (their clients retry); lanes free clean, and
                # stale fences drop so the failed dispatch cannot
                # re-raise at the next wait
                fences.clear()
                for lane, job in list(active.items()):
                    self.results.put((job.req, job.slot, e))
                    free.append(lane)
                active.clear()
                free.sort()

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30)


# ---------------------------------------------------------------------------
# RingExecutor: compiled programs + device state for one decode ring
# ---------------------------------------------------------------------------


class RingExecutor:
    """Owns everything device-side about one continuous-batching ring:
    the resident chunk/spec-round program, the per-bucket admission
    inserts (inline, suffix, chunked, spec variants), the KV cache or
    block pool, and the per-lane tok/temp/keys state.  The scheduler
    (infer/scheduler.py ContinuousBatcher) holds NO jax arrays of its
    own — it sequences dispatches on this object, which is what makes
    the prefill/decode executor split (and the watchdog's full device
    rebuild, :meth:`reset_state`) possible.
    """

    # a prefix hit with a LONGER divergent suffix admits through the
    # cold scatter prefill instead: the suffix insert's per-row pool
    # writes unroll O(rows) (paged._write_rows_paged), and past this
    # many rows the block-granular cold path compiles and runs faster
    # than what the cached prefix saves
    SUFFIX_PREFILL_MAX_ROWS = 256

    def __init__(self, params: Any, cfg: LlamaConfig, *, slots: int,
                 max_len: int, chunk_tokens: int,
                 prefill_buckets: Tuple[int, ...] = (),
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None, mesh=None,
                 draft_params: Any = None,
                 draft_cfg: Optional[LlamaConfig] = None, spec_k: int = 0,
                 paged: bool = False, block_size: int = 256,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefill_mode: str = "inline",
                 prefill_chunk: int = 64,
                 check_finite: bool = False,
                 kv_quant: str = "none",
                 host_cache_blocks: int = 0,
                 adapters=None,
                 megastep: int = 1,
                 prefill_client=None,
                 prefill_lanes: int = 1,
                 prefill_stream: bool = False,
                 prefill_prefix_blocks: int = 0) -> None:
        # many-adapter serving (ISSUE 10, infer/qos.py AdapterRegistry):
        # stacked LoRA deltas served off the one base param set.  The
        # registry's arrays ride every dispatch as trailing operands
        # (lora_step_tail / lora_insert_tail), so load/evict reaches
        # the compiled programs without retraces.  Spec rings refuse:
        # the draft stays base-only by design, and a drafted token
        # stream verified under a different (adapted) target would
        # collapse acceptance — scheduler.submit rejects per-request
        # adapters instead of silently serving base math.
        if adapters is not None and spec_k:
            raise ValueError(
                "adapters are not supported on speculative rings (the "
                "draft proposes base-only); disable one of them")
        self.adapters = adapters
        self.mesh = mesh
        if mesh is not None and D.mesh_tp(mesh) > 1:
            params = D.shard_params_for_serving(params, cfg, mesh)
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.chunk = chunk_tokens
        self.check_finite = check_finite
        self.prefill_mode = prefill_mode
        self.buckets = tuple(sorted(prefill_buckets)) or _default_buckets(
            max_len, int(block_size) if paged else 1)
        self.top_k, self.top_p = top_k, top_p
        self.paged = bool(paged)
        self.pool: Optional[Any] = None
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 (got {prefill_chunk})")
        # SERVE_KV_QUANT: int8 codes + per-block scales for the paged
        # pool, dequant fused into the kernels — ~2x resident lanes per
        # HBM byte; "none" (default) keeps the bf16 pool bit-identical
        # to pre-quantization behavior (infer/paged.py module note)
        if kv_quant not in PG.KV_QUANT_MODES:
            raise ValueError(f"kv_quant {kv_quant!r} not in "
                             f"{PG.KV_QUANT_MODES}")
        self.kv_quant = kv_quant
        self.quant = kv_quant == "int8"
        # an architecture other than LLaMA's serves on the paged ring's
        # plain mode alone; every other mode refuses it here, in one
        # sentence (none may run the LLaMA block over its weights)
        from paddle_operator_tpu.infer import afmoe_serve as AF

        self.expert_stack = AF.is_expert_stack(cfg)
        AF.refuse_modes(cfg, {
            "SERVE_PAGED=0": not paged, "SERVE_TP>1": D.mesh_tp(mesh) > 1,
            "SERVE_SPEC_K>0": spec_k, "SERVE_KV_QUANT=int8": self.quant,
            f"SERVE_PREFILL={prefill_mode}": prefill_mode != "inline",
            "SERVE_ADAPTERS": adapters is not None,
            "SERVE_MEGASTEP>1": int(megastep) > 1,
            "SERVE_PREFIX_CACHE=1": paged and prefix_cache})
        if self.quant and not self.paged:
            raise ValueError("kv_quant='int8' requires the paged ring "
                             "(the pool block is the quantization "
                             "unit); set paged=True / SERVE_PAGED=1")
        if self.paged:
            self._pg = PG
            self.block_size = int(block_size)
            self._num_blocks = num_blocks
            self.prefix_cache = prefix_cache and not spec_k
            # ISSUE 8 host spill tier: demoted radix blocks live in
            # host RAM and promote back on hit — only meaningful with
            # the prefix cache on (a spec ring turns both off)
            self.host_cache_blocks = (int(host_cache_blocks)
                                      if self.prefix_cache else 0)
            self.pool = PG.PagedCacheManager(
                slots, max_len, self.block_size, num_blocks,
                prefix_cache=self.prefix_cache,
                host_cache_blocks=self.host_cache_blocks)
            # demote/promote programs exist whenever the ring is paged:
            # the host tier uses them on evict/hit, and spill_lane /
            # restore_lane (the preemption primitive) reuse the same
            # byte-copy path with the tier off (both lru_cached)
            self._fetch_prog = PG.make_block_fetch(
                quant=(kv_quant == "int8"))
            self._promote_prog = PG.make_promote_blocks(
                self.block_size, quant=(kv_quant == "int8"))
            # prefill buckets scatter whole blocks: round each up to a
            # block multiple, capped at the lane view
            self.buckets = tuple(sorted(
                {min(-(-b // self.block_size) * self.block_size,
                     self.pool.view_len) for b in self.buckets}))
            self._copy_block = PG.make_block_copier(quant=self.quant)
            self._tail_init = PG.make_tail_init() if self.quant else None
        else:
            self.block_size = int(block_size)
            self.prefix_cache = False
            self.host_cache_blocks = 0
        # device-resident megastep (ISSUE 11): SERVE_MEGASTEP fused
        # ring iterations per dispatch.  Programs are compiled per N
        # (megastep_prog) so the scheduler can drop to N=1 (the
        # byte-identical oracle) at any time; ``megastep`` here is the
        # configured default the prewarm compiles ahead.
        self.megastep = max(1, int(megastep))
        self._mega: Dict[int, Any] = {}
        self._suffix_inserts: Dict[int, Any] = {}
        # the jitted inserts that compile_inserts replaced by their
        # executables (empty: ``inserts`` still holds the lazy jits)
        self._insert_jits: Dict[int, Any] = {}
        # chunked-prefill compile caches: intermediate slice + final
        # insert programs, keyed by staging length (contiguous) or just
        # the fixed slice bucket (paged — writes are table-driven)
        self._chunk_progs: Dict[Any, Any] = {}
        self._final_inserts: Dict[Any, Any] = {}
        self._attach = None
        self._spec_attach: Dict[int, Any] = {}
        self._transfer = None
        # demoted payloads whose device->host copy is still settling
        # (_demote_fetch): materialized to numpy on the next tier touch
        self._demote_lazy: List[Dict[str, Any]] = []

        self.spec_k = int(spec_k)
        self.draft_cfg = draft_cfg
        if self.spec_k > 0:
            from paddle_operator_tpu.infer.speculative import (
                check_draft_compat,
                make_spec_round_fn,
            )

            if draft_params is None or draft_cfg is None:
                raise ValueError("spec_k > 0 requires draft_params and "
                                 "draft_cfg (see LlamaConfig.draft())")
            check_draft_compat(cfg, draft_cfg)
            if max_len > draft_cfg.max_seq_len:
                raise ValueError(
                    f"draft max_seq_len ({draft_cfg.max_seq_len}) < ring "
                    f"max_len ({max_len}); derive the draft with "
                    "cfg.draft() to inherit the target's RoPE table")
            if mesh is not None and D.mesh_tp(mesh) > 1:
                draft_params = D.shard_params_for_serving(
                    draft_params, draft_cfg, mesh)
            self.draft_params = draft_params
            self.spec_step = make_spec_round_fn(
                cfg, draft_cfg, self.spec_k, top_k, top_p, mesh=mesh,
                paged=self.paged, quant=self.quant)
            self.step = None
            if self.paged:
                # target prefill scatters into the pool; the DRAFT lane
                # stays a contiguous splice (speculative.py docstring)
                self.inserts = {b: self._pg.make_paged_spec_prefill_insert(
                    cfg, draft_cfg, b, self.block_size, top_k, top_p,
                    mesh=mesh, quant=self.quant) for b in self.buckets}
            else:
                self.inserts = {b: make_spec_prefill_insert(
                    cfg, draft_cfg, b, top_k, top_p, mesh=mesh)
                    for b in self.buckets}
        else:
            self.draft_params = None
            self.spec_step = None
            if self.expert_stack:
                self.step = AF.make_paged_chunk_step(
                    cfg, chunk_tokens, top_k, top_p,
                    check_finite=check_finite)
                self.inserts = {b: AF.make_paged_prefill_insert(
                    cfg, b, self.block_size, top_k, top_p)
                    for b in self.buckets}
            elif self.paged:
                self.step = self._pg.make_paged_chunk_step(
                    cfg, chunk_tokens, top_k, top_p, mesh=mesh,
                    check_finite=check_finite, quant=self.quant)
                self.inserts = {b: self._pg.make_paged_prefill_insert(
                    cfg, b, self.block_size, top_k, top_p, mesh=mesh,
                    quant=self.quant, check_finite=check_finite)
                    for b in self.buckets}
            else:
                self.step = make_chunk_step(cfg, chunk_tokens, top_k,
                                            top_p, mesh=mesh,
                                            check_finite=check_finite)
                self.inserts = {b: make_prefill_insert(cfg, b, top_k,
                                                       top_p, mesh=mesh)
                                for b in self.buckets}
        # the LLaMA paged ring's cold inserts take the whole block table
        # and a lane mask (paged.make_paged_prefill_insert); every other
        # insert of a paged ring takes the inserted slot's row
        self.insert_takes_ring = (self.paged and not self.spec_k
                                  and not self.expert_stack)
        # ... and the rungs whose program carries a decode step of the
        # live lanes: the scheduler names the lanes that ride it
        self.insert_steps = frozenset(
            b for b in self.buckets if self.insert_takes_ring
            and PG.insert_carries_step(b, mesh, self.quant,
                                       adapters is not None))
        # which attention each rung's whole-prompt insert traces, from
        # the function the trace itself asks (an expert stack's block
        # attends in its own model file): static, shown on /statusz next
        # to the calls by rung
        self.prefill_attn = {
            b: (AF.prefill_attn_impl(cfg, b) if self.expert_stack
                else D.prefill_attn_impl(cfg, b, mesh))
            for b in self.buckets}

        # the disaggregated prefill engine (prefill_mode="disagg"):
        # built here so its compile set and pool live with the rest of
        # the device state; the scheduler drives its queues.  With a
        # ``prefill_client`` (ISSUE 13 cross-host disaggregation —
        # infer/prefill_serve.RemotePrefillClient) the engine lives in
        # its OWN pods: the client satisfies the same submit/results
        # contract, its results are HOST payloads the scheduler lands
        # through the promote scatter, and only the tiny attach
        # dispatch runs here — no local prefill pool, no local
        # whole-prompt compiles.
        self.prefill_exec: Optional[Any] = None
        self.prefill_remote = False
        self.prefill_lanes = max(1, int(prefill_lanes))
        self.prefill_stream = bool(prefill_stream)
        self._frame_transfer = None
        self._tail_copy = None
        if prefill_mode == "disagg":
            if not self.paged:
                raise ValueError("prefill_mode='disagg' requires the "
                                 "paged ring (block-granular handoff)")
            self._attach = make_attach_lane()
            if prefill_client is not None:
                self.prefill_exec = prefill_client
                self.prefill_remote = True
            else:
                self.prefill_exec = PrefillExecutor(
                    self.params, cfg, max_len=max_len,
                    block_size=self.block_size, buckets=self.buckets,
                    top_k=top_k, top_p=top_p, mesh=mesh,
                    kv_quant=self.kv_quant, adapters=adapters,
                    lanes=self.prefill_lanes,
                    prefill_chunk=self.prefill_chunk,
                    stream=self.prefill_stream,
                    prefix_blocks=int(prefill_prefix_blocks))
                if self.prefill_lanes > 1:
                    # N-lane engine handoffs land frame-wise: block
                    # groups via the frame transfer, the (quant)
                    # staging tail once via the lane-addressed copy —
                    # the 1-lane monolithic path keeps the fused
                    # make_pool_transfer (the oracle trace, untouched)
                    self._frame_transfer = self._pg.make_pool_frame_transfer(
                        self.pool.max_blocks, quant=self.quant)
                    if self.quant:
                        self._tail_copy = self._pg.make_pool_tail_copy()
                else:
                    self._transfer = self._pg.make_pool_transfer(
                        self.pool.max_blocks, quant=self.quant)

        self.reset_state()

    # -- state lifecycle ---------------------------------------------------

    def reset_state(self) -> None:
        """(Re)build every piece of mutable device state from scratch —
        construction AND the watchdog's self-heal both land here, so a
        rebuilt ring can never carry poisoned state forward.  Compiled
        programs are kept (they are pure)."""
        if self.paged:
            # ALWAYS a fresh allocator: the radix cache keys blocks of
            # the about-to-be-replaced device arrays — carrying it over
            # would map zeroed blocks as a "cached" prefix.  The host
            # tier resets WITH it: in-flight promotions are dropped and
            # a rebuilt ring re-walks the radix from cold (host payloads
            # keyed against the dead allocator's chain state must never
            # promote into the fresh pool)
            self.pool = self._pg.PagedCacheManager(
                self.slots, self.max_len, self.block_size,
                self._num_blocks, prefix_cache=self.prefix_cache,
                host_cache_blocks=self.host_cache_blocks)
            if self.host_cache_blocks:
                self.pool.demote_fetch = self._demote_fetch
            self._demote_lazy.clear()   # payloads of the dead tier
            self.cache = self._pg.init_paged_cache(
                self.cfg, self.slots, self.pool.total, self.block_size,
                mesh=self.mesh, quant=self.kv_quant)
        else:
            self.cache = init_ring_cache(self.cfg, self.slots,
                                         self.max_len, mesh=self.mesh)
        # bytes a token a layer the cache holds (``cacheRowBytes``)
        self.cache_row_bytes = PG.cache_row_bytes(self.cache)
        if self.spec_k:
            self.dcache = init_ring_cache(self.draft_cfg, self.slots,
                                          self.max_len, mesh=self.mesh)
        else:
            self.dcache = None
        self.tok = jnp.zeros((self.slots,), jnp.int32)
        self.temp = jnp.zeros((self.slots,), jnp.float32)
        self.keys = jnp.zeros((self.slots, 2), jnp.uint32)
        # per-lane adapter id HOST mirror (ISSUE 10): set at admission,
        # zeroed at evict, shipped with every adapter-aware dispatch.
        # Host-side (not donated device state) because it changes only
        # at admission and the step reads it as a tiny operand.
        self.aid = np.zeros((self.slots,), np.int32)

    def swap_weights(self, params: Any, draft_params: Any = None) -> tuple:
        """Replace the served param trees in place — the device half of
        the live weight swap (ISSUE 19), for a flip that keeps this
        executor (same cfg / mesh / ring geometry).  The compiled
        programs take params as a traced OPERAND, so a new checkpoint —
        even one whose weight-quant mode differs: the leaf types are
        the dispatch (infer/quant.py) — re-traces lazily on its first
        dispatch instead of needing any rebuild here.  Returns the old
        ``(params, draft_params)`` so the caller can roll back an
        aborted swap; dropping the returned references frees the HBM.

        The caller (ContinuousBatcher swap path) has QUIESCED the
        ring — nothing in flight, every lane parked — and runs
        reset_state() right after the flip, so cached KV computed
        under the old generation can never serve the new one."""
        if self.spec_k and draft_params is None:
            raise ValueError(
                "speculative ring: a weight swap must ship the draft "
                "with the target (drafts are verified against the NEW "
                "params only; a stale draft would silently collapse "
                "acceptance)")
        if self.mesh is not None and D.mesh_tp(self.mesh) > 1:
            params = D.shard_params_for_serving(params, self.cfg,
                                                self.mesh)
            if draft_params is not None:
                draft_params = D.shard_params_for_serving(
                    draft_params, self.draft_cfg, self.mesh)
        old, old_draft = self.params, self.draft_params
        if self._insert_jits and (_abstract_tree((params, draft_params))
                                  != _abstract_tree((old, old_draft))):
            # the executables were compiled for the old tree (another
            # weight-quant mode has other leaves): back to the jitted
            # functions, which re-trace lazily like every other program
            self.inserts.update(self._insert_jits)
            self._insert_jits = {}
        self.params = params
        if self.spec_k:
            self.draft_params = draft_params
        if self.prefill_exec is not None and not self.prefill_remote:
            # the in-process prefill engine dispatches the same tree
            # (already sharded above); the scheduler quiesced its
            # queues before the flip, so no job reads a torn reference
            self.prefill_exec.params = self.params
        return old, old_draft

    # -- adapter (LoRA) dispatch tails (ISSUE 10) --------------------------

    def lora_step_tail(self) -> tuple:
        """Trailing operands for the resident chunk step: the stacked
        adapter arrays + the per-lane id vector — or () when adapters
        are off, keeping every dispatch byte-identical to today's."""
        if self.adapters is None:
            return ()
        return (self.adapters.arrays(), jnp.asarray(self.aid))

    def lora_insert_tail(self, aid_val: int) -> tuple:
        """Trailing operands for a batch-of-one admission insert."""
        if self.adapters is None:
            return ()
        return (self.adapters.arrays(),
                jnp.full((1,), int(aid_val), jnp.int32))

    # -- plan replay: the ONE resident dispatch path (ISSUE 11) ------------

    def megastep_prog(self, n: int):
        """The compiled N-fused-iteration program for this ring's mode
        (contiguous / paged / quant / spec), compiled once per N."""
        prog = self._mega.get(n)
        if prog is None:
            if self.spec_k:
                from paddle_operator_tpu.infer.speculative import (
                    make_spec_megastep,
                )

                prog = make_spec_megastep(
                    self.cfg, self.draft_cfg, self.spec_k, n,
                    self.top_k, self.top_p, mesh=self.mesh,
                    paged=self.paged, quant=self.quant)
            elif self.paged:
                prog = self._pg.make_paged_megastep(
                    self.cfg, self.chunk, n, self.top_k, self.top_p,
                    mesh=self.mesh, check_finite=self.check_finite,
                    quant=self.quant)
            else:
                prog = make_megastep(
                    self.cfg, self.chunk, n, self.top_k, self.top_p,
                    mesh=self.mesh, check_finite=self.check_finite)
            self._mega[n] = prog
        return prog

    def replay(self, plan: ExecPlan) -> DispatchResult:
        """THE plan replayer: execute one scheduler-filled
        :class:`ExecPlan` against the ring's device state.  Every
        resident decode dispatch — 1-step or fused — enters the device
        through here, which is the seam the chaos injector wraps and
        the watchdog brackets.  At ``n_steps == 1`` the dispatch is
        operand-for-operand the pre-plan code path (the traced
        programs are the SAME objects — ``self.step``/``self.spec_step``
        — so pacing/chaos wrappers installed on them keep working and
        the N=1 stream is byte-identical to the pre-refactor ring)."""
        active = jnp.asarray(plan.active, bool)
        tbl = jnp.asarray(plan.table) if plan.table is not None else None
        rows = plan.n_steps * (self.spec_k + 1 if self.spec_k
                               else self.chunk)
        if plan.n_steps == 1:
            if self.spec_k:
                spec_args = (self.params, self.draft_params, self.cache,
                             self.dcache)
                if self.paged:
                    spec_args += (tbl,)
                (self.cache, self.dcache, self.tok, toks,
                 counts) = self.spec_step(
                    *spec_args, self.tok, self.temp, self.keys, active)
                return DispatchResult(toks, counts, None, counts, 1, rows)
            if self.paged:
                out = self.step(self.params, self.cache, tbl, self.tok,
                                self.temp, self.keys, active, *plan.lora)
            else:
                out = self.step(self.params, self.cache, self.tok,
                                self.temp, self.keys, active, *plan.lora)
            moe = None
            if self.expert_stack:      # the routing counters, last
                out, moe = out[:-1], out[-1]
            if self.check_finite:
                self.cache, self.tok, toks, ok = out
            else:
                (self.cache, self.tok, toks), ok = out, None
            return DispatchResult(toks, None, ok, None, 1, rows, moe)
        prog = self.megastep_prog(plan.n_steps)
        eos = jnp.asarray(plan.eos, jnp.int32)
        left = jnp.asarray(plan.left, jnp.int32)
        steps = jnp.asarray(plan.steps, jnp.int32)
        if self.spec_k:
            spec_args = (self.params, self.draft_params, self.cache,
                         self.dcache)
            if self.paged:
                spec_args += (tbl,)
            (self.cache, self.dcache, self.tok, toks, raw,
             counts) = prog(*spec_args, self.tok, self.temp, self.keys,
                            active, eos, left, steps)
            return DispatchResult(toks, counts, None, raw, plan.n_steps, rows)
        if self.paged:
            out = prog(self.params, self.cache, tbl, self.tok, self.temp,
                       self.keys, active, eos, left, steps, *plan.lora)
        else:
            out = prog(self.params, self.cache, self.tok, self.temp,
                       self.keys, active, eos, left, steps, *plan.lora)
        if self.check_finite:
            self.cache, self.tok, toks, counts, oks = out
        else:
            (self.cache, self.tok, toks, counts), oks = out, None
        return DispatchResult(toks, counts, oks, None, plan.n_steps, rows)

    # -- lazily-compiled admission programs --------------------------------

    def suffix_bucket(self, n: int) -> int:
        """Compile bucket for a prefix-hit SUFFIX forward — sized
        independently of the prompt buckets (whose smallest entry can
        be prompt-sized: a 1-token suffix must not pay a 2048-row
        forward).  Power-of-two ladder up to one block, then block
        multiples; the compile set stays bounded by
        log2(block_size) + SUFFIX_PREFILL_MAX_ROWS / block_size."""
        cap = self.pool.view_len
        b = 8
        while b < min(n, self.block_size):
            b *= 2
        if b < n:
            b = -(-n // self.block_size) * self.block_size
        return min(b, cap)

    def suffix_insert(self, sb: int):
        ins = self._suffix_inserts.get(sb)
        if ins is None:
            ins = self._pg.make_paged_suffix_insert(
                self.cfg, sb, self.block_size, self.top_k, self.top_p,
                mesh=self.mesh, quant=self.quant)
            self._suffix_inserts[sb] = ins
        return ins

    def pool_bytes(self) -> int:
        """Device bytes held by the KV cache (block pool incl. scale
        planes and staging tails, or the contiguous ring) — the
        ``tpujob_serve_kv_pool_bytes`` gauge.  Pure shape arithmetic,
        no device sync."""
        import numpy as np

        total = 0
        for key in ("k", "v", "ks", "vs", "kt", "vt"):
            buf = self.cache.get(key)
            if buf is not None:
                total += int(np.prod(buf.shape)) * buf.dtype.itemsize
        return total

    def param_bytes(self) -> int:
        """HBM bytes of the params tree(s) this ring dispatches (target
        + draft when speculative) — the ``tpujob_serve_param_bytes``
        gauge, pool_bytes()'s weight-side sibling.  Pure shape
        arithmetic, no device sync; int8 code leaves count 1 byte/param
        + their f32 scale planes, so the gauge shows the quantization
        saving directly."""
        from paddle_operator_tpu.infer import quant as Q

        total = Q.param_bytes(self.params)
        if getattr(self, "draft_params", None) is not None:
            total += Q.param_bytes(self.draft_params)
        return total

    # -- host spill tier: demote fetch + batched promote (ISSUE 8) --------

    def _demote_fetch(self, blk: int) -> Dict[str, Any]:
        """PagedCacheManager.demote_fetch hook: one block's exact device
        bytes, captured WITHOUT blocking the ring thread.  The slice is
        an async dispatch (stream-ordered after every write to the
        block, so it reads final content) and the device->host copy is
        kicked with ``copy_to_host_async`` — no sync here, residents
        never stall on a demotion.  The payload dict initially holds
        the small sliced device arrays; the NEXT tier touch (another
        demotion, or nothing — a promote reads them as-is) materializes
        the PREVIOUS payloads to numpy in place, releasing their device
        buffers, so at most one admission's worth of demoted slices is
        ever device-resident."""
        # materialize earlier payloads first: their D2H copies have
        # long completed, so the asarray is a cheap buffer read
        for d in self._demote_lazy:
            for key, val in d.items():
                if not isinstance(val, np.ndarray):
                    d[key] = np.asarray(val)
        self._demote_lazy.clear()
        c = self.cache
        if self.quant:
            kb, vb, ksb, vsb = self._fetch_prog(c["k"], c["v"], c["ks"],
                                                c["vs"], blk)
            payload = {"k": kb, "v": vb, "ks": ksb, "vs": vsb}
        else:
            kb, vb = self._fetch_prog(c["k"], c["v"], blk)
            payload = {"k": kb, "v": vb}
        for val in payload.values():
            try:
                val.copy_to_host_async()
            except AttributeError:      # interpret-mode ndarray
                pass
        self._demote_lazy.append(payload)
        return payload

    @staticmethod
    def _promote_pad(n: int) -> int:
        """Pad a promote batch to a power of two so a handful of
        compiles serves every batch size (the ids pad with the trash
        block — garbage written there is its job)."""
        p = 1
        while p < n:
            p *= 2
        return p

    def dispatch_promotions(self, promotes) -> None:
        """Upload a batch of host-tier payloads into their RESERVED
        pool blocks in one donated jit (``promotes``:
        pool.take_promotions() output).  The host->device transfer and
        the scatter are both ASYNC dispatches: they overlap the decode
        chunk already in flight on the device, and the runtime orders
        them before the admission insert / CoW dispatched next — the
        prefetch never stalls resident lanes and activation naturally
        waits on transfer completion."""
        n = len(promotes)
        pad = self._promote_pad(n)
        bs = self.block_size
        p0 = promotes[0][1]
        lcount, _, h, _, d = p0["k"].shape
        slab_k = np.zeros((lcount, 1, h, pad * bs, d), p0["k"].dtype)
        slab_v = np.zeros_like(slab_k)
        ids = np.full((pad,), self._pg.TRASH_BLOCK, np.int32)
        for j, (dst, payload, _key) in enumerate(promotes):
            ids[j] = dst
            slab_k[:, 0, :, j * bs:(j + 1) * bs] = payload["k"][:, 0]
            slab_v[:, 0, :, j * bs:(j + 1) * bs] = payload["v"][:, 0]
        c = self.cache
        if self.quant:
            # pad scale rows hold the all-zero-block sentinel 1.0 so a
            # (never-read) trash write still dequantizes finite
            srow_k = np.ones((lcount, pad, h), np.float32)
            srow_v = np.ones_like(srow_k)
            for j, (dst, payload, _key) in enumerate(promotes):
                srow_k[:, j] = payload["ks"][:, 0]
                srow_v[:, j] = payload["vs"][:, 0]
            c["k"], c["v"], c["ks"], c["vs"] = self._promote_prog(
                c["k"], c["v"], c["ks"], c["vs"], jnp.asarray(slab_k),
                jnp.asarray(slab_v), jnp.asarray(srow_k),
                jnp.asarray(srow_v), jnp.asarray(ids))
        else:
            c["k"], c["v"] = self._promote_prog(
                c["k"], c["v"], jnp.asarray(slab_k), jnp.asarray(slab_v),
                jnp.asarray(ids))

    # -- lane spill/restore: the preemption primitive (ISSUE 8) -----------

    def spill_lane(self, slot: int) -> Dict[str, Any]:
        """Capture a LIVE lane to host: its mapped blocks' exact pool
        bytes (codes + scales under int8, plus the bf16 staging tail),
        its fill position and its carry token / temperature / sampling
        key — everything :meth:`restore_lane` needs to resume the lane
        bit-identically.  The caller retires the lane afterwards
        (freeing its blocks for the preempting request); this method
        only reads.  This is the generic preemption/handoff primitive
        ROADMAP items 4 (priority preemption) and 5 (hot swap via lane
        handoff) consume — tested for exactness in
        tests/test_hostcache.py.

        The capture is plain host bytes on purpose: ISSUE 12 wraps it
        in a self-describing wire envelope (utils/fleetkv.encode_lane)
        and a PEER replica restores it through this same
        spill-dict contract (``ContinuousBatcher.adopt``) —
        cross-replica lane migration is this method plus HTTP.  The
        gather is full (unsharded) host bytes, so a tp=1 spill may
        restore onto a tp=2 ring: the promote scatter re-shards."""
        pm = self.pool
        m = pm.mapped_count[slot]
        ids = jnp.asarray([int(pm.table[slot][j]) for j in range(m)],
                          jnp.int32)
        c = self.cache
        spill: Dict[str, Any] = {
            "n_blocks": m,
            "pos": int(np.asarray(c["pos"])[slot]),
            "tok": int(np.asarray(self.tok)[slot]),
            "temp": float(np.asarray(self.temp)[slot]),
            "key": np.asarray(self.keys)[slot].copy(),
            "k": np.asarray(jnp.take(c["k"], ids, axis=1)),
            "v": np.asarray(jnp.take(c["v"], ids, axis=1)),
        }
        if self.quant:
            spill["ks"] = np.asarray(jnp.take(c["ks"], ids, axis=1))
            spill["vs"] = np.asarray(jnp.take(c["vs"], ids, axis=1))
            spill["kt"] = np.asarray(c["kt"][:, slot])
            spill["vt"] = np.asarray(c["vt"][:, slot])
        if self.spec_k:
            # the DRAFT lane is resident context too (contiguous ring):
            # a spec round resumed without it would re-propose from a
            # zeroed draft cache and diverge from the uninterrupted
            # stream the moment any draft is accepted.  The whole lane
            # alloc is captured — rows past dpos are junk the fill mask
            # already hides, and exactness beats a slice here.
            spill["dk"] = np.asarray(self.dcache["k"][:, slot])
            spill["dv"] = np.asarray(self.dcache["v"][:, slot])
            spill["dpos"] = int(np.asarray(self.dcache["pos"])[slot])
        if self.adapters is not None:
            spill["aid"] = int(self.aid[slot])
        return spill

    def restore_lane(self, slot: int, spill: Dict[str, Any]) -> None:
        """Re-admit a spilled lane into (empty) ``slot``: map fresh
        pool blocks, upload the spilled bytes through the same promote
        scatter a host hit uses, restore the staging tail, and attach
        the lane state (pos/tok/temp/keys) — the resumed decode stream
        is bit-identical to the uninterrupted one because every byte
        the forward reads is a copy of what was captured.  The re-admit
        rides the same suffix-insert-shaped contract as admission: the
        restored rows play the role of a full prefix hit, so no forward
        runs here at all."""
        pm = self.pool
        if pm.mapped_count[slot]:
            raise AssertionError(f"slot {slot} still holds blocks")
        m = spill["n_blocks"]
        pm.ensure(slot, m * self.block_size)
        promotes = []
        for j in range(m):
            payload = {"k": spill["k"][:, j:j + 1],
                       "v": spill["v"][:, j:j + 1]}
            if self.quant:
                payload["ks"] = spill["ks"][:, j:j + 1]
                payload["vs"] = spill["vs"][:, j:j + 1]
            promotes.append((int(pm.table[slot][j]), payload, None))
        if promotes:
            self.dispatch_promotions(promotes)
        if self.quant:
            self.cache["kt"] = self.cache["kt"].at[:, slot].set(
                jnp.asarray(spill["kt"]))
            self.cache["vt"] = self.cache["vt"].at[:, slot].set(
                jnp.asarray(spill["vt"]))
        if self.spec_k:
            self.dcache["k"] = self.dcache["k"].at[:, slot].set(
                jnp.asarray(spill["dk"]))
            self.dcache["v"] = self.dcache["v"].at[:, slot].set(
                jnp.asarray(spill["dv"]))
            self.dcache["pos"] = self.dcache["pos"].at[slot].set(
                spill["dpos"])
        if self.adapters is not None and "aid" in spill:
            self.aid[slot] = spill["aid"]
        self.cache["pos"] = self.cache["pos"].at[slot].set(spill["pos"])
        self.tok = self.tok.at[slot].set(spill["tok"])
        self.temp = self.temp.at[slot].set(spill["temp"])
        self.keys = self.keys.at[slot].set(jnp.asarray(spill["key"]))

    def chunk_prog(self, staging_len: Optional[int]):
        """Intermediate chunked-prefill slice program: paged (keyed by
        the fixed slice width) or contiguous (keyed by staging
        length)."""
        sb = self.prefill_chunk
        key = ("paged", sb) if self.paged else ("ring", sb, staging_len)
        prog = self._chunk_progs.get(key)
        if prog is None:
            if self.paged:
                prog = self._pg.make_paged_prefill_chunk(
                    self.cfg, sb, self.block_size, mesh=self.mesh,
                    quant=self.quant)
            else:
                prog = make_prefill_chunk(self.cfg, sb, staging_len,
                                          mesh=self.mesh)
            self._chunk_progs[key] = prog
        return prog

    def final_insert(self, staging_len: Optional[int],
                     bucket: Optional[int] = None):
        """Final chunked-prefill slice program.  Paged rings reuse the
        SUFFIX insert (a chunked prefill's last slice IS a suffix
        insert whose 'hit' is the rows the earlier slices wrote) —
        shared compile with the radix-hit path; spec rings get the
        draft-prefilling variants."""
        sb = self.prefill_chunk
        if self.paged and not self.spec_k:
            return self.suffix_insert(sb)
        if self.paged:
            key = ("paged-spec", sb, bucket)
            prog = self._final_inserts.get(key)
            if prog is None:
                prog = self._pg.make_paged_spec_suffix_insert(
                    self.cfg, self.draft_cfg, sb, bucket,
                    self.block_size, self.top_k, self.top_p,
                    mesh=self.mesh, quant=self.quant)
                self._final_inserts[key] = prog
            return prog
        if self.spec_k:
            key = ("ring-spec", sb, staging_len, bucket)
            prog = self._final_inserts.get(key)
            if prog is None:
                prog = make_spec_chunked_final_insert(
                    self.cfg, self.draft_cfg, sb, staging_len, bucket,
                    self.top_k, self.top_p, mesh=self.mesh)
                self._final_inserts[key] = prog
            return prog
        key = ("ring", sb, staging_len)
        prog = self._final_inserts.get(key)
        if prog is None:
            prog = make_chunked_final_insert(
                self.cfg, sb, staging_len, self.top_k, self.top_p,
                mesh=self.mesh)
            self._final_inserts[key] = prog
        return prog

    def spec_attach(self, bucket: int):
        prog = self._spec_attach.get(bucket)
        if prog is None:
            prog = make_spec_attach(self.cfg, self.draft_cfg, bucket,
                                    mesh=self.mesh)
            self._spec_attach[bucket] = prog
        return prog

    def staging_len(self, bucket: int) -> int:
        """Contiguous chunked prefill stages in a private lane cache
        whose length is the bucket rounded up to whole slices, so every
        full-width slice write stays in bounds (a clamped
        dynamic_update_slice would silently shift pad rows over real
        ones).  The splice truncates back to the ring allocation."""
        sb = self.prefill_chunk
        return -(-bucket // sb) * sb

    def make_staging(self, bucket: int) -> Tuple[jax.Array, jax.Array]:
        """Fresh zeroed staging K/V for one contiguous chunked prefill
        ([L, 1, H_kv, staging_len(bucket), D], kv-head-sharded like the
        ring cache so the slice programs compile against one layout)."""
        sl = self.staging_len(bucket)
        shape = (self.cfg.n_layers, 1, self.cfg.n_kv_heads, sl,
                 self.cfg.head_dim)
        return (D.alloc_kv_buffer(self.cfg, shape, self.mesh),
                D.alloc_kv_buffer(self.cfg, shape, self.mesh))

    # -- every rung ready before the ring is ------------------------------

    def _insert_operands(self, cache, dcache, tok, temp, keys, prompt,
                         tail, make=jnp.zeros) -> tuple:
        """A whole-prompt insert's operands in the order every call
        site passes them (scheduler ``_admit``, :meth:`cold_insert`),
        for this ring's mode: prompt length 1, lane 0, greedy, seed 0,
        an empty table and no lane riding (``make(shape, dtype)``
        builds them: zeros, or ``jax.ShapeDtypeStruct`` to lower
        from)."""
        head = ((self.params, self.draft_params, cache, dcache)
                if self.spec_k else (self.params, cache))
        lanes = (tok, temp, keys)
        if self.insert_takes_ring:
            head += (make((self.slots, self.pool.max_blocks), jnp.int32),)
            lanes += (make((self.slots,), bool),)
        elif self.paged:
            head += (make((self.pool.max_blocks,), jnp.int32),)
        return head + lanes + (prompt, 1, 0, 0.0, 0) + tuple(tail)

    def cold_insert(self, bucket: int, slot: int, table, riders,
                    prompt, n: int, temp_val: float, seed: int,
                    aid: int = 0):
        """Dispatch the paged ring's cold (whole-prompt) insert for
        ``slot`` and take the ring state it returns.  ``table`` is the
        pool's host table, ``riders`` the lanes whose next token the
        insert's carried step may advance (none for a program that takes
        no lane mask).  Returns ``(first, res)``: the first token's
        device scalar and — where the program returned the lanes' tokens
        — a one-step :class:`DispatchResult` (``toks [1, B]``) for the
        scheduler's pipeline, else None."""
        if self.insert_takes_ring:
            active = np.zeros((self.slots,), bool)
            active[list(riders)] = True
            tbl, mask = jnp.asarray(table), (jnp.asarray(active),)
        else:
            tbl, mask = jnp.asarray(table[slot]), ()
        out = self.inserts[bucket](
            self.params, self.cache, tbl, self.tok, self.temp, self.keys,
            *mask, prompt, n, slot, temp_val, seed,
            *self.lora_insert_tail(aid))
        self.cache, self.tok, self.temp, self.keys, first = out[:5]
        if len(out) == 5:
            return first, None
        ok = out[6] if self.check_finite else None
        return first, DispatchResult(out[5], None, ok, None, 1, 1)

    def compile_inserts(self) -> None:
        """Make every rung's insert an executable NOW: lowered from the
        abstract shapes of the ring's own state (no buffers, no second
        pool) and compiled, and ``self.inserts`` then holds the
        executables, so the first prompt on a rung neither traces nor
        asks the compile cache.  For the serving entry point, before
        it listens (infer/serve.py main; whatever SERVE_PREWARM says) —
        a ring built directly stays lazy and pays only for the rungs it
        is sent.  Only inline rings dispatch ``inserts``: chunked and
        disaggregated rings admit through their own programs.

        An executable is bound to its operands' shapes, dtypes and
        tree; :meth:`swap_weights` puts the jitted functions back when
        a new checkpoint's tree differs."""
        if self.prefill_mode != "inline" or self._insert_jits:
            return
        tail = self.lora_insert_tail(0)
        jits = dict(self.inserts)
        for b, fn in jits.items():
            operands = self._insert_operands(
                self.cache, self.dcache, self.tok, self.temp, self.keys,
                jax.ShapeDtypeStruct((1, b), jnp.int32), tail,
                make=jax.ShapeDtypeStruct)
            self.inserts[b] = fn.lower(
                *jax.tree.map(_abstract, operands)).compile()
        self._insert_jits = jits

    # -- prewarm -----------------------------------------------------------

    def prewarm(self) -> None:
        """Compile the admission/step programs NOW, against throwaway
        state of the real shapes/shardings, so the first long prompt of
        a fresh server never pays a multi-second XLA compile on the
        serving path (the jit dispatch cache keys on
        shape/dtype/sharding — identical dummies make the real call a
        cache hit).  Runs off-thread from the scheduler (opt-out:
        prewarm=False / SERVE_PREWARM=0); jax dispatch is thread-safe,
        and donated dummy buffers are garbage by design."""
        slots = self.slots
        if self.paged:
            cache = self._pg.init_paged_cache(
                self.cfg, slots, self.pool.total, self.block_size,
                mesh=self.mesh, quant=self.kv_quant)
            tbl = jnp.zeros((slots, self.pool.max_blocks), jnp.int32)
        else:
            cache = init_ring_cache(self.cfg, slots, self.max_len,
                                    mesh=self.mesh)
            tbl = None
        tok = jnp.zeros((slots,), jnp.int32)
        temp = jnp.zeros((slots,), jnp.float32)
        keys = jnp.zeros((slots, 2), jnp.uint32)
        active = jnp.zeros((slots,), bool)
        dcache = (init_ring_cache(self.draft_cfg, slots, self.max_len,
                                  mesh=self.mesh) if self.spec_k else None)
        # adapter-aware rings dispatch with trailing lora operands —
        # warm THOSE traces (the tail-less ones would never run)
        st = self.lora_step_tail()
        it = self.lora_insert_tail(0)
        # the resident step first: it is the program every lane shares
        if self.spec_k:
            args = (self.params, self.draft_params, cache, dcache)
            if self.paged:
                args += (tbl,)
            out = self.spec_step(*args, tok, temp, keys, active)
            cache, dcache, tok = out[0], out[1], out[2]
        elif self.paged:
            out = self.step(self.params, cache, tbl, tok, temp, keys,
                            active, *st)
            cache, tok = out[0], out[1]
            if self.expert_stack:
                # its inserts are executables already (compile_inserts);
                # the programs warmed below are the LLaMA block's, for
                # modes that refuse this architecture
                return
        else:
            out = self.step(self.params, cache, tok, temp, keys, active,
                            *st)
            cache, tok = out[0], out[1]
        if self.megastep > 1:
            # the configured megastep program (ISSUE 11): without this
            # the FIRST loaded moment after boot pays the N-step compile
            prog = self.megastep_prog(self.megastep)
            eos = jnp.full((slots,), -1, jnp.int32)
            left = jnp.ones((slots,), jnp.int32)
            stp = jnp.full((slots,), self.megastep, jnp.int32)
            if self.spec_k:
                args = (self.params, self.draft_params, cache, dcache)
                if self.paged:
                    args += (tbl,)
                out = prog(*args, tok, temp, keys, active, eos, left,
                           stp)
                cache, dcache, tok = out[0], out[1], out[2]
            elif self.paged:
                out = prog(self.params, cache, tbl, tok, temp, keys,
                           active, eos, left, stp, *st)
                cache, tok = out[0], out[1]
            else:
                out = prog(self.params, cache, tok, temp, keys, active,
                           eos, left, stp, *st)
                cache, tok = out[0], out[1]
        for b in self.buckets:
            if b in self._insert_jits:
                continue        # an executable already (compile_inserts)
            out = self.inserts[b](*self._insert_operands(
                cache, dcache, tok, temp, keys,
                jnp.zeros((1, b), jnp.int32), it))
            if self.spec_k:
                cache, dcache, tok, temp, keys = out[:5]
            else:
                cache, tok, temp, keys = out[:4]
        if self.paged and not self.spec_k:
            # the SUFFIX-insert ladder: a radix prefix hit (even a
            # partial-tail one on an otherwise cold prompt) admits
            # through make_paged_suffix_insert, and its first use used
            # to charge one request the compile — warm every bucket
            # the ladder can produce, plus the CoW block copier the
            # same admission path dispatches
            row = jnp.zeros((self.pool.max_blocks,), jnp.int32)
            cap = min(self.SUFFIX_PREFILL_MAX_ROWS, self.pool.view_len)
            sbs, n = set(), 1
            while n <= min(self.block_size, cap):   # power-of-2 rungs
                sbs.add(self.suffix_bucket(n))
                n *= 2
            n = self.block_size                     # block-multiple rungs
            while n <= cap:
                sbs.add(self.suffix_bucket(n))
                n += self.block_size
            for sb in sorted(sbs):
                toks = jnp.zeros((1, sb), jnp.int32)
                cache, tok, temp, keys, _ = self.suffix_insert(sb)(
                    self.params, cache, row, tok, temp, keys, toks,
                    1, 0, 0, 0.0, 0, *it)
            if self.quant:
                self._copy_block(jnp.zeros_like(cache["k"]),
                                 jnp.zeros_like(cache["v"]),
                                 jnp.zeros_like(cache["ks"]),
                                 jnp.zeros_like(cache["vs"]), 0, 0)
                # the mid-block radix-hit admission also dispatches the
                # staging-tail seed (scheduler._dispatch_cow)
                self._tail_init(jnp.zeros_like(cache["kt"]),
                                jnp.zeros_like(cache["vt"]),
                                cache["k"], cache["ks"], cache["v"],
                                cache["vs"], 0, 0)
            else:
                k = jnp.zeros_like(cache["k"])
                self._copy_block(k, jnp.zeros_like(cache["v"]), 0, 0)
            if self.host_cache_blocks or self.prefill_remote:
                # host-tier programs: the demote fetch and the promote
                # upload at the small pad ladder rungs a typical
                # admission batches into — otherwise the FIRST host hit
                # pays the promote compile inside its TTFT.  A REMOTE
                # disagg ring lands every cold handoff through the
                # same promote scatter, so it warms the ladder too.
                lc, _, h, bsz, dd = cache["k"].shape
                if self.host_cache_blocks and self.quant:
                    self._fetch_prog(cache["k"], cache["v"],
                                     cache["ks"], cache["vs"], 0)
                elif self.host_cache_blocks:
                    self._fetch_prog(cache["k"], cache["v"], 0)
                pad = 1
                # inclusive of _promote_pad(max_blocks): a 9-block
                # table pads its largest batch to 16, which must be in
                # the warmed set too
                while pad <= self._promote_pad(self.pool.max_blocks):
                    ids = jnp.zeros((pad,), jnp.int32)
                    slab = jnp.zeros((lc, 1, h, pad * bsz, dd),
                                     cache["k"].dtype)
                    if self.quant:
                        srow = jnp.ones((lc, pad, h), jnp.float32)
                        out = self._promote_prog(
                            jnp.zeros_like(cache["k"]),
                            jnp.zeros_like(cache["v"]),
                            jnp.zeros_like(cache["ks"]),
                            jnp.zeros_like(cache["vs"]),
                            slab, slab, srow, srow, ids)
                    else:
                        out = self._promote_prog(
                            jnp.zeros_like(cache["k"]),
                            jnp.zeros_like(cache["v"]), slab, slab, ids)
                    del out
                    pad *= 2
        if self.prefill_exec is not None and not self.prefill_remote:
            # the disagg engine's programs compile on the PREFILL
            # thread (they never stall decode), but the first cold
            # prompt would still pay them in its TTFT — run each
            # against the executor's own pool (no donation, and pool
            # content only matters mid-job, so racing a live job is
            # safe); the handoff transfer + attach ride along.
            # (Remote rings skip this: their whole-prompt programs
            # live — and prewarm — in the prefill pods.)
            pe = self.prefill_exec
            for b, prog in pe._progs.items():
                prog(self.params, pe.cache, pe.table_row,
                     jnp.zeros((1, b), jnp.int32), 1, 0.0, 0, *it)
            m = self.pool.max_blocks
            ids = jnp.zeros((m,), jnp.int32)
            if pe.lanes > 1:
                # the N-lane engine's batched slice/final programs —
                # one compile PER table-width ladder rung (_width's
                # power-of-two set: dispatches pass only as many
                # blocks as the deepest active job needs, and jit
                # shape-specializes) — plus the frame-wise handoff
                # ops (ISSUE 14)
                nl, sb = pe.lanes, pe.prefill_chunk
                z = lambda *s: jnp.zeros(s, jnp.int32)   # noqa: E731
                ptail = (pe.adapters.arrays(),
                         z(nl)) if pe.adapters is not None else ()
                mask = jnp.zeros((nl,), bool)
                w = 1
                while True:
                    mw = min(w, pe.max_blocks)
                    pe._slice_prog(self.params, pe.cache,
                                   z(nl, mw), z(nl, sb), z(nl),
                                   z(nl), mask, *ptail)
                    pe._final_prog(self.params, pe.cache,
                                   z(nl, mw), z(nl, sb),
                                   jnp.ones((nl,), jnp.int32), z(nl),
                                   jnp.zeros((nl,), jnp.float32),
                                   z(nl), z(nl), mask, *ptail)
                    if w >= pe.max_blocks:
                        break
                    w *= 2
                if self.quant:
                    self._frame_transfer(
                        jnp.zeros_like(cache["k"]),
                        jnp.zeros_like(cache["v"]),
                        jnp.zeros_like(cache["ks"]),
                        jnp.zeros_like(cache["vs"]),
                        pe.cache["k"], pe.cache["v"],
                        pe.cache["ks"], pe.cache["vs"], ids, ids)
                    self._tail_copy(jnp.zeros_like(cache["kt"]),
                                    jnp.zeros_like(cache["vt"]),
                                    pe.cache["kt"], pe.cache["vt"],
                                    0, 0)
                else:
                    self._frame_transfer(jnp.zeros_like(cache["k"]),
                                         jnp.zeros_like(cache["v"]),
                                         pe.cache["k"], pe.cache["v"],
                                         ids, ids)
            elif self.quant:
                self._transfer(jnp.zeros_like(cache["k"]),
                               jnp.zeros_like(cache["v"]),
                               jnp.zeros_like(cache["ks"]),
                               jnp.zeros_like(cache["vs"]),
                               jnp.zeros_like(cache["kt"]),
                               jnp.zeros_like(cache["vt"]),
                               pe.cache["k"], pe.cache["v"],
                               pe.cache["ks"], pe.cache["vs"],
                               pe.cache["kt"], pe.cache["vt"],
                               ids, ids, 0)
            else:
                self._transfer(jnp.zeros_like(cache["k"]),
                               jnp.zeros_like(cache["v"]),
                               pe.cache["k"], pe.cache["v"], ids, ids)
        if self.prefill_mode == "chunked":
            # the chunked path's first long prompt dispatches slice +
            # final programs instead of the bucket inserts — warm those
            # too, or the compile cliff just moves
            sb = self.prefill_chunk
            toks = jnp.zeros((1, sb), jnp.int32)
            if self.paged:
                row = jnp.zeros((self.pool.max_blocks,), jnp.int32)
                chunk_args = (self.params, cache, row, toks, 0, 0)
                if self.quant:      # quant slices take a trailing slot
                    chunk_args += (0,)
                cache = self.chunk_prog(None)(*chunk_args, *it)
                if self.spec_k:
                    for b in self.buckets:
                        prompt = jnp.zeros((1, b), jnp.int32)
                        out = self.final_insert(None, b)(
                            self.params, self.draft_params, cache,
                            dcache, row, tok, temp, keys, toks, 1, 0, 0,
                            prompt, 1, 0.0, 0)
                        cache, dcache, tok, temp, keys = out[:5]
                else:
                    out = self.final_insert(None)(
                        self.params, cache, row, tok, temp, keys, toks,
                        1, 0, 0, 0.0, 0, *it)
                    cache, tok, temp, keys = out[:4]
            else:
                for b in self.buckets:
                    sl = self.staging_len(b)
                    lk, lv = self.make_staging(b)
                    if sl > sb:
                        lk, lv = self.chunk_prog(sl)(self.params, lk, lv,
                                                     toks, 0, *it)
                    if self.spec_k:
                        prompt = jnp.zeros((1, b), jnp.int32)
                        out = self.final_insert(sl, b)(
                            self.params, self.draft_params, cache,
                            dcache, lk, lv, tok, temp, keys, toks, 1, 0,
                            prompt, 1, 0, 0.0, 0)
                        cache, dcache, tok, temp, keys = out[:5]
                    else:
                        out = self.final_insert(sl)(
                            self.params, cache, lk, lv, tok, temp, keys,
                            toks, 1, 0, 1, 0, 0.0, 0, *it)
                        cache, tok, temp, keys = out[:4]


def _abstract(x: Any) -> Any:
    """What ``jit`` would see of an insert's operand: a device array's
    shape, dtype and (where it is committed to one) sharding; a Python
    scalar weakly typed, as a traced call takes it."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    return jax.ShapeDtypeStruct((), jnp.result_type(x), weak_type=True)


def _abstract_tree(tree: Any) -> tuple:
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, [_abstract(x) for x in leaves]


def _default_buckets(max_len: int, block: int = 1) -> Tuple[int, ...]:
    """The prefill ladder of a ring ``max_len`` long: from 64 (rounded
    up to whole blocks of ``block``) doubling while below ``max_len``,
    then ``max_len`` itself, so every admissible prompt has a rung and
    none computes more than twice its own positions beyond the first.
    Where the top step is a whole doubling its 3:2 midpoint is a rung
    too: the widest programs cost the most a position (attention is
    quadratic in the width), so padding is dearest there.
    Block 256, ``max_len`` 4096: 256, 512, 1024, 2048, 3072, 4096."""
    out: List[int] = []
    b = -(-64 // block) * block
    while b < max_len:
        out.append(b)
        b *= 2
    if out and max_len == 2 * out[-1] and (max_len * 3 // 4) % block == 0:
        out.append(max_len * 3 // 4)
    out.append(max_len)
    return tuple(out)
