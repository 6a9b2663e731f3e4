"""Speculative decoding: draft-model propose + chunked target verify.

Decode at low batch is memory-bandwidth-bound (BENCH_r05: HBM util
0.23-0.31 on the XLA path at batch 1-8) — every generated token streams
the full weight set for ONE matmul-vector's worth of compute.
Speculative decoding (Leviathan et al., "Fast Inference from
Transformers via Speculative Decoding"; Chen et al., "Accelerating
Large Language Model Decoding with Speculative Sampling") converts that
idle bandwidth into tokens: a small DRAFT model proposes K tokens
autoregressively (cheap — its weight stream is a fraction of the
target's), then the TARGET model scores all K+1 positions in ONE
chunked forward (the same weight stream a single decode step pays) and
accepts the longest prefix consistent with its own distribution.  Per
accepted token the target streams its weights 1/(a+1) times.

Design, in this codebase's terms:

- **Draft propose** rides the ring's single-token step
  (infer/decode.py ``cached_step`` over the draft's contiguous view —
  per-lane positions, pallas kernel on TPU) for K+1 ticks: the last tick's logits are discarded
  but its cache write appends d_K's KV, so ANY accept length can rewind
  without a gap (the standard "feed the last draft too" trick).
- **Chunked verify** is one multi-token forward at per-lane offsets
  (infer/decode.py ``cached_forward``, the same forward over the target
  cache's view, contiguous or paged) — the prefill math of ``_layer``
  generalized to a per-lane position vector.  XLA einsum attention:
  T = K+1 is a handful of rows, the weight stream dominates.
- **Acceptance**: exact greedy equality at temperature 0 (output is
  BIT-IDENTICAL to autoregressive ``decode.generate`` — pinned by
  tests and the dryrun ``serve-spec`` gate), and textbook rejection
  sampling (accept d_i with prob min(1, p/q); on rejection sample the
  normalized residual max(0, p-q)) for temperature > 0, which preserves
  the target distribution exactly in expectation.
- **Cache rollback is a write-index rewind, no copy**: rejected
  positions' K/V rows simply stay behind the rewound per-lane ``pos``;
  the causal/fill mask never attends past ``pos`` and later writes
  overwrite them — the same invariant idle ring lanes already rely on.
- **No divergent compiles**: one jitted round serves every accept
  pattern; per-lane accept lengths land in a ``pos`` vector, and the
  greedy/sampled rules are computed side by side and selected per lane
  by ``temp > 0`` (the ``_sample_tokens`` discipline).

Capacity: a round starting at position p writes verify rows p..p+K, so
callers must leave ``spec_k - 1`` positions of headroom past
prompt+max_new_tokens (speculative_generate grows its allocation;
ContinuousBatcher.submit enforces it against max_len).

Fault tolerance (infer/resilience.py): the spec round is just another
resident dispatch to the batcher's host loop, so request deadlines,
the dispatch watchdog, and ring self-healing all apply unchanged — a
heal rebuilds BOTH caches (target + draft) and re-admits queued work.
The one exception is ``nan_check``: the per-lane isfinite fold is a
chunk-step output the spec round does not produce, so the batcher
rejects the combination up front rather than silently not checking.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.models.llama import LlamaConfig


def check_draft_compat(cfg: LlamaConfig, draft_cfg: LlamaConfig) -> None:
    """The one hard compatibility invariant: only TOKEN IDS cross
    between draft and target, so they must share a tokenizer.  Raises a
    clear error on vocab mismatch (everything else — depth, width,
    head counts — may differ freely; ``LlamaConfig.draft()`` builds a
    compatible config)."""
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            f"draft/target vocab mismatch: draft vocab_size="
            f"{draft_cfg.vocab_size} vs target {cfg.vocab_size} — "
            "speculative decoding exchanges token ids between the two "
            "models, so they must share one tokenizer")


# ---------------------------------------------------------------------------
# The speculative round: propose K, verify K+1, commit a+1, rewind
# ---------------------------------------------------------------------------


def make_spec_round_fn(cfg: LlamaConfig, dcfg: LlamaConfig, spec_k: int,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None, mesh=None,
                       paged: bool = False, quant: bool = False):
    """One jitted speculative round over ring-style caches (per-lane
    ``pos`` vectors), BOTH caches donated.

    ``round(params, dparams, tcache, dcache, tok [B], temp [B],
    keys [B,2], active [B]) -> (tcache', dcache', tok', committed
    [spec_k+1, B], n_commit [B])``

    ``tok`` is the per-lane carry token — committed but not yet in
    either cache.  ``committed[:n_commit[b], b]`` are lane b's newly
    committed tokens this round (accepted drafts then the
    correction/bonus token); inactive lanes freeze their output
    (n_commit 0, tok unchanged; their pos is zeroed — retired-lane
    hygiene) so the compiled program is one shape for every
    arrival/accept pattern.

    ``paged=True``: the TARGET cache is the paged block pool
    (infer/paged.py) — the round signature gains the block table after
    the caches (``round(params, dparams, tcache, dcache, table, ...)``)
    and the verify forward walks it (``paged.paged_view``).
    The DRAFT cache stays a contiguous ring either way: its propose
    loop keeps the fast contiguous write path and pays no paging.

    ``quant=True`` (with ``paged``): the target pool is the int8
    codes+scales+tails dict.  The one spec-specific wrinkle is the
    ROLLBACK: the verify wrote K+1 rows through the staging tail, so a
    rewind that crosses back over a completed block boundary leaves the
    tail holding a NEWER block than the lane's write frontier — the
    round re-seeds such lanes' tails by dequantizing the frontier block
    from the pool (its rows below the rewound pos are exactly the
    committed ones; rows above sit behind the fill mask and are
    overwritten before they become attendable, the standard rollback
    invariant).  Lanes whose frontier block never completed keep their
    live tail untouched."""
    _round = _build_spec_round(cfg, dcfg, spec_k, top_k, top_p, mesh,
                               paged, quant)

    if paged:
        def round_fn(params, dparams, tcache, dcache, table, tok, temp,
                     keys, active):
            return _round(params, dparams, tcache, dcache, tok, temp,
                          keys, active, table)
    else:
        def round_fn(params, dparams, tcache, dcache, tok, temp, keys,
                     active):
            return _round(params, dparams, tcache, dcache, tok, temp,
                          keys, active, None)

    return jax.jit(round_fn, donate_argnums=(2, 3))


def _build_spec_round(cfg, dcfg, spec_k, top_k, top_p, mesh, paged,
                      quant):
    """The RAW (un-jitted) speculative round body behind
    :func:`make_spec_round_fn` — extracted so the megastep
    (:func:`make_spec_megastep`) can scan it N times inside one
    compiled program.  The op sequence is exactly what the jitted
    1-round program traced before the extraction; nothing about the
    round changed."""
    kk = spec_k

    def _round(params, dparams, tcache, dcache, tok, temp, keys, active,
               table):
        b = tok.shape[0]
        tpos0, dpos0 = tcache["pos"], dcache["pos"]
        # decoupled sampling streams: draft draws, acceptance uniforms
        # and residual draws must not reuse each other's bits
        dkeys = jax.vmap(lambda u: jax.random.fold_in(u, 1))(keys)
        akeys = jax.vmap(lambda u: jax.random.fold_in(u, 2))(keys)
        rkeys = jax.vmap(lambda u: jax.random.fold_in(u, 3))(keys)

        def draft_tick(carry, _):
            dc, tk = carry
            p0 = dc["pos"]
            logits, dc = D.cached_step(
                dcfg, dparams, tk, D.ContiguousView(dcfg, dc, mesh))
            greedy = logits.argmax(-1).astype(jnp.int32)
            filt = D._filter_logits(
                logits / jnp.maximum(temp, 1e-6)[:, None], top_k, top_p)
            qdist = jax.nn.softmax(filt, axis=-1)            # [B, V] f32
            sub = jax.vmap(jax.random.fold_in)(dkeys, p0)
            drawn = jax.vmap(
                lambda u, l: jax.random.categorical(u, l))(sub, filt)
            nxt = jnp.where(temp > 0, drawn.astype(jnp.int32), greedy)
            return (dc, nxt), (nxt, qdist)

        # K+1 ticks: K proposals, plus one extra feed whose logits are
        # discarded but whose cache write appends d_K's KV — the rewind
        # then has no gap at full acceptance (module docstring)
        (dcache2, _), (ds, qdists) = jax.lax.scan(
            draft_tick, (dcache, tok), None, length=kk + 1)
        drafts = ds[:kk].T                                   # [B, K]
        q = jnp.transpose(qdists[:kk], (1, 0, 2))            # [B, K, V]

        seq = jnp.concatenate([tok[:, None], drafts], axis=1)  # [B, K+1]
        # the verify forward, over the target cache's view: paged, its
        # writes land in pool blocks and the attention gathers the lane
        # view; int8, masked lanes' rows go to the trash tail (their
        # tail rows may be live prefill state a resident dispatch must
        # not clobber)
        view = (PG.paged_view(cfg, tcache, table, lane_mask=active,
                              mesh=mesh)
                if paged else D.ContiguousView(cfg, tcache, mesh))
        tlogits, tcache2 = D.cached_forward(cfg, params, seq, view)
        tgt = tlogits.argmax(-1).astype(jnp.int32)           # [B, K+1]

        # greedy rule: accept while the draft equals the target argmax
        accept_g = drafts == tgt[:, :kk]
        # sampled rule: accept d_i with prob min(1, p(d_i)/q(d_i))
        tfilt = D._filter_logits(
            tlogits / jnp.maximum(temp, 1e-6)[:, None, None], top_k, top_p)
        pdist = jax.nn.softmax(tfilt, axis=-1)               # [B, K+1, V]
        p_tok = jnp.take_along_axis(
            pdist[:, :kk], drafts[..., None], -1)[..., 0]    # [B, K]
        q_tok = jnp.take_along_axis(q, drafts[..., None], -1)[..., 0]
        sub_a = jax.vmap(jax.random.fold_in)(akeys, tpos0)
        u = jax.vmap(lambda s_: jax.random.uniform(s_, (kk,)))(sub_a)
        accept_s = u * q_tok < p_tok
        accept = jnp.where(temp[:, None] > 0, accept_s, accept_g)
        # longest accepted prefix per lane, 0..K
        a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

        # the token after the accepted prefix: at a < K the correction
        # (greedy: target argmax; sampled: the normalized residual
        # max(0, p - q)), at a == K the bonus from the target's K-th
        # distribution — the same gather covers both (q padded with 0)
        nxt_g = jnp.take_along_axis(tgt, a[:, None], 1)[:, 0]
        q_pad = jnp.concatenate([q, jnp.zeros_like(q[:, :1])], axis=1)
        pd_a = jnp.take_along_axis(pdist, a[:, None, None], 1)[:, 0]
        qd_a = jnp.take_along_axis(q_pad, a[:, None, None], 1)[:, 0]
        resid = jnp.clip(pd_a - qd_a, 0.0, None)
        rs = resid.sum(-1, keepdims=True)
        resid = jnp.where(rs > 0, resid, pd_a)   # numerically-empty residual
        sub_r = jax.vmap(jax.random.fold_in)(rkeys, tpos0)
        nxt_s = jax.vmap(
            lambda s_, r: jax.random.categorical(s_, jnp.log(r)))(
            sub_r, resid).astype(jnp.int32)
        nxt = jnp.where(temp > 0, nxt_s, nxt_g)

        n_commit = jnp.where(active, a + 1, 0)
        drafts_pad = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)  # [B, K+1]
        idx = jnp.arange(kk + 1)[None, :]
        committed = jnp.where(
            idx < a[:, None], drafts_pad,
            jnp.where(idx == a[:, None], nxt[:, None], 0))
        tok_out = jnp.where(active, nxt, tok)
        # ROLLBACK: monotone write-index rewind — both caches advanced
        # spec_k+1 rows, committed only a+1; rejected rows stay behind
        # pos, never attended, overwritten by later writes.  Inactive
        # (retired/free) lanes get their position ZEROED rather than
        # frozen: serving_status must never see a stale fill position,
        # and under paging their writes route to the trash block via
        # the zeroed table row regardless.
        tcache2["pos"] = jnp.where(active, tpos0 + a + 1, 0)
        dcache2["pos"] = jnp.where(active, dpos0 + a + 1, 0)
        if paged and quant:
            # tail resync across a block-crossing rewind (docstring):
            # re-seed the tail from the pool's frontier block for lanes
            # whose rewound write block was completed+quantized by the
            # verify; inactive lanes keep their (possibly live-prefill)
            # tails untouched
            bs_q = tcache2["k"].shape[3]
            wb_after = (tpos0 + kk) // bs_q
            wb_new = tcache2["pos"] // bs_q
            need = active & (wb_new < wb_after)

            # behind a cond: a rewind crosses a completed block only
            # ~spec_k/block_size of rounds (and only on partial
            # accepts) — the two pool gathers + dequants + full-tail
            # rewrites must not tax every spec round
            def _resync(tails):
                kt, vt = tails
                blks = jnp.take_along_axis(table, wb_new[:, None],
                                           axis=1)[:, 0]       # [B]
                deqk = PG.dequantize_kv(
                    jnp.take(tcache2["k"], blks, axis=1),
                    jnp.take(tcache2["ks"], blks, axis=1),
                    kt.dtype)                           # [L, B, H, bs, D]
                deqv = PG.dequantize_kv(
                    jnp.take(tcache2["v"], blks, axis=1),
                    jnp.take(tcache2["vs"], blks, axis=1),
                    vt.dtype)
                sel = need[None, :, None, None, None]
                kt = kt.at[:, :b].set(jnp.where(sel, deqk, kt[:, :b]))
                vt = vt.at[:, :b].set(jnp.where(sel, deqv, vt[:, :b]))
                return kt, vt

            tcache2["kt"], tcache2["vt"] = jax.lax.cond(
                need.any(), _resync, lambda t: t,
                (tcache2["kt"], tcache2["vt"]))
        return tcache2, dcache2, tok_out, committed.T, n_commit

    return _round


def make_spec_megastep(cfg: LlamaConfig, dcfg: LlamaConfig, spec_k: int,
                       n_steps: int, top_k: Optional[int] = None,
                       top_p: Optional[float] = None, mesh=None,
                       paged: bool = False, quant: bool = False):
    """N fused SPECULATIVE rounds in one compiled dispatch (ISSUE 11):
    the raw round body (:func:`_build_spec_round`) scanned ``n_steps``
    times with the host's between-round decisions — eos inside a
    committed block, token budget, step budget — carried on device
    (decode._mega_advance over each round's committed tokens).  A
    lane that finishes mid-megastep free-runs masked: under paging its
    verify writes go through an effective table whose row is replaced
    by the trash block, its draft writes land past its frozen draft
    frontier (the rows a rollback already leaves there), and both
    positions are restored from the pre-round snapshot each boundary —
    so a lane frozen by its STEP budget resumes bit-identically later.

    ``mega(params, dparams, tcache, dcache[, table], tok, temp, keys,
    active, eos, left, steps) -> (tcache', dcache', tok',
    committed [n, K+1, B], raw [n, B], counts [n, B])``

    ``raw[r, b]`` is the round's device commit count (the oracle's
    acceptance-telemetry number; 0 for dead rounds), ``counts[r, b]``
    the rows of ``committed[r, :, b]`` the host consumes (eos/budget
    truncated — scheduler._consume's walk, precomputed)."""
    _round = _build_spec_round(cfg, dcfg, spec_k, top_k, top_p, mesh,
                               paged, quant)

    def _mega(params, dparams, tcache, dcache, tok, temp, keys, active,
              eos, left, steps, table):

        def outer(carry, _):
            tcache, dcache, tok, live, lleft, lsteps = carry
            tp0, dp0 = tcache["pos"], dcache["pos"]
            tbl_eff = (jnp.where(live[:, None], table, PG.TRASH_BLOCK)
                       if paged else None)
            tcache, dcache, tok, committed, n_commit = _round(
                params, dparams, tcache, dcache, tok, temp, keys, live,
                tbl_eff)
            count, live2, left2, lsteps2 = D._mega_continue(
                committed, n_commit, live, lleft, lsteps, eos)
            # frozen/dead lanes keep the positions their last consumed
            # token earned (the round zeroed them via the active mask)
            tcache["pos"] = jnp.where(live, tcache["pos"], tp0)
            dcache["pos"] = jnp.where(live, dcache["pos"], dp0)
            return ((tcache, dcache, tok, live2, left2, lsteps2),
                    (committed, n_commit, count))

        live0 = active & (left > 0) & (steps > 0)
        (tcache, dcache, tok, _, _, _), (committed, raws, counts) = \
            jax.lax.scan(outer, (tcache, dcache, tok, live0, left, steps),
                         None, length=n_steps)
        return tcache, dcache, tok, committed, raws, counts

    if paged:
        def mega(params, dparams, tcache, dcache, table, tok, temp,
                 keys, active, eos, left, steps):
            return _mega(params, dparams, tcache, dcache, tok, temp,
                         keys, active, eos, left, steps, table)
    else:
        def mega(params, dparams, tcache, dcache, tok, temp, keys,
                 active, eos, left, steps):
            return _mega(params, dparams, tcache, dcache, tok, temp,
                         keys, active, eos, left, steps, None)

    return jax.jit(mega, donate_argnums=(2, 3))


@functools.lru_cache(maxsize=16)
def _cached_round_fn(cfg, dcfg, spec_k, top_k, top_p, mesh):
    """Round programs keyed by (configs, K, filters, mesh) so repeated
    speculative_generate calls (bench sweeps, tests) reuse compiles."""
    return make_spec_round_fn(cfg, dcfg, spec_k, top_k, top_p, mesh=mesh)


@functools.lru_cache(maxsize=16)
def _cached_prefill(cfg, alloc_len, mesh):
    return jax.jit(lambda p, t: D.prefill(p, cfg, t, alloc_len, mesh=mesh))


# ---------------------------------------------------------------------------
# Host side: the standalone generate loop
# ---------------------------------------------------------------------------


def speculative_generate(params: Dict[str, Any],
                         draft_params: Dict[str, Any],
                         cfg: LlamaConfig, draft_cfg: LlamaConfig,
                         prompt: jax.Array, *, max_new_tokens: int,
                         spec_k: int = 4, temperature: float = 0.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         key: Optional[jax.Array] = None,
                         max_len: Optional[int] = None,
                         eos_token: Optional[int] = None, mesh=None,
                         return_stats: bool = False):
    """Speculative counterpart of decode.generate: prompt [B, S] ->
    [B, S + max_new_tokens].  At temperature 0 the output is exactly
    token-identical to ``decode.generate`` (greedy acceptance only ever
    commits tokens the target itself would have produced); at
    temperature > 0 rejection sampling preserves the target
    distribution (streams differ from generate's — distributional, not
    bitwise, equivalence).  Host-driven: rounds commit a data-dependent
    1..spec_k+1 tokens each, so the loop runs until every lane has its
    budget (lanes that finish early freeze via the active mask).

    ``mesh`` (make_serving_mesh): BOTH param trees must be laid out
    with decode.shard_params_for_serving; the draft's single-token
    steps and the chunked verify ride the same tp axis.

    ``return_stats``: also return {"accept_rate", "accepted",
    "drafted", "rounds", "spec_k"} — the serving acceptance telemetry.
    """
    check_draft_compat(cfg, draft_cfg)
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1 (got {spec_k})")
    b, s = prompt.shape
    cache_len = max_len or cfg.max_seq_len
    need = s + max_new_tokens
    if need > cache_len:
        raise ValueError(f"prompt ({s}) + max_new_tokens "
                         f"({max_new_tokens}) = {need} exceeds the cache "
                         f"({cache_len} positions)")
    # a verify round may write spec_k rows past the last committed
    # token; grow the allocation within the RoPE table and fail clearly
    # when it cannot fit
    alloc_len = min(cfg.max_seq_len, cache_len + spec_k)
    if need + spec_k - 1 > D.cache_alloc_len(alloc_len):
        raise ValueError(
            f"speculative decoding needs {spec_k - 1} positions of cache "
            f"headroom past prompt+max_new_tokens ({need}) but the RoPE "
            f"table caps the allocation at {alloc_len} "
            f"(cfg.max_seq_len={cfg.max_seq_len}); lower spec_k or "
            f"max_new_tokens")
    if alloc_len > draft_cfg.max_seq_len:
        raise ValueError(
            f"draft max_seq_len ({draft_cfg.max_seq_len}) is smaller than "
            f"the serving context ({alloc_len}); derive the draft with "
            f"cfg.draft() to inherit the target's RoPE table")
    if key is None:
        key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, b)

    logits, tc = _cached_prefill(cfg, alloc_len, mesh)(params, prompt)
    _, dc = _cached_prefill(draft_cfg, alloc_len, mesh)(draft_params,
                                                        prompt)
    # two distinct pos buffers: the round donates BOTH caches, and a
    # shared array would be donated twice
    tcache = {"k": tc["k"], "v": tc["v"],
              "pos": jnp.full((b,), s, jnp.int32)}
    dcache = {"k": dc["k"], "v": dc["v"],
              "pos": jnp.full((b,), s, jnp.int32)}

    temp_vec = jnp.full((b,), float(temperature), jnp.float32)
    if temperature <= 0:
        tok = logits.argmax(-1).astype(jnp.int32)
    else:
        filt = D._filter_logits(logits / temperature, top_k, top_p)
        tok = jax.vmap(lambda u, l: jax.random.categorical(u, l))(
            jax.vmap(lambda u: jax.random.fold_in(u, 0))(keys),
            filt).astype(jnp.int32)

    out = [[] for _ in range(b)]
    done = [False] * b
    first = np.asarray(tok)
    for i in range(b):
        t0 = int(first[i])
        out[i].append(t0)
        if eos_token is not None and t0 == eos_token:
            done[i] = True

    round_fn = _cached_round_fn(cfg, draft_cfg, spec_k, top_k, top_p, mesh)
    accepted = drafted = rounds = 0
    while True:
        act = [not done[i] and len(out[i]) < max_new_tokens
               for i in range(b)]
        if not any(act):
            break
        tcache, dcache, tok, committed, n_commit = round_fn(
            params, draft_params, tcache, dcache, tok, temp_vec, keys,
            jnp.asarray(act))
        committed = np.asarray(committed)             # [K+1, B]
        n_commit = np.asarray(n_commit)
        rounds += 1
        for i in range(b):
            if not act[i]:
                continue
            n = int(n_commit[i])
            drafted += spec_k
            accepted += n - 1
            for t in committed[:n, i]:
                if len(out[i]) >= max_new_tokens:
                    break
                out[i].append(int(t))
                if eos_token is not None and int(t) == eos_token:
                    done[i] = True
                    break

    # finished lanes keep emitting eos for their remaining positions —
    # decode.generate's static-shape eos semantics
    pad = eos_token if eos_token is not None else 0
    res = np.full((b, s + max_new_tokens), pad, np.int32)
    res[:, :s] = np.asarray(prompt)
    for i in range(b):
        res[i, s:s + len(out[i])] = out[i]
    tokens = jnp.asarray(res, prompt.dtype)
    if return_stats:
        stats = {
            "accept_rate": round(accepted / drafted, 4) if drafted else 0.0,
            "accepted": accepted, "drafted": drafted,
            "rounds": rounds, "spec_k": spec_k,
        }
        return tokens, stats
    return tokens
