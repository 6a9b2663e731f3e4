"""Pipeline parallelism over the ``pp`` mesh axis (GPipe schedule).

The reference is topology-unaware beyond a rank id (SURVEY.md §2: TP/PP
"absent — entirely inside PaddleNLP/Fleet"); here pipelining is a framework
primitive.  Design:

- The layer stack is already *stacked* on a leading ``layers`` axis (the
  ``nn.scan`` layout of models/llama.py), logically sharded ``layers → pp``,
  so each pp device holds a contiguous block of layers.
- :func:`pipeline_apply` runs inside ``shard_map``: microbatches stream
  through stages; activations hop stage→stage with ``ppermute``
  (point-to-point, ICI neighbors); every device executes the same program
  (SPMD) so the whole thing jits once and differentiates automatically
  (``ppermute``'s transpose is the reverse permute, giving the backward
  pipeline for free).
- **Composition with the other axes is by partial-manual shard_map**
  (``axis_names={"pp"}``): only ``pp`` is manual inside the body; dp, fsdp,
  tp, cp and ep stay *auto*, so GSPMD keeps stage params tp/fsdp-sharded
  in place (no boundary all-gather), inserts tp activation collectives
  inside each stage, and the stage body may itself open a nested manual
  region over ``cp`` (ring attention, parallel/ring_attention.py).
- Schedules: **GPipe** (:func:`pipeline_apply` — forward-only scan, the
  backward pipeline comes from autodiff) and **1F1B**
  (:func:`pipeline_1f1b_grads` — forward and backward interleaved in ONE
  scan, gradients computed manually).  GPipe's autodiff keeps residuals
  for every one of the M+P-1 forward ticks live until its backward runs;
  1F1B stashes only the stage *inputs* of the ≤ min(M, 2P-1) in-flight
  microbatches and recomputes each stage forward at backward time
  (jax.vjp per microbatch), so peak activation memory is O(P), not O(M) —
  the point of 1F1B at M >= 4·P.
- **No interleaved (virtual-stage) schedule, deliberately**: in the
  masked-SPMD scan formulation every round executes the full program and
  masks dead lanes, so a round costs the same whether its slot is live or
  a bubble.  Interleaving's benefit is exactly bubble-time reduction via
  per-device divergent chunk ordering — which SPMD masking cannot
  capture (each device would pay for all V chunks every round).  The
  schedules here optimize what the formulation CAN deliver: fewer masked
  rounds (both) and O(P) activation memory (1F1B).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _psum_act(x: jax.Array, axis_name: str) -> jax.Array:
    """psum that upcasts bf16 → f32 around the reduction: XLA:CPU folds a
    bf16 all-reduce inside a *partial-manual* region into an invalid binary
    "copy" instruction (hlo_instruction.cc CHECK crash, observed jax 0.9 /
    8-device host platform).  One upcast on the final pipeline output is
    noise next to the per-tick ppermutes, so apply it unconditionally."""
    if x.dtype == jnp.bfloat16:
        return jax.lax.psum(x.astype(jnp.float32),
                            axis_name).astype(jnp.bfloat16)
    return jax.lax.psum(x, axis_name)


def pipeline_apply(layer_fn: Callable,
                   stage_params: Any,
                   x: jax.Array,
                   extras: Any = None,
                   *, axis_name: str = "pp",
                   num_microbatches: int,
                   has_aux: bool = False,
                   compute_dtype: Any = None):
    """Run a stacked layer pipeline inside shard_map (manual over ``pp``).

    layer_fn(stage_params, h) applies THIS stage's local layer block; when
    ``has_aux`` it returns ``(h, aux_scalar)`` (e.g. the MoE load-balancing
    loss of the stage's layers) instead of ``h`` alone.

    extras: optional pytree of [M, ...] microbatched side inputs every
    stage needs for ITS current microbatch (e.g. packed-sequence
    segment_ids for attention masking).  A stage on tick t is processing
    microbatch t - stage, so the tick indexes extras accordingly and
    calls ``layer_fn(stage_params, h, extra_slice)``.

    x: [M, Bm, ...] microbatched input (every stage receives the same x;
    only stage 0 actually consumes it).  Returns the last stage's outputs
    [M, Bm, ...] **psum-replicated over pp** — every stage holds the same
    result, so the out_spec is pp-replicated and the loss computes
    identically everywhere.  With ``has_aux`` returns ``(out, aux)`` where
    aux is the per-layer aux summed over stages, averaged over the M
    microbatches, and likewise pp-replicated.
    """
    # bf16 boundary dance (see _psum_act): the caller passes x upcast to
    # f32 so the *cotangent* psum shard_map inserts for this replicated
    # input is f32 too; compute resumes in the model dtype immediately.
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    stage = jax.lax.axis_index(axis_name)
    n_stage = jax.lax.psum(1, axis_name)
    m = num_microbatches
    ticks = m + n_stage - 1

    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
    zero = jnp.zeros_like(x[0])

    def tick(carry, t):
        prev_out, aux_acc = carry              # activation arriving from left
        # stage 0 feeds microbatch t (clamped); others feed the received act
        mb_idx = jnp.clip(t, 0, m - 1)
        my_in = jnp.where(stage == 0,
                          jax.lax.dynamic_index_in_dim(x, mb_idx, 0,
                                                       keepdims=False),
                          prev_out)
        live = (t - stage >= 0) & (t - stage < m)
        args = (stage_params, my_in)
        if extras is not None:
            my_mb = jnp.clip(t - stage, 0, m - 1)   # this stage's microbatch
            args = args + (jax.tree.map(
                lambda e: jax.lax.dynamic_index_in_dim(e, my_mb, 0,
                                                       keepdims=False),
                extras),)
        if has_aux:
            out, aux = layer_fn(*args)
            aux_acc = aux_acc + jnp.where(live, aux.astype(jnp.float32), 0.0)
        else:
            out = layer_fn(*args)
        out = jnp.where(live, out, zero)
        nxt = jax.lax.ppermute(out, axis_name, perm)
        return (nxt, aux_acc), out

    (_, aux_total), outs = jax.lax.scan(
        tick, (zero, jnp.zeros((), jnp.float32)), jnp.arange(ticks))
    # The last stage emits microbatch j at tick j + (n_stage - 1); select
    # those ticks; psum the one-hot-by-stage contribution so every stage
    # returns the identical last-stage result (pp-replicated out_spec).
    idx = jnp.arange(m) + n_stage - 1
    mine = outs[idx]
    out = _psum_act(
        jnp.where(stage == n_stage - 1, mine, jnp.zeros_like(mine)),
        axis_name,
    )
    if not has_aux:
        return out
    # per-stage aux sums over that stage's live microbatches; psum over pp
    # adds the stages (≙ sum over all layers), /m averages the microbatches.
    aux_out = jax.lax.psum(aux_total, axis_name) / m
    return out, aux_out


def make_pipeline_fn(mesh: Mesh, layer_fn: Callable,
                     *, num_microbatches: int,
                     axis_name: str = "pp",
                     has_aux: bool = False,
                     with_extras: bool = False):
    """Partial-manual shard_map wrapper: ONLY ``pp`` is manual; every other
    mesh axis stays auto (GSPMD).  Consequences:

    - stage params arrive sharded ``layers → pp`` manually while their
      weight dims keep whatever fsdp/tp sharding the caller laid down —
      FSDP memory savings survive inside the pipeline body;
    - tensor-parallel collectives inside the stage block are inserted by
      XLA as usual;
    - the stage block may open a nested manual region over ``cp``
      (ring attention does, via the context mesh).
    """
    in_specs = (P(axis_name), P()) + ((P(),) if with_extras else ())
    out_specs = (P(), P()) if has_aux else P()

    def call(stage_params, x, extras=None):
        # bf16 crosses the shard_map boundary as f32: shard_map transposes
        # a replicated input into a psum of its cotangent, and a bf16 psum
        # in a partial-manual region crashes XLA:CPU (see _psum_act).  The
        # body casts straight back, so inter-stage ppermutes stay bf16.
        compute_dtype = None
        if x.dtype == jnp.bfloat16:
            compute_dtype, x = x.dtype, x.astype(jnp.float32)
        fn = jax.shard_map(
            functools.partial(pipeline_apply, layer_fn,
                              axis_name=axis_name,
                              num_microbatches=num_microbatches,
                              has_aux=has_aux,
                              compute_dtype=compute_dtype),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            axis_names=frozenset({axis_name}),
            check_vma=False,
        )
        if with_extras:
            return fn(stage_params, x, extras)
        return fn(stage_params, x)

    return call


def microbatch(x: jax.Array, num_microbatches: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...]."""
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by M={num_microbatches}")
    return x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])


def _masked_add(acc, new, live):
    """acc + new where live (per-leaf); dead-lane NaNs are selected away,
    not multiplied."""
    return jax.tree.map(
        lambda a, g: a + jnp.where(live, g, jnp.zeros_like(g)), acc, new)


def pipeline_1f1b_grads(stage_fn: Callable, head_loss_fn: Callable,
                        trunk_params: Any, head_params: Any,
                        xm: jax.Array, targets_m: jax.Array,
                        mask_m: jax.Array, seed: jax.Array,
                        aux_seed: Optional[jax.Array] = None,
                        extras: Any = None,
                        *, axis_name: str = "pp",
                        has_aux: bool = False,
                        compute_dtype: Any = None):
    """Fused 1F1B forward+backward inside shard_map (manual over ``pp``).

    Unlike :func:`pipeline_apply` (GPipe: all forwards in one scan, the
    backward pipeline generated by autodiff), this runs the PipeDream-flush
    schedule in a single scan and computes gradients manually: stage ``s``
    forwards microbatch ``f`` at round ``f + s`` and backwards microbatch
    ``b`` at round ``b + 2P-2-s``; the last stage backwards a microbatch
    the same round it forwards it.  Only the stage *inputs* of in-flight
    microbatches are stashed (ring buffer of min(M, 2P-1) slots); the
    backward recomputes the stage forward under ``jax.vjp`` — peak live
    activations O(P) instead of GPipe's O(M).

    stage_fn(trunk_params, h) -> h' (this stage's layer block), or with
    ``has_aux`` -> (h', aux_scalar) (e.g. the MoE load-balancing loss of
    the stage's layers, routed per microbatch).  The aux gradient enters
    as a CONSTANT cotangent on the stage vjp: ``aux_seed`` must equal
    d(total_loss)/d(one stage-microbatch aux unit) — for the trainer's
    ``total += weight * psum(aux)/M`` that is ``weight / M``.

    head_loss_fn(head_params, h, targets, mask) -> scalar SUM-loss (the
    caller seeds the gradient with ``seed`` = 1/denom to get mean-loss
    gradients; in SPMD every stage computes it, the last stage's value is
    the one kept).

    Returns (sum_loss, d_trunk, d_head, d_xm[, aux_mean]):
    sum_loss/d_head/d_xm/aux are psum-replicated over pp, d_trunk stays
    this stage's local shard; aux_mean is the per-layer aux summed over
    stages and averaged over microbatches (unscaled).

    Trade-offs vs GPipe (documented, deliberate): the drain adds P-1 extra
    rounds (R = M + 2P - 2 vs M + P - 1 per direction), and the loss head
    runs masked on every stage (SPMD) — at LLaMA widths the stage block
    dominates, and tp-sharding the head shrinks it like any other matmul.
    """
    if compute_dtype is not None:
        xm = xm.astype(compute_dtype)
    stage = jax.lax.axis_index(axis_name)
    n = jax.lax.psum(1, axis_name)
    m = xm.shape[0]
    k = min(m, 2 * n - 1)                 # stash ring-buffer slots
    rounds = m + 2 * n - 2
    is_last = stage == n - 1

    perm_fwd = [(i, i + 1) for i in range(n - 1)]   # activations →
    perm_bwd = [(i, i - 1) for i in range(1, n)]    # cotangents ←

    zero_act = jnp.zeros_like(xm[0])

    def round_fn(carry, r):
        (act_in, cot_in, stash, d_trunk, d_head, d_xm, loss_sum,
         aux_sum) = carry

        # ---- forward slot: microbatch f = r - stage -----------------
        f = r - stage
        fwd_live = (f >= 0) & (f < m)
        fc = jnp.clip(f, 0, m - 1)
        my_in = jnp.where(stage == 0,
                          jax.lax.dynamic_index_in_dim(xm, fc, 0,
                                                       keepdims=False),
                          act_in)
        slot_f = fc % k
        stash = jax.lax.dynamic_update_index_in_dim(
            stash,
            jnp.where(fwd_live, my_in,
                      jax.lax.dynamic_index_in_dim(stash, slot_f, 0,
                                                   keepdims=False)),
            slot_f, 0)
        def extras_at(idx):
            return jax.tree.map(
                lambda e: jax.lax.dynamic_index_in_dim(e, idx, 0,
                                                       keepdims=False),
                extras)

        fwd_args = (trunk_params, my_in)
        if extras is not None:
            fwd_args = fwd_args + (extras_at(fc),)
        if has_aux:
            out, aux_f = stage_fn(*fwd_args)
            aux_sum = aux_sum + jnp.where(fwd_live,
                                          aux_f.astype(jnp.float32), 0.0)
        else:
            out = stage_fn(*fwd_args)

        # last stage: head + loss + output cotangent for the SAME
        # microbatch (1F1B: bwd f starts the round it was forwarded)
        tgt = jax.lax.dynamic_index_in_dim(targets_m, fc, 0, keepdims=False)
        msk = jax.lax.dynamic_index_in_dim(mask_m, fc, 0, keepdims=False)
        sum_loss_f, head_vjp = jax.vjp(
            lambda hp, h: head_loss_fn(hp, h, tgt, msk), head_params, out)
        d_head_f, d_out_f = head_vjp(seed)
        take_loss = is_last & fwd_live
        loss_sum = loss_sum + jnp.where(take_loss,
                                        sum_loss_f.astype(jnp.float32), 0.0)
        d_head = _masked_add(d_head, d_head_f, take_loss)

        # ---- backward slot: microbatch b = r - (2n - 2 - stage) -----
        b = r - (2 * n - 2 - stage)
        bwd_live = (b >= 0) & (b < m)
        bc = jnp.clip(b, 0, m - 1)
        saved = jax.lax.dynamic_index_in_dim(stash, bc % k, 0,
                                             keepdims=False)
        cot = jnp.where(is_last, d_out_f.astype(out.dtype), cot_in)
        if extras is not None:
            # close over the saved microbatch's extras: jax.vjp then
            # differentiates wrt (params, activation) only
            ex_b = extras_at(bc)
            bwd_fn = lambda p, h: stage_fn(p, h, ex_b)  # noqa: E731
        else:
            bwd_fn = stage_fn
        if has_aux:
            # aux gradient: constant seed (dead slots masked via
            # _masked_add below, like the activation path)
            (_, aux_b), stage_vjp = jax.vjp(bwd_fn, trunk_params, saved)
            d_trunk_b, d_in_b = stage_vjp(
                (cot, jnp.asarray(aux_seed, aux_b.dtype)))
        else:
            _, stage_vjp = jax.vjp(bwd_fn, trunk_params, saved)
            d_trunk_b, d_in_b = stage_vjp(cot)
        d_trunk = _masked_add(d_trunk, d_trunk_b, bwd_live)
        d_in_b = jnp.where(bwd_live, d_in_b, jnp.zeros_like(d_in_b))
        d_xm = jax.lax.dynamic_update_index_in_dim(
            d_xm,
            jnp.where((stage == 0) & bwd_live, d_in_b,
                      jax.lax.dynamic_index_in_dim(d_xm, bc, 0,
                                                   keepdims=False)),
            bc, 0)

        # ---- neighbor communication for the next round --------------
        act_next = jax.lax.ppermute(
            jnp.where(fwd_live, out, zero_act), axis_name, perm_fwd)
        cot_next = jax.lax.ppermute(d_in_b, axis_name, perm_bwd)
        return (act_next, cot_next, stash, d_trunk, d_head, d_xm,
                loss_sum, aux_sum), None

    init = (
        zero_act,                                     # act_in
        zero_act,                                     # cot_in
        jnp.zeros((k,) + xm.shape[1:], xm.dtype),     # stash
        jax.tree.map(jnp.zeros_like, trunk_params),   # d_trunk
        jax.tree.map(jnp.zeros_like, head_params),    # d_head
        jnp.zeros_like(xm),                           # d_xm
        jnp.zeros((), jnp.float32),                   # loss_sum
        jnp.zeros((), jnp.float32),                   # aux_sum
    )
    (_, _, _, d_trunk, d_head, d_xm, loss_sum, aux_sum), _ = jax.lax.scan(
        round_fn, init, jnp.arange(rounds))

    # replicate the single-stage-owned results over pp (one-hot psums)
    loss_out = jax.lax.psum(loss_sum, axis_name)
    d_head_out = jax.tree.map(lambda g: _psum_act(g, axis_name), d_head)
    d_xm_out = _psum_act(d_xm, axis_name)
    if not has_aux:
        return loss_out, d_trunk, d_head_out, d_xm_out
    aux_out = jax.lax.psum(aux_sum, axis_name) / m
    return loss_out, d_trunk, d_head_out, d_xm_out, aux_out


def make_pipeline_1f1b_fn(mesh: Mesh, stage_fn: Callable,
                          head_loss_fn: Callable,
                          *, axis_name: str = "pp",
                          has_aux: bool = False,
                          with_extras: bool = False):
    """Partial-manual shard_map wrapper for :func:`pipeline_1f1b_grads`
    (same composition story as :func:`make_pipeline_fn`: only ``pp`` is
    manual; dp/fsdp/tp/cp stay auto under GSPMD)."""
    in_specs = (P(axis_name), P(), P(), P(), P(), P(), P()) \
        + ((P(),) if with_extras else ())
    out_specs = ((P(), P(axis_name), P(), P(), P()) if has_aux
                 else (P(), P(axis_name), P(), P()))

    def call(trunk_params, head_params, xm, targets_m, mask_m, seed,
             aux_seed=0.0, extras=None):
        compute_dtype = None
        if xm.dtype == jnp.bfloat16:   # boundary dance, see make_pipeline_fn
            compute_dtype, xm = xm.dtype, xm.astype(jnp.float32)
        fn = jax.shard_map(
            functools.partial(pipeline_1f1b_grads, stage_fn, head_loss_fn,
                              axis_name=axis_name,
                              has_aux=has_aux,
                              compute_dtype=compute_dtype),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            axis_names=frozenset({axis_name}),
            check_vma=False,
        )
        args = (trunk_params, head_params, xm, targets_m, mask_m, seed,
                jnp.asarray(aux_seed, jnp.float32))
        if with_extras:
            args = args + (extras,)
        return fn(*args)

    return call
