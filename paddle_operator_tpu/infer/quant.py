"""Weight-only int8 (int4 stretch) quantization for decoding.

Decode is HBM-bandwidth-bound: every step streams every weight.  Storing
the matmul kernels as int8 with per-output-channel f32 scales halves the
bytes streamed (activations and accumulation stay in the compute dtype —
"weight-only" quantization, the standard serving recipe).  Norm scales
and the embedding table stay full precision (tiny / gather-shaped).

``quantize_params`` maps the trained param tree to the same tree shape
with each targeted ``kernel`` leaf replaced by ``{"q": int8, "s": f32}``;
infer/decode.py's matmul helper consumes either form, so all decode entry
points (prefill / decode_step / generate / serve) work unchanged on
quantized params.  Accuracy is config-dependent; tests bound the logit
error on the tiny model.

Because the codes+scales live INSIDE the params pytree — which is already
a trailing operand of every compiled dispatch (step fns are traced over
``params``, the same way LoRA deltas ride ``*lora_args``) — a serving
process without quantization traces programs byte-identical to one built
before this module existed.  There is no quant flag threaded through the
executors: the leaf type IS the dispatch.

**Quantize-at-load, not a new checkpoint format.**  Serving quantizes the
bf16/f32 checkpoint after restore (``serve.py`` / ``prefill_serve.py``
under ``SERVE_WEIGHT_QUANT`` / ``SERVE_DRAFT_QUANT``).  Rounding is
round-half-even (``jnp.round`` is banker's rounding), which makes
quantize→dequant→quantize bit-stable: re-quantizing the dequantized tree
reproduces the codes and scales exactly, so a process restarted from a
dequantized snapshot serves identical logits.

**Skip list.**  The serving path (``skip=SERVING_SKIP``) keeps
embeddings (gather-shaped — int8 buys nothing on a one-row gather),
``lm_head`` (the logit matmul sets the sampling distribution; int8 error
there moves tokens directly instead of being absorbed by later layers),
and norm scales (tiny) in bf16.  The legacy no-kwargs call keeps the
original target set (lm_head included) for bench comparability.

**What bounds the speedup** (measured 2026-07 on one v5e chip, records
since removed — git history; not re-measured in this round.
dim-2048/L8/ffn-8192 model in bf16 serving dtype, greedy decode,
steady-state ms/token via bench.py's two-length differencing, in which
prefill and per-call overhead cancel):

    batch  8: int8 ~1.4-1.5x over bf16   batch 32: ~1.1x   batch 64: ~1.1x

not the ~2x the byte count suggests, because the int8→bf16 dequant feeding
the MXU caps the weight stream at ~220 GB/s of int8 bytes while the plain
bf16 stream runs ~340-400 GB/s (isolated-dot measurements) — past batch 8
the dot is dequant/MXU-bound, not HBM-bound.  Alternatives measured and
rejected on the same hardware:

- a pallas dequant-in-register kernel (int8 tiles HBM→VMEM, convert on
  the way into the MXU): ties bf16 on an isolated [8,2048]x[2048,8192]
  dot (83 vs 84 us) but LOSES to XLA's fused astype-then-dot inside the
  full decode step (2463 vs 2919 tok/s at batch 8);
- a native int8xint8 ``dot_general`` with dynamic activation quant
  (w8a8): 2x slower than bf16 (159 vs 75 us on the isolated dot) — the
  MXU path here gains nothing from int8 operands;
- scale folded as f32 after an f32 dot: within noise of astype-then-dot.

At batch 64 the dot is MXU-compute-bound and int8 buys nothing.  int4
(``mode="int4"``, absmax/7 scales, ``jnp.int4`` codes) halves the code
bytes again but the 4-bit grid is coarse enough that it is draft-model
territory — spec verify absorbs draft drift as accept-rate, so the
quality floor there is latency, not correctness.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# matmul kernels worth quantizing: attention + (dense or MoE) FFN + head
_TARGETS = re.compile(
    r"(attn/(wq|wk|wv|wo)/kernel"
    r"|mlp/w[123]/kernel"
    r"|moe/w[12]"
    r"|lm_head/kernel)$")

# Serving skip list (ISSUE 16): leaves that stay bf16 when quantizing for
# the serving fleet.  Embeddings are gather-shaped (one row read per
# token — quantizing saves resident HBM, not streamed bytes, and decode
# streams), lm_head errors land directly on the sampling distribution,
# norms are tiny.  Matched as substrings of the '/'-joined leaf path.
SERVING_SKIP = ("embed", "lm_head", "norm")

#: Recognized quantization modes → (max code magnitude, code dtype).
_MODES = {
    "int8": (127.0, jnp.int8),
    "int4": (7.0, jnp.int4),
}


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def quantize_leaf(w: jax.Array, mode: str = "int8") -> Dict[str, jax.Array]:
    """[..., in, out] kernel -> integer codes with per-out-channel scales
    (absmax over the contraction dim).  ``jnp.round`` is round-half-even,
    so re-quantizing the dequantized leaf is bit-stable.  Scale/round
    math runs in f32 even for bf16 checkpoints (no-op for f32 trees)."""
    qmax, qdtype = _MODES[mode]
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / qmax
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(w / s), -qmax, qmax).astype(qdtype)
    return {"q": q, "s": s.astype(jnp.float32)}


def dequantize_leaf(leaf, dtype) -> jax.Array:
    if isinstance(leaf, dict) and "q" in leaf:
        return (leaf["q"].astype(dtype) * leaf["s"].astype(dtype))
    return leaf.astype(dtype)


def serving_params(params: Dict[str, Any], dtype) -> Dict[str, Any]:
    """Cast float leaves to the serving/compute dtype (normally bf16).

    Training keeps f32 master params (train/trainer.py); serving them
    directly would stream 4 bytes/param from HBM in the decode hot loop —
    decode._mm converts at use, so storage dtype IS the streamed dtype.
    Every serving entry point (bench, infer/serve.py) should cast once
    up front.  Integer leaves (e.g. already-quantized int8) pass through."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)


def quantize_params(params: Dict[str, Any],
                    cfg: Any = None,
                    *,
                    mode: str = "int8",
                    skip: Optional[Iterable[str]] = None) -> Dict[str, Any]:
    """Return the params tree with the decode-relevant matmul kernels
    replaced by codes+scale pairs (everything else untouched).

    ``quantize_params(params)`` is the legacy form: int8, original target
    set (lm_head included).  The serving path passes ``cfg`` (reserved
    for per-config target tuning; unused today beyond documentation) and
    ``skip=SERVING_SKIP`` so embeddings/lm_head/norms stay bf16 — no new
    checkpoint format, quantization happens after restore.  ``mode`` is
    ``"int8"`` or ``"int4"``.  Scale leaves are ``{"s"}`` f32 planes with
    the contraction dim collapsed to 1; ``shard_params_for_serving``
    replicates them under TP (replicate_indivisible)."""
    del cfg  # target set is path-driven; cfg reserved for future tuning
    if mode not in _MODES:
        raise ValueError(
            f"unknown weight quant mode {mode!r} (want one of "
            f"{sorted(_MODES)})")
    skip_pats = tuple(skip) if skip is not None else ()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = params
    quantized = {}
    for path, leaf in flat:
        p = _path_str(path)
        if not _TARGETS.search(p):
            continue
        if any(re.search(pat, p) for pat in skip_pats):
            continue
        quantized[p] = quantize_leaf(leaf, mode)

    def rebuild(tree, prefix=""):
        if not isinstance(tree, dict):
            return tree
        return {k: (quantized[f"{prefix}{k}"]
                    if f"{prefix}{k}" in quantized
                    else rebuild(v, f"{prefix}{k}/"))
                for k, v in tree.items()}

    return rebuild(out)


def weight_quant_mode(params: Dict[str, Any]) -> str:
    """Detect the quantization mode of a params tree from its leaves:
    "int8" / "int4" when any quantized code leaf is present, else "none".
    Detection (not a threaded flag) keeps serving_status truthful about
    the tree actually dispatched."""
    mode = "none"
    for leaf in jax.tree_util.tree_leaves(params):
        dt = getattr(leaf, "dtype", None)
        if dt == jnp.int4:
            return "int4"
        if dt == jnp.int8:
            mode = "int8"
    return mode


def param_bytes(params: Dict[str, Any]) -> int:
    """Total HBM bytes of a params tree — pure shape arithmetic (no
    device sync), the weight-side sibling of executor.pool_bytes().
    int4 codes count 1 byte each (jax stores sub-byte dtypes unpacked
    on most backends; we report the conservative resident figure)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        shape = getattr(leaf, "shape", None)
        dt = getattr(leaf, "dtype", None)
        if shape is None or dt is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * max(1, dt.itemsize)
    return total
