"""LLaMA-family decoder — the flagship model (BASELINE.md config 4:
LLaMA-7B/13B hybrid-parallel pretrain; the reference runs this as a
PaddleNLP workload inside containers, out-of-repo).

TPU-first design decisions:

- **bfloat16 compute** with f32 parameters/optimizer (casts at use),
  f32 softmax and f32 RMSNorm accumulation — the MXU-native recipe.
- **`nn.scan` over layers** (`scan_layers=True`): one compiled layer body,
  layer-stacked params with a leading `layers` axis — fast compiles at
  depth, and the natural layout for pipeline parallelism (the `layers`
  logical axis maps to the `pp` mesh axis).
- **`jax.checkpoint`** (remat) around each layer (`remat=True`) trading
  FLOPs for HBM.
- **Attention via ops.attention** — pallas flash kernel on TPU.
- No data-dependent Python control flow anywhere under jit; static shapes.

Sharding is by parameter path (parallel/sharding.py): see
:data:`PARTITION_PATTERNS`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from paddle_operator_tpu.ops.attention import attention


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # compute dtype
    param_dtype: Any = jnp.float32   # storage dtype
    scan_layers: bool = True
    remat: bool = True
    # "full" (recompute everything — fastest measured on v5e),
    # "save_attn" (keep flash-attention outputs), "dots" (save matmul outs)
    remat_policy: str = "full"
    # context-parallel attention when cp > 1: "ring" (K/V rotation,
    # parallel/ring_attention.py) or "ulysses" (head/seq all-to-all,
    # parallel/ulysses.py — needs n_heads and n_kv_heads divisible by cp;
    # falls back to ring otherwise)
    cp_impl: str = "ring"
    # Mixture-of-Experts: n_experts > 0 replaces every layer's SwiGLU MLP
    # with a capacity-factor MoE (models/moe.py) — Switch-style top-1 or
    # GShard-style top-2 via moe_top_k — expert-sharded over the `ep`
    # mesh axis.  The model then returns (logits, aux_loss) where
    # aux_loss is the load-balancing loss already scaled by moe_aux_weight.
    n_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1
    moe_aux_weight: float = 0.01
    # Single-query attention implementation for the DECODE path
    # (infer/decode.py, infer/paged.py; training is untouched):
    # "auto" (pallas on TPU, einsum elsewhere — the default), "xla"
    # (dense einsum over the full allocated cache), "pallas"
    # (ops/decode_attention.py — reads only the FILLED prefix; measured
    # >= the einsum at EVERY fill level on v5e, r5), "pallas-interpret"
    # (same kernel in interpreter mode — CPU tests).
    decode_attn: str = "auto"

    def resolved_decode_attn(self) -> str:
        """Resolve "auto" at trace time: the pallas filled-prefix kernel
        on TPU, the XLA einsum everywhere else (interpret-mode pallas is
        orders slower on CPU; the einsum is the CPU-correct path).
        Configs whose head_dim is not lane-aligned (a multiple of 128 —
        debug/tiny shapes) fall back to the einsum: Mosaic cannot tile
        the kernel's [*, head_dim] slices below one 128-lane register."""
        if self.decode_attn == "auto":
            import jax

            if self.head_dim % 128:
                return "xla"
            return "pallas" if jax.default_backend() == "tpu" else "xla"
        return self.decode_attn

    def decode_tp_compatible(self, tp: int) -> bool:
        """Whether the pallas decode kernel can run tensor-parallel over
        ``tp`` shards: the cache's kv-head axis must split evenly so
        each shard contracts WHOLE GQA groups (Hq = n_rep * Hkv then
        splits with it).  Configs that fail this (or whose head_dim the
        kernel rejects) serve sharded through the GSPMD einsum path
        instead — same math, no filled-prefix block skipping."""
        return tp <= 1 or (self.n_kv_heads % tp == 0
                           and self.n_heads % tp == 0)

    def draft(self, **overrides) -> "LlamaConfig":
        """The companion draft-model config for speculative decoding
        (infer/speculative.py): shallow (depth/4) and narrow (heads/2 at
        the SAME head_dim, so the decode kernel's lane alignment is
        inherited), sharing everything that couples draft to target —
        tokenizer (vocab_size), RoPE table shape/theta, dtypes, decode
        attention impl.  The draft is a separate param tree with its own
        KV cache; only the token ids cross between the models, which is
        why vocab_size is the one compatibility invariant
        (speculative.check_draft_compat enforces it).  ``overrides``
        replace any field of the derived config (a hand-tuned draft
        preset can be passed straight through)."""
        n_heads = max(1, self.n_heads // 2)
        n_kv = max(1, self.n_kv_heads // 2)
        while n_heads % n_kv:       # GQA grouping must survive the halving
            n_kv -= 1
        kw = dict(
            n_layers=max(1, self.n_layers // 4),
            n_heads=n_heads,
            n_kv_heads=n_kv,
            dim=self.head_dim * n_heads,
            ffn_dim=max(self.head_dim, self.ffn_dim // 2),
        )
        kw.update(overrides)
        return dataclasses.replace(self, **kw)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6N_active +
        attention).  For MoE, N_active counts ONE expert per token (top-1
        routing) — using total params would inflate MFU by ~n_experts on
        the FFN share."""
        n_params = self.active_params()
        attn = 12 * self.n_layers * self.dim * self.max_seq_len
        return 6 * n_params + attn

    def active_params(self) -> int:
        """Params touched per token: equals num_params() for dense configs;
        for MoE the per-layer FFN counts router + the moe_top_k experts
        each token is routed to."""
        if self.n_experts <= 0:
            return self.num_params()
        d, f = self.dim, self.ffn_dim
        all_experts = self.n_experts * 2 * d * f
        active = self.moe_top_k * 2 * d * f
        return self.num_params() - self.n_layers * (all_experts - active)

    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        if self.n_experts > 0:
            # router [D, E] + per-expert w1 [D, F], w2 [F, D] (models/moe.py)
            ffn = d * self.n_experts + self.n_experts * 2 * d * f
        else:
            ffn = 3 * d * f                            # w1, w2, w3 (SwiGLU)
        per_layer = (
            d * self.n_heads * self.head_dim           # wq
            + 2 * d * self.n_kv_heads * self.head_dim  # wk, wv
            + self.n_heads * self.head_dim * d         # wo
            + ffn
            + 2 * d                                    # norms
        )
        return v * d + self.n_layers * per_layer + d + d * v


# Presets.  tiny = test/dryrun config; 7b/13b match the public LLaMA shapes.
CONFIGS = {
    "tiny": LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, max_seq_len=128),
    # tiny in float32: what the real serving CLI can run on the CPU
    # backend — XLA:CPU (jaxlib 0.9.0) refuses the bf16 x bf16 -> f32
    # probs @ V dot inside the decode layer scan
    "tiny-f32": LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                            dtype=jnp.float32),
    "tiny-moe": LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                            n_experts=4),
    "tiny-moe2": LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                             n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                             n_experts=4, moe_top_k=2),
    "1b": LlamaConfig(vocab_size=32000, dim=2048, n_layers=16, n_heads=16,
                      n_kv_heads=16, ffn_dim=5504),
    "7b": LlamaConfig(),
    "7b-moe": LlamaConfig(n_experts=8),   # Switch-style 8-expert variant
    "7b-moe2": LlamaConfig(n_experts=8, moe_top_k=2),  # GShard-style top-2
    "13b": LlamaConfig(dim=5120, n_layers=40, n_heads=40, n_kv_heads=40,
                       ffn_dim=13824),
}

# Presets of other architectures live beside LLaMA's, where MODEL_PRESET is
# looked up; the preset's type selects the code that serves it.
from paddle_operator_tpu.models.afmoe import CONFIGS as _AFMOE_CONFIGS  # noqa: E402
from paddle_operator_tpu.models.glm_moe_lite import CONFIGS as _GLM_LITE_CONFIGS  # noqa: E402

CONFIGS.update(_AFMOE_CONFIGS)
CONFIGS.update(_GLM_LITE_CONFIGS)


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


class RMSNorm(nn.Module):
    eps: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    @jax.named_scope("norm")
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype
        )
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps
        )
        return (norm * scale.astype(jnp.float32)).astype(self.dtype)


def rope_frequencies(head_dim: int, max_len: int,
                     theta: float) -> Tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)          # [S, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               offset: int = 0) -> jax.Array:
    """[B, S, H, D] rotary embedding, half-split ("rotate-half"/NeoX)
    formulation: the head dim is split into two contiguous halves rather
    than interleaved even/odd pairs.  Self-consistent for from-scratch
    training; importing official LLaMA checkpoints (which use interleaved
    pairs) requires a one-time permutation of wq/wk columns."""
    seq = x.shape[1]
    cos = jax.lax.dynamic_slice_in_dim(cos, offset, seq)[None, :, None, :]
    sin = jax.lax.dynamic_slice_in_dim(sin, offset, seq)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig
    # The job mesh the activations are sharded over; None => one device.
    # With cp > 1 attention runs as ring attention over the cp axis —
    # sequence sharded, K/V rotating on ICI (parallel/ring_attention.py);
    # otherwise the flash kernel enters the mesh through shard_map over
    # the batch and head axes (ops/attention.py) — a Mosaic kernel cannot
    # be partitioned by GSPMD, so a multi-chip TPU job MUST pass it.
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jax.Array, cos: jax.Array, sin: jax.Array,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        dense = lambda name, feats: nn.DenseGeneral(  # noqa: E731
            feats, use_bias=False, name=name, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02),
        )
        # the scopes name the same pieces as the serving block's
        # (infer/decode.py); the module's own name gives the outer "attn"
        b, s, _ = x.shape
        with jax.named_scope("attn.qkv"):
            q = dense("wq", cfg.n_heads * cfg.head_dim)(x)
            k = dense("wk", cfg.n_kv_heads * cfg.head_dim)(x)
            v = dense("wv", cfg.n_kv_heads * cfg.head_dim)(x)
            q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
            k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        with jax.named_scope("attn.rope"):
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        cp = 1
        if self.mesh is not None:
            cp = dict(zip(self.mesh.axis_names,
                          self.mesh.devices.shape)).get("cp", 1)
        if cp > 1:
            if cfg.cp_impl not in ("ring", "ulysses"):
                raise ValueError(f"unknown cp_impl {cfg.cp_impl!r} "
                                 "(expected 'ring' or 'ulysses')")
            if (cfg.cp_impl == "ulysses" and cfg.n_heads % cp == 0
                    and cfg.n_kv_heads % cp == 0):
                from paddle_operator_tpu.parallel.ulysses import (
                    make_ulysses_attention_fn,
                )

                out = make_ulysses_attention_fn(
                    self.mesh, causal=True)(q, k, v, segment_ids)
            else:
                from paddle_operator_tpu.parallel.ring_attention import (
                    make_ring_attention_fn,
                )

                out = make_ring_attention_fn(
                    self.mesh, causal=True)(q, k, v, segment_ids)
        else:
            out = attention(q, k, v, causal=True, segment_ids=segment_ids,
                            mesh=self.mesh)
        # Tag for remat_policy="save_attn": under that policy the flash
        # kernel is not re-run in the backward pass.  Under the default
        # full-remat policy the tag is a no-op and attention recomputes —
        # measured FASTER on v5e (HBM-bound; see bench sweep).
        from jax.ad_checkpoint import checkpoint_name

        out = checkpoint_name(out, "attn_out")
        with jax.named_scope("attn.out"):
            out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
            return dense("wo", cfg.dim)(out)


class MLP(nn.Module):
    """SwiGLU feed-forward."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = lambda name, feats: nn.DenseGeneral(  # noqa: E731
            feats, use_bias=False, name=name, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02),
        )
        gate = dense("w1", cfg.ffn_dim)(x)
        up = dense("w3", cfg.ffn_dim)(x)
        return dense("w2", cfg.dim)(nn.silu(gate) * up)


class DecoderLayer(nn.Module):
    cfg: LlamaConfig
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jax.Array, cos: jax.Array, sin: jax.Array,
                 segment_ids: Optional[jax.Array] = None):
        cfg = self.cfg
        h = x + Attention(cfg, self.mesh, name="attn")(
            RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name="attn_norm")(x), cos, sin, segment_ids)
        normed = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                         name="mlp_norm")(h)
        with jax.named_scope("ffn"):
            if cfg.n_experts > 0:
                from paddle_operator_tpu.models.moe import MoEConfig, MoELayer

                ffn_out, aux = MoELayer(MoEConfig(
                    dim=cfg.dim, ffn_dim=cfg.ffn_dim,
                    n_experts=cfg.n_experts,
                    capacity_factor=cfg.moe_capacity_factor,
                    top_k=cfg.moe_top_k,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                ), name="moe")(normed)
            else:
                ffn_out, aux = MLP(cfg, name="mlp")(normed), None
            out = h + ffn_out
        # (carry, scan-output) pair — the scan axis carries the hidden
        # state; the per-layer MoE aux loss rides the scan output (stacked
        # [n_layers] by nn.scan, summed in Llama.__call__).
        return out, aux


def _layer_cls(cfg: LlamaConfig):
    """DecoderLayer, optionally remat-wrapped per cfg (shared by Llama and
    LayerStack so the pipeline path runs byte-identical layer math)."""
    layer_cls = DecoderLayer
    if cfg.remat:
        policy = {
            "full": jax.checkpoint_policies.nothing_saveable,
            "save_attn": jax.checkpoint_policies.save_only_these_names(
                "attn_out"),
            "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        }[cfg.remat_policy]
        layer_cls = nn.remat(layer_cls, policy=policy)
    return layer_cls


def _scanned(layer_cls, length: int):
    """nn.scan over the layer axis: one traced body, params stacked on a
    leading `layers` axis (the pp-shardable layout)."""
    return nn.scan(
        layer_cls,
        variable_axes={"params": 0},
        split_rngs={"params": True},
        in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
        length=length,
        metadata_params={nn.PARTITION_NAME: "layers"},
    )


class LayerStack(nn.Module):
    """The decoder trunk alone: `n_layers` DecoderLayers under the same
    scan/remat machinery (and the same `layers/...` param paths) as
    :class:`Llama`.  The pipeline-parallel train step
    (train/trainer.py make_pp_train_step) applies this per stage inside
    shard_map with the stage's local slice of the layer-stacked params
    (`layers` axis sharded over the `pp` mesh axis).  `mesh` flows to the
    layers' Attention exactly as in :class:`Llama` (enables ring attention
    when cp > 1 — nested manual region inside the pipeline body).

    Returns ``(x, aux)``: aux is the summed per-layer MoE load-balancing
    loss (un-scaled), or ``None`` for dense configs."""

    cfg: LlamaConfig
    n_layers: int
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jax.Array, cos: jax.Array, sin: jax.Array,
                 segment_ids: Optional[jax.Array] = None):
        Scan = _scanned(_layer_cls(self.cfg), self.n_layers)
        x, aux = Scan(self.cfg, self.mesh, name="layers")(x, cos, sin,
                                                          segment_ids)
        return x, (aux.sum() if aux is not None else None)


def embed_module(cfg: LlamaConfig, name: Optional[str] = None) -> nn.Embed:
    """Token embedding — single definition shared by Llama.__call__ (as
    submodule "tok_embed") and the pipeline train step (applied standalone
    on the `tok_embed` param subtree), so names/dtypes cannot drift."""
    return nn.Embed(
        cfg.vocab_size, cfg.dim, name=name,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        embedding_init=nn.initializers.normal(0.02),
    )


def final_norm_module(cfg: LlamaConfig, name: Optional[str] = None) -> "RMSNorm":
    return RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype, name=name)


def lm_head_module(cfg: LlamaConfig, name: Optional[str] = None) -> nn.DenseGeneral:
    return nn.DenseGeneral(
        cfg.vocab_size, use_bias=False, name=name,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        kernel_init=nn.initializers.normal(0.02),
    )


class Llama(nn.Module):
    cfg: LlamaConfig
    mesh: Optional[Any] = None   # the job mesh (see Attention.mesh)

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 segment_ids: Optional[jax.Array] = None):
        """[B, S] int32 tokens -> [B, S, vocab] logits, or
        (logits, aux_loss) when the config is MoE (n_experts > 0): aux_loss
        is the summed per-layer load-balancing loss scaled by
        cfg.moe_aux_weight, to be ADDED to the task loss by the trainer."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = embed_module(cfg, name="tok_embed")(tokens)
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)

        layer_cls = _layer_cls(cfg)

        if cfg.scan_layers:
            x, aux = _scanned(layer_cls, cfg.n_layers)(
                cfg, self.mesh, name="layers")(x, cos, sin, segment_ids)
            aux_sum = aux.sum() if aux is not None else None
        else:
            aux_sum = None
            for i in range(cfg.n_layers):
                x, aux = layer_cls(cfg, self.mesh, name=f"layer_{i}")(
                    x, cos, sin, segment_ids)
                if aux is not None:
                    aux_sum = aux if aux_sum is None else aux_sum + aux

        x = final_norm_module(cfg, name="final_norm")(x)
        logits = lm_head_module(cfg, name="lm_head")(x)
        logits = logits.astype(jnp.float32)
        if cfg.n_experts > 0:
            return logits, aux_sum * cfg.moe_aux_weight
        return logits


# nn.scan stacks layer params with a leading dim; DecoderLayer body needs
# the non-scanned specs below prefixed with the "layers" logical axis.
_LAYER_PATTERNS = [
    (r"attn/wq/kernel", ("embed", "heads")),
    (r"attn/wk/kernel", ("embed", "heads")),
    (r"attn/wv/kernel", ("embed", "heads")),
    (r"attn/wo/kernel", ("heads", "embed")),
    (r"mlp/w1/kernel", ("embed", "mlp")),
    (r"mlp/w3/kernel", ("embed", "mlp")),
    (r"mlp/w2/kernel", ("mlp", "embed")),
    (r"attn_norm/scale", ("embed",)),
    (r"mlp_norm/scale", ("embed",)),
]


def partition_patterns(cfg: LlamaConfig):
    """(path-regex, logical spec) table for parallel.sharding.tree_shardings."""
    pats = [
        (r"tok_embed/embedding", ("vocab", "embed")),
        (r"final_norm/scale", ("embed",)),
        (r"lm_head/kernel", ("embed", "vocab")),
    ]
    layer_pats = list(_LAYER_PATTERNS)
    if cfg.n_experts > 0:
        # MoE params under the "moe" submodule: expert axis → ep mesh axis,
        # so GSPMD lowers dispatch/combine einsums to all-to-alls.  Derived
        # from moe.py's canonical table so the specs cannot drift.
        from paddle_operator_tpu.models.moe import moe_partition_patterns

        layer_pats += moe_partition_patterns(prefix="moe/")
    for pat, spec in layer_pats:
        if cfg.scan_layers:
            pats.append((pat, ("layers",) + spec))
        else:
            pats.append((pat, spec))
    return pats


def make_model(preset: str = "tiny", mesh=None, **overrides) -> Tuple[Llama, LlamaConfig]:
    """`mesh` is the job mesh (see ``Attention.mesh``): context
    parallelism when its cp axis is > 1, and the flash kernel's way onto
    a multi-chip TPU mesh."""
    cfg = dataclasses.replace(CONFIGS[preset], **overrides)
    if not isinstance(cfg, LlamaConfig):
        raise ValueError(
            f"preset {preset!r} ({type(cfg).__name__}) is served only "
            "(infer/afmoe_serve.py, the expert stack): the trainer has no "
            "dropless expert layer with a backward pass and no "
            "routing-bias update")
    return Llama(cfg, mesh), cfg
