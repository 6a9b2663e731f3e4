"""Inference: decode loops, paged KV cache, serving, durable KV store.

Exports are resolved lazily (PEP 562) so that jax-free submodules —
``infer.kvstore``, which the router process imports to consult the
durable prefix store — can be loaded without dragging in the jax-backed
decode stack via this package ``__init__``.
"""

_DECODE_EXPORTS = (
    "decode_step",
    "generate",
    "init_cache",
    "make_decode_fn",
    "prefill",
)

__all__ = [*_DECODE_EXPORTS, "speculative_generate"]


def __getattr__(name):
    if name in _DECODE_EXPORTS:
        from paddle_operator_tpu.infer import decode

        return getattr(decode, name)
    if name == "speculative_generate":
        from paddle_operator_tpu.infer import speculative

        return speculative.speculative_generate
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
