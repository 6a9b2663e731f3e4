"""The LLaMA/Mistral presets trace what they traced on the parent commit:
the jaxpr text of the tiny Llama ring's programs, compared with copies
taken there (``tests/fixtures/llama_*_jaxpr.txt``; regenerate with
``PIN_REGENERATE=1`` only on a tree whose Llama path is the reference).

``paged-ring`` is ISSUE 28's pin (the paged ring's decode step and
prefill insert, the text as traced).  ISSUE 30 took the other four on ITS
parent, before it folded the per-lane forwards into one
(``decode.cached_forward`` over a cache view): the contiguous ring's
step, the int8 pool's step, the paged suffix insert (a multi-token forward
through the block table) and the speculative round on the paged ring.
Those four compare the text after dead-code elimination: an equation
nothing reads is no part of the program (the parent's contiguous step
built a layer index its einsum path never used)."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.interpreters import partial_eval as pe

from paddle_operator_tpu.infer import executor as EX
from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.infer import speculative as SP
from paddle_operator_tpu.models.llama import CONFIGS

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
SLOTS, BLOCK, MAX_LEN, CHUNK, BUCKET, SUFFIX, SPEC_K = 2, 8, 32, 2, 16, 8, 2


def _params(cfg):
    from paddle_operator_tpu.infer.serve import load_serving_params

    return load_serving_params(cfg, None)[0]


def _lanes():
    """tok, temp, keys, active of an idle ring."""
    return (jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), jnp.float32),
            jnp.zeros((SLOTS, 2), jnp.uint32), jnp.ones((SLOTS,), bool))


def _ring(cfg):
    """The contiguous ring's cache, written out (its constructor is not
    where it was on the parent)."""
    shape = (cfg.n_layers, SLOTS, cfg.n_kv_heads, MAX_LEN, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((SLOTS,), jnp.int32)}


def _pool(cfg, quant="none"):
    total = SLOTS * (MAX_LEN // BLOCK) + 1
    return (PG.init_paged_cache(cfg, SLOTS, total, BLOCK, quant=quant),
            jnp.zeros((SLOTS, MAX_LEN // BLOCK), jnp.int32))


def _paged_ring(cfg):
    params = _params(cfg)
    cache, table = _pool(cfg)
    tok, temp, keys, active = _lanes()
    return [
        ("step", PG.make_paged_chunk_step(cfg, CHUNK),
         (params, cache, table, tok, temp, keys, active)),
        ("insert", PG.make_paged_prefill_insert(cfg, BUCKET, BLOCK),
         (params, cache, table, tok, temp, keys, active,
          jnp.zeros((1, BUCKET), jnp.int32), 5, 1, 0.0, 3)),
    ]


def _contiguous_step(cfg):
    return [("step", EX.make_chunk_step(cfg, CHUNK),
             (_params(cfg), _ring(cfg), *_lanes()))]


def _int8_step(cfg):
    cache, table = _pool(cfg, quant="int8")
    return [("step", PG.make_paged_chunk_step(cfg, CHUNK, quant=True),
             (_params(cfg), cache, table, *_lanes()))]


def _suffix_insert(cfg):
    cache, table = _pool(cfg)
    tok, temp, keys, _ = _lanes()
    return [("insert", PG.make_paged_suffix_insert(cfg, SUFFIX, BLOCK),
             (_params(cfg), cache, table[0], tok, temp, keys,
              jnp.zeros((1, SUFFIX), jnp.int32), 3, 8, 1, 0.0, 3))]


def _spec_round(cfg):
    dcfg = cfg.draft()
    cache, table = _pool(cfg)
    return [("round",
             SP.make_spec_round_fn(cfg, dcfg, SPEC_K, paged=True),
             (_params(cfg), _params(dcfg), cache, _ring(dcfg), table,
              *_lanes()))]


# case -> (fixture file, its programs, whether dead equations are dropped)
CASES = {
    "paged-ring": ("llama_paged_ring_jaxpr.txt", _paged_ring, False),
    "contiguous-step": ("llama_contiguous_step_jaxpr.txt",
                        _contiguous_step, True),
    "paged-int8-step": ("llama_paged_int8_step_jaxpr.txt", _int8_step, True),
    "paged-suffix-insert": ("llama_paged_suffix_insert_jaxpr.txt",
                            _suffix_insert, True),
    "paged-spec-round": ("llama_paged_spec_round_jaxpr.txt", _spec_round,
                         True),
}


def _text(programs, live_only: bool) -> str:
    out = []
    for title, fn, args in programs:
        closed = jax.make_jaxpr(fn)(*args)
        jaxpr = closed
        if live_only:
            jaxpr, _ = pe.dce_jaxpr(closed.jaxpr,
                                    [True] * len(closed.jaxpr.outvars))
        out.append(f"## {title}\n{jaxpr}")
    # object addresses in the text (custom_jvp thunks and the like) are
    # the process's, not the program's
    return re.sub(r"0x[0-9a-f]+", "0x", "\n".join(out)) + "\n"


@pytest.mark.parametrize("case", sorted(CASES))
def test_tiny_llama_ring_traces_the_parents_programs(case):
    name, programs, live_only = CASES[case]
    text = _text(programs(CONFIGS["tiny-f32"]), live_only)
    path = os.path.join(FIXTURES, name)
    if os.environ.get("PIN_REGENERATE") == "1":
        with open(path, "w") as f:
            f.write(text)
    with open(path) as f:
        want = f.read()
    assert text == want, (
        f"the tiny Llama ring's {case} traces another program than on the "
        "parent commit")
