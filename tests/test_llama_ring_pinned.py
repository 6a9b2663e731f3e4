"""The LLaMA/Mistral presets trace what they traced before the afmoe
plumbing went in (ISSUE 28): the jaxpr text of the tiny Llama paged ring's
decode step and prefill insert, compared with a copy taken on the parent
commit (``tests/fixtures/llama_paged_ring_jaxpr.txt``; regenerate with
``PIN_REGENERATE=1`` only on a tree whose Llama path is the reference)."""

import os
import re

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.models.llama import CONFIGS

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "llama_paged_ring_jaxpr.txt")
SLOTS, BLOCK, MAX_LEN, CHUNK, BUCKET = 2, 8, 32, 2, 16


def _texts() -> str:
    from paddle_operator_tpu.infer.serve import load_serving_params

    cfg = CONFIGS["tiny-f32"]
    params, _ = load_serving_params(cfg, None)
    total = SLOTS * (MAX_LEN // BLOCK) + 1
    cache = PG.init_paged_cache(cfg, SLOTS, total, BLOCK)
    table = jnp.zeros((SLOTS, MAX_LEN // BLOCK), jnp.int32)
    tok = jnp.zeros((SLOTS,), jnp.int32)
    temp = jnp.zeros((SLOTS,), jnp.float32)
    keys = jnp.zeros((SLOTS, 2), jnp.uint32)
    active = jnp.ones((SLOTS,), bool)
    step = PG.make_paged_chunk_step(cfg, CHUNK)
    insert = PG.make_paged_prefill_insert(cfg, BUCKET, BLOCK)
    out = [
        "## step\n" + str(jax.make_jaxpr(step)(
            params, cache, table, tok, temp, keys, active)),
        "## insert\n" + str(jax.make_jaxpr(insert)(
            params, cache, table[0], tok, temp, keys,
            jnp.zeros((1, BUCKET), jnp.int32), 5, 1, 0.0, 3)),
    ]
    # object addresses in the text (custom_jvp thunks and the like) are
    # the process's, not the program's
    return re.sub(r"0x[0-9a-f]+", "0x", "\n".join(out)) + "\n"


def test_tiny_llama_paged_ring_traces_the_parents_programs():
    text = _texts()
    if os.environ.get("PIN_REGENERATE") == "1":
        with open(FIXTURE, "w") as f:
            f.write(text)
    with open(FIXTURE) as f:
        want = f.read()
    assert text == want, (
        "the tiny Llama paged ring's step or insert traces another "
        "program than on the parent commit")
