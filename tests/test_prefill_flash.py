"""Whole-prompt prefill through the flash kernel (ISSUE 29).

A prompt that enters a cache its caller has just made attends over its own
q, k, v through ``ops.attention.attention`` wherever the kernel runs; every
other multi-token forward, and every whole-prompt prefill the kernel cannot
take, keeps the einsum over the cache.  Here the kernel runs in interpret
mode at the smallest shapes that take it (head size 128; width 1024, the
measured threshold, with the forward's blocks, and 1536 with the kernel's
defaults): the two paths must agree on logits, on the K/V they leave behind
and on the first token, through the real insert programs.
"""

import functools
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.infer import decode as D
from paddle_operator_tpu.infer import paged as PG
from paddle_operator_tpu.infer.executor import (
    init_ring_cache,
    make_prefill_insert,
)
from paddle_operator_tpu.infer.scheduler import ContinuousBatcher
from paddle_operator_tpu.models.llama import make_model
from paddle_operator_tpu.ops import attention as A
from paddle_operator_tpu.ops import pallas_attention as PA

BS = 64             # pool block
SLOTS = 2
TOL = dict(rtol=2e-4, atol=2e-4)       # float32 operands, two orders of sum


@pytest.fixture
def kernel_on(monkeypatch):
    """What a TPU process sees, on the CPU: the dispatcher's backend
    question answered "tpu", and the kernel it then calls interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        PA, "flash_attention",
        functools.partial(_interpreted, PA.flash_attention))


def _interpreted(flash, *args, **kw):
    return flash(*args, **{**kw, "interpret": True})


@functools.lru_cache(maxsize=None)
def _model(n_rep: int):
    """Two layers at head size 128 (the least the kernel tiles), float32
    (the CPU backend runs no bf16 decode scan), the decode kernel off."""
    model, cfg = make_model(
        "tiny", vocab_size=128, dim=512, n_layers=2, n_heads=4,
        n_kv_heads=4 // n_rep, ffn_dim=256, max_seq_len=2048,
        dtype=jnp.float32, decode_attn="xla")
    params = model.init(jax.random.PRNGKey(n_rep),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _padded(cfg, n, width, seed=0):
    out = np.zeros((1, width), np.int32)
    out[0, :n] = np.random.default_rng(seed).integers(0, cfg.vocab_size, n)
    return jnp.asarray(out)


def _lane_state():
    return (jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), jnp.float32),
            jnp.zeros((SLOTS, 2), jnp.uint32))


def _insert(kind, cfg, params, prompt, n, width):
    """One real insert of a prompt of ``n`` tokens on rung ``width`` into
    lane 1 of a fresh ring -> (first token, K rows written, V rows
    written), the rows as [L, H_kv, n, D] whatever the cache's layout."""
    tok, temp, keys = _lane_state()
    if kind == "contiguous":
        ins = make_prefill_insert(cfg, width)
        cache = init_ring_cache(cfg, SLOTS, cfg.max_seq_len)
        cache, _, _, _, first = ins(params, cache, tok, temp, keys, prompt,
                                    n, 1, 0.0, 0)
        return (int(first), np.asarray(cache["k"][:, 1, :, :n]),
                np.asarray(cache["v"][:, 1, :, :n]))
    quant = kind == "paged-int8"
    pool = PG.PagedCacheManager(SLOTS, cfg.max_seq_len, BS, None,
                                prefix_cache=False)
    cache = PG.init_paged_cache(cfg, SLOTS, pool.total, BS,
                                quant="int8" if quant else "none")
    table = jnp.zeros((SLOTS, pool.max_blocks), jnp.int32).at[1].set(
        jnp.arange(1, pool.max_blocks + 1))
    ins = PG.make_paged_prefill_insert(cfg, width, BS, quant=quant)
    cache, _, _, _, first = ins(params, cache, table, tok, temp, keys,
                                jnp.zeros((SLOTS,), bool), prompt, n, 1,
                                0.0, 0)[:5]
    whole = n // BS if quant else -(-n // BS)

    def rows(name):
        blocks = np.asarray(cache[name][:, 1:1 + whole]).astype(np.float32)
        if quant:                      # codes x the block's scale
            blocks *= np.asarray(cache[name + "s"][:, 1:1 + whole]
                                 )[..., None, None]
        lyr, _, h, _, d = blocks.shape
        out = blocks.transpose(0, 2, 1, 3, 4).reshape(lyr, h, whole * BS, d)
        if quant:                      # the partial block waits in the tail
            tail = np.asarray(cache[name + "t"][:, 1, :, :n % BS])
            out = np.concatenate([out, tail.astype(np.float32)], axis=2)
        return out[:, :, :n]

    return int(first), rows("k"), rows("v")


@pytest.mark.parametrize("kind,n_rep,n,width", [
    ("paged", 1, 900, 1024), ("paged", 1, 1024, 1024),
    ("paged", 4, 900, 1024), ("paged", 4, 1024, 1024),
    ("contiguous", 1, 900, 1024), ("contiguous", 1, 1024, 1024),
    ("contiguous", 4, 900, 1024), ("contiguous", 4, 1024, 1024),
    ("paged", 4, 1300, 1536), ("contiguous", 4, 1536, 1536),
    ("paged-int8", 4, 900, 1024),
])
def test_insert_through_the_kernel_equals_the_einsum(request, kind, n_rep,
                                                     n, width):
    """The insert program of a rung, traced with the kernel and without:
    the same first token and the same K/V rows for the real positions."""
    cfg, params = _model(n_rep)
    prompt = _padded(cfg, n, width, seed=width + n)
    assert D.prefill_attn_impl(cfg, width) == "einsum"
    first_e, k_e, v_e = _insert(kind, cfg, params, prompt, n, width)
    request.getfixturevalue("kernel_on")
    assert D.prefill_attn_impl(cfg, width) == "flash"
    first_f, k_f, v_f = _insert(kind, cfg, params, prompt, n, width)
    assert first_f == first_e
    if kind == "paged-int8":
        # one code's worth: a value on a rounding edge may fall either way
        step = np.abs(k_e).max() / 127
        np.testing.assert_allclose(k_f, k_e, atol=1.01 * step)
        np.testing.assert_allclose(v_f, v_e, atol=1.01 * np.abs(v_e).max()
                                   / 127)
    else:
        # layer 0's rows do not pass through attention at all
        np.testing.assert_array_equal(k_f[0], k_e[0])
        np.testing.assert_allclose(k_f, k_e, **TOL)
        np.testing.assert_allclose(v_f, v_e, **TOL)
    assert np.abs(k_e[1]).max() > 0.01 and k_e.shape[2] == n


@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("width", [1024, 1536])
def test_logits_of_every_real_row(request, n_rep, width):
    """``paged_prefill`` (what the paged insert and the prefill pod wrap)
    and ``prefill`` (``generate``'s): logits within tolerance at every
    real position, not only the one an insert samples."""
    cfg, params = _model(n_rep)
    n = width - 37
    prompt = _padded(cfg, n, width, seed=n)
    pool = PG.PagedCacheManager(SLOTS, cfg.max_seq_len, BS, None,
                                prefix_cache=False)
    row = jnp.arange(1, pool.max_blocks + 1, dtype=jnp.int32)

    def both():
        cache = PG.init_paged_cache(cfg, SLOTS, pool.total, BS)
        paged, _ = jax.jit(lambda t: PG.paged_prefill(
            params, cfg, t, cache, row, block_size=BS))(prompt)
        last, lane = jax.jit(lambda t: D.prefill(params, cfg, t, width))(
            prompt)
        return np.asarray(paged[0, :n]), np.asarray(last[0]), lane

    paged_e, last_e, _ = both()
    request.getfixturevalue("kernel_on")
    paged_f, last_f, lane = both()
    np.testing.assert_allclose(paged_f, paged_e, **TOL)
    np.testing.assert_allclose(last_f, last_e, **TOL)
    assert int(lane["pos"]) == width
    assert np.abs(paged_e).max() > 0.1


def _count_calls(monkeypatch, module, name):
    spy = mock.MagicMock(wraps=getattr(module, name))
    monkeypatch.setattr(module, name, spy)
    return spy


def test_a_continuation_still_reads_the_cache(kernel_on, monkeypatch):
    """A multi-token forward that enters at ``pos > 0`` (a chunked slice)
    must attend to what earlier calls wrote: it traces ``_attend_cache``
    on a TPU too, while the whole-prompt forward of the same width traces
    the kernel and no einsum."""
    cfg, params = _model(4)
    einsum = _count_calls(monkeypatch, D, "_attend_cache")
    flash = _count_calls(monkeypatch, A, "attention")
    tokens = jnp.zeros((1, 1024), jnp.int32)

    def slice_at_1024(t):
        cache = D.init_cache(cfg, 1, 2048)
        cache["pos"] = cache["pos"] + 1024
        return D._forward(cfg, params, t, cache)

    jax.eval_shape(slice_at_1024, tokens)
    assert einsum.called and not flash.called
    einsum.reset_mock()
    jax.eval_shape(lambda t: D._forward(cfg, params, t,
                                        D.init_cache(cfg, 1, 1024),
                                        whole_prompt=True), tokens)
    assert flash.called and not einsum.called
    # and a fresh cache alone does not make a caller whole-prompt: it says so
    flash.reset_mock()
    jax.eval_shape(lambda t: D._forward(cfg, params, t,
                                        D.init_cache(cfg, 1, 1024)), tokens)
    assert einsum.called and not flash.called


@pytest.mark.parametrize("n_kv,want", [(2, "flash"), (1, "einsum")])
def test_a_tp_mesh_takes_the_kernel_where_the_heads_split(request, n_kv,
                                                          want):
    """Under a tp mesh the kernel enters through shard_map in whole GQA
    groups; heads that do not split keep the GSPMD einsum.  Same logits
    either way."""
    from paddle_operator_tpu.parallel.mesh import make_serving_mesh

    cfg, params = _model(4 // n_kv)
    mesh = make_serving_mesh(2)
    sharded = D.shard_params_for_serving(params, cfg, mesh)
    prompt = _padded(cfg, 1024, 1024, seed=9)
    ref, _ = jax.jit(lambda t: D.prefill(params, cfg, t, 1024))(prompt)
    request.getfixturevalue("kernel_on")
    assert D.prefill_attn_impl(cfg, 1024, mesh) == want
    got, cache = jax.jit(lambda t: D.prefill(sharded, cfg, t, 1024,
                                             mesh=mesh))(prompt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_statusz_names_each_rungs_attention(request):
    """``prefillAttnByBucket``: static, one entry a rung, from the same
    function the trace asks — ``einsum`` everywhere on the CPU; on a TPU
    ``flash`` from the measured threshold up, where the kernel tiles."""
    cfg, params = _model(4)
    kw = dict(slots=SLOTS, max_len=2048, chunk_tokens=2, paged=True,
              block_size=BS, prefill_buckets=(64, 512, 1024, 1536, 1600))
    b = ContinuousBatcher(params, cfg, **kw)
    try:
        assert set(b.serving_status()["prefillAttnByBucket"].items()) == {
            ("64", "einsum"), ("512", "einsum"), ("1024", "einsum"),
            ("1536", "einsum"), ("1600", "einsum")}
    finally:
        b.close()
    request.getfixturevalue("kernel_on")
    b = ContinuousBatcher(params, cfg, **kw)
    try:
        assert b.serving_status()["prefillAttnByBucket"] == {
            "64": "einsum", "512": "einsum", "1024": "flash",
            "1536": "flash", "1600": "einsum"}
    finally:
        b.close()
