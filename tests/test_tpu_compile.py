"""AOT compiles for the chip, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a device
that is described, not attached (guide ``on-chip-measurement`` section 2).
Interpret mode cannot see what it refuses: a block shape the (8, 128)
tiling rejects, a kernel over its VMEM budget, a Mosaic call GSPMD is asked
to partition.  Every kernel of the main path at LLaMA-7B width (32 heads x
128) and the train step that wraps them compile here, so a later PR that
breaks one learns it at no chip time.

Rules this file keeps (one process at a time may load the TPU library, and
pytest-xdist workers all import every test file): the topology is described
inside a module-scoped, non-autouse fixture of THIS file, never at import,
in a ``skipif``, in ``parametrize`` or in ``conftest.py``; everything built
from it is built in fixtures or tests; all such tests live in this one
file; the persistent compile cache is off around them (a described-device
executable is written but can never be read back).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

H, D = 32, 128          # LLaMA-7B heads x head_dim
B, S = 8, 2048          # lanes / batch, context


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topo):
    from paddle_operator_tpu.parallel.mesh import make_serving_mesh

    return make_serving_mesh(4, devices=topo.devices)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_flash_forward_and_backward(one_chip):
    from paddle_operator_tpu.ops.pallas_attention import flash_attention

    q = sds((B, S, H, D), jnp.bfloat16, one_chip)
    seg = sds((B, S), jnp.int32, one_chip)

    def loss(q, k, v, seg=None):
        return flash_attention(q, k, v, segment_ids=seg).astype(
            jnp.float32).sum()

    assert kernel_calls(jax.jit(flash_attention).lower(q, q, q).compile()) == 1
    # fwd (residuals) + dkv + dq
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    assert kernel_calls(grad.lower(q, q, q).compile()) == 3
    assert kernel_calls(grad.lower(q, q, q, seg).compile()) == 3


def test_contiguous_decode(one_chip):
    from paddle_operator_tpu.ops.decode_attention import decode_attention

    L = 4
    c = jax.jit(lambda q, k, v, n, li: decode_attention(
        q, k, v, n, layer=li)).lower(
        sds((B, H, D), jnp.bfloat16, one_chip),
        sds((L, B, H, S, D), jnp.bfloat16, one_chip),
        sds((L, B, H, S, D), jnp.bfloat16, one_chip),
        sds((B,), jnp.int32, one_chip), sds((), jnp.int32, one_chip),
    ).compile()
    assert kernel_calls(c) == 1


def paged_args(sharding, block, quant):
    L, M = 4, S // block
    N = B * M + 1
    pool_dt = jnp.int8 if quant else jnp.bfloat16
    args = [sds((B, H, D), jnp.bfloat16, sharding["q"]),
            sds((L, N, H, block, D), pool_dt, sharding["pool"]),
            sds((L, N, H, block, D), pool_dt, sharding["pool"]),
            sds((B, M), jnp.int32, sharding["rep"]),
            sds((B,), jnp.int32, sharding["rep"]),
            sds((), jnp.int32, sharding["rep"])]
    if quant:
        args += [sds((L, N, H), jnp.float32, sharding["scale"])] * 2
        args += [sds((L, B + 1, H, block, D), jnp.bfloat16,
                     sharding["pool"])] * 2
    return args


def paged_call(q, kp, vp, tbl, lens, li, *quant):
    from paddle_operator_tpu.ops.decode_attention import (
        paged_decode_attention,
    )

    kw = dict(zip(("k_scale", "v_scale", "k_tail", "v_tail"), quant))
    return paged_decode_attention(q, kp, vp, tbl, lens, layer=li, **kw)


@pytest.mark.parametrize("block", [256, 64])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_decode(one_chip, block, quant):
    """The int8 variant is the one the compiler refused before PR 21: a
    ``(1, 1, hkv)`` scale block on a ``[L, N, hkv]`` array."""
    sh = dict.fromkeys(("q", "pool", "rep", "scale"), one_chip)
    c = jax.jit(paged_call).lower(*paged_args(sh, block, quant)).compile()
    assert kernel_calls(c) == 1


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_tp_paged_decode(tp_mesh, quant):
    """SERVE_TP: the kernel enters the 4-chip mesh through shard_map —
    custom call + all-reduce, and the pool is never all-gathered."""
    from paddle_operator_tpu.ops.decode_attention import (
        sharded_paged_decode_attention,
    )

    def ns(*spec):
        return NamedSharding(tp_mesh, P(*spec))

    sh = {"q": ns(None, "tp", None), "rep": ns(),
          "pool": ns(None, None, "tp", None, None),
          "scale": ns(None, None, "tp")}
    q, kp, vp, tbl, lens, li, *qargs = paged_args(sh, 256, quant)
    wo = sds((H * D, H * D), jnp.bfloat16, ns("tp", None))

    def call(q, kp, vp, tbl, lens, wo, li, *quant):
        kw = dict(zip(("k_scale", "v_scale", "k_tail", "v_tail"), quant))
        return sharded_paged_decode_attention(
            tp_mesh, q, kp, vp, tbl, lens, wo, layer=li, **kw)

    text = jax.jit(call).lower(q, kp, vp, tbl, lens, wo, li,
                               *qargs).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "all-reduce" in text and "all-gather" not in text


def test_tp_contiguous_decode(tp_mesh):
    from paddle_operator_tpu.ops.decode_attention import (
        sharded_decode_attention,
    )

    def ns(*spec):
        return NamedSharding(tp_mesh, P(*spec))

    L = 4
    cache = sds((L, B, H, S, D), jnp.bfloat16,
                ns(None, None, "tp", None, None))
    text = jax.jit(lambda q, k, v, n, wo, li: sharded_decode_attention(
        tp_mesh, q, k, v, n, wo, layer=li)).lower(
        sds((B, H, D), jnp.bfloat16, ns(None, "tp", None)), cache, cache,
        sds((B,), jnp.int32, ns()),
        sds((H * D, H * D), jnp.bfloat16, ns("tp", None)),
        sds((), jnp.int32, ns()),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "all-reduce" in text and "all-gather" not in text


@pytest.mark.parametrize("mesh_axes", [{}, {"fsdp": 2, "tp": 2}],
                         ids=["one-chip", "fsdp2-tp2"])
def test_train_step_7b_width(topo, monkeypatch, mesh_axes):
    """One whole train step, 2 layers of LLaMA-7B width, bf16 params, int8
    moments, batch 8 x 2048 — on one chip and on the ``fsdp=2, tp=2`` mesh
    of ``deploy/examples``.  The dispatchers ask ``jax.default_backend()``
    and here that is the CPU, so the test steers it (never the program).
    On the mesh the flash kernel must enter through shard_map: GSPMD
    refuses to partition a Mosaic call."""
    from paddle_operator_tpu.api.types import MeshSpec
    from paddle_operator_tpu.models import llama as L
    from paddle_operator_tpu.parallel.mesh import make_mesh
    from paddle_operator_tpu.parallel.sharding import batch_sharding
    from paddle_operator_tpu.train import trainer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = MeshSpec(**mesh_axes)
    n = 4 if mesh_axes else 1
    mesh = make_mesh(spec, devices=topo.devices[:n])
    cfg = dataclasses.replace(L.CONFIGS["7b"], n_layers=2,
                              param_dtype=jnp.bfloat16)
    model = L.Llama(cfg, mesh)
    opt = T.make_optimizer(moments="int8")
    args = (model, opt, mesh, L.partition_patterns(cfg),
            (jnp.zeros((B, 8), jnp.int32),))
    shardings, _ = T.state_shardings(*args)
    step = T.make_train_step(model, opt, mesh, shardings)
    batch = {"tokens": sds((B, S + 1), jnp.int32,
                           batch_sharding(mesh, extra_dims=1))}
    compiled = step.lower(T.abstract_state(*args), batch).compile()
    # forward, its remat re-run, dkv, dq
    assert kernel_calls(compiled) == 4
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


def _serving_shapes(cfg, sharding_of):
    """The serving parameter tree as shapes, each leaf with the sharding
    ``sharding_of(tree)`` gives it."""
    from paddle_operator_tpu.infer.quant import serving_params
    from paddle_operator_tpu.models import llama as L

    shapes = jax.eval_shape(
        lambda r: serving_params(
            L.Llama(cfg).init(r, jnp.zeros((1, 8), jnp.int32))["params"],
            cfg.dtype), jax.random.PRNGKey(0))
    return jax.tree.map(lambda x, s: sds(x.shape, x.dtype, s), shapes,
                        sharding_of(shapes))


def _gqa_7b(n_layers=2):
    from paddle_operator_tpu.models import llama as L

    return dataclasses.replace(L.CONFIGS["7b"], n_layers=n_layers,
                               n_kv_heads=8, max_seq_len=4096)


@pytest.mark.parametrize("width,calls", [(3072, 1), (1536, 1), (512, 0)])
def test_whole_prompt_prefill(one_chip, monkeypatch, width, calls):
    """ISSUE 29: a whole-prompt prefill at 7B width, 32 heads over 8 kv
    heads — the flash kernel (the layer scan's one custom call) with the
    forward's 1024 blocks at 3072 and the kernel's defaults at a 3:2
    midpoint, the einsum under the measured threshold."""
    from paddle_operator_tpu.infer import decode as D

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _gqa_7b()
    assert D.prefill_attn_impl(cfg, width) == ("flash" if calls
                                                else "einsum")
    params = _serving_shapes(
        cfg, lambda t: jax.tree.map(lambda _: one_chip, t))
    compiled = jax.jit(lambda p, t: D.prefill(p, cfg, t, width)).lower(
        params, sds((1, width), jnp.int32, one_chip)).compile()
    assert kernel_calls(compiled) == calls


def test_tp_whole_prompt_prefill(tp_mesh, monkeypatch):
    """Under SERVE_TP the prefill's kernel enters the mesh through
    shard_map in whole GQA groups (32 / 8 heads over tp = 4): a custom
    call, and k, v are never all-gathered around it."""
    from paddle_operator_tpu.infer import decode as D
    from paddle_operator_tpu.models.llama import partition_patterns
    from paddle_operator_tpu.parallel.sharding import tree_shardings

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _gqa_7b()
    assert D.prefill_attn_impl(cfg, 2048, tp_mesh) == "flash"
    params = _serving_shapes(cfg, lambda t: tree_shardings(
        t, tp_mesh, partition_patterns(cfg), replicate_indivisible=True))
    text = jax.jit(lambda p, t: D.prefill(p, cfg, t, 2048, mesh=tp_mesh)
                   ).lower(params, sds((1, 2048), jnp.int32,
                                       NamedSharding(tp_mesh, P()))
                           ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "all-reduce" in text and "all-gather" not in text


def _ring_lanes(sharding, lanes, blocks):
    """table, tok, temp, keys, active of a paged ring, as shapes."""
    return (sds((lanes, blocks), jnp.int32, sharding),
            sds((lanes,), jnp.int32, sharding),
            sds((lanes,), jnp.float32, sharding),
            sds((lanes, 2), jnp.uint32, sharding),
            sds((lanes,), jnp.bool_, sharding))


def test_serving_step_of_the_dense_cell(one_chip, monkeypatch):
    """ISSUE 31: ``jit_step`` at ``mistral-7b-serve-16l``'s shapes (16
    layers of 7B width over 8 kv heads, feed-forward 14336; 16 lanes,
    block 256, ``max_len`` 4096, chunk 8).  The decode kernel over its
    work list is the layer scan's ONE custom call, and the list costs no
    temporaries to speak of: 673.3 MB, the parent's 673.2 (two weight
    stacks' change of layout: PERF.md section 5)."""
    from paddle_operator_tpu.infer import paged as PG

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(_gqa_7b(16), ffn_dim=14336,
                              dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    on_chip = lambda t: jax.tree.map(                        # noqa: E731
        lambda x: sds(x.shape, x.dtype, one_chip), t)
    params = _serving_shapes(
        cfg, lambda t: jax.tree.map(lambda _: one_chip, t))
    pool = PG.PagedCacheManager(16, 4096, 256)
    cache = on_chip(jax.eval_shape(
        lambda: PG.init_paged_cache(cfg, 16, pool.total, 256)))
    compiled = PG.make_paged_chunk_step(cfg, 8).lower(
        params, cache, *_ring_lanes(one_chip, 16, pool.max_blocks)).compile()
    assert kernel_calls(compiled) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 674 * 10 ** 6


@pytest.mark.parametrize("width,calls", [(1024, 2), (256, 1), (3072, 1)])
def test_serving_insert_of_the_dense_cell(one_chip, monkeypatch, width, calls):
    """ISSUE 33: ``jit_insert`` at ``mistral-7b-serve-16l``'s shapes carries
    one decode step of the 16 lanes on the rungs up to 1024.  Its layer
    scan holds the decode kernel's call beside the flash kernel's (the
    einsum rungs: the decode kernel's alone); the head runs over 1 + 16
    rows; the function is still named ``insert`` (the roofline readers
    tell decode-kernel calls by ``"step" in module``).  A wider rung
    keeps the insert alone — the flash kernel's call, five outputs — with
    the head at the prompt's last real token: no ``[W, 32000]`` logits
    buffer exists on any rung (ROADMAP A1(d))."""
    import re

    from paddle_operator_tpu.infer import paged as PG

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(_gqa_7b(16), ffn_dim=14336,
                              dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    on_chip = lambda t: jax.tree.map(                        # noqa: E731
        lambda x: sds(x.shape, x.dtype, one_chip), t)
    params = _serving_shapes(
        cfg, lambda t: jax.tree.map(lambda _: one_chip, t))
    pool = PG.PagedCacheManager(16, 4096, 256)
    cache = on_chip(jax.eval_shape(
        lambda: PG.init_paged_cache(cfg, 16, pool.total, 256)))
    table, tok, temp, keys, active = _ring_lanes(one_chip, 16,
                                                 pool.max_blocks)
    compiled = PG.make_paged_prefill_insert(cfg, width, 256).lower(
        params, cache, table, tok, temp, keys, active,
        sds((1, width), jnp.int32, one_chip), 1, 0, 0.0, 0).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_insert")
    assert kernel_calls(compiled) == calls
    # (the parent's compiled text held it as ``bf16[W,32000]``: XLA had
    # pushed the conversion past the slice, not the slice past the product)
    assert not re.search(rf"\[(1,)?{width},32000\]", text)
    if not PG.insert_carries_step(width):
        assert re.search(r"\[(1,)?1,32000\]", text)   # the head: one row
        assert len(compiled.output_shardings) == 5
        return
    assert "bf16[17,32000]" in text             # the one head product
    assert len(compiled.output_shardings) == 6  # ... and ``toks [1, 16]``
    assert compiled.memory_analysis().temp_size_in_bytes < 200 * 10 ** 6


def test_serving_step_of_the_expert_cell(one_chip, monkeypatch):
    """ISSUE 31: the expert architecture's step at
    ``trinity-mini-serve-5l``'s shapes: the dense layer's decode call,
    and in the scanned layers' body one decode call (window and full
    layers alike: each layer's work list rides the scan) beside the
    three grouped products; 99.1 MB of temporaries (the parent's
    98.8)."""
    import json

    from benchmark.harness import afmoe as H
    from paddle_operator_tpu.infer import afmoe_serve as AF
    from paddle_operator_tpu.infer import paged as PG
    from paddle_operator_tpu.models import afmoe as M

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "trinity-mini-serve-5l.json")) as f:
        cfgj = json.load(f)
    s = cfgj["serve"]
    cfg = H.config(cfgj, s["max_len"])
    on_chip = lambda t: jax.tree.map(                        # noqa: E731
        lambda x: sds(x.shape, x.dtype, one_chip), t)
    pool = PG.PagedCacheManager(s["lanes"], s["max_len"], s["block"],
                                prefix_cache=False)
    cache = on_chip(jax.eval_shape(lambda: PG.init_paged_cache(
        cfg, s["lanes"], pool.total, s["block"])))
    compiled = AF.make_paged_chunk_step(cfg, s["chunk"]).lower(
        on_chip(M.param_shapes(cfg)), cache,
        *_ring_lanes(one_chip, s["lanes"], pool.max_blocks)).compile()
    assert kernel_calls(compiled) == 2 + 3
    assert compiled.memory_analysis().temp_size_in_bytes < 100 * 10 ** 6
