"""Flash-attention kernel correctness vs the XLA reference, run in pallas
interpret mode on CPU (the same kernels compile for TPU; see /verify runs
on hardware for compiled-path checks)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_operator_tpu.ops.attention import reference_attention
from paddle_operator_tpu.ops.pallas_attention import flash_attention


def rand_qkv(b, s, hq, hkv, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, hq, d), dtype),
            jax.random.normal(ks[1], (b, s, hkv, d), dtype),
            jax.random.normal(ks[2], (b, s, hkv, d), dtype))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_forward_matches_reference(causal, hq, hkv):
    q, k, v = rand_qkv(2, 256, hq, hkv, 64)
    ref = reference_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)


def test_gradients_match_reference():
    q, k, v = rand_qkv(1, 256, 2, 2, 64)

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=128,
                                block_k=128, interpret=True) ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_gqa_gradients_reduce_over_groups():
    q, k, v = rand_qkv(1, 128, 4, 2, 64)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=128,
                                block_k=128, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        assert a.shape == b.shape  # kv-head shaped, not q-head shaped
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_untileable_shapes_raise():
    q, k, v = rand_qkv(1, 100, 2, 2, 64)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)


def test_dispatcher_refuses_a_kernel_that_cannot_run():
    """Asking for the kernel on shapes it cannot tile is an error, not a
    silent O(S^2) reference run."""
    from paddle_operator_tpu.ops import attention as A

    q, k, v = rand_qkv(1, 100, 2, 2, 64)  # untileable
    with pytest.raises(NotImplementedError, match="cannot tile"):
        A.attention(q, k, v, use_pallas=True)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_dispatcher_decides_from_shapes_before_the_call(monkeypatch, backend):
    """Left to itself the dispatcher picks the reference for shapes the
    kernel cannot tile — on any backend, by asking flash_tiles first."""
    from paddle_operator_tpu.ops import attention as A

    monkeypatch.setattr(A.jax, "default_backend", lambda: backend)
    q, k, v = rand_qkv(1, 100, 2, 2, 64)  # untileable -> reference path
    out = A.attention(q, k, v)
    ref = A.reference_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def _seg_pattern(b, s, docs=3, seed=5):
    cuts = jnp.sort(jax.random.randint(jax.random.PRNGKey(seed),
                                       (b, docs - 1), 1, s), axis=1)
    return jnp.sum(jnp.arange(s)[None, :, None] >= cuts[:, None, :],
                   axis=-1).astype(jnp.int32)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_segmented_forward_matches_reference(causal, hq, hkv):
    """Packed-sequence masking in-kernel (both block tiles carry their
    segment-id slices) must equal the reference segment mask."""
    q, k, v = rand_qkv(2, 256, hq, hkv, 64)
    seg = _seg_pattern(2, 256)
    ref = reference_attention(q, k, v, causal=causal, segment_ids=seg)
    out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                          block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)


def test_segmented_gradients_match_reference():
    q, k, v = rand_qkv(1, 256, 2, 2, 64)
    seg = _seg_pattern(1, 256)

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True,
                                    segment_ids=seg) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, segment_ids=seg,
                                block_q=128, block_k=128,
                                interpret=True) ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_dispatcher_uses_pallas_for_segments():
    """segment_ids no longer bounce to the reference path — the dispatcher
    keeps the flash kernel (in-kernel masking)."""
    from unittest import mock

    from paddle_operator_tpu.ops import attention as A

    q, k, v = rand_qkv(1, 256, 2, 2, 64)
    seg = _seg_pattern(1, 256)
    with mock.patch.object(A, "reference_attention",
                           side_effect=AssertionError("fell back")):
        out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                              block_q=128, block_k=128, interpret=True)
    assert out.shape == q.shape


@pytest.mark.parametrize("packed", [False, True])
def test_sharded_flash_matches_reference_on_a_mesh(packed):
    """The kernel on a multi-device mesh (ops.attention
    sharded_flash_attention): batch over (dp, fsdp), heads over tp, each
    shard its own kernel call — GSPMD cannot partition a Mosaic call, so
    this is the only way the flash kernel reaches a multi-chip TPU job.
    Values and gradients against the reference, GQA included."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_operator_tpu.api.types import MeshSpec
    from paddle_operator_tpu.ops.attention import sharded_flash_attention
    from paddle_operator_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    q, k, v = rand_qkv(4, 128, 4, 2, 64)
    sh = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    seg = _seg_pattern(4, 128) if packed else None

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    def flash(q, k, v):
        return sharded_flash_attention(mesh, q, k, v, segment_ids=seg,
                                       interpret=True)

    def ref(q, k, v):
        return reference_attention(q, k, v, segment_ids=seg)

    out = jax.jit(flash)(q, k, v)
    assert out.sharding.is_equivalent_to(sh, out.ndim)
    np.testing.assert_allclose(out, ref(q, k, v), atol=2e-5, rtol=2e-5)
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(want, got):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_sharded_flash_refuses_heads_tp_cannot_split():
    from paddle_operator_tpu.api.types import MeshSpec
    from paddle_operator_tpu.ops import attention as A
    from paddle_operator_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(MeshSpec(dp=2, tp=4))
    q, k, v = rand_qkv(2, 128, 4, 2, 64)      # 2 kv heads over tp=4
    with pytest.raises(NotImplementedError, match="GQA groups"):
        A.sharded_flash_attention(mesh, q, k, v, interpret=True)
    # left to itself the dispatcher sees it from the shapes and goes to
    # the reference
    np.testing.assert_allclose(A.attention(q, k, v, mesh=mesh),
                               A.reference_attention(q, k, v), atol=1e-6)
