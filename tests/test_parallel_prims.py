"""Ring attention, pipeline parallelism, PS embedding — correctness on the
8-device CPU mesh."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_operator_tpu.api.types import MeshSpec
from paddle_operator_tpu.ops.attention import reference_attention
from paddle_operator_tpu.parallel import pipeline as PP
from paddle_operator_tpu.parallel import ps as PS
from paddle_operator_tpu.parallel.mesh import make_mesh
from paddle_operator_tpu.parallel.ring_attention import make_ring_attention_fn


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("cp", [2, 4])
    def test_matches_reference(self, causal, cp):
        mesh = make_mesh(MeshSpec(cp=cp, dp=8 // cp))
        b, s, h, d = 8 // cp * 2, 64 * cp, 4, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, h, d))
        v = jax.random.normal(ks[2], (b, s, h, d))
        ref = reference_attention(q, k, v, causal=causal)
        with mesh:
            ring = make_ring_attention_fn(mesh, causal=causal)
            out = jax.jit(ring)(q, k, v)
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)

    def test_gqa(self):
        mesh = make_mesh(MeshSpec(cp=2, dp=4))
        q = jax.random.normal(jax.random.PRNGKey(0), (4, 128, 4, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 2, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (4, 128, 2, 16))
        ref = reference_attention(q, k, v, causal=True)
        with mesh:
            out = jax.jit(make_ring_attention_fn(mesh))(q, k, v)
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)

    def test_gradients_flow(self):
        mesh = make_mesh(MeshSpec(cp=2, dp=4))
        q = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 2, 16))

        def loss_ring(q):
            with mesh:
                return (jax.jit(make_ring_attention_fn(mesh))(q, q, q) ** 2).sum()

        def loss_ref(q):
            return (reference_attention(q, q, q, causal=True) ** 2).sum()

        np.testing.assert_allclose(jax.grad(loss_ring)(q),
                                   jax.grad(loss_ref)(q),
                                   atol=5e-4, rtol=5e-4)


class TestPipeline:
    def _stacked_mlp(self, n_layers, dim, key):
        k1, k2 = jax.random.split(key)
        return {
            "w": jax.random.normal(k1, (n_layers, dim, dim)) * 0.3,
            "b": jax.random.normal(k2, (n_layers, dim)) * 0.1,
        }

    @staticmethod
    def _apply_block(params, h):
        """Apply this stage's local stacked layers sequentially."""
        def one(h, layer):
            return jnp.tanh(h @ layer["w"] + layer["b"]), None

        h, _ = jax.lax.scan(one, h, params)
        return h

    def _sequential(self, params, x):
        return self._apply_block(params, x)

    @pytest.mark.parametrize("pp,m", [(2, 4), (4, 8)])
    def test_matches_sequential(self, pp, m):
        mesh = make_mesh(MeshSpec(pp=pp, dp=8 // pp))
        n_layers, dim, bm = pp * 2, 16, 4
        params = self._stacked_mlp(n_layers, dim, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (m * bm, dim))

        want = self._sequential(params, x)

        xm = PP.microbatch(x, m)
        with mesh:
            fn = PP.make_pipeline_fn(mesh, self._apply_block,
                                     num_microbatches=m)
            got = jax.jit(fn)(params, xm).reshape(m * bm, dim)
        np.testing.assert_allclose(want, got, atol=1e-5, rtol=1e-5)

    def test_gradients_match(self):
        pp, m, dim, bm = 2, 4, 8, 4  # bm must divide by dp=4
        mesh = make_mesh(MeshSpec(pp=pp, dp=4))
        params = self._stacked_mlp(4, dim, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (m * bm, dim))
        xm = PP.microbatch(x, m)

        def loss_seq(p):
            return (self._sequential(p, x) ** 2).sum()

        def loss_pipe(p):
            with mesh:
                fn = PP.make_pipeline_fn(mesh, self._apply_block,
                                         num_microbatches=m)
                return (jax.jit(fn)(p, xm) ** 2).sum()

        gs = jax.grad(loss_seq)(params)
        gp = jax.grad(loss_pipe)(params)
        for k in gs:
            np.testing.assert_allclose(gs[k], gp[k], atol=1e-4, rtol=1e-4)

    def test_microbatch_validation(self):
        with pytest.raises(ValueError, match="not divisible"):
            PP.microbatch(jnp.zeros((5, 2)), 2)


class TestPSEmbedding:
    def test_lookup_matches_dense(self):
        mesh = make_mesh(MeshSpec(fsdp=4, dp=2))
        init_fn, lookup = PS.make_ps_embedding(mesh, vocab=64, dim=8)
        table = init_fn(jax.random.PRNGKey(0))
        assert len(table.sharding.device_set) > 1
        ids = jnp.array([0, 5, 17, 63, 32, 1], jnp.int32)
        with mesh:
            rows = jax.jit(lookup)(table, ids)
        np.testing.assert_allclose(rows, np.asarray(table)[np.asarray(ids)],
                                   atol=1e-6)

    def test_gradient_sparse_to_owner(self):
        mesh = make_mesh(MeshSpec(fsdp=4, dp=2))
        init_fn, lookup = PS.make_ps_embedding(mesh, vocab=16, dim=4)
        table = init_fn(jax.random.PRNGKey(0))
        ids = jnp.array([3, 12], jnp.int32)

        def loss(t):
            with mesh:
                return jax.jit(lookup)(t, ids).sum()

        g = np.asarray(jax.grad(loss)(table))
        nonzero_rows = set(np.nonzero(g.sum(axis=1))[0].tolist())
        assert nonzero_rows == {3, 12}

    def test_indivisible_vocab_rejected(self):
        mesh = make_mesh(MeshSpec(fsdp=4, dp=2))
        with pytest.raises(ValueError, match="not divisible"):
            PS.make_ps_embedding(mesh, vocab=63, dim=8)


class TestUlyssesAttention:
    """The all-to-all alternative to ring attention (parallel/ulysses.py):
    seq-sharded -> head-sharded -> full-seq attention -> back."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("cp", [2, 4])
    def test_matches_reference(self, causal, cp):
        from paddle_operator_tpu.parallel.ulysses import (
            make_ulysses_attention_fn,
        )

        mesh = make_mesh(MeshSpec(cp=cp, dp=8 // cp))
        b, s, h, d = 8 // cp * 2, 64 * cp, 4, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, h, d))
        v = jax.random.normal(ks[2], (b, s, h, d))
        ref = reference_attention(q, k, v, causal=causal)
        with mesh:
            out = jax.jit(make_ulysses_attention_fn(mesh, causal=causal))(
                q, k, v)
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)

    def test_gqa(self):
        from paddle_operator_tpu.parallel.ulysses import (
            make_ulysses_attention_fn,
        )

        mesh = make_mesh(MeshSpec(cp=2, dp=4))
        q = jax.random.normal(jax.random.PRNGKey(0), (4, 128, 4, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 2, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (4, 128, 2, 16))
        ref = reference_attention(q, k, v, causal=True)
        with mesh:
            out = jax.jit(make_ulysses_attention_fn(mesh))(q, k, v)
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)

    def test_gradients_flow(self):
        from paddle_operator_tpu.parallel.ulysses import (
            make_ulysses_attention_fn,
        )

        mesh = make_mesh(MeshSpec(cp=2, dp=4))
        q = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 2, 16))

        def loss_uly(q):
            with mesh:
                return (jax.jit(make_ulysses_attention_fn(mesh))(
                    q, q, q) ** 2).sum()

        def loss_ref(q):
            return (reference_attention(q, q, q, causal=True) ** 2).sum()

        np.testing.assert_allclose(jax.grad(loss_uly)(q),
                                   jax.grad(loss_ref)(q),
                                   atol=5e-4, rtol=5e-4)


class TestSegmentedContextParallel:
    """Packed-sequence (segment_ids) masking under both cp strategies:
    ring rotates the segment chunk with K/V; Ulysses all-gathers it."""

    def _inputs(self, b=4, s=128, h=4, hkv=2, d=16, docs=3):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, hkv, d))
        v = jax.random.normal(ks[2], (b, s, hkv, d))
        # contiguous documents of random boundaries per row
        cuts = jnp.sort(jax.random.randint(ks[3], (b, docs - 1), 1, s),
                        axis=1)
        seg = jnp.sum(jnp.arange(s)[None, :, None] >= cuts[:, None, :],
                      axis=-1).astype(jnp.int32)
        return q, k, v, seg

    @pytest.mark.parametrize("causal", [True, False])
    def test_ring_matches_reference(self, causal):
        mesh = make_mesh(MeshSpec(cp=2, dp=4))
        q, k, v, seg = self._inputs()
        ref = reference_attention(q, k, v, causal=causal, segment_ids=seg)
        with mesh:
            out = jax.jit(make_ring_attention_fn(mesh, causal=causal))(
                q, k, v, seg)
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_ulysses_matches_reference(self, causal):
        from paddle_operator_tpu.parallel.ulysses import (
            make_ulysses_attention_fn,
        )

        mesh = make_mesh(MeshSpec(cp=2, dp=4))
        q, k, v, seg = self._inputs()
        ref = reference_attention(q, k, v, causal=causal, segment_ids=seg)
        with mesh:
            out = jax.jit(make_ulysses_attention_fn(mesh, causal=causal))(
                q, k, v, seg)
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)

    def test_ring_cp4(self):
        mesh = make_mesh(MeshSpec(cp=4, dp=2))
        q, k, v, seg = self._inputs(b=2, s=256)
        ref = reference_attention(q, k, v, causal=True, segment_ids=seg)
        with mesh:
            out = jax.jit(make_ring_attention_fn(mesh))(q, k, v, seg)
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)


class TestFullMeshContextParallel:
    def test_ring_cp8(self):
        # the whole 8-device mesh on cp: 7 rotation hops
        mesh = make_mesh(MeshSpec(cp=8))
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 512, 4, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 512, 2, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 512, 2, 16))
        ref = reference_attention(q, k, v, causal=True)
        with mesh:
            out = jax.jit(make_ring_attention_fn(mesh))(q, k, v)
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)
