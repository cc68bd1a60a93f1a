"""Ring attention: sequence-parallel exact attention over the mesh
(SURVEY §5.7 long-context primitive). Golden = dense attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops.ring_attention import dense_attention, ring_attention


def _qkv(b=2, t=64, h=4, d=16, seed=0):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.normal(size=(b, t, h, d)).astype(np.float32))
    return mk(), mk(), mk()


class TestRingAttention:
    def test_matches_dense(self, devices8):
        q, k, v = _qkv()
        out = ring_attention(q, k, v)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_dense_causal(self, devices8):
        q, k, v = _qkv(seed=1)
        out = ring_attention(q, k, v, causal=True)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_sharded_inputs_stay_sharded(self, devices8):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mmlspark_tpu.parallel.mesh import get_mesh

        mesh = get_mesh()
        q, k, v = _qkv(seed=2)
        sh = NamedSharding(mesh, P(None, "data", None, None))
        q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
        out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh=mesh))(q, k, v)
        assert out.sharding.spec == P(None, "data", None, None)
        ref = dense_attention(*_qkv(seed=2))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_single_device_degenerates(self):
        q, k, v = _qkv(t=32, seed=3)
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        out = ring_attention(q, k, v, mesh=mesh, causal=True)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_long_sequence_blockwise_stability(self, devices8):
        # large magnitudes: the online-softmax rescaling must stay finite
        r = np.random.default_rng(4)
        q = jnp.asarray(r.normal(size=(1, 128, 2, 8)).astype(np.float32) * 8)
        k = jnp.asarray(r.normal(size=(1, 128, 2, 8)).astype(np.float32) * 8)
        v = jnp.asarray(r.normal(size=(1, 128, 2, 8)).astype(np.float32))
        out = ring_attention(q, k, v, causal=True)
        assert bool(jnp.isfinite(out).all())
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestKVMask:
    """Padding support: a (batch, seq) key-validity mask lets any sequence
    length shard over the ring — pad to a multiple of the axis size, mask
    the tail; the pad mask rotates with its K/V block."""

    def test_ring_mask_matches_dense_mask(self, devices8):
        q, k, v = _qkv(seed=3)
        r = np.random.default_rng(3)
        mask = jnp.asarray(r.random((2, 64)) > 0.3)
        out = ring_attention(q, k, v, kv_mask=mask)
        ref = dense_attention(q, k, v, kv_mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_padded_equals_unpadded(self, devices8):
        """Attention over a 56-token sequence padded to 64 (8-shard
        divisible) with the tail masked == dense attention over the
        unpadded 56 tokens. The practical recipe for non-divisible
        sequence lengths (e.g. ViT's 197)."""
        b, t_real, t_pad, h, d = 2, 56, 64, 4, 16
        r = np.random.default_rng(4)
        mk = lambda t: r.normal(size=(b, t, h, d)).astype(np.float32)
        q, k, v = mk(t_real), mk(t_real), mk(t_real)
        pad = ((0, 0), (0, t_pad - t_real), (0, 0), (0, 0))
        qp, kp, vp = (jnp.asarray(np.pad(a, pad)) for a in (q, k, v))
        mask = jnp.asarray(
            np.arange(t_pad)[None, :].repeat(b, 0) < t_real
        )
        out = ring_attention(qp, kp, vp, kv_mask=mask)
        ref = dense_attention(*map(jnp.asarray, (q, k, v)))
        np.testing.assert_allclose(
            np.asarray(out)[:, :t_real], np.asarray(ref),
            rtol=2e-5, atol=2e-5,
        )

    def test_causal_composes_with_mask(self, devices8):
        q, k, v = _qkv(seed=5)
        r = np.random.default_rng(5)
        # key 0 stays valid: under causal+mask a query with NO visible
        # keys is NaN in the dense softmax golden but a guarded 0 in the
        # ring's online softmax — ring's behavior is the useful one, and
        # the golden comparison needs every query to see >= 1 key
        mask = jnp.asarray(r.random((2, 64)) > 0.2).at[:, 0].set(True)
        out = ring_attention(q, k, v, causal=True, kv_mask=mask)
        ref = dense_attention(q, k, v, causal=True, kv_mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_fully_masked_query_is_zero_not_nan(self, devices8):
        """A query whose every visible key is padding returns 0 output
        (the online-softmax accumulators never fire), not NaN."""
        q, k, v = _qkv(seed=7)
        mask = jnp.zeros((2, 64), bool)
        out = np.asarray(ring_attention(q, k, v, kv_mask=mask))
        assert np.all(np.isfinite(out)) and np.all(out == 0.0)

    def test_dense_golden_gives_a_fully_masked_query_zero_too(self, devices8):
        """The golden agrees with the ring where no key is visible: key 0
        masked under causal masking leaves query 0 with nothing to see."""
        q, k, v = _qkv(seed=8)
        mask = jnp.ones((2, 64), bool).at[:, 0].set(False)
        ref = np.asarray(dense_attention(q, k, v, causal=True, kv_mask=mask))
        assert np.all(np.isfinite(ref)) and np.all(ref[:, 0] == 0.0)
        out = ring_attention(q, k, v, causal=True, kv_mask=mask)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)
        none = np.asarray(dense_attention(q, k, v, kv_mask=jnp.zeros((2, 64), bool)))
        assert np.all(none == 0.0)

    def test_single_device_mask(self):
        from jax.sharding import Mesh

        q, k, v = _qkv(seed=6)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        mask = jnp.asarray(np.arange(64)[None, :].repeat(2, 0) < 50)
        out = ring_attention(q, k, v, mesh=mesh, kv_mask=mask)
        ref = dense_attention(q, k, v, kv_mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestGradients:
    """Training through the ring is first-class: gradients flow through
    ppermute rotation + online softmax and match the dense reference."""

    def test_grad_matches_dense(self, devices8):
        q, k, v = _qkv(seed=8)
        r = np.random.default_rng(8)
        mask = jnp.asarray(r.random((2, 64)) > 0.3).at[:, 0].set(True)

        def lr(q, k, v):
            return (ring_attention(q, k, v, causal=True, kv_mask=mask) ** 2).sum()

        def ld(q, k, v):
            return (dense_attention(q, k, v, causal=True, kv_mask=mask) ** 2).sum()

        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gd):
            a, b = np.asarray(a), np.asarray(b)
            assert np.isfinite(a).all()
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_grad_finite_under_full_masking(self, devices8):
        """Queries whose every visible key is padding must produce ZERO
        (not NaN) gradients — the -inf score guards must not poison the
        backward pass (the classic where/-inf autodiff trap)."""
        q, k, v = _qkv(seed=9)
        mask = jnp.zeros((2, 64), bool)

        def lr(q, k, v):
            return (ring_attention(q, k, v, kv_mask=mask) ** 2).sum()

        gq, gk, gv = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for g in (gq, gk, gv):
            g = np.asarray(g)
            assert np.isfinite(g).all()
            assert (g == 0).all()
