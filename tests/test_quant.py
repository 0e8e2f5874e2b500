"""Quantization grid primitives: invariants + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core.quant import (QuantParams, compute_qparams, dequantize_codes,
                              fake_quantize, pack_int4, pack_quantized,
                              quantize_codes, unpack_int4, dequantize_packed)


def _rand(shape, seed=0, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


class TestGrid:
    def test_codes_in_range(self):
        w = _rand((16, 64), 1)
        qp = compute_qparams(w, 4, 16)
        q = quantize_codes(w, qp, 4, 16)
        assert int(q.min()) >= 0 and int(q.max()) <= 15

    def test_fake_quant_idempotent(self):
        w = _rand((8, 32), 2)
        w1 = fake_quantize(w, 4, 16)
        qp = compute_qparams(w1, 4, 16)
        w2 = fake_quantize(w1, 4, 16, qp=qp)
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=2e-5)

    def test_zero_column_safe(self):
        w = jnp.zeros((4, 32))
        out = fake_quantize(w, 4, 16)
        assert not bool(jnp.any(jnp.isnan(out)))

    def test_symmetric_grid(self):
        w = _rand((8, 32), 3)
        out = fake_quantize(w, 4, 16, symmetric=True)
        err = float(jnp.max(jnp.abs(out - w)))
        qp = compute_qparams(w, 4, 16, symmetric=True)
        assert err <= float(jnp.max(qp.scales)) * 0.51 + 1e-6

    @given(bits=st.sampled_from([2, 3, 4, 8]),
           rows=st.integers(1, 8), groups=st.integers(1, 4))
    @settings(max_examples=20, deadline=None)
    def test_roundtrip_error_bound(self, bits, rows, groups):
        g = 16
        w = _rand((rows, groups * g), seed=bits * 100 + rows)
        qp = compute_qparams(w, bits, g)
        out = fake_quantize(w, bits, g, qp=qp)
        # |w - Q(w)| <= scale/2 elementwise (within-range values)
        s = jnp.repeat(qp.scales, g, axis=1)
        assert bool(jnp.all(jnp.abs(out - w) <= s * 0.5 + 1e-5))


class TestPacking:
    def test_pack_unpack_roundtrip(self):
        q = jax.random.randint(jax.random.PRNGKey(0), (8, 64), 0, 16)
        packed = pack_int4(q)
        assert packed.shape == (8, 32) and packed.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                      np.asarray(q))

    @given(rows=st.integers(1, 8), cols=st.integers(1, 16))
    @settings(max_examples=20, deadline=None)
    def test_pack_unpack_property(self, rows, cols):
        q = np.random.RandomState(rows * 17 + cols).randint(
            0, 16, (rows, cols * 2))
        packed = pack_int4(jnp.asarray(q))
        np.testing.assert_array_equal(np.asarray(unpack_int4(packed)), q)

    @pytest.mark.parametrize("n,k,g", [(16, 128, 32), (64, 256, 128),
                                       (256, 512, 128), (32, 128, 64)])
    def test_pack_quantized_dequant(self, n, k, g):
        w = _rand((n, k), 5)
        qt = pack_quantized(w, 4, g)
        deq = dequantize_packed(qt)
        ref = fake_quantize(w, 4, g)
        np.testing.assert_allclose(np.asarray(deq), np.asarray(ref),
                                   atol=1e-5)

    def test_quantized_tensor_is_pytree(self):
        w = _rand((8, 64), 6)
        qt = pack_quantized(w, 4, 32)
        leaves = jax.tree_util.tree_leaves(qt)
        assert len(leaves) == 3
        qt2 = jax.tree_util.tree_map(lambda x: x, qt)
        assert qt2.group_size == qt.group_size and qt2.shape == qt.shape

    def test_quantized_tensor_under_jit(self):
        from repro.models.linear import dense
        w = _rand((64, 32), 7)       # (in, out) model layout
        from repro.core.pipeline import pack_for_serving
        qt = pack_quantized(w.T, 4, 32)   # (out, in)-major
        x = _rand((4, 64), 8)
        y = jax.jit(lambda p, x: dense(p, x))({"w": qt}, x)
        y_ref = x @ dequantize_packed(qt).T.astype(x.dtype)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-2, atol=2e-2)
