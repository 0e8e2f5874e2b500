"""Property tests for the shared int8 block codec (kernels/kv_codec.py).

One suite pins the invariants BOTH consumers rely on — the gradient wire
format (distributed/compression.py) and the quantized decode KV cache
(models/attention.py):

- round-trip error per element is bounded by half its block's scale
- all-zero blocks reconstruct exactly
- the flat codec's zero-padding tail never leaks into real elements
- enc∘dec∘enc is code-bitwise idempotent (requantizing a reconstruction
  reproduces the codes) on non-degenerate inputs
- the compression-module wrappers are bitwise the codec at WIRE_BLOCK=256
  (the wire format predates the shared codec and must not move)

Runs under tests/_hypothesis_shim.py: real hypothesis when installed, a
deterministic bounds+midpoint grid otherwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_shim import given, settings, st

from repro.distributed import compression as C
from repro.kernels import kv_codec


def _rand(shape, seed, scale=3.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0.0, scale, shape).astype(np.float32))


class TestFlatCodec:
    """enc_int8/dec_int8 — the ravel-pad-block wire entry point."""

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(min_value=1, max_value=700),
           seed=st.integers(min_value=0, max_value=3))
    def test_roundtrip_error_bound(self, n, seed):
        x = _rand((n,), seed)
        q, s = kv_codec.enc_int8(x)
        y = kv_codec.dec_int8(q, s, x.shape)
        # element i lives in block i // 256; |x - dec(enc(x))| <= scale/2
        per_elem_scale = np.repeat(np.asarray(s), kv_codec.WIRE_BLOCK)[:n]
        err = np.abs(np.asarray(y) - np.asarray(x))
        assert np.all(err <= per_elem_scale / 2 + 1e-7)

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(min_value=1, max_value=600))
    def test_all_zero_exact(self, n):
        x = jnp.zeros((n,), jnp.float32)
        q, s = kv_codec.enc_int8(x)
        assert not np.any(np.asarray(q))
        np.testing.assert_array_equal(
            np.asarray(kv_codec.dec_int8(q, s, x.shape)), 0.0)

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(min_value=1, max_value=700),
           seed=st.integers(min_value=0, max_value=3))
    def test_padding_tail_invariance(self, n, seed):
        """Encoding a ragged tail == encoding the explicitly zero-padded
        tensor then truncating — the pad never changes real elements."""
        x = _rand((n,), seed)
        blk = kv_codec.WIRE_BLOCK
        pad = (-n) % blk
        xp = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
        q1, s1 = kv_codec.enc_int8(x)
        q2, s2 = kv_codec.enc_int8(xp)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        np.testing.assert_array_equal(
            np.asarray(kv_codec.dec_int8(q1, s1, x.shape)),
            np.asarray(kv_codec.dec_int8(q2, s2, xp.shape))[:n])

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(min_value=1, max_value=700),
           seed=st.integers(min_value=0, max_value=3))
    def test_enc_dec_enc_idempotent(self, n, seed):
        """Requantizing a reconstruction is a code-level fixed point.
        (Scales match to ~1 ulp, not bitwise; degenerate eps-dominated
        blocks are excluded by the non-tiny magnitudes of _rand.)"""
        x = _rand((n,), seed)
        q1, s1 = kv_codec.enc_int8(x)
        y = kv_codec.dec_int8(q1, s1, x.shape)
        q2, s2 = kv_codec.enc_int8(y)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-6)


class TestBlockCodec:
    """enc_int8_blocks/dec_int8_blocks — the trailing-dim KV entry point."""

    @settings(max_examples=20, deadline=None)
    @given(block=st.sampled_from([32, 64, 128, 256]),
           nb=st.integers(min_value=1, max_value=3),
           seed=st.integers(min_value=0, max_value=2))
    def test_roundtrip_error_bound(self, block, nb, seed):
        x = _rand((2, 5, nb * block), seed)
        q, s = kv_codec.enc_int8_blocks(x, block)
        assert q.shape == x.shape and q.dtype == jnp.int8
        assert s.shape == x.shape[:-1] + (nb,)
        y = kv_codec.dec_int8_blocks(q, s, block)
        bound = np.repeat(np.asarray(s), block, axis=-1) / 2
        assert np.all(np.abs(np.asarray(y) - np.asarray(x))
                      <= bound + 1e-7)

    @settings(max_examples=10, deadline=None)
    @given(block=st.sampled_from([32, 64, 128, 256]))
    def test_all_zero_exact(self, block):
        x = jnp.zeros((3, 2, block), jnp.float32)
        q, s = kv_codec.enc_int8_blocks(x, block)
        np.testing.assert_array_equal(
            np.asarray(kv_codec.dec_int8_blocks(q, s, block)), 0.0)

    @settings(max_examples=20, deadline=None)
    @given(block=st.sampled_from([32, 64, 128, 256]),
           seed=st.integers(min_value=0, max_value=2))
    def test_enc_dec_enc_idempotent(self, block, seed):
        x = _rand((4, 2 * block), seed)
        q1, s1 = kv_codec.enc_int8_blocks(x, block)
        y = kv_codec.dec_int8_blocks(q1, s1, block)
        q2, s2 = kv_codec.enc_int8_blocks(y, block)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-6)

    def test_rejects_ragged_trailing_dim(self):
        with pytest.raises(AssertionError):
            kv_codec.enc_int8_blocks(jnp.zeros((2, 65)), 64)


class TestDefaultBlock:
    def test_prefers_largest_divisor(self):
        assert kv_codec.default_kv_block(128) == 128
        assert kv_codec.default_kv_block(256) == 128
        assert kv_codec.default_kv_block(64) == 64
        assert kv_codec.default_kv_block(96) == 32
        assert kv_codec.default_kv_block(80) == 80   # no divisor -> whole dim


class TestWireFormatPinned:
    """The gradient wire format must be bitwise what it was before the
    codec was extracted: per-256-block absmax, eps 1e-12, round+clip."""

    def test_wrappers_are_the_codec_at_wire_block(self):
        g = _rand((3, 7, 19), 0)
        q1, s1 = C._enc_int8(g.astype(jnp.float32))
        q2, s2 = kv_codec.enc_int8(g.astype(jnp.float32), block=256)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        np.testing.assert_array_equal(
            np.asarray(C._dec_int8(q1, s1, g.shape)),
            np.asarray(kv_codec.dec_int8(q2, s2, g.shape, block=256)))

    def test_bitwise_vs_inline_reference(self):
        """Inline re-statement of the pre-extraction math."""
        g = _rand((1000,), 1)
        flat = np.asarray(g, np.float32)
        n = flat.size
        nb = -(-n // 256)
        padded = np.zeros((nb * 256,), np.float32)
        padded[:n] = flat
        blocks = padded.reshape(nb, 256)
        scale = np.max(np.abs(blocks), axis=1) / 127.0 + 1e-12
        ref_q = np.clip(np.round(blocks / scale[:, None]), -127, 127
                        ).astype(np.int8)
        q, s = C._enc_int8(g)
        np.testing.assert_array_equal(np.asarray(q), ref_q)
        np.testing.assert_allclose(np.asarray(s), scale.astype(np.float32),
                                   rtol=0, atol=0)

    def test_compress_psum_int8_unchanged(self):
        """End-to-end wire path still reconstructs within codec error."""
        grads = {"w": _rand((300,), 2)}

        def f(g):
            out, err = C.compress_psum(g, "data", method="int8")
            return out, err

        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        out, err = jax.shard_map(
            f, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),),
            out_specs=jax.sharding.PartitionSpec(), check_vma=False)(grads)
        q, s = C._enc_int8(grads["w"])
        per_elem = np.repeat(np.asarray(s), 256)[:300]
        assert np.all(np.abs(np.asarray(out["w"]) - np.asarray(grads["w"]))
                      <= per_elem / 2 + 1e-7)
        np.testing.assert_allclose(np.asarray(grads["w"]),
                                   np.asarray(out["w"]) + np.asarray(err["w"]),
                                   rtol=1e-5, atol=1e-6)
