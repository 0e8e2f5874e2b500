"""Fused Pallas gptq_block kernel vs the XLA sweep and the NumPy oracle.

The kernel mirrors ``core/gptq._gptq_core`` op for op (masked one-hot
extractions are exact, the tail update uses identical dot shapes), so
interpret-mode output is pinned bitwise-close (≤1e-6) across symmetric/
asymmetric modes, group sizes, non-square shapes, a padded-Cout row tile,
and the stacked member axis the quant plan feeds it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_batched_parity import stack_problem  # noqa: F401  (fixture reuse)

from repro.core import hessian as hess
from repro.core.gptq import _gptq_core, gptq_quantize_batched
from repro.kernels import ops as kops
from repro.kernels import ref

pytestmark = pytest.mark.pallas


def _problem(cout, cin, seed=0):
    kw, kx = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(kw, (cout, cin)) * 0.1
    x = jax.random.normal(kx, (2 * cin, cin))
    st = hess.accumulate(hess.init_hessian(cin), x)
    u = hess.cholesky_inverse_upper(hess.damped(st, 0.01))
    return w, u


class TestGPTQBlockKernel:
    @pytest.mark.parametrize("symmetric", [False, True])
    @pytest.mark.parametrize("group_size,blocksize", [(64, 64), (128, 128),
                                                      (64, 128)])
    def test_matches_core_and_ref(self, symmetric, group_size, blocksize):
        """Non-square (48, 256): pallas == _gptq_core == NumPy oracle."""
        w, u = _problem(48, 256, seed=group_size + blocksize + symmetric)
        kw = dict(bits=4, group_size=group_size, blocksize=blocksize,
                  symmetric=symmetric)
        w_q, s, z, err = kops.gptq_block(w, u, impl="pallas", **kw)
        core = _gptq_core(w, u, **kw)
        np.testing.assert_allclose(np.asarray(w_q), np.asarray(core.w_q),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(s), np.asarray(core.scales),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(z), np.asarray(core.zeros),
                                   atol=1e-6)
        np.testing.assert_allclose(float(err), float(core.err), rtol=1e-4)
        wq_r, s_r, z_r, err_r = ref.gptq_block_ref(
            np.asarray(w), np.asarray(u), **kw)
        np.testing.assert_allclose(np.asarray(w_q), wq_r, atol=1e-6)
        np.testing.assert_allclose(np.asarray(s), s_r, atol=1e-6)
        np.testing.assert_allclose(np.asarray(z), z_r, atol=1e-6)

    def test_padded_cout_tile(self):
        """Cout = 20 with an explicit block_out = 8 → zero-padded row tile
        (24 rows, 3 grid tiles); padded rows must not perturb real ones."""
        w, u = _problem(20, 128, seed=3)
        kw = dict(bits=4, group_size=64, blocksize=64)
        w_q, s, z, err = kops.gptq_block(w, u, impl="pallas", block_out=8,
                                         **kw)
        core = _gptq_core(w, u, symmetric=False, **kw)
        assert w_q.shape == (20, 128) and s.shape == (20, 2)
        np.testing.assert_allclose(np.asarray(w_q), np.asarray(core.w_q),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(s), np.asarray(core.scales),
                                   atol=1e-6)
        np.testing.assert_allclose(float(err), float(core.err), rtol=1e-4)

    def test_streams_u_over_lazy_blocks(self):
        """(20, 512) in blocks of 128 with block_out = 8: U streams in four
        row slabs through three zero-padded row tiles, and the scales,
        zeros and Σerr² carried between lazy blocks match the XLA sweep
        and the NumPy oracle."""
        w, u = _problem(20, 512, seed=5)
        kw = dict(bits=4, group_size=64, blocksize=128, symmetric=False)
        w_q, s, z, err = kops.gptq_block(w, u, impl="pallas", block_out=8,
                                         **kw)
        core = _gptq_core(w, u, **kw)
        wq_r, s_r, z_r, _ = ref.gptq_block_ref(np.asarray(w), np.asarray(u),
                                               **kw)
        assert w_q.shape == (20, 512) and s.shape == (20, 8)
        for got, want in ((w_q, core.w_q), (s, core.scales),
                          (z, core.zeros), (w_q, wq_r), (s, s_r), (z, z_r)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6)
        np.testing.assert_allclose(float(err), float(core.err), rtol=1e-4)

    def test_row_tile_choice(self, monkeypatch):
        """The streamed residency is linear in Cin: the 128-row tile fits
        at in 768 and 3072 and not at 4096, short layers take their rows
        rounded up to 8, an explicit block_out is the tile that is
        checked, and a zero budget fits nothing, so "auto" on a TPU takes
        the counted XLA fallback (no kernel runs here)."""
        for in_dim in (768, 3072):
            assert kops._gptq_block_out(768, in_dim, 128, 128) == (128, True)
            assert kops._gptq_vmem_bytes(128, in_dim, 128, 128) <= \
                kops._VMEM_BUDGET_BYTES
        assert kops._gptq_block_out(768, 4096, 128, 128) == (128, False)
        assert kops._gptq_block_out(20, 768, 128, 128) == (24, True)
        assert kops._gptq_block_out(768, 3072, 128, 128,
                                    block_out=256) == (256, False)
        w, u = _problem(16, 128, seed=7)
        monkeypatch.setattr(kops, "_VMEM_BUDGET_BYTES", 0)
        assert kops._gptq_block_out(768, 768, 128, 128) == (128, False)
        monkeypatch.setattr(kops, "_on_tpu", lambda: True)
        kops.reset_fallback_stats()
        kw = dict(bits=4, group_size=64, blocksize=64)
        with pytest.warns(RuntimeWarning, match="vmem-budget"):
            w_q, *_ = kops.gptq_block(w, u, impl="auto", **kw)
        assert kops.fallback_stats() == {"gptq_block:vmem-budget": 1}
        np.testing.assert_array_equal(
            np.asarray(w_q), np.asarray(kops.gptq_block(w, u, impl="xla",
                                                        **kw)[0]))

    def test_batched_member_axis(self, stack_problem):
        """The stacked group slab maps onto the kernel's member grid axis:
        every lane matches the XLA batched path and per-member core."""
        p = stack_problem
        Hd = hess.damped(p["st"], 0.01)
        U = hess.cholesky_inverse_upper(Hd)
        kw = dict(bits=4, group_size=32, blocksize=64)
        res_p = gptq_quantize_batched(p["W"], U, impl="pallas", **kw)
        res_x = gptq_quantize_batched(p["W"], U, impl="xla", **kw)
        np.testing.assert_allclose(np.asarray(res_p.w_q),
                                   np.asarray(res_x.w_q), atol=1e-6)
        np.testing.assert_allclose(np.asarray(res_p.scales),
                                   np.asarray(res_x.scales), atol=1e-6)
        for i in range(p["B"]):
            r = _gptq_core(p["W"][i], U[i], symmetric=False, **kw)
            np.testing.assert_allclose(np.asarray(res_p.w_q[i]),
                                       np.asarray(r.w_q), atol=1e-6)

    def test_auto_impl_off_tpu_is_xla(self, stack_problem):
        p = stack_problem
        U = hess.cholesky_inverse_upper(hess.damped(p["st"], 0.01))
        kw = dict(bits=4, group_size=32, blocksize=64)
        res_a = gptq_quantize_batched(p["W"], U, impl="auto", **kw)
        res_x = gptq_quantize_batched(p["W"], U, impl="xla", **kw)
        np.testing.assert_array_equal(np.asarray(res_a.w_q),
                                      np.asarray(res_x.w_q))


class TestServingArtifactParity:
    def test_packed_artifacts_match_across_impls(self):
        """End to end: quantize + pack a tiny model under each sweep
        backend — packed int4 codes and grids must agree ≤1e-6."""
        from repro.configs import get_config
        from repro.core.pipeline import pack_for_serving, quantize_model
        from repro.core.quant import QuantizedTensor
        from repro.data import MarkovLM, calibration_batches
        from repro.models import transformer as T

        packs = []
        for impl in ("xla", "pallas"):
            cfg = get_config("opt-proxy", smoke=True)
            cfg.model.num_layers = 2
            cfg.quant.gptq_impl = impl
            cfg.quant.rpiq_iters = 2
            params = T.init_params(cfg.model, jax.random.PRNGKey(0))
            calib = calibration_batches(MarkovLM(cfg.model.vocab_size,
                                                 seed=2), 2, 2, 16)
            pq, _ = quantize_model(cfg, params, calib)
            packs.append(pack_for_serving(cfg, pq))
        flat0 = jax.tree_util.tree_leaves(
            packs[0], is_leaf=lambda x: isinstance(x, QuantizedTensor))
        flat1 = jax.tree_util.tree_leaves(
            packs[1], is_leaf=lambda x: isinstance(x, QuantizedTensor))
        assert len(flat0) == len(flat1)
        n_packed = 0
        for a, b in zip(flat0, flat1):
            if isinstance(a, QuantizedTensor):
                n_packed += 1
                np.testing.assert_array_equal(np.asarray(a.packed),
                                              np.asarray(b.packed))
                np.testing.assert_allclose(np.asarray(a.scales),
                                           np.asarray(b.scales), atol=1e-6)
                np.testing.assert_allclose(np.asarray(a.zeros),
                                           np.asarray(b.zeros), atol=1e-6)
            else:
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           atol=1e-6)
        assert n_packed > 0
