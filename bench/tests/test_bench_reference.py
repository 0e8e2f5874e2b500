"""The benchmark's plain reference against the program, at smoke size.

The reference imports nothing of the program; these tests are where the
two meet: GPTQ on one Hessian, and stage 2 on one instance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as ref


def _problem(seed=1, n=512, d=64, out=48):
    rng = np.random.default_rng(seed)
    mix = rng.standard_normal((d, d)).astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32) @ mix
    w = rng.standard_normal((out, d)).astype(np.float32) / 8
    return x, w, x.T @ x / n


def test_reference_gptq_matches_the_programs():
    from repro.core import hessian as hess
    from repro.core.gptq import gptq_from_hessian
    x, w, h = _problem()
    with jax.default_matmul_precision("highest"):
        state = hess.accumulate(hess.init_hessian(64), jnp.asarray(x))
        res = gptq_from_hessian(jnp.asarray(w), state, group_size=16,
                                blocksize=16, percdamp=0.01)
        ref_w, scales, zeros = ref.gptq(jnp.asarray(w), jnp.asarray(h), 4,
                                        16, 0.01)
        e_prog = float(ref.proxy_error(w, res.w_q, jnp.asarray(h)))
        e_ref = float(ref.proxy_error(w, ref_w, jnp.asarray(h)))
        e_rtn = float(ref.proxy_error(
            w, ref.rtn_dequant(jnp.asarray(w).T, 4, 16).T, jnp.asarray(h)))
    assert abs(e_prog / e_ref - 1.0) < 0.02
    assert e_ref < 0.8 * e_rtn      # correlated inputs: GPTQ beats rounding
    # the weights lie on the grid the reference returns
    s = np.repeat(np.asarray(scales), 16, axis=1)
    z = np.repeat(np.asarray(zeros), 16, axis=1)
    codes = np.asarray(ref_w) / s + z
    np.testing.assert_allclose(codes, np.round(codes), atol=1e-3)
    assert codes.min() > -1e-3 and codes.max() < 15 + 1e-3


def _rtn(w, group=16, qmax=15.0):
    """Round-to-nearest on per-(row, group) min-max grids: (weights,
    scales, zeros)."""
    g = w.reshape(w.shape[0], -1, group)
    lo, hi = np.minimum(g.min(-1), 0.0), np.maximum(g.max(-1), 0.0)
    scales = np.maximum((hi - lo) / qmax, 1e-8).astype(np.float32)
    zeros = np.clip(np.round(-lo / scales), 0.0, qmax).astype(np.float32)
    s, z = (np.repeat(a, group, axis=1) for a in (scales, zeros))
    wq = (np.clip(np.round(w / s) + z, 0.0, qmax) - z) * s
    return jnp.asarray(wq), jnp.asarray(scales), jnp.asarray(zeros)


@pytest.mark.parametrize("alpha,start", [(0.01, "gptq"), (0.3, "gptq"),
                                         (1.0, "rtn")])
def test_reference_stage2_matches_the_programs(alpha, start):
    """The program's stage 2 (its XLA path, f32 at highest) and the
    reference's, from the same stage-1 result: the same residual history,
    rounds and kept weights. From GPTQ's result the kept projection stays
    GPTQ's; from rounding it moves."""
    from repro.core.rpiq import rpiq_refine
    x, w, h = _problem(seed=2, n=256)
    s2 = ref.Stage2(block=16, alpha=alpha, rounds=5, early_stop=True)
    with jax.default_matmul_precision("highest"):
        if start == "gptq":
            w1, scales, zeros = ref.gptq(jnp.asarray(w), jnp.asarray(h), 4,
                                         16, 0.01)
        else:
            w1, scales, zeros = _rtn(w)
        hd = h + 0.01 * np.mean(np.diag(h)) * np.eye(64, dtype=np.float32)
        got = rpiq_refine(w1, jnp.asarray(w), jnp.asarray(x),
                          jnp.asarray(hd * 256), scales, zeros, bits=4,
                          group_size=16, block_size=16, alpha=alpha,
                          t_max=5, impl="xla")
        kept, hist, rounds = ref.stage2(jnp.asarray(w), w1, scales, zeros,
                                        jnp.asarray(x),
                                        jnp.asarray(hd * 256), 4, 16, s2)
    assert int(rounds) == int(got.iters_run)
    want = np.asarray(got.loss_history)
    mine = np.asarray(hist)
    live = np.isfinite(want)
    np.testing.assert_array_equal(live, np.isfinite(mine))
    # at alpha 1 the iterate sits on the grid, where one rounding tie
    # decided the other way moves a late round's residual by ~0.2 %
    np.testing.assert_allclose(mine[live], want[live], rtol=5e-3)
    np.testing.assert_allclose(np.asarray(kept), np.asarray(got.w_q),
                               atol=1e-5)
    moved = int(np.sum(np.asarray(kept) != np.asarray(w1)))
    assert (moved > 0) == (start == "rtn")
