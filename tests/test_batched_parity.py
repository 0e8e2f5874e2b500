"""Batched quant-plan executors vs singleton reference.

The whole point of the QuantPlan refactor is that grouping same-shape
linears into one vmapped dispatch changes NOTHING numerically: every test
here pins the batched entry points against mapping the single-linear
functions over the stack (the plan-level reference is the test-only
per-linear executor in tests/_perlinear.py), including the MoE
starved-expert RTN mask and the full pipeline on an MoE model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _perlinear import execute_plan_per_linear
from repro.configs import get_config
from repro.core import hessian as hess
from repro.core import plan as qplan
from repro.core.gptq import (gptq_quantize, gptq_quantize_batched,
                             rtn_quantize, rtn_quantize_batched)
from repro.core.rpiq import rpiq_refine, rpiq_refine_batched


@pytest.fixture(scope="module")
def stack_problem():
    """B same-shape linears with correlated inputs + accumulated Hessians."""
    B, Cout, Cin, N = 4, 48, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 3 * B)
    Ws, Xs, sts = [], [], []
    for i in range(B):
        W = jax.random.normal(ks[i], (Cout, Cin)) * 0.1
        A = jax.random.normal(ks[B + i], (Cin, Cin)) * 0.2 + jnp.eye(Cin)
        X = jax.random.normal(ks[2 * B + i], (N, Cin)) @ A
        st = hess.init_hessian(Cin)
        for b in range(2):
            st = hess.accumulate(st, X[b * 128:(b + 1) * 128])
        Ws.append(W)
        Xs.append(X[-128:])
        sts.append(st)
    return dict(W=jnp.stack(Ws), X=jnp.stack(Xs), sts=sts,
                st=hess.stack_states(sts), B=B, N=128)


class TestStackedHessian:
    def test_stacked_accumulate_matches_singleton(self):
        e, n, d = 3, 64, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (e, n, d))
        st = hess.accumulate(hess.init_hessian(d, batch=e), x)
        for i in range(e):
            ref = hess.accumulate(hess.init_hessian(d), x[i])
            np.testing.assert_allclose(np.asarray(st.H[i]),
                                       np.asarray(ref.H), rtol=1e-5,
                                       atol=1e-4)
        assert st.count.shape == (e,) and int(st.count[0]) == n

    def test_stacked_damped_and_cholesky(self, stack_problem):
        p = stack_problem
        Hd = hess.damped(p["st"], 0.01)
        U = hess.cholesky_inverse_upper(Hd)
        for i, st_i in enumerate(p["sts"]):
            Hd_i = hess.damped(st_i, 0.01)
            np.testing.assert_allclose(np.asarray(Hd[i]), np.asarray(Hd_i),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(U[i]),
                np.asarray(hess.cholesky_inverse_upper(Hd_i)),
                rtol=1e-4, atol=1e-5)

    def test_stacked_damped_dead_column_rescue(self):
        e, d = 2, 16
        x = jnp.zeros((e, 32, d)).at[:, :, :8].set(
            jax.random.normal(jax.random.PRNGKey(1), (e, 32, 8)))
        st = hess.accumulate(hess.init_hessian(d, batch=e), x)
        Hd = hess.damped(st, 0.01)
        assert np.linalg.eigvalsh(np.asarray(Hd)).min() > 0


class TestBatchedGPTQ:
    def test_matches_singleton_stack(self, stack_problem):
        p = stack_problem
        Hd = hess.damped(p["st"], 0.01)
        U = hess.cholesky_inverse_upper(Hd)
        res_b = gptq_quantize_batched(p["W"], U, bits=4, group_size=32,
                                      blocksize=64)
        for i in range(p["B"]):
            Hd_i = hess.damped(p["sts"][i], 0.01)
            r = gptq_quantize(p["W"][i], hess.cholesky_inverse_upper(Hd_i),
                              bits=4, group_size=32, blocksize=64)
            np.testing.assert_allclose(np.asarray(res_b.w_q[i]),
                                       np.asarray(r.w_q), atol=1e-5)
            np.testing.assert_allclose(np.asarray(res_b.scales[i]),
                                       np.asarray(r.scales), atol=1e-6)
            np.testing.assert_allclose(np.asarray(res_b.zeros[i]),
                                       np.asarray(r.zeros), atol=1e-6)
            np.testing.assert_allclose(float(res_b.err[i]), float(r.err),
                                       rtol=1e-3, atol=1e-4)

    def test_rtn_batched_matches_singleton(self, stack_problem):
        p = stack_problem
        res_b = rtn_quantize_batched(p["W"], bits=4, group_size=32)
        for i in range(p["B"]):
            r = rtn_quantize(p["W"][i], bits=4, group_size=32)
            np.testing.assert_array_equal(np.asarray(res_b.w_q[i]),
                                          np.asarray(r.w_q))


class TestBatchedRPIQ:
    def _stage1(self, p):
        Hd = hess.damped(p["st"], 0.01)
        return Hd, gptq_quantize_batched(p["W"], hess.cholesky_inverse_upper(
            Hd), bits=4, group_size=32, blocksize=64)

    def test_matches_singleton_stack(self, stack_problem):
        p = stack_problem
        Hd, res1 = self._stage1(p)
        xc = jnp.full((p["B"],), p["N"], jnp.int32)
        res2 = rpiq_refine_batched(res1.w_q, p["W"], p["X"], Hd, res1.scales,
                                   res1.zeros, h_count=p["st"].count,
                                   x_count=xc, bits=4, group_size=32,
                                   block_size=64, alpha=0.25, t_max=4)
        for i in range(p["B"]):
            r = rpiq_refine(res1.w_q[i], p["W"][i], p["X"][i], Hd[i],
                            res1.scales[i], res1.zeros[i],
                            h_count=p["sts"][i].count,
                            x_count=jnp.asarray(p["N"], jnp.int32),
                            bits=4, group_size=32, block_size=64,
                            alpha=0.25, t_max=4)
            np.testing.assert_allclose(np.asarray(res2.w_q[i]),
                                       np.asarray(r.w_q), atol=1e-5)
            np.testing.assert_allclose(float(res2.proj_loss[i]),
                                       float(r.proj_loss), rtol=1e-3)
            assert int(res2.iters_run[i]) == int(r.iters_run)

    def test_no_count_rescale_path(self, stack_problem):
        """h_count=None / x_count=None lanes (in_axes=None broadcast)."""
        p = stack_problem
        Hd, res1 = self._stage1(p)
        res2 = rpiq_refine_batched(res1.w_q, p["W"], p["X"], Hd, res1.scales,
                                   res1.zeros, bits=4, group_size=32,
                                   block_size=64, alpha=0.01, t_max=2)
        r = rpiq_refine(res1.w_q[0], p["W"][0], p["X"][0], Hd[0],
                        res1.scales[0], res1.zeros[0], bits=4, group_size=32,
                        block_size=64, alpha=0.01, t_max=2)
        np.testing.assert_allclose(np.asarray(res2.w_q[0]),
                                   np.asarray(r.w_q), atol=1e-5)

    def test_exact_gram_mode(self, stack_problem):
        p = stack_problem
        Hd, res1 = self._stage1(p)
        res2 = rpiq_refine_batched(res1.w_q, p["W"], p["X"], Hd, res1.scales,
                                   res1.zeros, bits=4, group_size=32,
                                   block_size=64, alpha=1.0, t_max=3,
                                   exact_gram=True)
        assert bool(jnp.all(res2.proj_loss <= res2.loss_history[:, 0] + 1e-4))

    @pytest.mark.pallas
    @pytest.mark.parametrize("exact_gram,alpha", [(False, 0.1), (True, 1.0)])
    def test_exact_gram_iters_parity_across_impls(self, stack_problem,
                                                  exact_gram, alpha):
        """iters_run (early-stop round count) must agree lane for lane
        between the singleton path, the batched XLA body, and the fused
        kernel — in both curvature modes."""
        p = stack_problem
        Hd, res1 = self._stage1(p)
        xc = jnp.full((p["B"],), p["N"], jnp.int32)
        kw = dict(bits=4, group_size=32, block_size=64, alpha=alpha,
                  t_max=5, exact_gram=exact_gram)
        res_b = rpiq_refine_batched(res1.w_q, p["W"], p["X"], Hd,
                                    res1.scales, res1.zeros,
                                    h_count=p["st"].count, x_count=xc,
                                    impl="xla", **kw)
        res_k = rpiq_refine_batched(res1.w_q, p["W"], p["X"], Hd,
                                    res1.scales, res1.zeros,
                                    h_count=p["st"].count, x_count=xc,
                                    impl="pallas", **kw)
        np.testing.assert_array_equal(np.asarray(res_b.iters_run),
                                      np.asarray(res_k.iters_run))
        np.testing.assert_allclose(np.asarray(res_b.w_q),
                                   np.asarray(res_k.w_q), atol=1e-6)
        for i in range(p["B"]):
            r = rpiq_refine(res1.w_q[i], p["W"][i], p["X"][i], Hd[i],
                            res1.scales[i], res1.zeros[i],
                            h_count=p["st"].count[i], x_count=xc[i], **kw)
            assert int(r.iters_run) == int(res_b.iters_run[i])
            np.testing.assert_allclose(np.asarray(res_b.w_q[i]),
                                       np.asarray(r.w_q), atol=1e-5)


class TestPlanExecution:
    def _members(self, p, starve=()):
        ms = []
        for i in range(p["B"]):
            ms.append(qplan.PlanMember(
                f"lin{i}", p["W"][i], p["sts"][i], p["X"][i],
                x_count=None, starved=i in starve))
        return ms

    def _qc(self):
        from repro.config import QuantConfig
        return QuantConfig(group_size=32, blocksize=64, rpiq_iters=3,
                           rpiq_alpha=0.25)

    def test_grouping(self, stack_problem):
        p = stack_problem
        qc = self._qc()
        ms = self._members(p)
        # a second shape class → its own group; unaligned → fallback
        odd_w = jax.random.normal(jax.random.PRNGKey(9), (8, 64)) * 0.1
        odd_x = jax.random.normal(jax.random.PRNGKey(10), (32, 64))
        ms.append(qplan.PlanMember(
            "odd", odd_w, hess.accumulate(hess.init_hessian(64), odd_x),
            odd_x, x_count=None))
        bad_w = jax.random.normal(jax.random.PRNGKey(11), (8, 72)) * 0.1
        bad_x = jax.random.normal(jax.random.PRNGKey(12), (32, 72))
        ms.append(qplan.PlanMember(
            "unaligned", bad_w, hess.accumulate(hess.init_hessian(72),
                                                bad_x), bad_x, x_count=None))
        plan = qplan.build_plan(qc, ms)
        sizes = sorted(len(g.members) for g in plan.groups)
        assert sizes == [1, p["B"]]
        assert [m.name for m in plan.fallbacks] == ["unaligned"]
        assert plan.n_members == len(ms)

    def test_batched_matches_singleton_execution(self, stack_problem):
        """Same plan through the executor and the per-linear reference →
        same weights, grids, modes — including the starved-member RTN
        mask."""
        p = stack_problem
        qc = self._qc()
        rep_b, rep_s = qplan.QuantReport(), qplan.QuantReport()
        plan_b = qplan.build_plan(qc, self._members(p, starve=(2,)))
        plan_s = qplan.build_plan(qc, self._members(p, starve=(2,)))
        out_b = qplan.execute_plan(qc, plan_b, rep_b)
        out_s = execute_plan_per_linear(qc, plan_s, rep_s)
        assert out_b.keys() == out_s.keys()
        for name in out_b:
            np.testing.assert_allclose(np.asarray(out_b[name].w_q),
                                       np.asarray(out_s[name].w_q),
                                       atol=2e-5)
            np.testing.assert_allclose(np.asarray(out_b[name].grid[0]),
                                       np.asarray(out_s[name].grid[0]),
                                       atol=1e-6)
        modes_b = {l.name: l.mode for l in rep_b.linears}
        modes_s = {l.name: l.mode for l in rep_s.linears}
        assert modes_b == modes_s
        assert modes_b["lin2"] == "rtn-fallback"
        assert rep_b.seconds_stage1 > 0 and rep_b.seconds_stage2 > 0

    def test_zero_token_starved_lane(self, stack_problem):
        """A starved member with ZERO routed tokens (H = 0, x_count = 0)
        must not poison the group: outputs stay finite, modes match the
        per-linear reference, and the lane's early stop fires instead of
        pinning the vmapped while_loop at t_max."""
        p = stack_problem
        qc = self._qc()
        in_dim = p["W"].shape[2]
        dead = qplan.PlanMember(
            "dead", jnp.zeros_like(p["W"][0]) + 0.05 * p["W"][0],
            hess.init_hessian(in_dim), jnp.zeros_like(p["X"][0]),
            x_count=jnp.zeros((), jnp.int32), starved=True)
        outs = {}
        for batched, execute in ((True, qplan.execute_plan),
                                 (False, execute_plan_per_linear)):
            rep = qplan.QuantReport()
            plan = qplan.build_plan(qc, self._members(p) + [dead])
            outs[batched] = execute(qc, plan, rep)
            assert {l.name: l.mode for l in rep.linears}["dead"] \
                == "rtn-fallback"
        for name in outs[True]:
            w_b, w_s = outs[True][name].w_q, outs[False][name].w_q
            assert not bool(jnp.any(jnp.isnan(w_b)))
            np.testing.assert_allclose(np.asarray(w_b), np.asarray(w_s),
                                       atol=2e-5)

    def test_fallback_starved_group_aligned_keeps_grid(self, stack_problem):
        """group_size-aligned but blocksize-unaligned starved expert still
        gets per-group RTN, with a stored grid."""
        qc = self._qc()                  # group 32, blocksize 64
        w = jax.random.normal(jax.random.PRNGKey(3), (8, 96)) * 0.1
        x = jnp.zeros((16, 96))
        m = qplan.PlanMember("starved96", w, hess.init_hessian(96), x,
                             x_count=jnp.zeros((), jnp.int32), starved=True)
        plan = qplan.build_plan(qc, [m])
        assert plan.groups == [] and len(plan.fallbacks) == 1
        rep = qplan.QuantReport()
        out = qplan.execute_plan(qc, plan, rep)["starved96"]
        assert out.grid is not None and out.grid[0].shape == (8, 3)
        from repro.core.gptq import rtn_quantize
        ref = rtn_quantize(w, bits=qc.bits, group_size=32)
        np.testing.assert_array_equal(np.asarray(out.w_q),
                                      np.asarray(ref.w_q))

    def test_stacked_member_matches_singletons(self, stack_problem):
        """A pre-stacked member (the MoE expert slab) must produce the
        same lanes as submitting its slices as singleton members."""
        p = stack_problem
        qc = self._qc()
        S = p["B"]
        stacked = qplan.PlanMember(
            "slab", p["W"], p["st"], p["X"],
            x_count=jnp.full((S,), p["N"], jnp.int32),
            starved=np.array([False, False, True, False]),
            names=[f"slab[{i}]" for i in range(S)])
        singles = [qplan.PlanMember(
            f"slab[{i}]", p["W"][i], p["sts"][i], p["X"][i],
            x_count=jnp.asarray(p["N"], jnp.int32), starved=(i == 2))
            for i in range(S)]
        rep_a, rep_b = qplan.QuantReport(), qplan.QuantReport()
        out_a = qplan.execute_plan(qc, qplan.build_plan(qc, [stacked]),
                                   rep_a)
        out_b = qplan.execute_plan(qc, qplan.build_plan(qc, singles),
                                   rep_b)
        assert out_a["slab"].w_q.shape == (S, *p["W"].shape[1:])
        for i in range(S):
            np.testing.assert_allclose(
                np.asarray(out_a["slab"].w_q[i]),
                np.asarray(out_b[f"slab[{i}]"].w_q), atol=2e-5)
        assert {l.name: l.mode for l in rep_a.linears} \
            == {l.name: l.mode for l in rep_b.linears}
        # the per-linear reference over the stacked member agrees too
        rep_c = qplan.QuantReport()
        out_c = execute_plan_per_linear(
            qc, qplan.build_plan(qc, [stacked]), rep_c)
        np.testing.assert_allclose(np.asarray(out_a["slab"].w_q),
                                   np.asarray(out_c["slab"].w_q), atol=2e-5)

    def test_stacked_fallback_mixed_lanes(self):
        """Unaligned stacked member: starved lanes RTN, others keep fp."""
        qc = self._qc()                  # group 32, blocksize 64
        w = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 96)) * 0.1
        x = jnp.zeros((2, 16, 96))
        m = qplan.PlanMember(
            "mix", w, hess.init_hessian(96, batch=2), x,
            x_count=jnp.zeros((2,), jnp.int32),
            starved=np.array([True, False]), names=["mix[0]", "mix[1]"])
        plan = qplan.build_plan(qc, [m])
        assert len(plan.fallbacks) == 1
        rep = qplan.QuantReport()
        out = qplan.execute_plan(qc, plan, rep)["mix"]
        modes = {l.name: l.mode for l in rep.linears}
        assert modes == {"mix[0]": "rtn-fallback", "mix[1]": "skipped"}
        from repro.core.gptq import rtn_quantize
        ref = rtn_quantize(w[0], bits=qc.bits, group_size=32)
        np.testing.assert_array_equal(np.asarray(out.w_q[0]),
                                      np.asarray(ref.w_q))
        np.testing.assert_array_equal(np.asarray(out.w_q[1]),
                                      np.asarray(w[1]))
        assert out.grid is None          # mixed lanes → no stored grid

    def test_gptq_only_mode(self, stack_problem):
        p = stack_problem
        qc = self._qc()
        qc.rpiq_iters = 0
        rep = qplan.QuantReport()
        plan = qplan.build_plan(qc, self._members(p))
        out = qplan.execute_plan(qc, plan, rep)
        assert all(l.mode == "gptq" for l in rep.linears)
        assert len(out) == p["B"]


class TestExecutorCache:
    """Cross-layer jit-cache sharing: identically keyed groups anywhere in
    the stack must reuse ONE compiled executor entry per stage."""

    def test_identical_groups_share_entries(self, stack_problem):
        p = stack_problem
        from repro.config import QuantConfig
        qc = QuantConfig(group_size=32, blocksize=64, rpiq_iters=2,
                         rpiq_alpha=0.25)
        qplan.clear_executor_cache()

        def run_once(tag):
            ms = [qplan.PlanMember(f"{tag}{i}", p["W"][i], p["sts"][i],
                                   p["X"][i], x_count=None)
                  for i in range(p["B"])]
            plan = qplan.build_plan(qc, ms)
            qplan.execute_plan(qc, plan, qplan.QuantReport())

        run_once("layer0.")          # cold: one miss per stage
        s1 = qplan.executor_cache_stats()
        assert s1 == {"hits": 0, "misses": 2}
        run_once("layer1.")          # same group signature → pure hits
        s2 = qplan.executor_cache_stats()
        assert s2 == {"hits": 2, "misses": 2}

    def test_new_signature_is_a_miss(self, stack_problem):
        p = stack_problem
        from repro.config import QuantConfig
        qc = QuantConfig(group_size=32, blocksize=64, rpiq_iters=2,
                         rpiq_alpha=0.25)
        qplan.clear_executor_cache()
        m = qplan.PlanMember("a", p["W"][0], p["sts"][0], p["X"][0],
                             x_count=None)
        qplan.execute_plan(qc, qplan.build_plan(qc, [m]),
                           qplan.QuantReport())
        # different group_size → different signature → fresh entries
        qc2 = QuantConfig(group_size=64, blocksize=64, rpiq_iters=2,
                          rpiq_alpha=0.25)
        m2 = qplan.PlanMember("b", p["W"][0], p["sts"][0], p["X"][0],
                              x_count=None)
        qplan.execute_plan(qc2, qplan.build_plan(qc2, [m2]),
                           qplan.QuantReport())
        st = qplan.executor_cache_stats()
        assert st["misses"] == 4 and st["hits"] == 0


@pytest.mark.slow
class TestPipelineParity:
    def test_moe_pipeline_batched_matches_perlinear(self, monkeypatch):
        """Quantized MoE params (8 experts) identical on a fixed seed
        whether groups run through the executor or the per-linear
        reference."""
        from repro.core.pipeline import quantize_model
        from repro.data import MarkovLM, calibration_batches

        from repro.models import transformer as T

        outs, reports = [], []
        for execute in (execute_plan_per_linear, qplan.execute_plan):
            monkeypatch.setattr(qplan, "execute_plan", execute)
            cfg = get_config("olmoe-1b-7b", smoke=True)
            mc = cfg.model
            params = T.init_params(mc, jax.random.PRNGKey(0))
            calib = calibration_batches(MarkovLM(mc.vocab_size, seed=1),
                                        3, 4, 24)
            pq, rep = quantize_model(cfg, params, calib)
            outs.append(pq)
            reports.append(rep)
        flat0 = jax.tree_util.tree_leaves(outs[0])
        flat1 = jax.tree_util.tree_leaves(outs[1])
        assert len(flat0) == len(flat1)
        for a, b in zip(flat0, flat1):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=2e-4)
        names0 = [(l.name, l.mode) for l in reports[0].linears]
        names1 = [(l.name, l.mode) for l in reports[1].linears]
        assert sorted(names0) == sorted(names1)
