"""Per-linear reference executor for the quant-plan parity tests.

One linear at a time through the single-linear entry points: stage 1 is
``core.gptq.gptq_quantize`` on the damped Hessian's Cholesky factor,
stage 2 ``core.rpiq.rpiq_refine``; starved lanes get RTN. A stacked
member (an MoE expert slab) runs lane by lane and its results are stacked
back. Unaligned members take the program's own fallback path. The
signature matches :func:`repro.core.plan.execute_plan`, so a test can
monkeypatch it in for a whole-model run.
"""
import jax.numpy as jnp
import numpy as np

from repro.core import hessian as hess
from repro.core import plan as qplan
from repro.core.gptq import gptq_quantize, rtn_quantize
from repro.core.rpiq import rpiq_refine


def _lanes(m: qplan.PlanMember):
    """(name, w_oi, HessianState, x_last, x_count, starved) per lane."""
    sv = m.starved_mask()
    if not m.stacked:
        yield m.name, m.w_oi, m.hessian, m.x_last, m.x_count, bool(sv[0])
        return
    counts = jnp.asarray(m.hessian.count, jnp.int32).reshape(-1)
    xc = (None if m.x_count is None
          else jnp.asarray(m.x_count, jnp.int32).reshape(-1))
    for li, name in enumerate(m.lane_names):
        yield (name, m.w_oi[li], hess.HessianState(m.hessian.H[li],
                                                   counts[li]),
               m.x_last[li], None if xc is None else xc[li], bool(sv[li]))


def _quantize_lane(qc, name, w, st, x, x_count, starved, report,
                   rpiq_enabled):
    w = jnp.asarray(w, jnp.float32)
    shape = tuple(w.shape)
    if starved:
        r = rtn_quantize(w, bits=qc.bits, group_size=qc.group_size,
                         symmetric=qc.symmetric)
        report.linears.append(qplan.LinearRecord(
            name, shape, 0.0, [], 0.0, 0, "rtn-fallback"))
        return r.w_q, (r.scales, r.zeros)
    hd = hess.damped(st, qc.percdamp)
    res1 = gptq_quantize(w, hess.cholesky_inverse_upper(hd), bits=qc.bits,
                         group_size=qc.group_size, blocksize=qc.blocksize,
                         symmetric=qc.symmetric, impl=qc.gptq_impl)
    grid = (res1.scales, res1.zeros)
    if not rpiq_enabled or qc.rpiq_iters <= 0:
        report.linears.append(qplan.LinearRecord(
            name, shape, float(res1.err), [], 0.0, 0, "gptq"))
        return res1.w_q, grid
    res2 = rpiq_refine(res1.w_q, w, jnp.asarray(x, jnp.float32), hd,
                       res1.scales, res1.zeros, h_count=st.count,
                       x_count=x_count, bits=qc.bits,
                       group_size=qc.group_size, block_size=qc.blocksize,
                       alpha=qc.rpiq_alpha, t_max=qc.rpiq_iters,
                       early_stop=qc.rpiq_early_stop,
                       exact_gram=not qc.rpiq_use_global_hessian,
                       symmetric=qc.symmetric, impl=qc.rpiq_impl)
    gamma = [float(g) for g in np.asarray(res2.loss_history)
             if np.isfinite(g)]
    report.linears.append(qplan.LinearRecord(
        name, shape, float(res1.err), gamma, float(res2.proj_loss),
        int(res2.iters_run), "rpiq"))
    return res2.w_q, grid


def execute_plan_per_linear(qc, plan, report, rpiq_enabled=True, **_):
    """Every group member lane by lane, then every fallback; returns
    {member name → MemberResult} like ``execute_plan``."""
    out = {}
    for group in plan.groups:
        for m in group.members:
            lanes = [_quantize_lane(qc, *lane, report, rpiq_enabled)
                     for lane in _lanes(m)]
            if m.stacked:
                out[m.name] = qplan.MemberResult(
                    m.name, jnp.stack([w for w, _ in lanes]),
                    (jnp.stack([g[0] for _, g in lanes]),
                     jnp.stack([g[1] for _, g in lanes])))
            else:
                out[m.name] = qplan.MemberResult(m.name, *lanes[0])
    for m in plan.fallbacks:
        out[m.name] = qplan._execute_fallback(qc, m, report)
    return out
