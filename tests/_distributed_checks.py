"""Multi-device checks executed in a subprocess with forced host devices.

Invoked by test_distributed.py as::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tests/_distributed_checks.py <check-name>

Keeping these out of the main pytest process means unit tests still see the
single real CPU device.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402
from jax.sharding import AxisType             # noqa: E402


def check_sharded_train_matches_single():
    """Sharded (2 data × 2 model) train step == unsharded numerics."""
    from repro.configs import get_config
    from repro.data import MarkovLM
    from repro.distributed import sharding as shd
    from repro.training.train_step import init_train_state, make_train_step

    cfg = get_config("internlm2-1.8b", smoke=True)
    st = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = MarkovLM(cfg.model.vocab_size, seed=1).batch(4, 16)
    step = make_train_step(cfg)
    st1, m1 = jax.jit(step)(st, batch)

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rules = shd.make_rules(mesh, cfg.parallel)
    pshard = shd.param_shardings(st.params, rules)
    st_sh = st._replace(params=jax.device_put(st.params, pshard))
    bsh = jax.device_put(batch, shd.batch_shardings(batch, rules))

    def fn(state, batch):
        with shd.use_rules(rules):
            return step(state, batch)

    with mesh:
        st2, m2 = jax.jit(fn)(st_sh, bsh)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-2, \
        (float(m1["loss"]), float(m2["loss"]))
    for a, b in zip(jax.tree_util.tree_leaves(st1.params),
                    jax.tree_util.tree_leaves(st2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(jax.device_get(b), np.float32),
                                   rtol=3e-2, atol=3e-3)
    print("OK sharded==single")


def check_elastic_restore():
    """Checkpoint on a (4,) DP mesh, restore onto (2, 2) mesh."""
    from repro.configs import get_config
    from repro.distributed import sharding as shd
    from repro.distributed.checkpoint import Checkpointer
    from repro.training.train_step import init_train_state
    import tempfile

    cfg = get_config("opt-proxy", smoke=True)
    st = init_train_state(cfg, jax.random.PRNGKey(0))
    mesh1 = jax.make_mesh((4, 1), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    r1 = shd.make_rules(mesh1, cfg.parallel)
    st1 = st._replace(params=jax.device_put(
        st.params, shd.param_shardings(st.params, r1)))
    d = tempfile.mkdtemp()
    ck = Checkpointer(d, async_write=False)
    ck.save(1, st1, extra={"step": 1})

    mesh2 = jax.make_mesh((2, 2), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    r2 = shd.make_rules(mesh2, cfg.parallel)
    sh2 = shd.param_shardings(st.params, r2)
    restored, _ = ck.restore(st, shardings=None)
    params2 = jax.device_put(restored.params, sh2)
    for a, b in zip(jax.tree_util.tree_leaves(st.params),
                    jax.tree_util.tree_leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(jax.device_get(b)))
    print("OK elastic restore")


def check_grad_compression():
    """int8/bf16 compressed psum with error feedback ≈ exact mean over
    steps; single-step int8 error is bounded; error feedback shrinks bias."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import compress_psum

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 33))

    def run(method, steps=6):
        errs = []
        err = None
        acc_true = jnp.zeros((64, 33))
        acc_comp = jnp.zeros((64, 33))
        for s in range(steps):
            gs = g_global * (1.0 + 0.3 * s)

            def body(g, e):
                g = g[0]
                red, ne = compress_psum({"g": g}, "data", method,
                                        None if e is None else {"g": e[0]})
                ne_out = ne["g"] if ne is not None else jnp.zeros_like(g)
                return red["g"], ne_out[None] if ne_out.ndim == g.ndim \
                    else ne_out

            body_sm = jax.shard_map(
                lambda g, e: body(g, e), mesh=mesh,
                in_specs=(P("data"), P("data")),
                out_specs=(P(), P("data")), check_vma=False)
            e_in = jnp.zeros((8, 64, 33)) if err is None else err
            red, err = body_sm(gs, e_in)
            true = jnp.mean(gs, axis=0)
            acc_true = acc_true + true
            acc_comp = acc_comp + red
            errs.append(float(jnp.linalg.norm(red - true)
                              / jnp.linalg.norm(true)))
        cum = float(jnp.linalg.norm(acc_comp - acc_true)
                    / jnp.linalg.norm(acc_true))
        return errs, cum

    errs8, cum8 = run("int8")
    assert errs8[0] < 0.05, errs8          # per-step int8 noise small
    assert cum8 < 0.02, cum8               # error feedback kills the bias
    errsb, cumb = run("bf16")
    assert cumb < 0.01, cumb
    print(f"OK compression int8 step={errs8[0]:.4f} cum={cum8:.4f} "
          f"bf16 cum={cumb:.4f}")


def check_gpipe_equivalence():
    """2-stage GPipe over 'pod' == plain stacked forward."""
    from repro.distributed.pipeline_parallel import (gpipe_forward,
                                                     make_stage_fn)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    n_layers, d = 4, 32
    ws = jax.random.normal(jax.random.PRNGKey(0),
                           (n_layers, d, d)) * (d ** -0.5)

    def layer_apply(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
    ref = x
    for i in range(n_layers):
        ref = layer_apply(ws[i], ref)

    stage_params = ws.reshape(2, 2, d, d)      # 2 stages × 2 layers
    stage_fn = make_stage_fn(layer_apply, per_stage=2)
    with mesh:
        out = gpipe_forward(mesh, stage_fn, stage_params, x,
                            n_microbatches=4, axis="pod")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    print("OK gpipe == stacked")


def check_quantize_rows_sharded():
    """Row-sharded GPTQ == single-device GPTQ (rows independent given U)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import hessian as hess
    from repro.core.gptq import gptq_quantize

    Cout, Cin = 64, 128
    W = jax.random.normal(jax.random.PRNGKey(0), (Cout, Cin)) * 0.1
    X = jax.random.normal(jax.random.PRNGKey(1), (256, Cin))
    st = hess.accumulate(hess.init_hessian(Cin), X)
    U = hess.cholesky_inverse_upper(hess.damped(st, 0.01))

    res_single = gptq_quantize(W, U, bits=4, group_size=32, blocksize=32)

    mesh = jax.make_mesh((8,), ("rows",), axis_types=(AxisType.Auto,))
    Wsh = jax.device_put(W, NamedSharding(mesh, P("rows", None)))
    Ur = jax.device_put(U, NamedSharding(mesh, P(None, None)))
    with mesh:
        res_sh = jax.jit(lambda w, u: gptq_quantize(
            w, u, bits=4, group_size=32, blocksize=32))(Wsh, Ur)
    np.testing.assert_allclose(np.asarray(res_single.w_q),
                               np.asarray(jax.device_get(res_sh.w_q)),
                               rtol=1e-5, atol=1e-6)
    print("OK row-sharded GPTQ exact")


def check_sharded_plan_parity():
    """Sharded group execution == single-device batched quantize_model.

    End-to-end over the knob route: ``quant.mesh="2x2"`` builds the
    (data, model) mesh through launch/mesh.make_quant_mesh and every
    divisible plan group runs lane-sharded over ``data`` with Cout row
    tiles over ``model`` (DESIGN.md §2.6); single-lane groups (e.g. the
    down-projection) exercise the per-axis divisibility fallback inside
    the same run. Group-level and non-divisible-group parity is pinned in
    tests/test_plan_sharded.py (the scripts/check.sh multi-device leg).
    """
    from repro.configs import get_config
    from repro.core.pipeline import quantize_model
    from repro.data import MarkovLM, calibration_batches
    from repro.models import transformer as T

    # make_quant_mesh raises on too few devices; fail here with the
    # clearer message
    assert jax.device_count() >= 4, \
        f"forced host devices missing (XLA_FLAGS?): {jax.device_count()}"
    cfg = get_config("opt-proxy", smoke=True)
    params = T.init_params(cfg.model, jax.random.PRNGKey(0))
    calib = calibration_batches(MarkovLM(cfg.model.vocab_size, seed=0),
                                2, 2, 32)
    pq1, rep1 = quantize_model(cfg, params, calib)
    cfg.quant.mesh = "2x2"
    pq2, rep2 = quantize_model(cfg, params, calib)

    mism, total, worst = 0, 0, 0.0
    for a, b in zip(jax.tree_util.tree_leaves(pq1),
                    jax.tree_util.tree_leaves(pq2)):
        a = np.asarray(a, np.float32)
        b = np.asarray(jax.device_get(b), np.float32)
        bad = ~np.isclose(a, b, rtol=1e-5, atol=1e-6)
        mism += int(bad.sum())
        total += a.size
        if bad.any():
            worst = max(worst, float(np.max(np.abs(a - b))))
    # functional equivalence: tiny fp divergence may flip the odd grid
    # cell; on the CPU host mesh the paths are in practice bitwise equal
    assert mism / total <= 1e-3, (mism, total, worst)
    for l1, l2 in zip(rep1.linears, rep2.linears):
        assert (l1.name, l1.mode) == (l2.name, l2.mode), (l1, l2)
    # the work really spread: some stage-1 sweep held 4 distinct shards
    assert not rep1.mesh_spread, rep1.mesh_spread
    assert rep2.mesh_spread.get("stage1_shards=4", 0) > 0, rep2.mesh_spread
    print(f"OK sharded plan == single-device batched "
          f"(mismatch {mism}/{total})")


def check_moe_expert_sharded():
    """Expert-parallel quantization == single-device, end-to-end + bitwise.

    The routed-MoE config quantizes once single-device and once on a
    ``quant.mesh="1x2x4"`` (data, model, expert) mesh: the stacked
    (E, ·, ·) expert groups shard lanes over the ``expert`` axis while
    dense groups keep the data/model rules — the ISSUE 10 scaled-down
    stand-in for the 671B shape. The olmoe smoke config has E=8 experts,
    so the expert axis (4) divides the slab. Runs under
    ``quant.pipeline=overlap`` so the flip repair and the expert-sharded
    executors compose in one run.
    """
    from repro.configs import get_config
    from repro.core.pipeline import quantize_model
    from repro.data import MarkovLM, calibration_batches
    from repro.models import transformer as T

    assert jax.device_count() >= 8, \
        f"forced host devices missing (XLA_FLAGS?): {jax.device_count()}"
    cfg = get_config("olmoe-1b-7b", smoke=True)
    cfg.quant.pipeline = "overlap"
    params = T.init_params(cfg.model, jax.random.PRNGKey(0))
    calib = calibration_batches(MarkovLM(cfg.model.vocab_size, seed=0),
                                2, 2, 32)
    pq1, rep1 = quantize_model(cfg, params, calib)
    cfg.quant.mesh = "1x2x4"
    pq2, rep2 = quantize_model(cfg, params, calib)
    assert rep2.pipeline_stats["moe_spec_layers"] > 0, \
        rep2.pipeline_stats

    mism, total, worst = 0, 0, 0.0
    for a, b in zip(jax.tree_util.tree_leaves(pq1),
                    jax.tree_util.tree_leaves(pq2)):
        a = np.asarray(a, np.float32)
        b = np.asarray(jax.device_get(b), np.float32)
        bad = ~np.isclose(a, b, rtol=1e-5, atol=1e-6)
        mism += int(bad.sum())
        total += a.size
        if bad.any():
            worst = max(worst, float(np.max(np.abs(a - b))))
    assert mism / total <= 1e-3, (mism, total, worst)
    for l1, l2 in zip(rep1.linears, rep2.linears):
        assert (l1.name, l1.mode) == (l2.name, l2.mode), (l1, l2)
    print(f"OK expert-sharded MoE == single-device "
          f"(mismatch {mism}/{total})")


CHECKS = {
    "sharded_train": check_sharded_train_matches_single,
    "elastic_restore": check_elastic_restore,
    "grad_compression": check_grad_compression,
    "gpipe": check_gpipe_equivalence,
    "gptq_rows": check_quantize_rows_sharded,
    "plan_sharded": check_sharded_plan_parity,
    "moe_expert_sharded": check_moe_expert_sharded,
}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()
