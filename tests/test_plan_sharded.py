"""Sharded group execution parity on a forced host mesh (DESIGN.md §2.6).

Every test here needs ≥4 host devices, so the plain tier-1 run — which must
keep the single real CPU device (tests/conftest.py) —
skips the whole file; scripts/check.sh runs it as a dedicated leg under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  End-to-end
``quantize_model`` parity additionally runs as a subprocess check from
tests/test_distributed.py (``plan_sharded``), so plain ``pytest`` covers
the mesh path too.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config import QuantConfig
from repro.core import hessian as hess
from repro.core import plan as qplan
from repro.distributed.sharding import quant_group_sharding
from repro.kernels import ops as kops

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs ≥4 host devices (scripts/check.sh multi-device leg)")


def _mesh22():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))


def _member(i: int, out_dim: int, in_dim: int, n_last: int = 64,
            n_calib: int = 128) -> qplan.PlanMember:
    w = jax.random.normal(jax.random.PRNGKey(i), (out_dim, in_dim)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(100 + i), (n_calib, in_dim))
    st = hess.accumulate(hess.init_hessian(in_dim), x)
    return qplan.PlanMember(f"m{i}", w, st, x[-n_last:], x_count=None)


def _run_plan(qc, members, mesh=None, rpiq=True):
    qplan.clear_executor_cache()
    plan = qplan.build_plan(qc, members)
    report = qplan.QuantReport()
    res = qplan.execute_plan(qc, plan, report, rpiq_enabled=rpiq, mesh=mesh)
    return plan, report, res


def _assert_member_parity(r1, r2):
    assert r1.keys() == r2.keys()
    for name in r1:
        a, b = r1[name], r2[name]
        np.testing.assert_allclose(np.asarray(a.w_q),
                                   np.asarray(jax.device_get(b.w_q)),
                                   rtol=1e-5, atol=1e-6, err_msg=name)
        for ga, gb in zip(a.grid, b.grid):
            np.testing.assert_allclose(np.asarray(ga),
                                       np.asarray(jax.device_get(gb)),
                                       rtol=1e-5, atol=1e-6, err_msg=name)


# ---------------------------------------------------------------------------
# Placement rules (pure logic, but Mesh construction needs the devices)
# ---------------------------------------------------------------------------

@needs_mesh
def test_quant_group_sharding_guards():
    mesh = _mesh22()
    gs = quant_group_sharding(mesh, lanes=4, out_dim=64)
    assert (gs.lane_axis, gs.row_axis) == ("data", "model")
    # lanes don't divide data → lane axis dropped, rows keep model
    gs = quant_group_sharding(mesh, lanes=3, out_dim=64)
    assert (gs.lane_axis, gs.row_axis) == (None, "model")
    # Cout doesn't divide model → row axis dropped, lanes keep data
    gs = quant_group_sharding(mesh, lanes=4, out_dim=33)
    assert (gs.lane_axis, gs.row_axis) == ("data", None)
    # neither divides → the group stays unsharded entirely
    assert quant_group_sharding(mesh, lanes=3, out_dim=33) is None
    assert quant_group_sharding(None, lanes=4, out_dim=64) is None


@needs_mesh
def test_quant_group_specs_and_hessian_placement():
    from jax.sharding import PartitionSpec as P
    mesh = _mesh22()
    gs = quant_group_sharding(mesh, lanes=4, out_dim=64)
    assert gs.spec("w") == P("data", "model", None)
    assert gs.spec("hessian") == P("data", None, None)
    assert gs.spec("lane") == P("data")
    st = hess.HessianState(jnp.zeros((4, 32, 32)),
                           jnp.zeros((4,), jnp.int32))
    st_sh = hess.shard_stacked(st, gs)
    assert st_sh.H.sharding.spec == P("data", None, None)
    assert st_sh.count.sharding.spec == P("data")
    # rows-only groups replicate the state across the mesh — still
    # committed, so it can't clash with the mesh-committed weights
    gs_rows = quant_group_sharding(mesh, lanes=3, out_dim=64)
    st_rep = hess.shard_stacked(st, gs_rows)
    assert st_rep.H.sharding.spec == P(None, None, None)
    assert hess.shard_stacked(st, None) is st


# ---------------------------------------------------------------------------
# Kernel-dispatch level: gptq_block_sharded == gptq_block
# ---------------------------------------------------------------------------

def _sweep_inputs(b=4, out_dim=32, in_dim=64):
    w = jax.random.normal(jax.random.PRNGKey(0), (b, out_dim, in_dim)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 256, in_dim))
    h = jnp.einsum("bni,bnj->bij", x, x,
                   precision=jax.lax.Precision.HIGHEST)
    hd = hess.damped(hess.HessianState(h, None), 0.01)
    return w, hess.cholesky_inverse_upper(hd)


@needs_mesh
@pytest.mark.parametrize("axes", [("data", "model"), ("data", None),
                                  (None, "model")])
def test_gptq_block_sharded_matches_single(axes):
    w, u = _sweep_inputs()
    kw = dict(bits=4, group_size=32, blocksize=32, symmetric=False)
    ref = kops.gptq_block(w, u, impl="xla", **kw)
    out = kops.gptq_block_sharded(w, u, mesh=_mesh22(), lane_axis=axes[0],
                                  row_axis=axes[1], impl="xla", **kw)
    for name, a, b in zip(("w_q", "scales", "zeros", "err"), ref, out):
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(jax.device_get(b)),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


@needs_mesh
@pytest.mark.pallas
def test_gptq_block_sharded_pallas_interpret():
    """Per-shard pallas (interpret off-TPU) under shard_map == XLA path."""
    w, u = _sweep_inputs(b=2, out_dim=16, in_dim=32)
    kw = dict(bits=4, group_size=16, blocksize=16, symmetric=False)
    ref = kops.gptq_block(w, u, impl="xla", **kw)
    out = kops.gptq_block_sharded(w, u, mesh=_mesh22(), lane_axis="data",
                                  row_axis="model", impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(ref[0]),
                               np.asarray(jax.device_get(out[0])),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Kernel-dispatch level: rpiq_block_sharded == rpiq_block (stage-2 twin)
# ---------------------------------------------------------------------------

def _rpiq_inputs(b=4, out_dim=32, in_dim=64, n=128):
    w, u = _sweep_inputs(b, out_dim, in_dim)
    from repro.core.gptq import gptq_quantize_batched
    res1 = gptq_quantize_batched(w, u, bits=4, group_size=32, blocksize=32)
    x = jax.random.normal(jax.random.PRNGKey(5), (b, n, in_dim))
    h = jnp.einsum("bni,bnj->bij", x, x,
                   precision=jax.lax.Precision.HIGHEST)
    hd = hess.damped(hess.HessianState(h, None), 0.01)
    return w, x, hd, res1


_RPIQ_KW = dict(bits=4, group_size=32, block_size=32, alpha=1.0, t_max=4,
                exact_gram=True)


@needs_mesh
def test_rpiq_block_sharded_lane_axis_bitwise():
    """Lane-only sharding: members are fully independent, so the sharded
    twin must match the single-device dispatch BITWISE."""
    w, x, hd, res1 = _rpiq_inputs()
    ref = kops.rpiq_block_sharded(res1.w_q, w, x, hd, res1.scales,
                                  res1.zeros, mesh=None, lane_axis=None,
                                  row_axis=None, impl="xla", **_RPIQ_KW)
    out = kops.rpiq_block_sharded(res1.w_q, w, x, hd, res1.scales,
                                  res1.zeros, mesh=_mesh22(),
                                  lane_axis="data", row_axis=None,
                                  impl="xla", **_RPIQ_KW)
    for name, a, b in zip(("w_q", "w_cont", "hist", "proj_loss", "iters"),
                          ref, out):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(jax.device_get(b)),
                                      err_msg=name)


@needs_mesh
@pytest.mark.parametrize("axes", [("data", "model"), (None, "model")])
def test_rpiq_block_sharded_xla_gathers_rows(axes):
    """With an XLA-resolved backend the closed loop cannot row-shard (the
    while-loop trip count is per-lane data-dependent), so the twin gathers
    rows and shards lanes only — results match single-device."""
    w, x, hd, res1 = _rpiq_inputs()
    ref = kops.rpiq_block_sharded(res1.w_q, w, x, hd, res1.scales,
                                  res1.zeros, mesh=None, lane_axis=None,
                                  row_axis=None, impl="xla", **_RPIQ_KW)
    out = kops.rpiq_block_sharded(res1.w_q, w, x, hd, res1.scales,
                                  res1.zeros, mesh=_mesh22(),
                                  lane_axis=axes[0], row_axis=axes[1],
                                  impl="xla", **_RPIQ_KW)
    np.testing.assert_array_equal(np.asarray(ref[4]),
                                  np.asarray(jax.device_get(out[4])))
    for name, a, b in zip(("w_q", "w_cont", "hist", "proj_loss"), ref, out):
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(jax.device_get(b)),
                                   rtol=1e-6, atol=1e-7, err_msg=name)


@needs_mesh
@pytest.mark.pallas
def test_rpiq_block_sharded_pallas_row_psum():
    """Per-shard fused kernel (interpret off-TPU) with the row axis kept:
    the Γ/projected-loss partials psum-fold across row shards before the
    deferred bookkeeping, so early stops and the best projection match the
    single-device kernel."""
    w, x, hd, res1 = _rpiq_inputs(b=2, out_dim=16, in_dim=32, n=64)
    ref = kops.rpiq_block_sharded(res1.w_q, w, x, hd, res1.scales,
                                  res1.zeros, mesh=None, lane_axis=None,
                                  row_axis=None, impl="pallas", **_RPIQ_KW)
    out = kops.rpiq_block_sharded(res1.w_q, w, x, hd, res1.scales,
                                  res1.zeros, mesh=_mesh22(),
                                  lane_axis="data", row_axis="model",
                                  impl="pallas", **_RPIQ_KW)
    np.testing.assert_array_equal(np.asarray(ref[4]),
                                  np.asarray(jax.device_get(out[4])))
    np.testing.assert_allclose(np.asarray(ref[0]),
                               np.asarray(jax.device_get(out[0])),
                               rtol=1e-6, atol=1e-6)
    ha = np.asarray(ref[2])
    hb = np.asarray(jax.device_get(out[2]))
    fin = np.isfinite(ha)
    assert (fin == np.isfinite(hb)).all()
    np.testing.assert_allclose(ha[fin], hb[fin], rtol=1e-5)


# ---------------------------------------------------------------------------
# Executor level: sharded plan == single-device batched plan
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("rpiq", [False, True])
def test_group_parity_sharded_vs_single(rpiq):
    """4-lane group over the full (2, 2) mesh: lanes × row tiles."""
    qc = QuantConfig(group_size=16, blocksize=16)
    _, rep1, r1 = _run_plan(qc, [_member(i, 64, 64) for i in range(4)],
                            rpiq=rpiq)
    _, rep2, r2 = _run_plan(qc, [_member(i, 64, 64) for i in range(4)],
                            mesh=_mesh22(), rpiq=rpiq)
    _assert_member_parity(r1, r2)
    for l1, l2 in zip(rep1.linears, rep2.linears):
        assert (l1.name, l1.mode, l1.iters) == (l2.name, l2.mode, l2.iters)
        np.testing.assert_allclose(l1.gamma_final, l2.gamma_final,
                                   rtol=1e-4, atol=1e-6)


@needs_mesh
def test_non_divisible_lanes_shard_rows_only():
    """3 lanes on a 2-wide data axis: lane axis dropped, rows still shard."""
    qc = QuantConfig(group_size=16, blocksize=16)
    members = lambda: [_member(i, 64, 64) for i in range(3)]
    _, _, r1 = _run_plan(qc, members())
    _, _, r2 = _run_plan(qc, members(), mesh=_mesh22())
    _assert_member_parity(r1, r2)


@needs_mesh
def test_non_divisible_group_takes_unsharded_fallback():
    """Neither lanes (3) nor Cout (33) divide → whole group unsharded."""
    mesh = _mesh22()
    assert quant_group_sharding(mesh, 3, 33) is None
    qc = QuantConfig(group_size=16, blocksize=16)
    members = lambda: [_member(i, 33, 64) for i in range(3)]
    _, _, r1 = _run_plan(qc, members())
    _, _, r2 = _run_plan(qc, members(), mesh=mesh)
    _assert_member_parity(r1, r2)


@needs_mesh
def test_starved_mask_parity_sharded():
    """Stacked member with starved lanes: the RTN mask survives sharding."""
    qc = QuantConfig(group_size=16, blocksize=16)

    def stacked():
        w = jnp.stack([_member(i, 32, 64).w_oi for i in range(4)])
        x = jax.random.normal(jax.random.PRNGKey(7), (4, 64, 64))
        h = jnp.einsum("bni,bnj->bij", x, x,
                       precision=jax.lax.Precision.HIGHEST)
        st = hess.HessianState(h, jnp.full((4,), 64, jnp.int32))
        return [qplan.PlanMember(
            "experts", w, st, x, x_count=jnp.full((4,), 64, jnp.int32),
            starved=np.array([False, True, False, True]),
            names=[f"experts[{i}]" for i in range(4)])]

    _, rep1, r1 = _run_plan(qc, stacked())
    _, rep2, r2 = _run_plan(qc, stacked(), mesh=_mesh22())
    _assert_member_parity(r1, r2)
    modes1 = [l.mode for l in rep1.linears]
    assert modes1 == [l.mode for l in rep2.linears]
    assert modes1.count("rtn-fallback") == 2


def _mesh_e(data=1, model=1, expert=4):
    from jax.sharding import Mesh
    n = data * model * expert
    return Mesh(np.array(jax.devices()[:n]).reshape(data, model, expert),
                ("data", "model", "expert"))


@needs_mesh
def test_quant_group_sharding_expert_axis():
    """Expert-stacked groups offer lanes to the expert axis; dense groups
    ignore it (DESIGN.md §2.6 expert parallelism)."""
    # pure expert axis: lanes over "expert", no row tiling
    gs = quant_group_sharding(_mesh_e(1, 1, 4), lanes=8, out_dim=64,
                              expert_stacked=True)
    assert (gs.lane_axis, gs.row_axis) == ("expert", None)
    # expert × data product: lanes over the combined tuple
    gs = quant_group_sharding(_mesh_e(2, 1, 2), lanes=8, out_dim=64,
                              expert_stacked=True)
    assert (gs.lane_axis, gs.row_axis) == (("expert", "data"), None)
    # expert + model: lanes over expert, rows over model
    gs = quant_group_sharding(_mesh_e(1, 2, 2), lanes=8, out_dim=64,
                              expert_stacked=True)
    assert (gs.lane_axis, gs.row_axis) == ("expert", "model")
    # non-expert groups never touch the expert axis (data has size 1
    # here, so lanes stay unsharded entirely)
    gs = quant_group_sharding(_mesh_e(1, 2, 2), lanes=8, out_dim=64,
                              expert_stacked=False)
    assert (gs.lane_axis, gs.row_axis) == (None, "model")
    # divisibility guard: lanes that fit no candidate fall through to
    # rows-only
    gs = quant_group_sharding(_mesh_e(1, 2, 2), lanes=3, out_dim=64,
                              expert_stacked=True)
    assert (gs.lane_axis, gs.row_axis) == (None, "model")


@needs_mesh
@pytest.mark.parametrize("shape", [(1, 1, 4), (2, 1, 2), (1, 2, 2)])
def test_expert_sharded_group_parity(shape):
    """Stacked 8-expert slab over an expert mesh == single-device."""
    qc = QuantConfig(group_size=16, blocksize=16)

    def stacked():
        w = jnp.stack([_member(i, 32, 64).w_oi for i in range(8)])
        x = jax.random.normal(jax.random.PRNGKey(7), (8, 64, 64))
        h = jnp.einsum("bni,bnj->bij", x, x,
                       precision=jax.lax.Precision.HIGHEST)
        st = hess.HessianState(h, jnp.full((8,), 64, jnp.int32))
        return [qplan.PlanMember(
            "experts", w, st, x, x_count=jnp.full((8,), 64, jnp.int32),
            names=[f"experts[{i}]" for i in range(8)])]

    mesh = _mesh_e(*shape)
    gs = quant_group_sharding(mesh, 8, 32, expert_stacked=True)
    assert gs is not None and gs.lane_axis is not None
    _, rep1, r1 = _run_plan(qc, stacked())
    _, rep2, r2 = _run_plan(qc, stacked(), mesh=mesh)
    _assert_member_parity(r1, r2)
    assert [l.mode for l in rep1.linears] == [l.mode for l in rep2.linears]


@needs_mesh
def test_executor_cache_keyed_by_mesh():
    """Same group signature, with vs without mesh → distinct stage entries;
    a second sharded run over an equal mesh hits the cached entries."""
    qc = QuantConfig(group_size=16, blocksize=16)
    members = lambda: [_member(i, 64, 64) for i in range(4)]
    _run_plan(qc, members())
    base = qplan.executor_cache_stats()["misses"]
    plan = qplan.build_plan(qc, members())
    qplan.execute_plan(qc, plan, qplan.QuantReport(), mesh=_mesh22())
    after_sharded = qplan.executor_cache_stats()
    assert after_sharded["misses"] == base + 2      # stage1 + stage2 anew
    qplan.execute_plan(qc, qplan.build_plan(qc, members()),
                       qplan.QuantReport(), mesh=_mesh22())
    again = qplan.executor_cache_stats()
    assert again["misses"] == after_sharded["misses"]
    assert again["hits"] >= after_sharded["hits"] + 2


# ---------------------------------------------------------------------------
# quant.mesh knob
# ---------------------------------------------------------------------------

def test_make_quant_mesh_off_variants():
    from repro.launch.mesh import make_quant_mesh
    for spec in ("off", "", "none", "1x1", "1", "1x1x1"):
        assert make_quant_mesh(spec) is None
    # malformed specs raise: a run that asked for a mesh must not quietly
    # run single-device
    for spec in ("x4", "axb", "-2x-2", "0x4", "2x2x2x2"):
        with pytest.raises(ValueError, match="is not"):
            make_quant_mesh(spec)
    # "DxMxE" is valid grammar; without enough devices it raises like any
    # oversized spec
    if jax.device_count() < 8:
        with pytest.raises(ValueError, match="needs 8 devices"):
            make_quant_mesh("2x2x2")
    # uppercase separator is accepted
    assert make_quant_mesh("1X1") is None


@needs_mesh
def test_make_quant_mesh_shapes_and_fallback():
    from repro.launch.mesh import make_quant_mesh
    mesh = make_quant_mesh("2x2")
    assert mesh.axis_names == ("data", "model")
    assert tuple(mesh.devices.shape) == (2, 2)
    auto = make_quant_mesh("auto")
    assert dict(zip(auto.axis_names, auto.devices.shape))["model"] == 1
    # more devices than the host has → an error, never a silent
    # single-device run
    with pytest.raises(ValueError, match="needs 4096 devices"):
        make_quant_mesh("64x64")
