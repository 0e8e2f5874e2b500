"""Host spans of the quantize path (core/spans.py).

A tiny quantize + pack runs under ``jax.profiler``; the host plane of the
profile it writes must hold the named spans, one ``quant.step`` a layer
with every phase inside its step, one ``quant.fwd_build`` a
``ForwardCache`` miss, and the report must keep the same spans as the
profile. The packed artifact is bitwise the one of an untraced run.
"""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions

from repro.configs import get_config
from repro.core import plan as qplan
from repro.core import spans
from repro.core.pipeline import (capture_cache_stats, pack_for_serving,
                                 quantize_model)
from repro.data import MarkovLM, calibration_batches
from repro.models import transformer as T

WALK = (spans.JOB, spans.WALKER, spans.STEP, spans.RESOLVE, spans.CAPTURE,
        spans.FWD_BUILD, spans.PLAN, spans.STAGE1_INPUTS, spans.STAGE1,
        spans.STAGE2_INPUTS, spans.STAGE2, spans.RESULTS, spans.SCATTER,
        spans.PROPAGATE)


def _fixture():
    cfg = get_config("opt-proxy", smoke=True)
    params = T.init_params(cfg.model, jax.random.PRNGKey(0))
    calib = calibration_batches(MarkovLM(cfg.model.vocab_size, seed=1),
                                2, 2, 16)
    return cfg, params, calib


def _trace(trace_dir):
    """A profiler session without the Python tracer, which only slows
    the run down: the spans are host TraceMe events."""
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(trace_dir), profiler_options=opts)


def _job(cfg, params, calib):
    params_q, report = quantize_model(cfg, params, calib)
    misses = capture_cache_stats()["misses"]
    return report, misses, jax.device_get(pack_for_serving(cfg, params_q))


def _host_spans(trace_dir):
    """{name: [(start_ns, end_ns)]} of the profile's ``quant.*`` host
    events (the name before any ``#`` metadata)."""
    pd = ProfileData.from_file(max(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)))
    out = collections.defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#")[0]
                if name.startswith("quant."):
                    out[name].append((e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    cfg, params, calib = _fixture()
    plain = _job(cfg, params, calib)
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    with _trace(trace_dir):
        traced = _job(cfg, params, calib)
    return cfg, plain, traced, _host_spans(trace_dir)


def test_span_names_are_in_the_profile(runs):
    _, _, _, host = runs
    assert set(WALK) == set(host)


def test_one_step_span_per_layer(runs):
    cfg, _, _, host = runs
    assert len(host[spans.STEP]) == cfg.model.num_layers
    assert len(host[spans.JOB]) == 1


@pytest.mark.parametrize("name", [spans.CAPTURE, spans.PLAN, spans.STAGE1,
                                  spans.SCATTER])
def test_phases_lie_inside_a_step(runs, name):
    _, _, _, host = runs
    steps = host[spans.STEP]
    assert host[name]
    for a, b in host[name]:
        assert any(s <= a and b <= e for s, e in steps), (name, a, b)


def test_forward_builds_are_the_cache_misses(runs):
    _, _, (_, misses, _), host = runs
    assert misses > 0
    assert len(host[spans.FWD_BUILD]) == misses


def test_packed_artifact_is_bitwise_the_untraced_one(runs):
    _, (_, _, packed_plain), (_, _, packed_traced), _ = runs
    la, ta = jax.tree_util.tree_flatten(packed_plain)
    lb, tb = jax.tree_util.tree_flatten(packed_traced)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_report_keeps_the_spans_of_a_traced_run(runs):
    _, (plain, _, _), (traced, _, _), host = runs
    assert plain.spans == []
    rec = collections.defaultdict(list)
    for name, a, b in traced.spans:
        rec[name].append((a, b))
    assert {k: len(v) for k, v in rec.items()} == {
        k: len(v) for k, v in host.items()}
    # one clock shift maps the report's spans into the profile's
    (a0, _), = rec[spans.JOB]
    (p0, _), = host[spans.JOB]
    shift = p0 - a0
    for name, ivs in rec.items():
        for (a, b), (pa, pb) in zip(sorted(ivs), host[name]):
            assert pa - 2_000_000 <= a + shift and b + shift <= pb \
                + 2_000_000, name
            assert b - a <= pb - pa + 100_000, name


def test_no_profiler_session_records_nothing():
    out = []
    with spans.recording(out):
        with spans.span(spans.STEP, layer=0):
            pass
    assert out == []


def test_spans_nest_and_close_on_errors(tmp_path):
    out = []
    with _trace(tmp_path), spans.recording(out):
        with spans.span(spans.STEP, layer=3):
            with pytest.raises(ValueError):
                with spans.span(spans.CAPTURE):
                    raise ValueError("boom")
    assert [n for n, _, _ in out] == [spans.CAPTURE, spans.STEP]
    (_, ca, cb), (_, sa, sb) = out
    assert sa <= ca <= cb <= sb


@pytest.mark.parametrize("out_dims", [(16, 16), (16, 24)],
                         ids=["one-group", "two-groups"])
def test_executor_has_the_stage_spans(tmp_path, out_dims):
    """The executor opens each stage's input and program spans, and one
    results span, once a group: two linears of one shape make one group,
    two shapes make two."""
    cfg = get_config("opt-proxy", smoke=True)
    qc = cfg.quant
    in_dim, n = qc.blocksize, 16
    key = jax.random.PRNGKey(3)
    members = []
    for i, out_dim in enumerate(out_dims):
        kw, kx = jax.random.split(jax.random.fold_in(key, i))
        x = jax.random.normal(kx, (n, in_dim), jnp.float32)
        h = qplan.hess.accumulate(qplan.hess.init_hessian(in_dim), x)
        members.append(qplan.PlanMember(
            f"lin{i}", jax.random.normal(kw, (out_dim, in_dim)), h, x,
            x_count=None))
    plan = qplan.build_plan(qc, members)
    groups = len(set(out_dims))
    assert len(plan.groups) == groups
    report = qplan.QuantReport()
    with _trace(tmp_path), spans.recording(report.spans):
        qplan.execute_plan(qc, plan, report)
    names = collections.Counter(n for n, _, _ in report.spans)
    assert names == {spans.STAGE1_INPUTS: groups, spans.STAGE1: groups,
                     spans.STAGE2_INPUTS: groups, spans.STAGE2: groups,
                     spans.RESULTS: groups}
    assert [r.mode for r in report.linears] == ["rpiq", "rpiq"]


def test_stage2_program_is_named():
    cfg = get_config("opt-proxy", smoke=True)
    assert qplan._make_stage2(cfg.quant, "xla").__name__ == "stage2"
