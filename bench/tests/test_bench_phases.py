"""Idle time and device programs put down to the program's host spans
(``bench/phases.py``) and the four readers over it, on a hand-made
nested profile, on a traced chip run recorded by
``bench/tests/record_spans.py``, and in a traced CPU run of the harness.
The seven readers that were there before read the older recording
exactly as they did before."""
import gzip
import json
import math
import os
import time
from types import SimpleNamespace as NS

import jax
import pytest

from bench import harness, phases
from bench import trace as tr
from bench import weights as W
from bench.tests import tiny

BENCH = tiny.BENCH
NEW = ("fwd_build_s_per_job", "walk_idle_s_per_layer",
       "executor_idle_s_per_layer", "programs_per_layer")
OLD = ("capture_s_per_layer", "stage1_s_per_layer", "stage2_s_per_layer",
       "gptq_block_roofline", "hessian_accum_roofline", "quant_mfu",
       "device_idle_pct.quant")
SHIFT = 1_000_000          # the report's clock behind the profile's


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "bench_metric_" + name.replace(".", "_"))


def _ctx(summary, report, **records):
    config = json.load(open(os.path.join(BENCH, "configs",
                                         "opt-proxy-125m.json")))
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    oc = {}

    def opcount(kernel):
        if kernel not in oc:
            oc[kernel] = harness.load_module(
                os.path.join(BENCH, "opcount", kernel + ".py"),
                "bench_opcount_" + kernel)
        return oc[kernel]
    return NS(trace=summary, config=config, log=lambda msg: None,
              peaks=peaks["devices"]["TPU v5 lite"], opcount=opcount,
              records=dict({"jobs": [{"report": report}], "traced_job": 0},
                           **records))


# -- a hand-made job: two layer steps (times in ns on the profile's clock)

PROGRAM = [
    ("quant.job", 1010, 2490), ("quant.walker", 1010, 1100),
    ("quant.step", 1100, 1800), ("quant.resolve", 1100, 1150),
    ("quant.capture", 1150, 1400), ("quant.fwd_build", 1200, 1300),
    ("quant.plan", 1400, 1450), ("quant.stage1.inputs", 1450, 1500),
    ("quant.stage1", 1500, 1600), ("quant.stage2.inputs", 1600, 1620),
    ("quant.stage2", 1620, 1700), ("quant.results", 1700, 1750),
    ("quant.scatter", 1750, 1760), ("quant.propagate", 1760, 1800),
    ("quant.step", 1800, 2480), ("quant.capture", 1800, 2000),
    ("quant.stage1", 2000, 2400)]


def _profile():
    busy = [(1000, 20), (1300, 100), (1520, 80), (1650, 50), (2100, 200)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev(f"jit_p{i}({i})", a, d)
                                       for i, (a, d) in enumerate(busy)]),
        NS(name="XLA Ops", events=[_ev(f"%fusion.{i} = f32[8] fusion()",
                                       a, d)
                                   for i, (a, d) in enumerate(busy)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev(tr.WINDOW_SPAN, 1000, 2000), _ev("quantize_model", 1010, 1485),
        _ev("pack_for_serving", 2500, 490), _ev("unrelated", 1000, 10)])])
    return NS(planes=[dev, host])


def _report(program, shift=SHIFT):
    return NS(spans=[(n, a - shift, b - shift) for n, a, b in program])


@pytest.fixture(scope="module")
def hand():
    s = tr.Summary.from_profile(_profile())
    return s, phases.Phases.of(_ctx(s, _report(PROGRAM)))


def test_gaps_split_at_span_boundaries_by_innermost_span(hand):
    s, ph = hand
    idle = {k: round(v * 1e9) for k, v in ph.idle.items()}
    # [1020,1300) straddles the walker and the first step's resolve,
    # capture and forward build; [2300,3000) runs out of the job, through
    # the harness spans and past them
    assert idle == {
        "quant.walker": 80, "quant.resolve": 50, "quant.capture": 250,
        "quant.fwd_build": 100, "quant.plan": 50,
        "quant.stage1.inputs": 50, "quant.stage1": 220,
        "quant.stage2.inputs": 20, "quant.stage2": 30,
        "quant.results": 50, "quant.scatter": 10, "quant.propagate": 40,
        "quant.step": 80, "quant.job": 10, "quantize_model": 5,
        "pack_for_serving": 490, "other": 15}
    assert sum(ph.idle.values()) == pytest.approx(
        s.window_s - s.busy_s, abs=1e-15)
    assert ph.idle_gaps(top=1) == [["pack_for_serving", pytest.approx(
        490e-9)]]


def test_helpers(hand):
    _, ph = hand
    assert ph.count("quant.step") == 2
    assert ph.seconds_in("quant.fwd_build") == pytest.approx(100e-9)
    assert ph.idle_in(phases.WALK) == pytest.approx(480e-9)
    assert ph.idle_in(phases.EXECUTOR) == pytest.approx(370e-9)
    # jit_p0 starts before the first step; the others inside one
    assert ph.programs_in("quant.step") == 4


def test_the_readers_on_the_hand_made_job(hand):
    s, _ = hand
    ctx = _ctx(s, _report(PROGRAM))
    got = {n: _reader(n).read(ctx) for n in NEW}
    assert got == pytest.approx({
        "fwd_build_s_per_job": 100e-9, "walk_idle_s_per_layer": 240e-9,
        "executor_idle_s_per_layer": 185e-9, "programs_per_layer": 2.0})


@pytest.mark.parametrize("report", [NS(), NS(spans=[]), None])
def test_the_readers_stay_silent_without_spans(hand, report):
    """A report of a program that opens no spans, or of an untraced job,
    and a run with no trace, leave the four metrics out."""
    s, _ = hand
    ctx = _ctx(s if report is not None else None, report or NS())
    assert phases.Phases.of(ctx) is None
    assert [_reader(n).read(ctx) for n in NEW] == [None] * 4


def test_span_names_are_the_programs():
    from repro.core import spans
    assert phases.JOB == spans.JOB and phases.STEP == spans.STEP
    assert phases.FWD_BUILD == spans.FWD_BUILD
    assert set(phases.WALK) == {spans.STEP, spans.RESOLVE, spans.CAPTURE,
                                spans.PLAN, spans.SCATTER, spans.PROPAGATE}
    assert set(phases.EXECUTOR) == {spans.STAGE1_INPUTS, spans.STAGE1,
                                    spans.STAGE2_INPUTS, spans.STAGE2,
                                    spans.RESULTS}


# -- the readers that were there before, on the older recording -----------

def _recorded(name):
    with gzip.open(os.path.join(BENCH, "testdata", name), "rt") as f:
        raw = json.load(f)
    return NS(planes=[NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[_ev(*e) for e in ln["events"]])
        for ln in p["lines"]]) for p in raw["planes"]])


def _old_records():
    """A window of two jobs of 12 steps and the traced job's 72 linears
    (q, k, v, o, up, down a layer; every one through stage 2, two
    rounds), as ``bench/drivers/quantize.py`` hands them to readers."""
    shapes = [(768, 768)] * 4 + [(3072, 768), (768, 3072)]
    linears = [NS(name=f"l{i}", shape=shapes[i % 6], mode="rpiq", iters=2)
               for i in range(72)]
    rep = NS(layer_step_seconds=[0.25] * 12, seconds_stage1=0.8,
             seconds_stage2=0.5, linears=linears)
    rep2 = NS(layer_step_seconds=[0.24] * 12, seconds_stage1=0.82,
              seconds_stage2=0.48, linears=linears)
    config = json.load(open(os.path.join(BENCH, "configs",
                                         "opt-proxy-125m.json")))
    return rep, {"jobs": [{"report": rep}, {"report": rep2}],
                 "dims": W.Dims.of(config["model"]),
                 "calib_shape": (8, 16, 512)}


# what these readers read, before the program had spans, on
# quant_trace.json.gz with _old_records()
OLD_VALUES = {
    "capture_s_per_layer": 0.1366666666666667,
    "stage1_s_per_layer": 0.0675,
    "stage2_s_per_layer": 0.04083333333333333,
    "gptq_block_roofline": 0.5374833038635022,
    "hessian_accum_roofline": 20.546283635603068,
    "quant_mfu": 7.561891374439204,
    "device_idle_pct.quant": 50.36511112913201}


@pytest.fixture(scope="module")
def old():
    return tr.Summary.from_profile(_recorded("quant_trace.json.gz"))


def test_the_older_readers_read_what_they_read_before(old):
    rep, records = _old_records()
    ctx = _ctx(old, rep, **records)
    got = {n: _reader(n).read(ctx) for n in OLD}
    assert got == pytest.approx(OLD_VALUES, rel=1e-12)
    # and the four new ones find nothing to read there
    assert [_reader(n).read(ctx) for n in NEW] == [None] * 4


def test_the_older_recording_reduces_as_before(old):
    assert old.busy_s == pytest.approx(1.679422423, rel=1e-12)
    assert old.breakdown()["device_ops"][0] == [
        "jit_sweep/while", pytest.approx(0.625806241, rel=1e-12)]


# -- a traced run of the quantize cell on one v5e, with the program's spans

@pytest.fixture(scope="module")
def spans_run():
    s = tr.Summary.from_profile(_recorded("quant_spans.json.gz"))
    program = [(sp.name, sp.start, sp.end) for sp in _host_spans(
        _recorded("quant_spans.json.gz"))]
    return s, _ctx(s, _report(program, shift=0))


def _host_spans(profile):
    return [tr._op(e) for p in profile.planes if p.name.startswith("/host")
            for ln in p.lines for e in ln.events
            if e.name.startswith(phases.PREFIX)]


def test_the_recorded_job_has_twelve_steps_and_two_builds(spans_run):
    _, ctx = spans_run
    ph = phases.Phases.of(ctx)
    assert ph.count("quant.step") == 12
    assert ph.count("quant.fwd_build") == 2
    assert ph.count("quant.job") == 1


def test_the_readers_on_the_recorded_job(spans_run):
    s, ctx = spans_run
    got = {n: _reader(n).read(ctx) for n in NEW}
    assert all(v is not None and math.isfinite(v) and v > 0
               for v in got.values()), got
    ph = phases.Phases.of(ctx)
    idle = s.window_s - s.busy_s
    assert sum(ph.idle.values()) == pytest.approx(idle, rel=1e-9)
    # the bare harness span keeps at most a tenth of the job's idle time
    assert ph.idle.get("quantize_model", 0.0) <= 0.1 * idle
    assert got["programs_per_layer"] * 12 <= len(s.modules)


def test_lining_up_the_openings_moves_little_idle_time(spans_run):
    """The report's spans, shifted so that ``quant.job`` opens with
    ``quantize_model``, against the profile's own spans of the job: under
    1 % of the idle time changes phase."""
    s, ctx = spans_run
    shifted = phases.Phases.of(ctx).idle
    exact = phases.Phases(s, _host_spans(
        _recorded("quant_spans.json.gz"))).idle
    moved = sum(abs(shifted.get(k, 0.0) - exact.get(k, 0.0))
                for k in set(shifted) | set(exact)) / 2
    assert moved < 0.01 * sum(exact.values())


# -- a traced CPU run of the harness reads them ------------------------------

EXTRA = [{"name": n, "unit": u, "better": "lower", "source": "program_span",
          "layer": "quantize walk", "moves": "quant_layers_per_s",
          "workloads": [tiny.CELL]}
         for n, u in zip(NEW, ("s", "s", "s", "count"))]


def test_a_traced_cpu_run_reads_the_four_metrics(tmp_path):
    jax.config.update("jax_enable_compilation_cache", False)
    root = tiny.make_root(str(tmp_path), EXTRA)
    res = harness.run_cell(root, tiny.CELL, 2 ** 33 + 5, 1.0, True,
                           time.perf_counter(), require_chip=False)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(m)
    # the tiny job's capture and propagate forwards: one build each
    assert m["fwd_build_s_per_job"] > 0.0
    assert m["walk_idle_s_per_layer"] > 0.0
    assert m["executor_idle_s_per_layer"] > 0.0
    # a CPU trace holds no device line, so no program is counted
    assert m["programs_per_layer"] == 0.0
    assert res["correct"]
