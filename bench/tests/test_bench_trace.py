"""The trace reduction: busy and idle time, programs, kernel selection,
the shapes a kernel's event names and the breakdown, on a hand-made
profile with the structure JAX's profiler writes (device planes with
``XLA Modules`` / ``XLA Ops`` lines, a host plane with the harness's
spans)."""
import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

from bench import trace as tr

GPTQ = ("%gptq_block_pallas.1 = (f32[4,768,768]{2,1,0:T(8,128)}, "
        "f32[4,768,6]{2,1,0:T(8,128)S(1)}) custom-call(f32[4,768,768]"
        "{2,1,0:T(8,128)} %w.1, f32[4,768,768]{2,1,0:T(8,128)} %u.1), "
        "custom_call_target=\"tpu_custom_call\"")


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def _profile():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_sweep(7)", 1000, 400),
            _ev("jit_fwd(9)", 1600, 300)]),
        NS(name="XLA Ops", events=[
            _ev("%fusion.1 = f32[8] fusion(%p)", 900, 150),  # cut at 1000
            _ev(GPTQ, 1050, 100),
            _ev("%hessian_accum_pallas.5 = f32[768,768]{1,0} "
                "custom-call(f32[8192,768]{1,0} %x)", 1120, 100),  # overlaps
            _ev("%get-tuple-element.7 = f32[4,768,768] "    # reads .1's result
                "get-tuple-element(%gptq_block_pallas.1), index=0",
                1230, 10),
            _ev("%fusion.2 = f32[8] fusion(%q)", 1650, 200),
            _ev("%copy.3 = f32[8] copy(%r)", 2900, 50)])])  # after window
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev(tr.WINDOW_SPAN, 1000, 1500),
        _ev("quantize_model", 990, 700),
        _ev("pack_for_serving", 1700, 800),
        _ev("unrelated", 1000, 10)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), dev, host])


def test_busy_idle_and_window():
    s = tr.Summary.from_profile(_profile())
    assert s.window == (1000, 2500)
    assert s.window_s == pytest.approx(1.5e-6)
    # union in the window: [1000,1220) + [1230,1240) + [1650,1850)
    assert s.busy_s == pytest.approx((220 + 10 + 200) * 1e-9)
    assert s.idle_pct == pytest.approx(100 * (1 - 430 / 1500))


def test_programs_kernels_and_attribution():
    s = tr.Summary.from_profile(_profile())
    assert [m.name for m in s.programs("sweep")] == ["jit_sweep(7)"]
    g = s.select("gptq_block_pallas", module="sweep")
    assert [o.name for o in g] == ["gptq_block_pallas.1"]
    assert s.select("gptq_block_pallas", module="fwd") == []
    assert s.seconds(s.select("hessian_accum_pallas")) == pytest.approx(1e-7)


def test_kernel_events_name_their_shapes():
    s = tr.Summary.from_profile(_profile())
    g, = s.select("gptq_block_pallas")
    assert g.shapes == ((4, 768, 768), (4, 768, 6), (4, 768, 768),
                        (4, 768, 768))
    h, = s.select("hessian_accum_pallas")
    assert h.shapes == ((768, 768), (8192, 768))


def test_breakdown_names_gaps_by_host_span():
    b = tr.Summary.from_profile(_profile()).breakdown()
    gaps = dict(b["idle_gaps"])
    # [1220,1230) and [1240,1650) in quantize_model; [1850,2500) packing
    assert gaps["quantize_model"] == pytest.approx(420e-9)
    assert gaps["pack_for_serving"] == pytest.approx(650e-9)
    ops = dict(b["device_ops"])
    assert ops["jit_fwd/fusion"] == pytest.approx(200e-9)
    assert ops["jit_sweep/gptq_block_pallas"] == pytest.approx(100e-9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_profile_without_the_window_span_is_refused():
    p = _profile()
    p.planes[2].lines[0].events = p.planes[2].lines[0].events[1:]
    with pytest.raises(ValueError):
        tr.Summary.from_profile(p)


# -- a traced run of the quantize cell on one v5e, recorded by
# bench/tests/record_trace.py

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "quant_trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        raw = json.load(f)
    return NS(planes=[NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[_ev(*e) for e in ln["events"]])
        for ln in p["lines"]]) for p in raw["planes"]])


def test_recorded_trace_reduces(recorded):
    s = tr.Summary.from_profile(recorded)
    assert s.n_devices == 1
    assert 0.0 < s.busy_s < s.window_s
    b = s.breakdown()
    assert {name for name, _ in b["idle_gaps"]} <= {
        "quantize_model", "pack_for_serving", "other"}
    assert b["device_ops"][0][1] > 0.0


def test_recorded_kernel_calls_match_the_linears(recorded):
    """The traced job of 12 layers: one ``gptq_block`` call a layer for
    q/k/v/o (4 lanes of 768 x 768) and one for up (3072 x 768); down
    (in 3072) takes the XLA sweep. Every Hessian call is counted under
    the chip's peak, so its roofline share is a share."""
    from bench.opcount import hessian_accum
    s = tr.Summary.from_profile(recorded)
    g = s.select("gptq_block_pallas")
    assert sorted({o.shapes[0] for o in g}) == [(1, 3072, 768),
                                               (4, 768, 768)]
    assert len(g) == 24
    h = s.select("hessian_accum_pallas")
    least = sum(max(hessian_accum.count(*o.shapes[1])[0] / 197e12,
                    hessian_accum.count(*o.shapes[1])[1] / 819e9)
                for o in h)
    assert 0.0 < least < s.seconds(h)
