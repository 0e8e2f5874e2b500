"""Streaming LayerWalker pipeline: quant.pipeline=overlap == serial.

The stream scheduler (core/stream.py) must be a pure re-scheduling of the
serial walk — same dispatches, same accumulation order — so the two modes
are pinned BITWISE on fixed-seed pipeline fixtures across all three
architectures the walker covers (decoder-only, encoder-decoder, MoE):
on-grid params, report Γ histories/modes, and packed serving artifacts.
A forced-fallback lane marks every layer's Hessian repair unsound and
checks the scheduler degrades to serial re-capture without changing
results; the capture-forward cache counters (satellite of the same PR)
are asserted on both walkers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import pipeline as qpipe
from repro.core.pipeline import (capture_cache_stats, pack_for_serving,
                                 quantize_model)
from repro.data import MarkovLM, calibration_batches
from repro.models import transformer as T

ARCHS = ("opt-proxy", "whisper-large-v3", "olmoe-1b-7b")


def _fixture(arch, n_batches=3, bs=4, seq=24):
    cfg = get_config(arch, smoke=True)
    mc = cfg.model
    key = jax.random.PRNGKey(0)
    params = (T.init_encdec_params(mc, key) if mc.is_encoder_decoder
              else T.init_params(mc, key))
    calib = calibration_batches(MarkovLM(mc.vocab_size, seed=1),
                                n_batches, bs, seq)
    if mc.is_encoder_decoder:
        for i, b in enumerate(calib):
            b["frames"] = jax.random.normal(
                jax.random.PRNGKey(i), (bs, mc.encoder_seq_len, mc.d_model))
    return cfg, params, calib


def _run(arch, pipeline, **qkw):
    cfg, params, calib = _fixture(arch)
    cfg.quant.pipeline = pipeline
    for k, v in qkw.items():
        setattr(cfg.quant, k, v)
    params_q, report = quantize_model(cfg, params, calib)
    packed = pack_for_serving(cfg, params_q)
    return params_q, report, packed


def _assert_trees_bitwise(a, b, what):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, f"{what}: tree structure differs"
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what}: leaf {i}")


def _assert_reports_equal(rep_s, rep_o):
    recs_s = [(l.name, l.shape, l.mode, l.gptq_err, l.gamma, l.gamma_final,
               l.iters) for l in rep_s.linears]
    recs_o = [(l.name, l.shape, l.mode, l.gptq_err, l.gamma, l.gamma_final,
               l.iters) for l in rep_o.linears]
    assert recs_s == recs_o


class TestOverlapParity:
    """pipeline=overlap is bitwise pipeline=serial on every walker."""

    @pytest.mark.parametrize("arch", ARCHS)
    def test_overlap_matches_serial(self, arch):
        pq_s, rep_s, packed_s = _run(arch, "serial")
        pq_o, rep_o, packed_o = _run(arch, "overlap")
        _assert_trees_bitwise(pq_s, pq_o, f"{arch} on-grid params")
        _assert_trees_bitwise(packed_s, packed_o, f"{arch} packed artifacts")
        _assert_reports_equal(rep_s, rep_o)
        assert rep_o.pipeline_stats["mode"] == "overlap"
        assert rep_s.pipeline_stats["mode"] == "serial"
        assert rep_o.pipeline_stats["steps"] == len(rep_o.layer_step_seconds)

    def test_overlap_speculates_on_dense(self):
        """Dense stacks capture-ahead every adjacent same-slot pair and
        repair each speculation exactly once."""
        _, rep, _ = _run("opt-proxy", "overlap")
        st = rep.pipeline_stats
        assert st["spec_captures"] == st["steps"] - 1 > 0
        assert st["repairs"] == st["spec_captures"]
        assert st["serial_fallbacks"] == 0

    def test_moe_speculates_with_flip_repair(self):
        """Routed MoE now speculates like dense stacks: the plan-level
        flip repair (core/pipeline._moe_members) verifies the speculative
        routing on the true stream instead of degrading to serial."""
        _, rep, _ = _run("olmoe-1b-7b", "overlap")
        st = rep.pipeline_stats
        assert st["spec_captures"] == st["steps"] - 1 > 0
        assert st["repairs"] == st["spec_captures"]
        assert st["serial_fallbacks"] == 0
        # every speculated MoE layer went through the flip-repair ledger
        assert st["moe_spec_layers"] == st["spec_captures"]
        assert st["moe_assignments"] > 0
        assert st["moe_plan_reuses"] + st["moe_flip_repairs"] > 0
        assert st["fallback_flip_budget"] == 0

    def test_moe_flip_budget_zero_forces_serial_replan(self):
        """quant.moe_flip_budget=-1 rejects every speculative plan (any
        flip count exceeds a negative budget) — the layer re-plans
        serially, counted per reason, and results stay bitwise serial."""
        pq_s, rep_s, packed_s = _run("olmoe-1b-7b", "serial")
        pq_o, rep_o, packed_o = _run("olmoe-1b-7b", "overlap",
                                     moe_flip_budget=-1.0)
        st = rep_o.pipeline_stats
        assert st["fallback_flip_budget"] == st["moe_spec_layers"] > 0
        assert st["serial_fallbacks"] == st["fallback_flip_budget"]
        assert st["moe_plan_reuses"] == st["moe_flip_repairs"] == 0
        _assert_trees_bitwise(pq_s, pq_o, "flip-budget params")
        _assert_trees_bitwise(packed_s, packed_o, "flip-budget packed")
        _assert_reports_equal(rep_s, rep_o)

    def test_encdec_fence_blocks_speculation(self):
        """Speculation never crosses the enc→dec StreamSwitch: with 2+2
        layers, exactly the two within-stream pairs speculate."""
        _, rep, _ = _run("whisper-large-v3", "overlap")
        st = rep.pipeline_stats
        assert st["steps"] == 4
        assert st["spec_captures"] == st["repairs"] == 2

    def test_forced_fallback_lane(self, monkeypatch):
        """Repair marked unsound everywhere → scheduler degrades every
        step to serial re-capture, results still bitwise serial."""
        pq_s, rep_s, packed_s = _run("opt-proxy", "serial")
        monkeypatch.setattr(qpipe, "_layer_repair_sound", lambda lp: False)
        pq_o, rep_o, packed_o = _run("opt-proxy", "overlap")
        st = rep_o.pipeline_stats
        assert st["spec_captures"] == 0
        assert st["serial_fallbacks"] == st["steps"] - 1 > 0
        _assert_trees_bitwise(pq_s, pq_o, "forced-fallback params")
        _assert_trees_bitwise(packed_s, packed_o, "forced-fallback packed")
        _assert_reports_equal(rep_s, rep_o)

    def test_unknown_pipeline_mode_raises(self):
        cfg, params, calib = _fixture("opt-proxy")
        cfg.quant.pipeline = "threaded"
        with pytest.raises(ValueError, match="quant.pipeline"):
            quantize_model(cfg, params, calib)


class TestCaptureCacheStats:
    """Per-run fwd_cache hygiene: repeated identical layers must HIT the
    compiled-forward cache on both walkers, and the counters are exposed
    next to plan.executor_cache_stats()."""

    def test_dense_walker_hits(self):
        _run("opt-proxy", "serial")
        st = capture_cache_stats()
        # 2 identical layers × 3 batches × (capture + propagate) lookups;
        # only the first layer's two entries miss
        assert st["misses"] == 2
        assert st["hits"] > st["misses"]

    def test_encdec_walker_hits(self):
        _run("whisper-large-v3", "serial")
        st = capture_cache_stats()
        assert st["hits"] > 0
        # repeated enc layers share entries; dec entries key per batch
        # (enc_out baked into the trace) yet still hit on the second layer
        assert st["hits"] >= st["misses"]

    def test_overlap_speculation_shares_entries(self):
        """The speculative capture must reuse the same compiled entries as
        its exact repair — overlap adds lookups, never compiles."""
        _run("opt-proxy", "serial")
        serial_misses = capture_cache_stats()["misses"]
        _run("opt-proxy", "overlap")
        st = capture_cache_stats()
        assert st["misses"] == serial_misses
        assert st["hits"] > 0
