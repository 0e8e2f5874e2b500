"""Paper Table 4: quantization wall time — GPTQ vs RPIQ (ΔT), plus the
quant-plan executor comparison and the stage-1 sweep-backend comparison.

Across model widths; RPIQ's stage 2 adds a bounded, roughly width-
proportional overhead (paper: +12-18s on 7-13B GPUs; CPU-scale here).

The ``batched`` rows measure the QuantPlan batched executors
(core/plan.py: same-shape linears grouped into one vmapped GPTQ+RPIQ
dispatch) against the legacy per-linear dispatch on the SAME model/calib —
each opt-proxy layer holds 4 same-shape attention linears, and the MoE row
stacks 8 experts (gate/up share one 16-member group). Cold = first run
(includes compile); warm = second run (steady-state throughput, the
paper's deployment claim). Parity of the two paths is pinned bitwise-close
in tests/test_batched_parity.py.

The impl rows compare the per-stage backends behind ``kernels/ops`` on the
batched executor — stage 1 (``gptq_block``) AND stage 2 (``rpiq_block``)
set to the same backend per row — and MEASURE the dispatch-overhead claim
instead of asserting it: ``xla_ops`` / ``xla_ops_s2`` are the executed-
XLA-op counts of the stage-1 / stage-2 dispatches for the row's largest
group, and ``executor_s`` splits into ``stage1_s``/``stage2_s`` so the
closed-loop cost is visible on its own —

  - ``xla``: the vmapped loop bodies compiled locally, counted
    trip-count-aware (``launch/hlo_analysis.executed_op_count``) — O(Cin)
    ops per stage-1 sweep, O(t·n_blocks) per stage-2 refinement (the
    stage-2 ``while`` has no known trip count, so its body is counted
    once — a LOWER bound on the xla side, conservative for the claim);
  - ``pallas``: the fused kernels lowered FOR TPU via cross-platform
    export (``tpu_exported_op_count``) — each whole stage is one
    ``tpu_custom_call``, so the count is the handful of pad/reduce ops
    around it.  (Compiling the pallas path on CPU would count the
    interpret-mode emulation loop, which is an artifact of the CPU
    container, not the hardware dispatch story; for the same reason the
    interpret-mode ``pallas`` WALL times here do not represent TPU.)

The ``pipeline`` field records the layer-walk schedule behind every row
(core/stream.py): the impl rows run the default ``serial`` walk; each
config additionally gets one ``pipeline="overlap"`` row (impl ``xla``) —
the streaming scheduler A/B, compared against the matching serial row in
``overlap_delta_s``/``overlap_speedup``. On this CPU container the two
schedules share one synchronous device stream, so the overlap win is
bounded by host-side stall removal (deferred per-stage sync + record
materialization) and is largest where executor time dominates (the MoE
row); the speculative capture-ahead is extra stream work here, while on
TPU meshes it rides the executor gap (DESIGN.md §2.7 — same family of
caveat as the interpret-mode pallas wall times below).

Every ``pipeline="overlap"`` row also carries the scheduler's
``pipeline_stats`` counters (spec_captures / repairs / serial_fallbacks
plus the per-reason and MoE flip-repair tallies) so the bench artifact is
EVIDENCE that speculation actually engaged — scripts/check_bench.py gates
on it: a routed-MoE overlap row whose stats show serial re-capture instead
of flip repair fails CI. The MoE row additionally gets one
expert-sharded overlap cell (``quant_mesh="1x2x4"``): the same config
quantized with the expert mesh axis live, timed in a subprocess because
the expert axis needs a forced multi-device host platform
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) that must be set
before jax initializes. Parity of that path is pinned bitwise in
tests/test_distributed.py::test_moe_expert_sharded_matches_single.

Row schema and regeneration contract: docs/BENCHMARKS.md.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.common import bench_config
from repro.core import plan as qplan
from repro.core.pipeline import quantize_model
from repro.data import MarkovLM, calibration_batches
from repro.kernels import ops as kops
from repro.launch import hlo_analysis as ha
from repro.models import transformer as T


def _largest_group_shape(cfg) -> tuple:
    """(lanes, out, in) of the row's biggest quant group (MoE gate/up
    share a 2E-member group; dense layers group the 4 attention taps)."""
    mc = cfg.model
    if mc.moe.num_experts:
        return (2 * mc.moe.num_experts, mc.moe.d_ff_expert, mc.d_model)
    return (4, mc.d_model, mc.d_model)


def _quant_stage_op_counts(cfg, n_last: int = 128) -> dict:
    """Executed-XLA-op counts of the stage-1 AND stage-2 dispatches per
    impl, for the row's largest group: {impl: {"s1": ops, "s2": ops}}.

    ``n_last`` mirrors the calibration instance rows the timed runs below
    feed stage 2 (batch 4 × seq 32)."""
    qc = cfg.quant
    b, out_d, in_d = _largest_group_shape(cfg)
    bs = qc.blocksize
    w = jnp.zeros((b, out_d, in_d), jnp.float32)
    u = jnp.broadcast_to(jnp.eye(in_d, dtype=jnp.float32), (b, in_d, in_d))
    x = jnp.zeros((b, n_last, in_d), jnp.float32)
    s = jnp.ones((b, out_d, in_d // qc.group_size), jnp.float32)
    z = jnp.zeros_like(s)
    # (M, bs, bs) explicit block inverses: like the stage-1 count (which
    # takes the Cholesky factor U as an input), the curvature pre-factor is
    # excluded — it is the SAME code on both backends, so counting it would
    # only dilute the backend comparison the row exists to measure
    hinv = jnp.broadcast_to(jnp.eye(bs, dtype=jnp.float32),
                            (b, in_d // bs, bs, bs))
    kw1 = dict(bits=qc.bits, group_size=qc.group_size, blocksize=bs,
               symmetric=qc.symmetric)
    kw2 = dict(bits=qc.bits, group_size=qc.group_size, block_size=bs,
               alpha=qc.rpiq_alpha, t_max=qc.rpiq_iters,
               early_stop=qc.rpiq_early_stop, symmetric=qc.symmetric)

    def stage2(impl, **over):
        return lambda w, wf, x, hv, s, z: kops.rpiq_block(
            w, wf, x, hv, s, z, impl=impl, **kw2, **over)

    xla1 = jax.jit(
        lambda w, u: kops.gptq_block(w, u, impl="xla", **kw1)
    ).lower(w, u).compile().as_text()
    xla2 = jax.jit(stage2("xla")).lower(w, w, x, hinv, s,
                                        z).compile().as_text()
    return {
        "xla": {"s1": ha.executed_op_count(xla1),
                "s2": ha.executed_op_count(xla2)},
        "pallas": {
            "s1": ha.tpu_exported_op_count(
                lambda w, u: kops.gptq_block(w, u, impl="pallas",
                                             interpret=False, **kw1), w, u),
            "s2": ha.tpu_exported_op_count(
                stage2("pallas", interpret=False), w, w, x, hinv, s, z),
        },
    }


def _timed_repeats(cfg, params, calib, repeats: int):
    """Best-of-``repeats`` post-compile runs: (min wall seconds,
    (executor_s, stage1_s, stage2_s) of the best-executor run)."""
    walls, stats = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        _, rep = quantize_model(cfg, params, calib)
        walls.append(time.perf_counter() - t0)
        stats.append((rep.seconds_stage1 + rep.seconds_stage2,
                      rep.seconds_stage1, rep.seconds_stage2))
    return min(walls), min(stats)


def _time_impls(cfg, params, calib, label: str, repeats: int = 3,
                op_counts: bool = True,
                impls: tuple = ("xla", "pallas"),
                pipeline: str = None) -> list:
    """Flat BENCH rows: batched executor with BOTH per-stage backends set
    to the row's impl (stage-1 gptq_block + stage-2 rpiq_block).
    ``pipeline`` overrides ``quant.pipeline`` for these rows — the
    serial-vs-overlap A/B reuses this exact scaffold (same cold/warm
    protocol, same row schema) via :func:`_time_overlap`."""
    ops_by_impl = _quant_stage_op_counts(cfg) if op_counts else {}
    rows = []
    cfg.quant.batched_executor = True
    prev_pipeline = cfg.quant.pipeline
    if pipeline is not None:
        cfg.quant.pipeline = pipeline
    for impl in impls:
        cfg.quant.gptq_impl = impl
        cfg.quant.rpiq_impl = impl
        jax.clear_caches()
        qplan.clear_executor_cache()
        t0 = time.perf_counter()
        _, rep = quantize_model(cfg, params, calib)
        cold = time.perf_counter() - t0
        wall, best = _timed_repeats(cfg, params, calib, repeats)
        ops = ops_by_impl.get(impl, {}) or {}
        row = {
            "config": label, "impl": impl,
            "pipeline": cfg.quant.pipeline,
            "cold_s": round(cold, 2), "warm_s": round(wall, 2),
            "executor_s": round(best[0], 3),
            "stage1_s": round(best[1], 3), "stage2_s": round(best[2], 3),
            "xla_ops": ops.get("s1"), "xla_ops_s2": ops.get("s2"),
        }
        if cfg.quant.pipeline == "overlap":
            # scheduler evidence: check_bench.py gates on these counters
            # (speculation engaged, MoE layers flip-repaired not re-planned)
            row["pipeline_stats"] = dict(rep.pipeline_stats)
        rows.append(row)
    cfg.quant.pipeline = prev_pipeline
    cfg.quant.gptq_impl = "auto"
    cfg.quant.rpiq_impl = "auto"
    return rows


def _time_overlap(cfg, params, calib, label: str, repeats: int = 3) -> list:
    """The streaming-scheduler A/B row: batched executor, xla backends,
    ``quant.pipeline=overlap`` (cold + best-of-``repeats`` warm).

    Skipped under the ``REPRO_BENCH_PIPELINE`` smoke override — it
    already forces every impl row onto one schedule, so this row would
    re-run an identical configuration with no serial row to compare to.
    """
    if os.environ.get("REPRO_BENCH_PIPELINE"):
        return []
    return _time_impls(cfg, params, calib, label, repeats=repeats,
                       op_counts=False, impls=("xla",), pipeline="overlap")


_EXPERT_MESH = "1x2x4"  # DxMxE: rows over model=2, expert lanes over E=4


def _expert_cell() -> dict:
    """The expert-sharded MoE cell: quantize the MoE bench config with
    ``quant.mesh=_EXPERT_MESH`` under the overlap scheduler; returns the
    bench row."""
    cfg = bench_config("olmoe-1b-7b")
    cfg.quant.batched_executor = True
    cfg.quant.pipeline = "overlap"
    cfg.quant.mesh = _EXPERT_MESH
    params = T.init_params(cfg.model, jax.random.PRNGKey(0))
    calib = calibration_batches(
        MarkovLM(cfg.model.vocab_size, seed=0), 3, 4, 32)
    t0 = time.perf_counter()
    _, rep = quantize_model(cfg, params, calib)
    cold = time.perf_counter() - t0
    wall, best = _timed_repeats(cfg, params, calib, repeats=2)
    return {
        "config": f"moe-{cfg.model.name}", "impl": "xla",
        "pipeline": "overlap", "quant_mesh": _EXPERT_MESH,
        "cold_s": round(cold, 2), "warm_s": round(wall, 2),
        "executor_s": round(best[0], 3),
        "stage1_s": round(best[1], 3), "stage2_s": round(best[2], 3),
        "xla_ops": None, "xla_ops_s2": None,
        "pipeline_stats": dict(rep.pipeline_stats),
    }


def _expert_cell_main() -> None:
    """Subprocess entry: the cell's row as JSON on the last stdout line."""
    print(json.dumps(_expert_cell()))


def _time_expert_sharded(label: str) -> list:
    """The expert-parallel A/B cell for the MoE row (see
    :func:`_expert_cell`). Skipped under ``REPRO_BENCH_PIPELINE`` for the
    same reason as :func:`_time_overlap`.

    Runs in this process when it already sees enough devices. Otherwise,
    on the CPU, a child with a forced multi-device host platform runs it
    (``XLA_FLAGS`` only takes effect before jax initializes, and the
    parent keeps its single device). On an accelerator the cell is
    skipped instead: this process holds the chip, so a child that needs
    it would fail or hang."""
    if os.environ.get("REPRO_BENCH_PIPELINE"):
        return []
    need = math.prod(int(a) for a in _EXPERT_MESH.split("x"))
    if jax.device_count() >= need:
        cell = _expert_cell()
        assert cell["config"] == label, (cell["config"], label)
        return [cell]
    if jax.default_backend() != "cpu":
        print(f"  [table4] skipping the {_EXPERT_MESH} expert-sharded "
              f"cell: needs {need} devices, have {jax.device_count()}")
        return []
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.table4_time import _expert_cell_main; "
         "_expert_cell_main()"],
        capture_output=True, text=True, timeout=1800, env=env)
    if r.returncode != 0:
        raise RuntimeError(
            f"expert-sharded bench cell failed:\n{r.stderr[-3000:]}")
    cell = json.loads(r.stdout.strip().splitlines()[-1])
    assert cell["config"] == label, (cell["config"], label)
    return [cell]


def _overlap_summary(row: dict) -> None:
    """Fold the serial-vs-overlap warm delta into the table row (the
    matching serial reference is the impl="xla" row of the same config)."""
    serial = next((b for b in row["bench"] if b["impl"] == "xla"
                   and b.get("pipeline") != "overlap"), None)
    ov = next((b for b in row["bench"]
               if b.get("pipeline") == "overlap"), None)
    if serial is None or ov is None:
        return
    row["t_overlap_s"] = ov["warm_s"]
    row["overlap_delta_s"] = round(serial["warm_s"] - ov["warm_s"], 2)
    row["overlap_speedup"] = round(
        serial["warm_s"] / max(ov["warm_s"], 1e-9), 2)


def _time_exec_paths(cfg, params, calib, repeats: int = 5) -> dict:
    """Cold+warm wall-clock for per-linear vs batched plan execution.

    Warm = best of ``repeats`` post-compile runs (total wall-clock is
    dominated by the shared capture/propagate forwards, so single-shot
    timing is noisy); ``exec`` isolates the synchronized stage-1+stage-2
    executor seconds where the dispatch-count win lives.
    """
    out = {}
    for label, flag in (("perlinear", False), ("batched", True)):
        cfg.quant.batched_executor = flag
        # symmetric cold starts: earlier runs in this process may have
        # compiled one path's executors (e.g. the t_gptq/t_rpiq timings
        # run with the default batched executor)
        jax.clear_caches()
        qplan.clear_executor_cache()
        t0 = time.perf_counter()
        quantize_model(cfg, params, calib)
        out[f"t_{label}_cold_s"] = round(time.perf_counter() - t0, 2)
        wall, best = _timed_repeats(cfg, params, calib, repeats)
        out[f"t_{label}_s"] = round(wall, 2)
        out[f"t_{label}_exec_s"] = round(best[0], 3)
        out[f"t_{label}_s1_s"] = round(best[1], 3)
        out[f"t_{label}_s2_s"] = round(best[2], 3)
    out["speedup_warm"] = round(
        out["t_perlinear_s"] / max(out["t_batched_s"], 1e-9), 2)
    out["speedup_exec"] = round(
        out["t_perlinear_exec_s"] / max(out["t_batched_exec_s"], 1e-9), 2)
    return out


def run(tiny: bool = False) -> list:
    rows = []
    dense_grid = ((64, 256, 2),) if tiny else ((64, 256, 2), (128, 512, 2),
                                               (128, 512, 4))
    repeats = 2 if tiny else 5
    for d_model, d_ff, layers in dense_grid:
        cfg = bench_config("opt-proxy", d_model=d_model, d_ff=d_ff,
                           num_layers=layers,
                           num_heads=max(4, d_model // 16),
                           num_kv_heads=max(4, d_model // 16))
        cfg.model.head_dim = 0
        cfg.model.__post_init__()
        params = T.init_params(cfg.model, jax.random.PRNGKey(0))
        calib = calibration_batches(
            MarkovLM(cfg.model.vocab_size, seed=0), 3, 4, 32)

        cfg_g = bench_config("opt-proxy", d_model=d_model, d_ff=d_ff,
                             num_layers=layers,
                             num_heads=max(4, d_model // 16),
                             num_kv_heads=max(4, d_model // 16))
        cfg_g.model.head_dim = 0
        cfg_g.model.__post_init__()
        cfg_g.quant.rpiq_iters = 0
        t0 = time.perf_counter()
        quantize_model(cfg_g, params, calib)
        t_gptq = time.perf_counter() - t0

        t0 = time.perf_counter()
        _, rep = quantize_model(cfg, params, calib)
        t_rpiq = time.perf_counter() - t0
        label = f"d{d_model}-L{layers}"
        row = {
            "table": "table4", "d_model": d_model, "layers": layers,
            "t_gptq_s": round(t_gptq, 2), "t_rpiq_s": round(t_rpiq, 2),
            "delta_s": round(t_rpiq - t_gptq, 2),
            "stage2_s": round(rep.seconds_stage2, 2),
        }
        # plan-executor comparison: 4 same-shape q/k/v/o linears per layer
        row.update(_time_exec_paths(cfg, params, calib, repeats=repeats))
        row["bench"] = [
            {"config": label, "impl": "perlinear",
             "pipeline": cfg.quant.pipeline,
             "cold_s": row["t_perlinear_cold_s"],
             "warm_s": row["t_perlinear_s"],
             "executor_s": row["t_perlinear_exec_s"],
             "stage1_s": row["t_perlinear_s1_s"],
             "stage2_s": row["t_perlinear_s2_s"],
             "xla_ops": None, "xla_ops_s2": None},
        ] + _time_impls(cfg, params, calib, label, repeats=repeats) \
          + _time_overlap(cfg, params, calib, label, repeats=repeats)
        _overlap_summary(row)
        rows.append(row)

    if tiny:
        return rows

    # MoE: 8 experts/layer → gate/up stack into one 16-member group,
    # down into an 8-member group; per-linear pays 24 dispatch pairs/layer.
    cfg = bench_config("olmoe-1b-7b")
    params = T.init_params(cfg.model, jax.random.PRNGKey(0))
    calib = calibration_batches(
        MarkovLM(cfg.model.vocab_size, seed=0), 3, 4, 32)
    row = {"table": "table4", "d_model": cfg.model.d_model,
           "layers": cfg.model.num_layers,
           "moe_experts": cfg.model.moe.num_experts}
    row.update(_time_exec_paths(cfg, params, calib))
    label = f"moe-{cfg.model.name}"
    row["bench"] = [
        {"config": label, "impl": "perlinear",
         "pipeline": cfg.quant.pipeline,
         "cold_s": row["t_perlinear_cold_s"], "warm_s": row["t_perlinear_s"],
         "executor_s": row["t_perlinear_exec_s"],
         "stage1_s": row["t_perlinear_s1_s"],
         "stage2_s": row["t_perlinear_s2_s"],
         "xla_ops": None, "xla_ops_s2": None},
    ] + _time_impls(cfg, params, calib, label) \
      + _time_overlap(cfg, params, calib, label) \
      + _time_expert_sharded(label)
    _overlap_summary(row)
    # the headline fused-kernel claims, measured (≥10× required per stage):
    # (serial impl rows only — the overlap A/B row shares impl="xla" but
    # carries no op counts)
    impls = {b["impl"]: b for b in row["bench"]
             if b.get("pipeline") != "overlap"}
    if impls.get("pallas", {}).get("xla_ops"):
        row["op_reduction"] = round(
            impls["xla"]["xla_ops"] / impls["pallas"]["xla_ops"], 1)
    if impls.get("pallas", {}).get("xla_ops_s2"):
        row["op_reduction_s2"] = round(
            impls["xla"]["xla_ops_s2"] / impls["pallas"]["xla_ops_s2"], 1)
    rows.append(row)
    return rows
