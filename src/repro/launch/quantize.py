"""Quantization launcher: calibrate → GPTQ → RPIQ → packed artifacts.

    PYTHONPATH=src python -m repro.launch.quantize --arch opt-proxy --smoke \
        quant.rpiq_iters=5 quant.rpiq_alpha=0.01

Loads a checkpoint when train.ckpt_dir has one (quantizing a *trained*
model); otherwise quantizes a fresh init (still exercises the full path).
Prints the per-layer Γ convergence summary (paper Table 5) and writes the
packed int4 params + report.

``quant.mesh`` (e.g. ``quant.mesh=auto``, ``quant.mesh=8x2``, or
``quant.mesh=2x1x4`` with an expert axis) turns on sharded group
execution: every quant-plan group that divides the mesh runs
lane-sharded over ``data`` and row-tiled over ``model``; stacked MoE
expert slabs shard lanes over ``expert`` when the third axis is given
(DESIGN.md §2.6, docs/QUANTIZATION.md). Default "off" = single device.

``quant.pipeline=overlap`` switches the layer walk to the streaming
scheduler (core/stream.py, DESIGN.md §2.7): executor dispatches stay
async and the next layer's capture forward runs speculatively on the
pre-quantization stream with exact Hessian repair after the scatter —
routed MoE included, via the plan-level flip repair gated by
``quant.moe_flip_budget``. Artifacts are bitwise-identical to the
default ``serial`` schedule.
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.config import apply_overrides, parse_overrides
from repro.configs.registry import get_config
from repro.core import faults
from repro.core.pipeline import pack_for_serving, quantize_model
from repro.data import MarkovLM, calibration_batches
from repro.distributed.checkpoint import Checkpointer, save_artifact
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_quant_mesh
from repro.models import transformer as T


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="artifacts/quantized")
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    apply_overrides(cfg, parse_overrides(args.overrides))
    mc, qc = cfg.model, cfg.quant
    setup_compile_cache()
    faults.install_from_config(cfg)
    if cfg.faults.arm:
        print(f"[quantize] fault plane armed: {cfg.faults.arm}")
    if qc.ckpt_dir:
        print(f"[quantize] step checkpoints → {qc.ckpt_dir} "
              f"(quant.resume={qc.resume})")

    key = jax.random.PRNGKey(0)
    params = (T.init_encdec_params(mc, key) if mc.is_encoder_decoder
              else T.init_params(mc, key))
    ckpt = Checkpointer(cfg.train.ckpt_dir)
    if ckpt.latest_step() is not None:
        from repro.training.train_step import init_train_state
        state, _ = ckpt.restore(init_train_state(cfg, key))
        params = state.params
        print(f"[quantize] loaded checkpoint step {ckpt.latest_step()}")

    data = MarkovLM(mc.vocab_size, seed=7)
    calib = calibration_batches(data, qc.calib_batches, qc.calib_batch_size,
                                min(qc.calib_seq_len, mc.max_seq_len - 8))
    if mc.is_encoder_decoder:
        import jax.numpy as jnp
        for i, b in enumerate(calib):
            b["frames"] = jax.random.normal(
                jax.random.PRNGKey(i),
                (qc.calib_batch_size, mc.encoder_seq_len, mc.d_model),
                jnp.float32)

    mesh = make_quant_mesh(qc.mesh)
    if mesh is not None:
        print(f"[quantize] sharded group execution on mesh "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    if qc.pipeline != "serial":
        print(f"[quantize] streaming layer walk: quant.pipeline="
              f"{qc.pipeline}")
    params_q, report = quantize_model(cfg, params, calib, verbose=True,
                                      mesh=mesh)
    print(f"[quantize] {report.summary()}")
    if report.pipeline_stats.get("resumed_at") is not None:
        print(f"[quantize] resumed from checkpoint at walk item "
              f"{report.pipeline_stats['resumed_at']}")
    st = report.pipeline_stats
    if st.get("mode") == "overlap":
        print(f"[quantize] schedule: {st['steps']} steps, "
              f"{st.get('spec_captures', 0)} speculative captures, "
              f"{st.get('repairs', 0)} repairs, "
              f"{st.get('serial_fallbacks', 0)} serial fallbacks")
        reasons = {k[len("fallback_"):]: v for k, v in st.items()
                   if k.startswith("fallback_") and v}
        if reasons:
            print(f"[quantize] fallback reasons: {reasons}")
        if st.get("moe_spec_layers"):
            n_a = max(1, st.get("moe_assignments", 0))
            print(f"[quantize] moe flip repair: "
                  f"{st.get('moe_plan_reuses', 0)} plan reuses, "
                  f"{st.get('moe_flip_repairs', 0)} re-sorts, "
                  f"flip rate {st.get('moe_flipped_assignments', 0)}/{n_a}"
                  f" (budget {qc.moe_flip_budget})")
    if report.moe_capacity_dropped:
        print(f"[quantize] moe capacity-dropped assignments: "
              f"{report.moe_capacity_dropped}")
    if report.guardrail_stats:
        print(f"[quantize] guardrail: {report.guardrail_stats}")
    if report.kernel_fallbacks:
        print(f"[quantize] kernel fallbacks: {report.kernel_fallbacks}")
    if report.mesh_spread:
        print(f"[quantize] mesh spread: {report.mesh_spread}")
    packed = pack_for_serving(cfg, params_q)

    os.makedirs(args.out, exist_ok=True)
    tag = mc.name
    with open(os.path.join(args.out, f"{tag}.report.json"), "w") as f:
        json.dump([{**vars(r)} for r in report.linears], f, indent=1)
    # atomic write + sha256 sidecar manifest: launch.serve (and the
    # supervisor's params reload) verify the digest at load, so a flipped
    # byte in the artifact is a typed error, never a silent garbage load
    save_artifact(os.path.join(args.out, f"{tag}.params.pkl"),
                  jax.device_get(packed), extra={"arch": tag})
    print(f"[quantize] wrote {args.out}/{tag}.params.pkl (+ integrity "
          "manifest)")
    return report


if __name__ == "__main__":
    main()
