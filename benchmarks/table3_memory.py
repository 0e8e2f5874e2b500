"""Paper Table 3 + eq. 15-16: peak calibration residency.

Single-instance calibration keeps O(‖X_last‖ + ‖H‖) resident during stage 2
vs O(‖[X^(1..k)]‖) for all-batch schemes. Measured as actual resident array
bytes for the pipeline's stage-2 inputs across calibration-set sizes, plus
the deployment memory claim (paper abstract: 60-75% reduction): bf16 vs
int4-packed weight bytes per arch.

``table3-kv`` rows measure decode-cache bytes per sequence (eval_shape over
the real cache constructors, benchmarks/common.cache_bytes_per_seq): fp32 /
fp16 / int8 per arch per context length, with both reduction ratios. The
int8 layout pays per-block f32 scales + error-feedback accumulators on top
of the 1-byte codes, so the honest ceiling vs fp16 is < 2×; the ≥3.5×
reduction lands on the fp32 column. Architectures whose caches the sentinel
keeps in float (MLA latents, recurrent states) report ratios near 1 — that
is the measured truth, not a bug.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import bench_config, cache_bytes_per_seq
from repro.configs import ARCH_IDS, get_config
from repro.models import transformer as T


def total_param_count(mc) -> int:
    """All parameters (MoE counts every expert), from the shapes the real
    param constructors return under ``jax.eval_shape`` — nothing is
    materialized."""
    init = T.init_encdec_params if mc.is_encoder_decoder else T.init_params
    tree = jax.eval_shape(lambda: init(mc, jax.random.PRNGKey(0)))
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def run() -> list:
    rows = []
    cfg = bench_config("opt-proxy")
    mc = cfg.model
    d = mc.d_model
    seq, bs = 128, 8
    x_batch = bs * seq * d * 4                      # one batch of layer X
    h_bytes = d * d * 4
    for k in (4, 16, 64, 128):
        rows.append({
            "table": "table3", "calib_batches": k,
            "single_instance_bytes": x_batch + h_bytes,
            "all_batches_bytes": k * x_batch + h_bytes,
            "ratio": round((k * x_batch + h_bytes)
                           / (x_batch + h_bytes), 2),
        })

    # deployment memory: the paper's 60-75% claim, per assigned arch
    for arch in ARCH_IDS:
        mc = get_config(arch).model
        n = total_param_count(mc)
        bf16 = 2.0 * n
        # int4 + per-128-group f32 scale+zero on quantized linears (~97% of
        # params); embeddings/norms stay bf16 (~vocab*d)
        emb = mc.vocab_size * mc.d_model * (1 if mc.tie_embeddings else 2)
        lin = max(n - emb, 0)
        int4 = 0.5 * lin + (8.0 / 128.0) * lin + 2.0 * emb
        rows.append({
            "table": "table3-deploy", "arch": arch,
            "params_B": round(n / 1e9, 3),
            "bf16_GB": round(bf16 / 2**30, 2),
            "int4_GB": round(int4 / 2**30, 2),
            "reduction_pct": round(100 * (1 - int4 / bf16), 1),
        })

    # decode-cache residency: bytes per sequence at each context length,
    # per cache precision (serve.kv_cache knob; docs/SERVING.md)
    import jax.numpy as jnp
    for arch in ARCH_IDS:
        mc = get_config(arch).model
        for ctx in (512, 2048, 8192):
            if ctx > mc.max_seq_len:
                continue
            fp32 = cache_bytes_per_seq(mc, ctx, jnp.float32)
            fp16 = cache_bytes_per_seq(mc, ctx, jnp.float16)
            int8 = cache_bytes_per_seq(mc, ctx, "int8")
            rows.append({
                "table": "table3-kv", "arch": arch, "ctx": ctx,
                "fp32_bytes_per_seq": fp32,
                "fp16_bytes_per_seq": fp16,
                "int8_bytes_per_seq": int8,
                "int8_bytes_per_token": round(int8 / ctx, 1),
                "ratio_vs_fp32": round(fp32 / int8, 2),
                "ratio_vs_fp16": round(fp16 / int8, 2),
            })
    return rows
