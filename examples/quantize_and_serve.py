"""Serve a quantized model with batched requests (the paper's deployment).

    PYTHONPATH=src python examples/quantize_and_serve.py [--arch internlm2-1.8b]

Quantizes the chosen architecture's smoke config with RPIQ, packs to int4
(≈ 23% of the bf16 weight bytes incl. scales), and serves a batch of
prompts through prefill + jit'd decode (``serving.engine.serve_step``).
"""
import argparse
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.pipeline import pack_for_serving, quantize_model
from repro.data import MarkovLM, calibration_batches
from repro.models import transformer as T
from repro.serving.engine import generate

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="internlm2-1.8b")
ap.add_argument("--batch", type=int, default=4)
ap.add_argument("--new-tokens", type=int, default=12)
args = ap.parse_args()

cfg = get_config(args.arch, smoke=True)
mc = cfg.model
cfg.quant.rpiq_use_global_hessian = False
cfg.quant.rpiq_alpha = 0.3

params = T.init_params(mc, jax.random.PRNGKey(0))
calib = calibration_batches(MarkovLM(mc.vocab_size, seed=7), 3, 4, 32)
params_q, report = quantize_model(cfg, params, calib)
packed = pack_for_serving(cfg, params_q)
print(f"quantized {args.arch}: {report.summary()}")


def tree_bytes(t):
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(t)
               if hasattr(l, "dtype"))


bf16 = jax.tree_util.tree_map(
    lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, params)
print(f"weights: bf16 {tree_bytes(bf16)/1e6:.2f} MB → int4+scales "
      f"{tree_bytes(packed)/1e6:.2f} MB")

prompts = MarkovLM(mc.vocab_size, seed=3).batch(args.batch, 8)
res = generate(cfg, packed, prompts, max_new_tokens=args.new_tokens,
               temperature=0.0)
for i in range(args.batch):
    print(f"request {i}: prompt={list(map(int, prompts['tokens'][i]))} "
          f"-> {list(map(int, res.tokens[i]))}")
