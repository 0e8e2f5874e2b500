"""A benchmark root with a tiny cell, for the CPU tests: a copy of
``bench/`` plus a ``BENCHMARK.json`` naming a configuration, a job and
limits that exist only in that copy, so adding them is files only."""
from __future__ import annotations

import json
import os
import shutil
from typing import Dict

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

MODEL = {"family": "dense", "num_layers": 4, "d_model": 128, "num_heads": 4,
         "num_kv_heads": 4, "d_ff": 256, "vocab_size": 256,
         "max_seq_len": 128, "norm": "layernorm", "act": "gelu",
         "gated_mlp": False, "tie_embeddings": True, "use_rope": True,
         "rope_theta": 10000.0, "dtype": "bfloat16"}

QUANT = {"bits": 4, "group_size": 32, "blocksize": 32, "percdamp": 0.01,
         "rpiq_iters": 5, "rpiq_alpha": 0.01, "rpiq_early_stop": True,
         "rpiq_use_global_hessian": True, "keep_best_projection": True,
         "calib_batches": 2, "calib_batch_size": 8, "calib_seq_len": 64,
         "pipeline": "serial", "mesh": "off"}

CONFIGS = {"tiny-quant": {"model": dict(MODEL, name="tiny-quant"),
                          "quant": QUANT}}
MIXES = {"tiny-quant-job": {"kind": "quantize"}}
CELL = "tiny-quant.tiny-quant-job"
LIMITS = {CELL: {"quant_excess_error": 0.2, "stage2_residual_gap": 0.5}}

PER_LAYER = [(n, u) for n, u in (
    ("capture_s_per_layer", "s"), ("stage1_s_per_layer", "s"),
    ("stage2_s_per_layer", "s"), ("gptq_block_roofline", "%"),
    ("hessian_accum_roofline", "%"), ("quant_mfu", "%"),
    ("device_idle_pct.quant", "%"))]


def make_root(tmp: str, extra_per_layer=()) -> str:
    """Build the root under ``tmp``; returns its path. ``src`` is linked
    so the drivers import the program as in a checkout."""
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    real = _read(os.path.join(REPO, "BENCHMARK.json"))
    for name, body in CONFIGS.items():
        _write(os.path.join(root, "bench", "configs", name + ".json"), body)
    for name, body in MIXES.items():
        _write(os.path.join(root, "bench", "traffic", name + ".json"), body)
    for name, body in LIMITS.items():
        _write(os.path.join(root, "bench", "limits", name + ".json"), body)
    bench = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 2,
        "configs": [{"name": n, "source": "test", "reduced": [],
                     "file": f"bench/configs/{n}.json", "why": "test"}
                    for n in CONFIGS],
        "workloads": [{"name": c, "config": c.split(".")[0],
                       "traffic": c.split(".")[1], "chips": 1, "why": "test"}
                      for c in LIMITS],
        "end_to_end": [
            {"name": "quant_layers_per_s", "unit": "layers/s",
             "better": "higher", "bound": 0.25, "source": "host_clock",
             "workloads": [CELL]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": n, "unit": u, "better": "higher",
                       "source": "device_trace", "layer": "test",
                       "moves": "quant_layers_per_s", "workloads": [CELL]}
                      for n, u in PER_LAYER] + list(extra_per_layer),
    }
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _write(path: str, body: Dict) -> None:
    with open(path, "w") as f:
        json.dump(body, f, indent=1)
