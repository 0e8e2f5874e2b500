"""The harness: it refuses anything but a TPU, and finds a new
configuration, traffic mix and per-layer metric from files alone."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness
from bench.tests import tiny

REPO = tiny.REPO


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "opt-proxy-125m.quantize", "--seed", str(2 ** 33), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_py_on_cpu_fails_and_names_the_platform():
    p = _run_py(REPO)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


EXTRA = {"name": "jobs_in_window.test", "unit": "jobs", "better": "higher",
         "source": "program_counter", "layer": "quantize walk",
         "moves": "quant_layers_per_s", "workloads": [tiny.CELL]}
READER = '''
def read(ctx):
    jobs = ctx.records.get("jobs")
    return None if not jobs else float(len(jobs))
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    jax.config.update("jax_enable_compilation_cache", False)
    r = tiny.make_root(str(tmp_path_factory.mktemp("bench")), [EXTRA])
    with open(os.path.join(r, "bench", "metrics", EXTRA["name"] + ".py"),
              "w") as f:
        f.write(READER)
    return r


def _cell(root, trace, seed=2 ** 33 + 1):
    return harness.run_cell(root, tiny.CELL, seed, 1.0, trace,
                            time.perf_counter(), require_chip=False)


def test_added_cell_runs_from_files_alone(root):
    res = _cell(root, False)
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"quant_layers_per_s", "setup_s"}
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


def test_added_metric_is_read_in_a_traced_run(root):
    res = _cell(root, True)
    assert res["metrics"]["jobs_in_window.test"]["unit"] == "jobs"
    assert res["metrics"]["jobs_in_window.test"]["value"] >= 1
    assert {"capture_s_per_layer", "stage1_s_per_layer",
            "stage2_s_per_layer"} <= set(res["metrics"])
    assert "quant_layers_per_s" not in res["metrics"]
    # a CPU trace holds no device line and no Pallas kernel: those
    # readers stay silent
    assert "gptq_block_roofline" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["correct"]
