"""Multi-device behaviour, run in subprocesses so the main pytest process
keeps the single real CPU device."""
import os
import subprocess
import sys

import pytest

_SCRIPT = os.path.join(os.path.dirname(__file__), "_distributed_checks.py")


def _run(check: str, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, _SCRIPT, check],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, \
        f"{check} failed:\nSTDOUT:{r.stdout[-3000:]}\nSTDERR:{r.stderr[-3000:]}"
    assert "OK" in r.stdout


@pytest.mark.slow
def test_sharded_train_matches_single():
    _run("sharded_train")


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    _run("elastic_restore")


@pytest.mark.slow
def test_grad_compression_error_feedback():
    _run("grad_compression")


@pytest.mark.slow
def test_gpipe_matches_stacked_forward():
    _run("gpipe")


@pytest.mark.slow
def test_row_sharded_gptq_exact():
    _run("gptq_rows")


@pytest.mark.slow
def test_sharded_plan_matches_batched():
    """Sharded group execution (quant.mesh knob) == single-device batched.

    Group-level/non-divisible parity lives in tests/test_plan_sharded.py,
    which runs under the scripts/check.sh forced-device-count leg."""
    _run("plan_sharded")


@pytest.mark.slow
def test_moe_expert_sharded_matches_single():
    """Expert-parallel quantization (quant.mesh="1x2x4") == single-device
    on the routed-MoE config, under the overlap scheduler."""
    _run("moe_expert_sharded")
