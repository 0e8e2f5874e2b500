"""``correct`` against the control and against faults planted in the timed
path, at a size a test run holds (the same comparisons decide it on the
chip at the cell's own size; PERF.md gives those readings).

Each test drives a whole run past the harness's look for a chip, with
the control or a fault in the program's place (``bench/faults.py``), and
sees ``correct`` come out false: the harness's own comparison decides."""
import time

import jax
import pytest

from bench import faults, harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    jax.config.update("jax_enable_compilation_cache", False)
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, hooks, seed=2 ** 33 + 3):
    return harness.run_cell(root, tiny.CELL, seed, 1.0, False,
                            time.perf_counter(), require_chip=False,
                            hooks=hooks)


def test_sound_run_is_correct(root):
    res = _run(root, {})
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"quant_excess_error",
                                  "stage2_residual_gap"}


@pytest.mark.parametrize("name", sorted(faults.HOOKS))
def test_control_and_faults_are_not_correct(root, name):
    res = _run(root, faults.HOOKS[name]())
    assert not res["correct"], res["checks"]


def test_stage2_left_out_fails_the_stage2_number(root):
    checks = _run(root, faults.HOOKS["stage2-skipped"]())["checks"]
    gap = checks["stage2_residual_gap"]
    assert gap["value"] > gap["limit"]


def test_stage2_gap_is_scaled_by_size_not_sign():
    """On the chip most linears' first round raises the instance residual
    and early stop ends the loop, so the shares removed are mostly
    negative: the gap stays positive, and a stage 2 left out (share 0)
    reads 1 at the median linear."""
    import numpy as np
    from bench.drivers import quantize as qd
    d_ref = np.array([-0.04, -0.03, -0.02, 0.01, -0.05])
    near = qd.stage2_gaps(d_ref * 1.1, d_ref)
    assert (near >= 0).all() and np.median(near) < 0.15
    skipped = qd.stage2_gaps(np.zeros_like(d_ref), d_ref)
    assert np.median(skipped) == 1.0
