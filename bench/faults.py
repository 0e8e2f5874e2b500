"""The control and the faults planted in the quantize cell's timed path,
by name, as the hooks ``bench/drivers/quantize.py`` takes. The tests
drive them at a size a test run holds; ``bench/control.py`` at the
cell's own size on the chip. Each run with one of them must come out not
``correct``."""
from __future__ import annotations

import copy
from typing import Callable, Dict


def _quantize_model(cfg, params, calib):
    from repro.core.pipeline import quantize_model
    return quantize_model(cfg, params, calib)


def stage2_skipped(cfg, params, calib):
    """Stage 2 left out: the stage-1 weights go to the artifact."""
    cfg = copy.copy(cfg)
    cfg.quant = copy.copy(cfg.quant)
    cfg.quant.rpiq_iters = 0
    return _quantize_model(cfg, params, calib)


def weights_unchanged(cfg, params, calib):
    """The float weights handed back as given (packing rounds them)."""
    _, report = _quantize_model(cfg, params, calib)
    return params, report


def half_calibration(cfg, params, calib):
    """Half of the calibration batches left out, the Hessians the mean of
    the rest."""
    return _quantize_model(cfg, params, calib[: len(calib) // 2])


def codes_altered(cfg, params_q):
    """One layer's codes of one linear altered where the artifact is
    packed."""
    import jax.numpy as jnp
    from repro.core.pipeline import pack_for_serving
    out = pack_for_serving(cfg, params_q)
    qt = out["blocks"][0]["sub0"]["mlp"]["up"]["w"]
    qt.packed = qt.packed.at[0].set(qt.packed[0] ^ jnp.uint8(0x11))
    return out


HOOKS: Dict[str, Callable[[], Dict]] = {
    "control": lambda: {"control": True},
    "stage2-skipped": lambda: {"quantize_model": stage2_skipped},
    "weights-unchanged": lambda: {"quantize_model": weights_unchanged},
    "half-calibration": lambda: {"quantize_model": half_calibration},
    "codes-altered": lambda: {"pack_for_serving": codes_altered},
}
