"""The benchmark's side of the system under test: it builds the program's
``Config`` from a configuration file and makes the program's input, the
float model to quantize, on the device from the seed. The weights come
from ``bench/weights.py``, so the reference makes the same ones again."""
from __future__ import annotations

from typing import Dict

import jax

from bench import weights as W


def program_config(cfgd: Dict):
    """The program's ``Config`` for a configuration file: its ``model``
    section builds the ``ModelConfig``, its ``quant`` section overrides
    fields of the quantizer's."""
    from repro.config import Config, ModelConfig
    cfg = Config()
    cfg.model = ModelConfig(**cfgd["model"])
    for k, v in cfgd["quant"].items():
        if not hasattr(cfg.quant, k):
            raise KeyError(f"unknown quant field {k!r}")
        setattr(cfg.quant, k, v)
    return cfg


def float_model(dims: W.Dims, seed: int) -> Dict:
    """The float32 model to quantize, made on the device in one call."""
    return jax.jit(W.float_params, static_argnums=0)(dims,
                                                      W.root_key(seed))
