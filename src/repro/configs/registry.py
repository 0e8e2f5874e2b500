"""--arch resolution.

Every architecture module exposes ``full()`` and ``smoke()`` returning a
:class:`repro.config.Config`. ``smoke`` is a reduced same-family config that
runs a forward/train step on CPU in seconds; ``full`` is the published
configuration.
"""
from __future__ import annotations

import importlib

from repro.config import Config


ARCH_IDS = [
    "whisper-large-v3",
    "minicpm-2b",
    "h2o-danube-1.8b",
    "stablelm-1.6b",
    "internlm2-1.8b",
    "recurrentgemma-9b",
    "olmoe-1b-7b",
    "deepseek-v3-671b",
    "pixtral-12b",
    "falcon-mamba-7b",
    # the paper's own model family (OPT-style proxy used by benchmarks)
    "opt-proxy",
]

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "_")
            for a in ARCH_IDS}


def get_config(arch: str, smoke: bool = False) -> Config:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(_MODULES[arch])
    cfg: Config = mod.smoke() if smoke else mod.full()
    cfg.model.__post_init__()
    return cfg
