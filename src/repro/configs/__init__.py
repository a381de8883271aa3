"""Architecture registry: one module per assigned arch (+ tiny for demos).

``get(arch_id)`` returns the full published config; ``get_smoke(arch_id)``
the reduced same-family config used by CPU smoke tests and examples.
"""

from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig

ARCHS = [
    "mamba2-130m",
    "granite-8b",
    "qwen2.5-14b",
    "mistral-nemo-12b",
    "llama3-405b",
    "recurrentgemma-2b",
    "internvl2-26b",
    "mixtral-8x22b",
    "moonshot-v1-16b-a3b",
    "seamless-m4t-large-v2",
]

_EXTRA = ["tiny"]  # paper-scale demo model (~100M) for the e2e driver


def _module(arch: str):
    mod_name = arch.replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{mod_name}")


def get(arch: str) -> ModelConfig:
    if arch not in ARCHS + _EXTRA:
        raise KeyError(f"unknown arch {arch!r}; have {ARCHS + _EXTRA}")
    return _module(arch).CONFIG


def get_smoke(arch: str) -> ModelConfig:
    if arch not in ARCHS + _EXTRA:
        raise KeyError(f"unknown arch {arch!r}; have {ARCHS + _EXTRA}")
    return _module(arch).SMOKE


__all__ = ["ModelConfig", "ARCHS", "get", "get_smoke"]
