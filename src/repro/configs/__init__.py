"""Architecture config registry: one module per assigned architecture.

``get_config(name)`` returns the full published config; pair it with
``repro.models.config.smoke_config`` for CPU-runnable reduced versions.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import ModelConfig

ARCHS: List[str] = [
    "musicgen-medium",
    "minitron-4b",
    "gemma3-1b",
    "glm4-9b",
    "qwen3-0.6b",
    "mamba2-130m",
    "zamba2-2.7b",
    "granite-moe-3b-a800m",
    "dbrx-132b",
    "internvl2-2b",
]

#: configurations priced for training only (a tracked train step): they
#: have no serving path (prefill, decode), so the per-arch suites skip them
TRAIN_ONLY: List[str] = [
    "granite-4.0-h-small",
]

_MODULE_FOR = {name: name.replace("-", "_").replace(".", "_")
               for name in ARCHS + TRAIN_ONLY}


def get_config(name: str) -> ModelConfig:
    if name not in _MODULE_FOR:
        raise KeyError(f"unknown arch {name!r}; known: "
                       f"{ARCHS + TRAIN_ONLY}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[name]}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {name: get_config(name) for name in ARCHS}
