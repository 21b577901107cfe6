"""Assigned-architecture registry: ``get_config(name)`` / ``ARCHS``.

Every config cites its source in ``citation`` and carries the exact
dims from the assignment card.  ``smoke_config(name)`` returns the
reduced same-family variant used by per-arch smoke tests
(<= 2 layers, d_model <= 512, <= 4 experts).
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from ..models.config import ArchConfig

ARCHS: List[str] = [
    "qwen2-vl-2b",
    "deepseek-v3-671b",
    "deepseek-v2-236b",
    "deepseek-v2-lite",
    "stablelm-12b",
    "command-r-35b",
    "recurrentgemma-9b",
    "llama3_2-3b",
    "falcon-mamba-7b",
    "gemma3-12b",
    "musicgen-medium",
]

_ALIASES = {"llama3.2-3b": "llama3_2-3b"}


def _module(name: str):
    name = _ALIASES.get(name, name)
    return importlib.import_module(
        f"repro.configs.{name.replace('-', '_').replace('.', '_')}")


def get_config(name: str) -> ArchConfig:
    cfg = _module(name).CONFIG
    cfg.validate()
    return cfg


def smoke_config(name: str) -> ArchConfig:
    cfg = _module(name).smoke()
    cfg.validate()
    return cfg


def all_configs() -> Dict[str, ArchConfig]:
    return {a: get_config(a) for a in ARCHS}
