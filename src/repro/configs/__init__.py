"""Architecture registry: one module per assigned arch (+ the paper's own
CNNs + a tiny test config). Each module exposes ``config()`` (the exact
published dims) and ``smoke()`` (a reduced same-family config for CPU tests).
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.config import Config

# arch id -> module name
_MODULES = {
    "granite-8b": "granite_8b",
    "gemma2-2b": "gemma2_2b",
    "llama3.2-3b": "llama3_2_3b",
    "smollm-360m": "smollm_360m",
    "zamba2-7b": "zamba2_7b",
    "mixtral-8x22b": "mixtral_8x22b",
    "arctic-480b": "arctic_480b",
    "mellum2-12b": "mellum2_12b",
    "llama-3.2-vision-11b": "llama3_2_vision_11b",
    "hubert-xlarge": "hubert_xlarge",
    "mamba2-780m": "mamba2_780m",
    "alexnet": "alexnet",
    "resnet20": "resnet20",
    "tiny": "tiny",
}


def _load(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


# Production-mesh training defaults for the LM family: full-scan remat +
# 8-way gradient accumulation keep live activations ≈ (batch/accum)·seq·d
# per chip (without them every 4k×256 cell blows past 16 GB HBM — see
# DESIGN.md §3 and EXPERIMENTS.md §Dry-run). arctic-480b additionally
# accumulates grads in bf16: its f32 master+grads alone are ~15 GB/chip.
_LM_TRAIN = {"remat": "full", "accum_steps": 8}
_ARCH_TRAIN = {
    "arctic-480b": {**_LM_TRAIN, "accum_dtype": "bfloat16"},
}


def get_config(arch: str) -> Config:
    import dataclasses
    cfg = _load(arch).config()
    if cfg.model.family != "cnn" and arch != "tiny":
        kw = _ARCH_TRAIN.get(arch, _LM_TRAIN)
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, **kw))
    return cfg


def get_smoke_config(arch: str) -> Config:
    return _load(arch).smoke()


def list_archs() -> List[str]:
    return sorted(_MODULES)


def assigned_archs() -> List[str]:
    """The assigned LM-family architectures (excludes paper CNNs/tiny)."""
    return [a for a in _MODULES if a not in ("alexnet", "resnet20", "tiny")]
